"""The program's scopes and the engine's spans in a trace
(bench/benchlib/scopes.py): on hand-made traces with known answers, on a
chip trace of a program that has neither (every reading ``trace.Trace``
gives stays the same), on a chip trace with both, and on an HLO the
profiler keeps on the CPU."""
import glob
import pathlib
import types

import numpy as np
import pytest
from tiny import spec  # noqa: F401

from benchlib import readers, scopes, trace

DATA = pathlib.Path(__file__).parent / "data"
UNSCOPED = DATA / "long_decode_trace.json.gz"
SCOPED = DATA / "long_decode_scoped_trace.json.gz"


def hand_trace():
    # ops: [dev, start, dur, name, rows, scope]; spans nest as a poll's do
    return {"devices": [0], "modules": [
        [0, 0.0, 400.0, "jit_decode_paged(7)", 1],
        [0, 500.0, 100.0, "jit_extend_paged(8)", 2],
        [0, 700.0, 100.0, "jit_decode_paged(7)", 3],
    ], "ops": [
        [0, 0.0, 400.0, "while", 1, ""],                 # the layer loop
        [0, 0.0, 100.0, "fusion", 0, "dense"],
        [0, 100.0, 50.0, "fusion", 0, "kv_write"],
        [0, 150.0, 100.0, "fusion", 0, "retrieve"],
        [0, 200.0, 100.0, "copy-start", 0, "prepare"],   # overlaps retrieve
        [0, 300.0, 40.0, "relevancy_topk", 0, "relevancy"],
        [0, 340.0, 60.0, "paged_decode_attention", 0, "apply"],
        [0, 500.0, 100.0, "while", 64, ""],              # a prefill chunk
        [0, 500.0, 100.0, "fusion", 0, "dense"],
        [0, 700.0, 100.0, "while", 1, ""],
        [0, 700.0, 20.0, "fusion", 0, "dense"],
        [0, 720.0, 30.0, "fusion", 0, "retrieve"],
        [0, 750.0, 50.0, "convert", 0, ""],
    ], "spans": [
        [0.0, 1000.0, "bench.window"],
        [380.0, 420.0, "bench.poll"],          # [380, 800]
        [390.0, 400.0, "engine.poll"],         # [390, 790]
        [400.0, 80.0, "engine.decode.sync"],   # [400, 480]
        [480.0, 10.0, "engine.decode.emit"],   # [480, 490]
        [490.0, 150.0, "engine.prefill"],      # [490, 640]
        [640.0, 60.0, "engine.decode.launch"],  # [640, 700]
        [850.0, 100.0, "bench.wait"],
    ]}


def test_scope_time_and_buckets():
    tr = scopes.ScopedTrace.from_dict(hand_trace())
    assert tr.scoped
    got = tr.scope_s(exclude=("prefill",))
    want = {"dense": 120, "kv_write": 50, "retrieve": 130, "prepare": 100,
            "relevancy": 40, "apply": 60,
            # busy [0,400] + [700,800]; scoped ops cover [0,400] + [700,750]
            "other": 50}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    busy = tr.busy_s(exclude=("prefill",))
    assert busy == pytest.approx(500e-9)
    # the buckets add up to busy plus the 50 ns prepare overlaps retrieve
    assert sum(got.values()) == pytest.approx(busy + 50e-9)
    assert tr.scope_s()["dense"] == pytest.approx(220e-9)


def test_idle_time_by_innermost_span():
    tr = scopes.ScopedTrace.from_dict(hand_trace())
    idle = tr.idle_by_span()
    # gaps [400,500], [600,700], [800,1000]
    want = {"engine.decode.sync": 80, "engine.decode.emit": 10,
            "engine.prefill": 50, "engine.decode.launch": 60,
            "engine.poll": 0, "bench.wait": 100, "idle": 100}
    assert {k: v for k, v in idle.items()} == {
        k: pytest.approx(v * 1e-9) for k, v in want.items() if v}
    gaps = tr.idle_gaps(10)
    assert gaps == [["bench.wait", pytest.approx(200e-9)],
                    ["engine.decode.sync", pytest.approx(100e-9)],
                    ["engine.decode.launch", pytest.approx(100e-9)]]
    assert tr.top_ops(2) == [["dense/fusion", pytest.approx(220e-9)],
                             ["retrieve/fusion", pytest.approx(130e-9)]]


def test_readings_against_hand_counts():
    tr = scopes.ScopedTrace.from_dict(hand_trace())
    assert scopes.stage_ms(tr, 2, "retrieve") == pytest.approx(130e-6 / 2)
    assert scopes.stage_ms(tr, 2, "apply") == pytest.approx(60e-6 / 2)
    # idle under engine.* spans: 80 + 10 + 50 + 60 ns over 2 steps
    assert scopes.host_turn_ms(tr, 2) == pytest.approx(200e-6 / 2)
    with pytest.raises(RuntimeError):
        scopes.stage_ms(tr, 0, "prepare")
    d = hand_trace()
    d["ops"] = [o for o in d["ops"] if o[5] != "prepare"]
    with pytest.raises(RuntimeError):      # scoped, but no prepare op
        scopes.stage_ms(scopes.ScopedTrace.from_dict(d), 2, "prepare")


def test_a_program_without_scopes_or_engine_spans_reads_nothing():
    d = hand_trace()
    d["ops"] = [o[:5] for o in d["ops"]]
    d["spans"] = [s for s in d["spans"] if not s[2].startswith("engine.")]
    tr = scopes.ScopedTrace.from_dict(d)
    assert not tr.scoped
    assert scopes.stage_ms(tr, 2, "prepare") is None
    assert scopes.host_turn_ms(tr, 2) is None
    assert scopes.stage_ms(None, 2, "apply") is None


def _ctx(tr, steps=6):
    cell = types.SimpleNamespace(
        name="qwen3-32b-l4.long-decode",
        config=spec.load_json(spec.BENCH / "configs" / "qwen3-32b-l4.json"))
    stats = {"tokens": 12, "tok_s": 48.0, "queue_wait_s": [0.1, 0.2]}
    peak = spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"]
    return readers.Context(cell=cell, stats=stats, trace=tr, peak=peak,
                           contexts=[18000.5, 19000.5],
                           prompt_tokens_traced=0, decode_steps=steps)


def test_existing_readers_read_the_same_on_an_unscoped_trace():
    """Every reading ``trace.Trace`` gives on the recorded trace of a
    program without scopes or engine spans, and every per-layer reader over
    it, is the same from a ``ScopedTrace``; the readers of scopes and engine
    spans find nothing there."""
    d = trace.load(str(UNSCOPED))
    old, new = trace.Trace.from_dict(d), scopes.ScopedTrace.from_dict(d)
    assert not new.scoped
    assert (new.window_s, new.busy_s()) == (old.window_s, old.busy_s())
    assert new.busy_s(exclude=("prefill",)) == \
        old.busy_s(exclude=("prefill",))
    assert new.program("decode") == old.program("decode")
    assert new.kind == old.kind
    assert new.top_ops(10) == old.top_ops(10)
    assert new.idle_gaps(10) == old.idle_gaps(10)
    names = sorted(p.stem for p in (spec.BENCH / "metrics").glob("*.py"))
    assert len(names) >= 7
    for name in names:
        read = readers.module("metrics", name).read
        if name.startswith("stage_") or name == "host_turn_ms":
            assert read(_ctx(new)) is None, name
        else:
            assert read(_ctx(new)) == read(_ctx(old)), name


def test_hlo_op_names_from_a_cpu_profile(tmp_path):
    """The profiler keeps each program's HLO in the metadata plane; its
    instructions' op_names carry the scopes (on the CPU its op events do
    not)."""
    import jax
    import jax.numpy as jnp

    def staged(x, w):
        with jax.named_scope("prepare"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("relevancy"):
            return jnp.sort(y, axis=-1).sum()

    f = jax.jit(staged)
    x = jnp.ones((64, 64))
    f(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x, x).block_until_ready()
    jax.profiler.stop_trace()
    (xp,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(xp, "rb") as fh:
        names = scopes.hlo_op_names(fh.read())
    (prog,) = [v for v in names.values()
               if any(p.startswith("jit(staged)/") for p in v.values())]
    got = {scopes.scope_of(p) for p in prog.values()}
    assert {"prepare", "relevancy"} <= got
    assert scopes.scope_of("jit(f)/while/body/retrieve/apply/dot") == "apply"
    assert scopes.scope_of("jit(f)/reduce_sum") == ""
    assert scopes.instruction_name("%fusion.12 = bf16[2] fusion()") == \
        "fusion.12"


def _timeline(tr, ops):
    """100 ns bins of the window in which one of ``ops`` ran."""
    t = np.zeros(int(tr.window_s * 1e7) + 1, bool)
    for o in ops:
        a = int((max(o.start, tr.t0) - tr.t0) / 100)
        b = int(np.ceil((min(o.start + o.dur, tr.t1) - tr.t0) / 100))
        t[a:b] = True
    return t


def test_recorded_scoped_chip_trace():
    """A quarter second of qwen3-32b-l4.long-decode recorded on a v5e with
    the program's scopes (read from the HLO the profiler keeps) and the
    engine's spans: six decode steps of four layers, each followed by the
    host's argmax."""
    tr = scopes.ScopedTrace.from_dict(trace.load(str(SCOPED)))
    steps = 6
    names = {(m.name.split("(")[0], k) for m, k in zip(tr.modules, tr.kind)}
    assert names == {("jit_decode_paged", "decode"),
                     ("jit__argmax", "other")}
    assert tr.program("decode")[1] == steps
    for kernel, stage in (("relevancy_topk", "relevancy"),
                          ("paged_decode_attention", "apply")):
        assert {o.scope for o in tr.ops if o.name == kernel} == {stage}
    # each reading against a 100 ns timeline of the same ops
    busy = tr.busy_s(exclude=("prefill",))
    for stage in scopes.STAGES:
        got = scopes.stage_ms(tr, steps, stage)
        hand = _timeline(tr, [o for o in tr.ops if o.scope == stage
                              and o.name not in trace.CONTAINERS]).sum()
        assert got == pytest.approx(hand * 1e-4 / steps, rel=5e-3), stage
        assert got > 0.1
    share = 100 * sum(scopes.stage_ms(tr, steps, s) for s in scopes.STAGES) \
        / (1e3 * busy / steps)
    assert 20 < share < 60
    # the seven buckets account for the decode device time
    buckets = tr.scope_s(exclude=("prefill",))
    assert busy <= sum(buckets.values()) <= 1.05 * busy
    assert buckets["other"] > 0 and buckets["kv_write"] > 0
    # the host turn: idle bins under an engine.* span as the innermost one
    idle = ~_timeline(tr, tr.ops)
    inner = np.full(idle.shape, "", object)
    for s in sorted((s for s in tr.spans if s.name != trace.WINDOW_SPAN),
                    key=lambda s: (s.start, -s.dur)):
        a = max(int((s.start - tr.t0) / 100), 0)
        b = int(np.ceil((min(s.start + s.dur, tr.t1) - tr.t0) / 100))
        inner[a:b] = s.name                   # later (inner) spans win
    engine = idle & np.array([n.startswith("engine.") for n in inner])
    assert scopes.host_turn_ms(tr, steps) == pytest.approx(
        engine.sum() * 1e-4 / steps, rel=1e-2)
    # the longest idle gaps carry the engine's names, not the bench loop's
    assert all(g[0].startswith("engine.") for g in tr.idle_gaps(6))
    # and the longest ops their scope
    assert {"retrieve/fusion", "apply/copy_bitcast_fusion", "prepare/reshape",
            "apply/paged_decode_attention", "relevancy/relevancy_topk"} <= \
        {k for k, _ in tr.top_ops(16)}


def test_every_per_layer_metric_of_a_cell_reads_the_scoped_chip_trace():
    """Each per-layer reader a long-decode cell lists in BENCHMARK.json
    finds its number in the recorded scoped trace (six decode steps), the
    stage readers the same numbers ``scopes`` gives."""
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    cell = "qwen3-32b-l4.long-decode"
    names = [m["name"] for m in bench["per_layer"]
             if cell in m.get("workloads", [cell])]
    tr = scopes.ScopedTrace.from_dict(trace.load(str(SCOPED)))
    got = readers.read_all(_ctx(tr), names)
    assert sorted(got) == sorted(names)
    for stage in scopes.STAGES:
        assert got[f"stage_{stage}_ms"] == scopes.stage_ms(tr, 6, stage)
    assert got["host_turn_ms"] == scopes.host_turn_ms(tr, 6)
