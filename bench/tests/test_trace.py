"""Trace reduction (bench/benchlib/trace.py) on a hand-made trace with known
answers, and on a trace recorded on the chip."""
import pathlib

import numpy as np
import pytest
from tiny import spec  # noqa: F401

from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data"


def hand_trace():
    # modules: [dev, start, dur, name, run]; ops: [dev, start, dur, name, rows]
    return {"devices": [0], "modules": [
        [0, 0.0, 250.0, "jit__lambda(1)", 1],
        [0, 300.0, 100.0, "jit__lambda(1)", 2],
        [0, 1200.0, 60.0, "jit__lambda(2)", 3],          # after the window
    ], "ops": [
        [0, 0.0, 250.0, "while", 1],                     # the layer loop
        [0, 0.0, 100.0, "fusion", 0],
        [0, 50.0, 150.0, "copy_fusion", 0],
        [0, 300.0, 100.0, "while", 1],
        [0, 300.0, 100.0, "relevancy_topk", 0],
        [0, 1200.0, 50.0, "while", 128],
    ], "spans": [
        [0.0, 1000.0, "bench.window"],
        [150.0, 750.0, "bench.poll"],
        [900.0, 100.0, "bench.wait"],
    ]}


def test_busy_idle_programs_kernels():
    tr = trace.Trace.from_dict(hand_trace())
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.busy_s() == pytest.approx(350e-9)       # [0,250] + [300,400]
    assert tr.program("decode") == (pytest.approx(350e-9), 2)
    assert tr.program("prefill") == (0.0, 0)          # outside the window
    assert tr.kernel("relevancy_topk") == (pytest.approx(100e-9), 1)
    gaps = tr.idle_gaps(10)
    assert gaps[0] == ["bench.poll", pytest.approx(600e-9)]   # [400, 1000]
    assert gaps[1] == ["bench.poll", pytest.approx(50e-9)]    # [250, 300]
    assert tr.top_ops(1) == [["copy_fusion", pytest.approx(150e-9)]]


def test_hlo_names():
    hlo = ("%while.13 = (s32[]{:T(128)}, bf16[2,1,5120]{2,0,1}, "
           "bf16[4,8192,5120]{2,1,0}) while((s32[], bf16[2,1,5120]) %t)")
    assert trace.short_name(hlo) == "while"
    assert trace.token_rows(hlo, 5120) == 1
    assert trace.token_rows(hlo.replace("[2,1,5120]", "[8,128,5120]"),
                            5120) == 128
    assert trace.short_name("%relevancy_topk.3 = (f32[2]) custom-call()") \
        == "relevancy_topk"
    assert trace.token_rows("%fusion.2 = bf16[2,1,5120]{} fusion()", 5120) == 0


def test_recorded_chip_trace():
    """A quarter second of qwen3-32b-l4.long-decode recorded on a v5e: six
    decode steps of four layers, each followed by the host's argmax."""
    d = trace.load(str(DATA / "long_decode_trace.json.gz"))
    tr = trace.Trace.from_dict(d)
    kinds = dict(zip([m.run for m in tr.modules], tr.kind))
    names = {m.run: m.name.split("(")[0] for m in tr.modules}
    assert sorted(set(zip(names.values(), kinds.values()))) == [
        ("jit__argmax", "other"), ("jit__lambda", "decode")]
    busy, runs = tr.program("decode")
    assert runs == 6
    # each kernel runs once per layer of every decode step
    for k in ("relevancy_topk", "paged_decode_attention"):
        seconds, calls = tr.kernel(k)
        assert calls == 4 * runs and 0 < seconds < busy
    # busy time against a 100 ns timeline of the same ops
    t = np.zeros(int(tr.window_s * 1e7) + 1, bool)
    for o in tr.ops:
        a = int((max(o.start, tr.t0) - tr.t0) / 100)
        b = int(np.ceil((min(o.start + o.dur, tr.t1) - tr.t0) / 100))
        t[a:b] = True
    assert tr.busy_s() == pytest.approx(t.sum() * 1e-7, rel=2e-3)
    assert busy <= tr.busy_s() < tr.window_s
    assert all(label.startswith("bench.") for label, _ in tr.idle_gaps(5))
    # no prefill in the window: the step's device time is the decode
    # program's and the host argmax's, a hair above the decode program's
    per_step = tr.busy_s(exclude=("prefill",)) / runs
    assert busy / runs <= per_step < 1.001 * busy / runs


def test_decode_step_ms_divides_by_engine_steps():
    """Device time outside prefill programs over the decode steps the
    engine counted: a dispatch that carries several steps reads the same
    per step, and a window with no decode step is an error."""
    import types

    from benchlib import readers
    d = hand_trace()
    d["modules"].append([0, 500.0, 100.0, "jit__lambda(3)", 4])
    d["ops"] += [[0, 500.0, 100.0, "while", 64], [0, 520.0, 50.0, "fusion", 0]]
    tr = trace.Trace.from_dict(d)
    assert tr.program("prefill") == (pytest.approx(100e-9), 1)
    assert tr.busy_s(exclude=("prefill",)) == pytest.approx(350e-9)
    read = readers.module("metrics", "decode_step_ms").read
    ctx = lambda n: types.SimpleNamespace(
        trace=tr, decode_steps=n, cell=types.SimpleNamespace(name="c"))
    assert read(ctx(2)) == pytest.approx(350e-9 * 1e3 / 2)
    assert read(ctx(14)) == pytest.approx(350e-9 * 1e3 / 14)
    with pytest.raises(RuntimeError):
        read(ctx(0))
