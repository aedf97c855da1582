"""One run of one cell: build the engine from the cell's files, warm every
shape its traffic reaches, open the window, serve, close it, read the
metrics, free the program's state, and judge what it served against the
plain reference.

Set-up (``setup_s``) is everything from the process's start to the window's
opening: importing, drawing the weights, building the engine, compiling or
loading every program, and for an open loop the ``warm_s`` of traffic that
brings the queue to its steady state.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from benchlib import correct, drive, readers, scopes, spec, trace, weights
from benchlib.spec import BENCH, ROOT

pc = time.perf_counter
OUT = ROOT / ".bench_out"          # what a run leaves behind (gitignored)


class CompileClock:
    """Programs obtained (compiled or loaded from the persistent cache) and
    the seconds it took, as JAX reports them."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.count, self.seconds, self.hits, self.misses)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, keep_trace: Optional[str] = None,
             fault: Optional[Callable] = None, control: bool = False) -> Dict:
    """Returns the result line (without ``device``'s identity fields), with
    the program's readings and verdict also under ``sound``. ``fault`` is
    handed the engine before any request (bench/benchlib/faults.py breaks
    the timed path through it); ``control`` also reads the float8 control
    on the same sample and judges it by the same limits
    (``result["control"]``)."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs.base import ArchConfig, MemoryConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Engine, OffloadConfig, Request, ServeConfig

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    traffic, config = cell.traffic, cell.config
    cfg = spec.arch(config).program_config(config, ArchConfig, MemoryConfig)
    sc = spec.serve_config(config, traffic, ServeConfig, OffloadConfig)

    params, indexer = weights.generate(config, seed)
    jax.block_until_ready((params, indexer))
    eng = Engine(cfg, params, sc, key=weights.seed_key(seed, salt=2))
    if eng.sparse_params is not None:
        eng.sparse_params = indexer       # the benchmark's, like the rest
    if fault is not None:
        fault(eng)
    plan = readers.module("traffic", "generator").plan(
        traffic, seed, config["vocab_size"], seconds)
    drv = drive.ServingLoop(eng, Request)
    t0 = pc()
    drv.warm_shapes(plan.shape_groups)
    t_shapes = pc() - t0
    sessions = []
    if plan.sessions:
        sessions = drv.start_sessions(plan.sessions, plan.session_warm_tokens)
    t_base = pc()
    t_open = t_base + plan.warm_s
    t_close = t_open + seconds
    marks: Dict[str, object] = {}
    trace_dir = OUT / "trace"
    span = []

    def on_open():
        marks["open"] = clock.snap()
        marks["setup_s"] = pc() - t_start
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the bench.* spans only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        marks["steps0"] = dict(eng.stats)
        span.append(jax.profiler.TraceAnnotation(trace.WINDOW_SPAN))
        span[0].__enter__()

    def on_close():
        span[0].__exit__(None, None, None)
        if traced:
            jax.profiler.stop_trace()
        marks["close"] = clock.snap()
        marks["steps1"] = dict(eng.stats)
        marks["queue"] = eng.queue_depth()

    drv.run(plan.arrivals, t_base, t_open, t_close, plan.drain_s,
            on_open, on_close)
    recs = list(drv.recs.values())
    stats = drive.window_stats([r for r in recs if r.phase != "shape"],
                               t_open, t_close)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    c_open, c_close = marks["open"], marks["close"]
    in_window = c_close[0] - c_open[0]
    steps = marks["steps1"]["decode_steps"] - marks["steps0"]["decode_steps"]

    # -- metrics ---------------------------------------------------------
    result: Dict = {"correct": False, "attempted": stats["attempted"],
                    "failed": stats["failed"], "metrics": {},
                    "device": {"memory_peak_bytes": peak}}
    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    if traced:
        tr = None
        xp = trace.find_xplane(str(trace_dir))
        if xp is not None:
            raw = scopes.extract(xp, config["hidden_size"])
            if keep_trace:
                trace.save(raw, keep_trace)
                shutil.copy(xp, keep_trace + ".xplane.pb")
            tr = scopes.ScopedTrace.from_dict(raw)
            shutil.rmtree(trace_dir, ignore_errors=True)
            per = 1e3 / max(steps, 1)
            log("device ms per decode step by scope: " + ", ".join(
                f"{k} {v * per:.3f}" for k, v in
                tr.scope_s(exclude=scopes.DECODE_ONLY).items()))
            log("idle ms per decode step by host span: " + ", ".join(
                f"{k} {v * per:.3f}" for k, v in
                sorted(tr.idle_by_span().items(), key=lambda kv: -kv[1])))
        contexts = []
        for r in sessions:
            a = len(r.prompt) + sum(1 for s in r.stamps if s < t_open)
            b = len(r.prompt) + sum(1 for s in r.stamps if s < t_close)
            contexts.append((a + b) / 2)
        ptoks = sum(len(r.prompt) for r in recs
                    if r.phase in ("warm", "window", "drain") and r.stamps
                    and t_open <= r.stamps[0] < t_close)
        ctx = readers.Context(cell=cell, stats=stats, trace=tr,
                              peak=peak_of(dev.device_kind), contexts=contexts,
                              prompt_tokens_traced=ptoks,
                              decode_steps=steps)
        vals = readers.read_all(ctx, [m["name"] for m in cell.per_layer()])
        if tr is not None:
            result["device"]["busy_s"] = tr.busy_s()
            result["device"]["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(10),
                                   "idle_gaps": tr.idle_gaps(10)}
    else:
        vals = {"setup_s": marks["setup_s"], "tok_s": stats["tok_s"]}
        for name, key, q in (("itl_p95_ms", "itl_s", 95),
                             ("ttft_p50_ms", "ttft_s", 50),
                             ("ttft_p95_ms", "ttft_s", 95)):
            v = drive.percentile(stats[key], q)
            if v is not None:
                vals[name] = 1e3 * v
        wanted = {m["name"] for m in cell.end_to_end()}
        vals = {k: v for k, v in vals.items() if k in wanted}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in vals.items()}

    late = stats["late_s"]
    log(f"set-up {marks['setup_s']:.3f}s (shape warm-up {t_shapes:.3f}s); "
        f"programs obtained: {c_open[0]} in set-up ({c_open[1]:.1f}s, "
        f"cache hits {c_open[2]}, misses {c_open[3]}), {in_window} in the "
        f"window; compile cache {cache}")
    log(f"window {seconds}s: {stats['tokens']} tokens, "
        f"{stats['tok_s']:.3f} tok/s; decode steps {steps}; polls {drv.polls}; peak HBM {peak / 2**30:.3f} GiB")
    if late:
        log(f"generator lateness: p50 {1e3 * np.median(late):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms over {len(late)} requests")
    due = [r for r in recs if r.phase == "window"]
    log(f"requests due in the window {len(due)}, started "
        f"{sum(1 for r in due if r.admitted is not None)}, first token "
        f"{sum(1 for r in due if r.stamps)}, finished "
        f"{sum(1 for r in due if r.done)}; sessions {len(sessions)}; queue "
        f"at the close {marks['queue']}")
    for k, v in result["metrics"].items():
        log(f"metric {k} = {v['value']!r} {v['unit']}")

    # -- correctness, after the program's state is gone --------------------
    samples = correct.sample(recs, seed, t_close)
    del eng, drv, sessions
    gc.collect()
    t0 = pc()
    g = correct.gaps(params, indexer, config, samples)
    found = correct.summary(g)
    lims = correct.limits(cell.name) or {k: {} for k in found}
    result["checks"], ok = correct.judge(found, lims)
    result["correct"] = bool(stats["failed"] == 0 and g.size and ok)
    result["sound"] = dict(found, **correct.spread(g),
                           correct=result["correct"])
    log(f"reference over {len(samples)} requests, {g.size} served tokens, "
        f"{pc() - t0:.3f}s; gaps {correct.spread(g)}")
    if control:
        cg = correct.gaps(params, indexer, config, samples, quant="fp8")
        cfound = correct.summary(cg)
        _, cok = correct.judge(cfound, lims)
        result["control"] = dict(cfound, **correct.spread(cg),
                                 correct=bool(cg.size and cok))
        log(f"control (float8) {result['control']}")
    for k, c in result["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    return result


def peak_of(kind: str) -> Dict:
    peaks = spec.load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]
