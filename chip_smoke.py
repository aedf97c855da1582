#!/usr/bin/env python3
"""Chip smoke test: the sparse-attention serving path on a TPU.

One process. With no arguments it needs one chip and runs three phases:

  1. kernels  — ``paged_decode_attention``, ``relevancy_topk`` and
     ``page_minmax`` at Qwen3-32B widths against the float32 oracles of
     ``kernels/ref.py`` (relevancy selections compared as sets, modulo
     ties at the k-th score);
  2. inline   — one ``Engine(method="dsa")`` built through the builders of
     ``launch/serve.py`` at the published qwen3-32b widths (depth cut to
     8 layers, random weights from a fixed seed) serves two waves through
     ``submit``/``drain``: 2 prompts of 64 tokens, which stay on the dense
     branch, and 2 of 4,608 tokens, which take chunked prefill and cross
     ``min_context=4096`` onto the sparse branch; 16 new tokens each;
  3. overlap  — the same waves under ``OffloadConfig(mode="overlap")``
     (two-phase apply ``decode_step_paged_presel`` + offload-side
     selection, both on the one chip).

``--chips 4`` runs only the four-chip path: the waves under
``OffloadConfig(mode="overlap", shards=2, main_mesh=2)`` (apply mesh on two
chips, one selection shard on each of the other two) against the same
requests under ``mode="sync"`` with one shard and no mesh, and prints the
token-match rate.

Every request must finish, and the decode program a phase ran must hold a
Mosaic kernel (``tpu_custom_call``). Compile seconds, TTFT and tok/s are
printed as unbenchmarked figures. The last line of standard output is one
JSON object naming the device; without a TPU, or away from the repository,
the script exits non-zero and prints no result.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # one 2x2 host
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3-32b"
SHORT, LONG, MAX_NEW, SLOTS = 64, 4608, 16, 2
TP = 4                      # launch/serve.py --tp default (head padding)
LAYERS = 8                  # of 64: what one 16 GB v5e holds with the pool
SEED = 0                    # weights, prompts and kernel inputs


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


class CompileClock:
    """Sums the backend compile time JAX reports while it is installed."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def check_kernels(cfg) -> None:
    """Kernels at the model's widths vs the float32 oracles (32k context)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.serving import ServeConfig

    mem, page = cfg.memory, ServeConfig().page
    B, S = 2, 32768
    KV, Hq, dh = cfg.n_kv_heads, cfg.n_heads, cfg.hd
    n_sel = mem.top_k // page + 1            # selection + the recency page
    rng = np.random.default_rng(SEED)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.bfloat16)
    exact = jax.default_matmul_precision("highest")

    # apply stage: attention over the selected pages
    q = normal(ks[0], (B, Hq, dh))
    kc, vc = normal(ks[1], (B, S, KV, dh)), normal(ks[2], (B, S, KV, dh))
    pages = np.stack([rng.choice(S // page, n_sel, replace=False)
                      for _ in range(B)]).astype(np.int32)
    pages[0, -3:] = -1                        # holes are skipped
    length = rng.integers(S // 2, S + 1, B).astype(np.int32)
    o1, l1 = ops.paged_decode_attention(q, kc, vc, jnp.asarray(pages),
                                        jnp.asarray(length), page_size=page)
    with exact:
        o2, l2 = ref.paged_decode_attention(q, kc, vc, jnp.asarray(pages),
                                            page, jnp.asarray(length))
    err_o = float(jnp.max(jnp.abs(o1 - o2)))
    err_l = float(jnp.max(jnp.abs(l1 - l2)))
    print(f"kernel paged_decode_attention B={B} S={S} KV={KV} G={Hq // KV} "
          f"dh={dh} page={page} n_sel={n_sel}: max|out err|={err_o:.3e} "
          f"max|lse err|={err_l:.3e}", flush=True)
    assert err_o < 2e-2 and err_l < 2e-2, (err_o, err_l)

    # relevancy + retrieve: fused scoring and top-k over the page index
    n_pages, k = S // page, mem.top_k // page
    qi = normal(ks[3], (B, mem.index_heads, mem.index_dim))
    keys = normal(ks[4], (B, n_pages, mem.index_dim))
    w = jax.nn.softmax(jax.random.normal(ks[5], (B, mem.index_heads)), -1)
    v1, i1 = ops.relevancy_topk(qi, keys, w, k, block=2048)
    with exact:
        scores = np.asarray(ref.relevancy_scores(qi, keys, w))
    v2 = -np.sort(-scores, axis=-1)[:, :k]
    err_v = float(np.max(np.abs(np.asarray(v1) - v2)))
    tie = 2e-3 * float(np.abs(v2).max())
    swaps = 0
    for b in range(B):
        got, want = set(np.asarray(i1[b]).tolist()), set(
            np.argsort(-scores[b], kind="stable")[:k].tolist())
        for i in got ^ want:                  # only ties at the k-th score
            assert abs(scores[b, i] - v2[b, -1]) <= tie, (b, i)
            swaps += 1
    print(f"kernel relevancy_topk B={B} pages={n_pages} heads="
          f"{mem.index_heads} dim={mem.index_dim} k={k} block=2048: "
          f"max|val err|={err_v:.3e} tie swaps={swaps // 2}", flush=True)
    assert err_v <= tie, err_v

    # LServe prepare stage: page-wise min/max
    ps = mem.block_size
    mn1, mx1 = ops.page_minmax(kc, page_size=ps)
    mn2, mx2 = ref.page_minmax(kc, ps)
    err_m = max(float(jnp.max(jnp.abs(mn1 - mn2))),
                float(jnp.max(jnp.abs(mx1 - mx2))))
    print(f"kernel page_minmax B={B} S={S} page={ps}: max|err|={err_m:.3e}",
          flush=True)
    assert err_m == 0.0, err_m


def decode_program(eng, lengths) -> str:
    """Lowered text of the decode step the engine dispatches at these slot
    lengths (inline pooled decode, or the two-phase apply step)."""
    import jax.numpy as jnp
    import numpy as np

    lengths = np.asarray(lengths, np.int32)
    n = eng.sc.n_slots
    table = eng._table_view(lengths)
    if eng.hetero is None:
        fn, extra = eng._decode_paged, eng.sparse_params
    else:
        fn, extra = eng.hetero._apply_fn(table.shape[1]), eng.hetero._neg_sel
    return fn.lower(eng.params, jnp.zeros((n,), jnp.int32),
                    eng.pool.device["k_pages"], eng.pool.device["v_pages"],
                    table, jnp.asarray(lengths), jnp.ones((n,), bool),
                    extra).as_text()


def serve_waves(cfg, params, offload, label: str, clock):
    """Serve the two waves twice (cold, then warm) on one engine; returns
    (tokens per request of the warm pass, engine)."""
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.serving import Engine, Request

    sc = serve.serve_config(width="full", max_len=LONG + MAX_NEW,
                            n_slots=SLOTS, method="dsa", tp=TP,
                            offload_cfg=offload)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(SEED + 1))
    rng = np.random.default_rng(SEED)
    prompts = {name: [rng.integers(0, cfg.vocab_size, plen)
                      for _ in range(SLOTS)]
               for name, plen in (("dense", SHORT), ("sparse", LONG))}
    tokens, rid = {}, 0
    for rnd in ("cold", "warm"):
        for name, batch in prompts.items():
            c0, t0 = clock.seconds, time.perf_counter()
            handles = []
            for p in batch:
                handles.append(eng.submit(Request(rid, p, MAX_NEW)))
                rid += 1
            eng.drain()
            wall = time.perf_counter() - t0
            done = [h for h in handles
                    if h.done and len(h.tokens) == MAX_NEW]
            assert len(done) == len(handles), \
                f"{label}/{name}: {len(done)}/{len(handles)} finished"
            ttft = max(h.ttft_s() for h in handles)
            n_tok = sum(len(h.tokens) for h in handles)
            print(f"{label} {rnd} {name} wave (unbenchmarked): "
                  f"{len(done)}/{len(handles)} requests x {MAX_NEW} tokens, "
                  f"prompt {len(batch[0])}, wall {wall:.2f}s, max TTFT "
                  f"{ttft:.3f}s, {n_tok / wall:.1f} tok/s, compile "
                  f"{clock.seconds - c0:.1f}s", flush=True)
            if rnd == "warm":
                tokens[name] = [list(h.tokens) for h in handles]
    for name, plen in (("dense", SHORT), ("sparse", LONG)):
        text = decode_program(eng, [plen + MAX_NEW - 1] * SLOTS)
        assert "tpu_custom_call" in text, f"{label}/{name}: no Mosaic kernel"
    print(f"{label}: all {rid} requests finished; decode programs hold "
          f"Mosaic kernels (tpu_custom_call)", flush=True)
    return tokens, eng


def one_chip(clock) -> None:
    import jax

    from repro.configs import get_arch
    from repro.launch import serve
    from repro.serving import OffloadConfig

    check_kernels(get_arch(ARCH))
    gc.collect()
    t0 = time.perf_counter()
    cfg, params = serve.build_model(ARCH, width="full", layers=LAYERS,
                                    tp=TP, seed=SEED)
    jax.block_until_ready(params)
    print(f"model {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads / "
          f"{cfg.n_kv_heads} kv, head_dim {cfg.hd}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.n_layers} layers; init "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    for label, offload in (("inline", OffloadConfig()),
                           ("overlap", OffloadConfig(mode="overlap"))):
        _, eng = serve_waves(cfg, params, offload, label, clock)
        if eng.hetero is not None:
            rep = eng.hetero.report()
            print(f"{label}: {rep['offload_steps']} offloaded steps, "
                  f"{rep['local_fallback_steps']} dense-fallback steps",
                  flush=True)
            assert rep["offload_steps"] > 0, "no step took the offload path"
        del eng                     # frees its pool and indexer weights
        gc.collect()


def four_chips(clock) -> None:
    import jax

    from repro.launch import serve
    from repro.serving import OffloadConfig

    cfg, params = serve.build_model(ARCH, width="full", layers=LAYERS,
                                    tp=TP, seed=SEED)
    jax.block_until_ready(params)
    base, eng = serve_waves(cfg, params, OffloadConfig(mode="sync"),
                            "sync/1x1", clock)
    del eng
    gc.collect()
    mesh, eng = serve_waves(
        cfg, params, OffloadConfig(mode="overlap", shards=2, main_mesh=2),
        "overlap/shards2xmesh2", clock)
    devs = eng.hetero.report()["devices"]
    placed = list(devs["main_mesh"]) + list(devs["offload"])
    print(f"placement: main mesh {devs['main_mesh']}, offload "
          f"{devs['offload']}", flush=True)
    assert len(set(placed)) == 4, f"mesh and offload share chips: {placed}"
    same = total = 0
    for name in base:
        for a, b in zip(base[name], mesh[name]):
            same += sum(x == y for x, y in zip(a, b))
            total += len(a)
            print(f"{name}: sync {a}\n{' ' * len(name)}  mesh {b}")
    print(f"token match rate (overlap shards=2 x mesh=2 vs sync 1x1): "
          f"{same}/{total} = {same / total:.3f}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: kernels + inline + overlap on one chip; "
                         "4: only the mesh/shard path vs its baseline")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro" / "serving").is_dir():
        return _fail(f"no repro package under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax

        devs = jax.devices()
    except Exception as e:  # no backend at all
        return _fail(f"JAX found no device: {e}")
    if devs[0].platform != "tpu":
        return _fail(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < args.chips:
        return _fail(f"--chips {args.chips} but JAX found {len(devs)}")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(clock)
    stats = devs[0].memory_stats() or {}
    print(f"done in {time.perf_counter() - t0:.1f}s; compile "
          f"{clock.seconds:.1f}s over {clock.count} programs; peak device "
          f"memory {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
