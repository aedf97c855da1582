"""Serving launcher CLI — batched requests through the continuous-batching
scheduler with the memory pipeline enabled.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --method dsa --requests 8

``--disaggregate`` demonstrates the paper's prefill/decode role split
(Fig. 6b): the mesh's data axis is partitioned into prefill/decode submeshes
(on this CPU container both resolve to the same device; the mesh plumbing is
exercised either way).

``--offload on`` serves through the hetero offload executor (overlapped
lookahead selection on a second device, src/repro/hetero) and prints its
per-stage overhead breakdown; launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` for a real split.

``--main-mesh N`` (with ``--offload``) runs the apply phase itself
sequence-parallel over an N-device main mesh — LSE-merged
``distributed_paged_sparse_decode`` behind the engine's ``page_attn`` seam;
composes with ``--offload-shards M`` for the full M-selection x N-apply
topology under ``XLA_FLAGS=--xla_force_host_platform_device_count=N+M``.

``--retrieval on`` enables the document-memory service (src/repro/retrieval):
per-slot FLARE triggers over the decode logits, retrieved documents (or MaC
memory embeddings with ``--retrieval-kind mac``) spliced into the paged pool
overlapped against decode. Composes with ``--offload``.

``--replicas N`` serves the same request stream through a :class:`Router`
over N engine replicas, each pinned to its own device group, sharing one
retrieval corpus — the fleet-scale front of the same request-level API.

``--width smoke`` (the default) builds the CPU-sized ``.smoke()`` config;
``--width full`` keeps the published widths of ``--arch`` and cuts only the
depth (``--layers``), with page sizes from the ``ServeConfig`` defaults.
Weights are random, drawn from ``--seed``:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b \
        --width full --layers 8 --method dsa --slots 2 --prompt-len 4608
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import Engine, OffloadConfig, Request, Router, \
    ServeConfig


def build_model(arch: str, *, width: str = "smoke", layers: int = 0,
                tp: int = 4, seed: int = 0):
    """(cfg, params): the smoke config, or the published widths with the
    depth cut to ``layers`` (0 keeps the config's own depth)."""
    cfg = get_arch(arch)
    if width == "smoke":
        cfg = cfg.smoke()
    if layers:
        cfg = cfg.replace(n_layers=layers)
    return cfg, init_params(cfg, jax.random.PRNGKey(seed), tp=tp)


def serve_config(*, width: str = "smoke", **kw) -> ServeConfig:
    """ServeConfig for the launcher; the smoke width keeps its 8-token DSA
    page, the full width takes every page size from the defaults."""
    if width == "smoke":
        kw.setdefault("page", 8)
    return ServeConfig(**kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--width", default="smoke", choices=["smoke", "full"],
                    help="smoke = CPU-sized .smoke() config; full = the "
                         "published widths of --arch")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0 = keep)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--method", default="dsa",
                    choices=["none", "dsa", "seer", "lserve"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--disaggregate", action="store_true")
    ap.add_argument("--offload", default="off",
                    choices=["on", "off", "sync", "overlap"],
                    help="hetero offload executor (on = overlap)")
    ap.add_argument("--offload-shards", type=int, default=1,
                    help="KV-sequence shards on the offload side (one "
                         "device per shard; launch with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N+1)")
    ap.add_argument("--main-mesh", type=int, default=1,
                    help="devices in the MAIN apply mesh (sequence-"
                         "parallel LSE-merged apply; composes with "
                         "--offload-shards: launch with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N+M)")
    ap.add_argument("--offload-validate", action="store_true",
                    help="replay every consumed lookahead selection "
                         "synchronously and bit-check it")
    ap.add_argument("--fused-steps", type=int, default=1,
                    help="decode steps fused into one on-device lax.scan "
                         "per host dispatch (1 = stepped host loop)")
    ap.add_argument("--retrieval", default="off",
                    choices=["on", "off", "inline", "sync", "overlap"],
                    help="document-memory service (on = overlap)")
    ap.add_argument("--retrieval-kind", default="rag",
                    choices=["rag", "mac"])
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a Router over N engine replicas, "
                         "each pinned to its own device group (launch "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=K*N for a real split); rag "
                         "retrieval shares ONE corpus across the fleet")
    ap.add_argument("--docs", type=int, default=2048,
                    help="synthetic corpus size for --retrieval-kind rag")
    args = ap.parse_args(argv)
    from repro.hetero import resolve_cli_offload, resolve_cli_retrieval
    try:
        offload = resolve_cli_offload(args.offload, args.method)
        ret_mode = resolve_cli_retrieval(args.retrieval)
    except ValueError as e:
        ap.error(str(e))

    enable_compile_cache()
    cfg, params = build_model(args.arch, width=args.width,
                              layers=args.layers, tp=args.tp, seed=args.seed)
    if args.disaggregate and jax.device_count() >= 2:
        from repro.launch.mesh import make_mesh, split_mesh_roles
        mesh = make_mesh((jax.device_count() // 1, 1), ("data", "model"))
        pre, dec = split_mesh_roles(mesh)
        print(f"disaggregated roles: prefill={pre.devices.size} devices, "
              f"decode={dec.devices.size} devices")
    retrieval = None
    if ret_mode:
        from repro.core.methods.mac import MacConfig
        from repro.retrieval import RetrievalConfig
        if args.retrieval_kind == "rag":
            from repro.data import build_corpus
            corpus = build_corpus(args.docs, retrieval_vocab=1024,
                                  doc_max=16, gen_vocab=cfg.vocab_size,
                                  seed=0)
            retrieval = RetrievalConfig(kind="rag", mode=ret_mode,
                                        corpus=corpus, k=2,
                                        min_interval=4, max_retrievals=2)
        else:
            retrieval = RetrievalConfig(
                kind="mac", mode=ret_mode, min_interval=4, max_retrievals=2,
                mac=MacConfig(segment_len=16, memory_slots=8, retrieve_k=2))
    extra = 96 if retrieval is not None else 16
    offload_cfg = OffloadConfig(
        mode=offload, validate=args.offload_validate,
        shards=args.offload_shards if offload != "off" else 1,
        main_mesh=args.main_mesh if offload != "off" else 1)
    sc = serve_config(width=args.width,
                      max_len=args.prompt_len + args.max_new + extra,
                      n_slots=args.slots, method=args.method,
                      tp=args.tp, offload_cfg=offload_cfg,
                      fused_steps=args.fused_steps, retrieval=retrieval)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len), args.max_new)
            for i in range(args.requests)]
    if args.replicas > 1:
        front = Router.build(cfg, params, sc, n_replicas=args.replicas,
                             key=jax.random.PRNGKey(args.seed + 1))
        engines = [r.engine for r in front.replicas]
    else:
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(args.seed + 1))
        front, engines = eng, [eng]
    t0 = time.perf_counter()
    handles = [front.submit(r) for r in reqs]
    done = front.drain()
    wall = time.perf_counter() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttft = [h.ttft_s() for h in handles if h.ttft_s() is not None]
    shards = args.offload_shards if offload != "off" else 1
    mesh_n = args.main_mesh if offload != "off" else 1
    print(f"method={args.method} offload={offload}"
          f"{f'/shards={shards}' if shards > 1 else ''}"
          f"{f'/mesh={mesh_n}' if mesh_n > 1 else ''} "
          f"retrieval={ret_mode or 'off'}"
          f"{f' replicas={args.replicas}' if args.replicas > 1 else ''}: "
          f"{len(done)}/{args.requests} requests, "
          f"{toks} tokens, {toks / wall:.1f} tok/s, "
          f"p50 TTFT {1e3 * float(np.median(ttft)):.1f}ms")
    if args.replicas > 1:
        print("router report:")
        print(json.dumps(front.report(), indent=2, sort_keys=True))
    if args.fused_steps > 1:
        hs = sum(e.stats["host_steps"] for e in engines)
        ds = sum(e.stats["decode_steps"] for e in engines)
        print(f"fused decode: {ds} device steps in {hs} host dispatches "
              f"({ds / max(hs, 1):.1f} steps/dispatch)")
    for i, e in enumerate(engines):
        tag = f" (replica {i})" if len(engines) > 1 else ""
        if e.hetero is not None:
            print(f"hetero per-stage breakdown{tag} (Fig. 3 style):")
            print(json.dumps(e.hetero.report(), indent=2, sort_keys=True))
        if e.retrieval is not None:
            print(f"retrieval service report{tag}:")
            print(json.dumps(e.retrieval.report(), indent=2,
                             sort_keys=True))


if __name__ == "__main__":
    main()
