#!/usr/bin/env python3
"""Compile rehearsal: each cell's prefill and decode programs, compiled for
a described TPU v5e (no chip needed), with the device memory each needs.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell> ...]

For every cell of BENCHMARK.json it compiles, at the cell's own sizes and on
one chip of a described ``v5e:2x2``:

  extend   chunked prefill (``models.extend_paged``, the engine's default
           chunk) over the full ``max_len`` view of every slot;
  decode   one pooled decode step (``models.decode_step_paged``) over the
           full view, with the engine's DSA fallback cond when the
           configuration runs DSA;
  prefill  bucketed admission prefill (``models.prefill_bucketed``) of every
           slot at the largest bucket the traffic admits together;

and prints ``compiled.memory_analysis()``: arguments, outputs, temporaries,
and their sum against the chip's 16 GB. Nothing runs; the figures are the
compiler's, and they count one program at a time.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def engine_programs(cfg, sc):
    """The three jitted programs as the engine builds them (decode with the
    dynamic-fallback cond of its sparse method)."""
    import jax

    from repro.core import placement
    from repro.core.methods import get_sparse_method
    from repro.models import attention as A
    from repro.models import model as M

    mem = cfg.memory.replace(method=sc.method)
    sparse_fn = None
    if sc.method != "none":
        _, mk = get_sparse_method(sc.method)
        raw = mk(cfg, mem, tp=sc.tp, page=sc.page)

        def sparse_fn(q, kc, vc, length, sp, k_new=None):
            return jax.lax.cond(
                placement.traced_use_sparse(length, mem),
                lambda _: raw(q, kc, vc, length, sp, k_new=k_new),
                lambda _: A.attention_decode(q, kc, vc, length, cfg,
                                             tp=sc.tp), None)

    decode = jax.jit(lambda p, tok, kp, vp, table, lengths, live, sp:
                     M.decode_step_paged(
                         p, cfg, tok, {"k_pages": kp, "v_pages": vp,
                                       "page_table": table,
                                       "lengths": lengths},
                         live, tp=sc.tp, sparse_fn=sparse_fn,
                         sparse_params=sp), donate_argnums=(2, 3))
    extend = jax.jit(lambda p, toks, kp, vp, table, lengths, nv:
                     M.extend_paged(p, cfg, toks, {
                         "k_pages": kp, "v_pages": vp, "page_table": table,
                         "lengths": lengths}, nv, tp=sc.tp),
                     donate_argnums=(2, 3))
    prefill = jax.jit(lambda p, toks, lens: M.prefill_bucketed(
        p, cfg, toks, lens, tp=sc.tp))
    return decode, extend, prefill


def rehearse(name: str, dev) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchlib import spec
    from repro.configs.base import ArchConfig, MemoryConfig
    from repro.serving import OffloadConfig, ServeConfig

    cell = spec.load_cell(name)
    arch = spec.arch(cell.config)
    cfg = arch.program_config(cell.config, ArchConfig, MemoryConfig)
    sc = spec.serve_config(cell.config, cell.traffic, ServeConfig,
                           OffloadConfig)
    on = SingleDeviceSharding(dev)
    shaped = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on), t)
    params, indexer = shaped(arch.shapes(cell.config))
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=on)
    B, L = sc.n_slots, sc.max_len
    ps = sc.kv_page_size
    pages = B * L // ps + 1
    kv = S((cfg.n_layers, pages, ps, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    table = S((B, L // ps), jnp.int32)
    vec = S((B,), jnp.int32)
    decode, extend, prefill = engine_programs(cfg, sc)
    sp = indexer if sc.method == "dsa" else None
    progs = {
        "decode": decode.lower(params, vec, kv, kv, table, vec,
                               S((B,), jnp.bool_), sp),
        "extend": extend.lower(params, S((B, sc.prefill_chunk), jnp.int32),
                               kv, kv, table, vec, vec),
    }
    upto = int(cell.traffic.get("warm", {}).get("batched_upto", 0))
    if upto:
        progs["prefill"] = prefill.lower(params, S((B, upto), jnp.int32),
                                         vec)
    for what, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        args, out, tmp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                          m.temp_size_in_bytes)
        alias = getattr(m, "alias_size_in_bytes", 0)
        total = args + out + tmp - alias
        print(f"{name} {what}: arguments {args / 2**30:.2f} GiB, outputs "
              f"{out / 2**30:.2f} GiB (aliased {alias / 2**30:.2f}), "
              f"temporaries {tmp / 2**30:.2f} GiB, total "
              f"{total / 2**30:.2f} GiB of 14.9 GiB", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", default=None)
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies

    from benchlib import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"] for w in spec.load_json(
        spec.ROOT / "BENCHMARK.json")["workloads"]]
    from repro.kernels import ops
    ops._interp = lambda: False      # compile the Mosaic kernels, not interp
    for name in names:
        rehearse(name, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
