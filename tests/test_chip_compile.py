"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e.

Each kernel is compiled at Qwen3-32B widths (64 query heads / 8 KV,
head_dim 128, 32k context, DSA indexer 64 x 128 with relevancy block 2048),
and the paged kernel's pool form also at Qwen2-7B widths (28 / 4), for a
DESCRIBED v5e — the TPU compiler is installed, the chip is not — and
the program must hold the Mosaic kernel (``tpu_custom_call``). This catches
what interpret mode cannot: block shapes Mosaic refuses, primitives it
cannot lower, VMEM overuse. Nothing runs, so results are not checked here
(``chip_smoke.py`` checks them on the chip).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bm25_topk, flash_attention, page_pool, \
    relevancy_topk, sparse_decode_attention

from hlo_checks import DSA_VIEW_OPS, view_ops

B, S, HQ, KV, DH = 2, 32768, 64, 8, 128     # qwen3-32b heads, 32k context
PAGE, N_SEL = 16, 2048 // 16 + 1            # DSA micro-page, top-k + recency
IDX_HEADS, IDX_DIM, REL_BLOCK = 64, 128, 2048
DOCS, TERMS, BM25_BLOCK = 32768, 16, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e device, with the persistent compilation cache
    off (a described-chip entry cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _paged(sds):
    fn = functools.partial(sparse_decode_attention.paged_decode_attention,
                           page_size=PAGE, interpret=False)
    return fn, (sds((B, HQ, DH), jnp.bfloat16),
                sds((B, S, KV, DH), jnp.bfloat16),
                sds((B, S, KV, DH), jnp.bfloat16),
                sds((B, N_SEL), jnp.int32), sds((B,), jnp.int32))


def _paged_pool(kv, g, n_layers):
    """The pool form at a cell's widths: a stacked pool of 4,097 pages of
    16 (two 32k slots and the zero page), a traced layer index, the page
    table and the selection."""
    pool_pages = B * S // PAGE + 1

    def build(sds):
        def fn(q, kp, vp, layer, table, pids, length):
            return sparse_decode_attention.paged_decode_attention(
                q, page_pool.PooledKV(kp, layer, table),
                page_pool.PooledKV(vp, layer, table), pids, length,
                page_size=PAGE, interpret=False)
        pool = sds((n_layers, pool_pages, PAGE, kv, DH), jnp.bfloat16)
        return fn, (sds((B, kv * g, DH), jnp.bfloat16), pool, pool,
                    sds((), jnp.int32), sds((B, S // PAGE), jnp.int32),
                    sds((B, N_SEL), jnp.int32), sds((B,), jnp.int32))
    return build


def _relevancy(sds):
    n_pages = S // PAGE
    fn = functools.partial(relevancy_topk.relevancy_topk_candidates,
                           block=REL_BLOCK, c=0, valid_len=n_pages - 5,
                           interpret=False)
    return fn, (sds((B, IDX_HEADS, IDX_DIM), jnp.bfloat16),
                sds((B, n_pages, IDX_DIM), jnp.bfloat16),
                sds((B, IDX_HEADS), jnp.float32))


def _bm25(sds):
    fn = functools.partial(bm25_topk.bm25_topk_candidates, block=BM25_BLOCK,
                           c=64, valid=DOCS - 7, interpret=False)
    return fn, (sds((B, DOCS, TERMS), jnp.float32),
                sds((B, DOCS), jnp.float32), sds((B, TERMS), jnp.float32))


def _minmax(sds):
    fn = functools.partial(page_pool.page_minmax, page_size=64,
                           interpret=False)
    return fn, (sds((B, S, KV, DH), jnp.bfloat16),)


def _flash(sds):
    fn = functools.partial(flash_attention.flash_attention, bq=512, bk=512,
                           interpret=False)
    return fn, (sds((1, S, HQ, DH), jnp.bfloat16),
                sds((1, S, KV, DH), jnp.bfloat16),
                sds((1, S, KV, DH), jnp.bfloat16))


@pytest.mark.parametrize("build", [_paged, _relevancy, _bm25, _minmax,
                                   _flash, _paged_pool(KV, HQ // KV, 4),
                                   _paged_pool(4, 7, 7)],
                         ids=["paged_decode_attention",
                              "relevancy_topk_candidates",
                              "bm25_topk_candidates", "page_minmax",
                              "flash_attention",
                              "paged_decode_attention_pool_qwen3_32b",
                              "paged_decode_attention_pool_qwen2_7b"])
def test_kernel_compiles_for_v5e(one_chip, build):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args = build(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _view_sized(hlo, n):
    """Opcodes of the instructions whose result holds ``n`` elements."""
    ops_ = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* ([\w-]+)\(", hlo):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) == n:
            ops_.append(m.group(2))
    return ops_


def test_dsa_decode_step_reads_the_pool_for_v5e(one_chip, monkeypatch):
    """The pooled decode step with the engine's DSA ``sparse_fn``, at smoke
    widths with the Mosaic kernel: the kernel reads the carried pool, so the
    program holds no pool-sized copy, slice or update-slice, three
    view-sized gathers (K and V in the dense fallback, K for the index
    projection: one outside the dense fallback) and no head-major copy of
    a view."""
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.models import init_params
    from repro.models import model as M
    from repro.serving import Engine, ServeConfig

    cfg = get_arch("llama3.2-1b").smoke()
    B, max_len, ps = 2, 64, 16
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0), tp=4),
                 ServeConfig(max_len=max_len, n_slots=B, method="dsa", tp=4,
                             page=ps, kv_page_size=ps),
                 key=jax.random.PRNGKey(0))
    monkeypatch.setattr(ops, "_interp", lambda: False)
    on = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    pool = on(jax.eval_shape(lambda: M.make_page_pool(
        cfg, B, max_len, page_size=ps, total_pages=B * max_len // ps + 1,
        tp=4)))

    def decode(p, tok, kp, vp, table, lengths, live, sp):
        return M.decode_step_paged(
            p, cfg, tok, {"k_pages": kp, "v_pages": vp, "page_table": table,
                          "lengths": lengths}, live, tp=4,
            sparse_fn=eng._sparse_fn, sparse_params=sp)

    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    hlo = jax.jit(decode, donate_argnums=(2, 3)).lower(
        on(eng.params), vec, pool["k_pages"], pool["v_pages"],
        pool["page_table"], vec,
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip),
        on(eng.sparse_params)).compile().as_text()
    assert "tpu_custom_call" in hlo
    L, P, _, kv, dh = pool["k_pages"].shape
    for n in (L * P * ps * kv * dh, P * ps * kv * dh):   # pool, one layer
        bad = {"copy", "dynamic-slice", "dynamic-update-slice"}
        assert not bad & set(_view_sized(hlo, n))
    assert view_ops(hlo, pool["k_pages"].shape, B,
                    max_len // ps) == DSA_VIEW_OPS
