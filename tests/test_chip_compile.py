"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e.

Each kernel is compiled at Qwen3-32B widths (64 query heads / 8 KV,
head_dim 128, 32k context, DSA indexer 64 x 128 with relevancy block 2048)
for a DESCRIBED v5e — the TPU compiler is installed, the chip is not — and
the program must hold the Mosaic kernel (``tpu_custom_call``). This catches
what interpret mode cannot: block shapes Mosaic refuses, primitives it
cannot lower, VMEM overuse. Nothing runs, so results are not checked here
(``chip_smoke.py`` checks them on the chip).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bm25_topk, flash_attention, page_pool, \
    relevancy_topk, sparse_decode_attention

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
                                   _flash],
                         ids=["paged_decode_attention",
                              "relevancy_topk_candidates",
                              "bm25_topk_candidates", "page_minmax",
                              "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, build):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args = build(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
