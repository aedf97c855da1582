"""Fused BM25 scoring + top-k over gathered term columns (RAG relevancy +
retrieval, paper Fig. 10 right / Table 1 "BM25 + Top-k").

TPU adaptation (DESIGN.md §2): BM25's irregular per-term histogram lookups are
hoisted OUT of the kernel — the data pipeline gathers the query's term-
frequency columns once into a dense [D, T] panel — while the streaming
score + top-k stays fused in VMEM, mirroring the FPGA dataflow engine.

The live document count is a SCALAR-PREFETCH operand (same idiom as the
paged sparse-decode kernel), not a static trace constant: the serving-side
corpus store appends documents incrementally and must not re-jit the
retrieval path every time the corpus grows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic import bitonic_topk


def _kernel(nd_ref, tf_ref, dl_ref, idf_ref, vals_ref, idx_ref,
            *, k1: float, b: float, avgdl: float, bd: int, c: int):
    j = pl.program_id(1)
    tf = tf_ref[0].astype(jnp.float32)        # [bd, T]
    dl = dl_ref[0].astype(jnp.float32)        # [bd, 1]
    idf = idf_ref[0].astype(jnp.float32)      # [1, T]
    denom = tf + k1 * (1.0 - b + b * dl / avgdl)
    sat = tf * (k1 + 1.0) / denom             # [bd, T]
    scores = jax.lax.dot_general(           # float32 passes, as relevancy
        idf, sat, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)   # [1, bd] lane-dense row
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
    scores = jnp.where(j * bd + pos < nd_ref[0], scores, -jnp.inf)
    top_v, top_pos = bitonic_topk(scores, pos, c)
    vals_ref[0, 0] = top_v
    idx_ref[0, 0] = j * bd + top_pos


@functools.partial(
    jax.jit,
    static_argnames=("block", "c", "k1", "b", "avgdl", "interpret"),
)
def bm25_topk_candidates(
    tf: jnp.ndarray,       # [B, D, T] term frequencies (query's terms only)
    doc_len: jnp.ndarray,  # [B, D]
    idf: jnp.ndarray,      # [B, T]
    *,
    block: int = 4096,
    c: int = 64,
    k1: float = 1.5,
    b: float = 0.75,
    avgdl: float = 100.0,
    valid=0,               # live doc count (traced ok); 0 -> D
    interpret: bool,
):
    """Per-block BM25 top-c candidates: (vals [B,nb,c], idx [B,nb,c]).

    doc_len rides as a [B, D, 1] column and idf as a [B, 1, T] row so every
    block's last two dims are full or tile-aligned (legal for Mosaic)."""
    B, D, T = tf.shape
    block = min(block, D)
    assert D % block == 0
    nb = D // block
    c = min(c, block)
    nd = jnp.asarray(valid, jnp.int32)
    nd = jnp.where(nd > 0, nd, D).reshape(1)
    kern = functools.partial(_kernel, k1=k1, b=b, avgdl=avgdl, bd=block, c=c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, block, T), lambda bi, j, nd: (bi, j, 0)),
            pl.BlockSpec((1, block, 1), lambda bi, j, nd: (bi, j, 0)),
            pl.BlockSpec((1, 1, T), lambda bi, j, nd: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, c), lambda bi, j, nd: (bi, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda bi, j, nd: (bi, j, 0, 0)),
        ],
    )
    vals, idx = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((B, nb, 1, c), jnp.int32),
        ],
        interpret=interpret,
        name="bm25_topk",
    )(nd, tf, doc_len.reshape(B, D, 1), idf.reshape(B, 1, T))
    return vals.reshape(B, nb, c), idx.reshape(B, nb, c)
