"""Sparse MLA decode attention — the Apply stage of a latent-attention
model (DeepSeek-V3.2) over the rows its lightning indexer chose.

Each slot's query heads, absorbed into the latent (``q_nope W_uk`` beside
the rope query, ``dl + dr`` wide), attend to the selected latent rows
``[c_kv | k_rope]`` of one shared key head: keys are whole rows, values are
their first ``dv`` (the latent) columns. FlashDecoding-style online softmax
over row blocks; the selected rows come in gathered, best first, and the
first ``n_valid[b]`` of them are real.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(n_ref, q_ref, rows_ref, out_ref, m_scr, l_scr, acc_scr, *,
            block: int, n_blocks: int, dv: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # [H, dl + dr]
    rows = rows_ref[0].astype(jnp.float32)            # [block, dl + dr]
    sc = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    sc = jnp.where(pos < n_ref[b], sc, NEG_INF)       # [H, block]
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, rows[:, :dv], preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        out_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("dv", "scale", "block", "interpret"))
def mla_sparse_decode_attention(
    q: jnp.ndarray,        # [B, H, dl + dr] absorbed query
    rows: jnp.ndarray,     # [B, N, dl + dr] selected latent rows
    n_valid: jnp.ndarray,  # [B] int32: rows[b, :n_valid[b]] are real
    *,
    dv: int,               # value width: the first dv columns of a row
    scale: float,          # softmax scale
    block: int,            # rows per grid step; divides N
    interpret: bool,
):
    """-> out [B, H, dv] float32 (the attention output in the latent)."""
    B, H, W = q.shape
    N = rows.shape[1]
    assert N % block == 0, (N, block)
    n_blocks = N // block
    kern = functools.partial(_kernel, block=block, n_blocks=n_blocks, dv=dv,
                             scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, j, n: (b, 0, 0)),
            pl.BlockSpec((1, block, W), lambda b, j, n: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dv), lambda b, j, n: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
        interpret=interpret,
        name="mla_sparse_decode_attention",
    )(n_valid.astype(jnp.int32), q, rows)
