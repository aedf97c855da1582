"""Paged sparse decode attention — the Apply-to-Inference stage.

Gathers ONLY the retrieved KV pages (top-k indices from the relevancy kernel)
directly HBM->VMEM via a scalar-prefetch block index map (the TPU analogue of
the paper keeping KV extraction on the engine that owns the KV, §5.2), and
runs a FlashDecoding-style online softmax over them.

Emits (out, lse) so sequence-sharded shards can LSE-merge partial results —
the distributed form exchanges only (out, lse) pairs, never KV.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pages_ref, length_ref, q_ref, k_ref, v_ref, out_ref, lse_ref,
            m_scr, l_scr, acc_scr, *, ps: int, n_sel: int, kv: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = pages_ref[b, j]
    length = length_ref[b]
    tok = page_id * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    valid = (page_id >= 0) & (tok < length)            # [1, ps]
    # one DMA brings the page of every KV head; heads run in a static loop
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32)            # [G, dh]
        k = k_ref[0, 0, h].astype(jnp.float32)         # [ps, dh]
        v = v_ref[0, 0, h].astype(jnp.float32)         # [ps, dh]
        dh = q.shape[-1]
        sc = jnp.dot(q / np.sqrt(dh), k.T,
                     preferred_element_type=jnp.float32)  # [G, ps]
        sc = jnp.where(valid, sc, NEG_INF)

        m_prev = m_scr[h]                              # [G, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(j == n_sel - 1)
    def _finish():
        l = l_scr[...]                                 # [KV, G, 1]
        out_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(jnp.maximum(l, 1e-30))


@functools.partial(
    jax.jit, static_argnames=("page_size", "interpret"),
)
def paged_decode_attention(
    q: jnp.ndarray,         # [B, Hq, dh]
    k_cache: jnp.ndarray,   # [B, S, KV, dh]
    v_cache: jnp.ndarray,   # [B, S, KV, dh]
    page_ids: jnp.ndarray,  # [B, P] int32 page indices, -1 invalid
    length: jnp.ndarray,    # [B] int32 valid token count
    *,
    page_size: int = 64,
    interpret: bool,
):
    """-> (out [B, Hq, dh] fp32, lse [B, Hq] fp32).

    Pages are laid out head-major ([B, n_pages, KV, ps, dh]) so one block
    is a whole page of every KV head, whose last two dims (ps, dh) are full
    array dims — legal for Mosaic at any page size. That layout costs an
    XLA transpose of the whole cache view per call, O(context) bytes, until
    the pool itself is stored head-major."""
    B, S, KV, dh = k_cache.shape
    Hq = q.shape[1]
    G = Hq // KV
    ps = page_size
    assert S % ps == 0
    n_pages = S // ps
    n_sel = page_ids.shape[1]
    qg = q.reshape(B, KV, G, dh)
    kp = k_cache.reshape(B, n_pages, ps, KV, dh).transpose(0, 1, 3, 2, 4)
    vp = v_cache.reshape(B, n_pages, ps, KV, dh).transpose(0, 1, 3, 2, 4)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    def page_block(b, j, pages, lens):
        return (b, jnp.maximum(pages[b, j], 0), 0, 0, 0)

    kern = functools.partial(_kernel, ps=ps, n_sel=n_sel, kv=KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_sel),
        in_specs=[
            pl.BlockSpec((1, KV, G, dh), lambda b, j, pages, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, KV, ps, dh), page_block),
            pl.BlockSpec((1, 1, KV, ps, dh), page_block),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, G, dh), lambda b, j, pages, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, G, 1), lambda b, j, pages, lens: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, dh), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode_attention",
    )(page_ids, length, qg, kp, vp)
    return out.reshape(B, Hq, dh), lse.reshape(B, Hq)


def lse_merge(outs: jnp.ndarray, lses: jnp.ndarray):
    """Merge N partial attention results: outs [N, B, H, dh], lses [N, B, H].

    Standard FlashDecoding combine: softmax over shard LSEs reweights shard
    outputs. This is the only cross-shard math in distributed sparse decode.
    """
    m = lses.max(axis=0)                              # [B, H]
    w = jnp.exp(lses - m[None])                       # [N, B, H]
    den = w.sum(axis=0)
    out = (outs * w[..., None]).sum(axis=0) / jnp.maximum(den[..., None], 1e-30)
    return out, m + jnp.log(jnp.maximum(den, 1e-30))
