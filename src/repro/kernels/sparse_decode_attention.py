"""Paged sparse decode attention — the Apply-to-Inference stage.

Reads ONLY the retrieved KV pages (top-k indices from the relevancy kernel)
HBM->VMEM via a scalar-prefetch block index map, straight from the paged
pool in the layout it is stored in (the TPU analogue of the paper keeping
KV extraction on the engine that owns the KV, §5.2), and runs a
FlashDecoding-style online softmax over them. A contiguous cache view is
the same call: it is a one-layer pool with the identity page table.

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

from repro.kernels.page_pool import as_pool

NEG_INF = -1e30


# pages a grid step reads: the kernel is bound by the cost of its grid
# steps, not by its bytes or FLOPs, so fewer, larger steps run faster
PAGES_PER_STEP = 8


def _kernel(layer_ref, phys_ref, first_ref, length_ref, q_ref, *refs,
            ps: int, span: int, n_steps: int, kv: int, g: int, m: int):
    k_refs, v_refs = refs[:m], refs[m:2 * m]
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs[2 * m:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # A page arrives as the pool stores it, [ps, KV, dh]. Read as
    # [ps * KV, dh], its column c is token c // KV of KV head c % KV: all
    # query heads [Hq, dh] take one product with it and keep the columns of
    # their own KV head, which gives each head's scores with no relayout of
    # the page in VMEM.
    length = length_ref[b]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, ps * kv), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (kv * g, 1), 0)
    head = jnp.zeros_like(row)                         # KV head of each row
    for h in range(1, kv):
        head += (row >= h * g).astype(jnp.int32)
    own_head = col % kv == head                        # [Hq, ps * KV]
    q = q_ref[0].astype(jnp.float32)                   # [Hq, dh]
    dh = q.shape[-1]
    q = q / np.sqrt(dh)
    scores = []
    for i in range(m):
        first = first_ref[b, j * m + i]
        base = jnp.maximum(first, 0) // ps * ps        # the pool page's token 0
        tok = base + col // kv
        valid = ((first >= 0) & (tok >= first) & (tok < first + span)
                 & (tok < length) & own_head)
        k = k_refs[i][0, 0].astype(jnp.float32).reshape(ps * kv, dh)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        scores.append(jnp.where(valid, sc, NEG_INF))

    # one online-softmax update for the step's m pages
    m_prev = m_scr[...]                                # [Hq, 1]
    m_new = m_prev
    for sc in scores:
        m_new = jnp.maximum(m_new, sc.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l = l_scr[...] * corr
    acc = acc_scr[...] * corr
    for sc, v_ref in zip(scores, v_refs):
        p = jnp.exp(sc - m_new)
        v = v_ref[0, 0].astype(jnp.float32).reshape(ps * kv, dh)
        l += p.sum(axis=-1, keepdims=True)
        acc += jnp.dot(p, v, preferred_element_type=jnp.float32)
    l_scr[...] = l
    acc_scr[...] = acc
    m_scr[...] = m_new

    @pl.when(j == n_steps - 1)
    def _finish():
        l = l_scr[...]                                 # [Hq, 1]
        out_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(jnp.maximum(l, 1e-30))


def pool_pages(table: jnp.ndarray, page_ids: jnp.ndarray, page_size: int,
               pool_page: int):
    """Selected logical pages of ``page_size`` tokens -> the pool pages
    that hold them.

    table [B, NP] (physical id of each logical pool page); page_ids [B, n]
    (-1 = none) -> (phys [B, N], first [B, N], span): pool page ``phys``
    holds the ``span`` selected tokens from logical position ``first``
    (-1 = none). A selected page larger than a pool page spans several pool
    pages (N = n * page_size / pool_page); a smaller one reads part of one.
    """
    assert page_size % pool_page == 0 or pool_page % page_size == 0, \
        (page_size, pool_page)
    r = max(page_size // pool_page, 1)
    B, n = page_ids.shape
    first = (page_ids[:, :, None] * page_size
             + jnp.arange(r, dtype=jnp.int32) * pool_page)
    first = jnp.where(page_ids[:, :, None] >= 0, first, -1).reshape(B, n * r)
    phys = jnp.take_along_axis(table, jnp.maximum(first, 0) // pool_page,
                               axis=1, mode="clip")
    return phys, first, min(page_size, pool_page)


@functools.partial(
    jax.jit, static_argnames=("page_size", "interpret"),
)
def paged_decode_attention(
    q: jnp.ndarray,         # [B, Hq, dh]
    k_cache,                # [B, S, KV, dh] view, or a PooledKV
    v_cache,                # the same form as k_cache
    page_ids: jnp.ndarray,  # [B, P] int32 page indices, -1 invalid
    length: jnp.ndarray,    # [B] int32 valid token count
    *,
    page_size: int = 64,
    interpret: bool,
):
    """-> (out [B, Hq, dh] fp32, lse [B, Hq] fp32).

    The caches are a contiguous view or a ``page_pool.PooledKV`` (the
    stacked pool [L, P, ps, KV, dh], a layer index, the page table); a view
    is read as a one-layer pool with the identity table, so both forms run
    the same kernel. The page table composed with the selection gives the
    physical page of every grid step. One block is one whole pool page of
    every KV head, (1, 1, ps, KV, dh): its last two dims are full array
    dims, so it is legal for Mosaic at any page size and KV width, and the
    pool is read in its own layout, with no copy or transpose of a view.
    The heads share one masked product per page (``_kernel``): KV times the
    FLOPs of per-head products, but fewer, larger MXU passes in a kernel
    bound by its grid steps, not its FLOPs; for the same reason a grid step
    reads ``PAGES_PER_STEP`` pages (the pool is passed once per page read).
    """
    kp, vp = as_pool(k_cache, page_size), as_pool(v_cache, page_size)
    B, Hq, dh = q.shape
    ps, KV = kp.pages.shape[2], kp.pages.shape[3]
    phys, first, span = pool_pages(kp.table, page_ids, page_size, ps)
    m = min(PAGES_PER_STEP, phys.shape[1])
    pad = -phys.shape[1] % m              # padded steps read a page, masked
    phys = jnp.pad(phys, ((0, 0), (0, pad)), mode="edge")
    first = jnp.pad(first, ((0, 0), (0, pad)), constant_values=-1)
    n_steps = phys.shape[1] // m
    layer = jnp.reshape(jnp.asarray(kp.layer, jnp.int32), (1,))
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    def page_block(i):
        return lambda b, j, layer, phys, *_: (layer[0], phys[b, j * m + i],
                                              0, 0, 0)

    def per_row(b, j, *_):
        return (b, 0, 0)

    kern = functools.partial(_kernel, ps=ps, span=span, n_steps=n_steps,
                             kv=KV, g=Hq // KV, m=m)
    pages = [pl.BlockSpec((1, 1, ps, KV, dh), page_block(i))
             for i in range(m)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_steps),
        in_specs=[pl.BlockSpec((1, Hq, dh), per_row)] + pages + pages,
        out_specs=[
            pl.BlockSpec((1, Hq, dh), per_row),
            pl.BlockSpec((1, Hq, 1), per_row),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, dh), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode_attention",
    )(layer, phys, first, length, q, *[kp.pages] * m, *[vp.pages] * m)
    return out, lse[..., 0]


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
