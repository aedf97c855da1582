"""Blockwise causal flash attention (train / prefill path).

Standard FlashAttention-2 tiling: grid (B, H, nq, nk) with the KV dimension
innermost-sequential; running (m, l, acc) live in VMEM scratch. GQA is folded
into the K/V BlockSpec index map (kv_head = h // G — static arithmetic, no
data-dependent indexing). Optional sliding window (Mixtral).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bk: int, nk: int, window: int, seq: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = i * bq + jax.lax.iota(jnp.int32, bq)
    kpos = j * bk + jax.lax.iota(jnp.int32, bk)
    # block-level causal skip: this KV block starts after the last query row
    needed = (j * bk) <= (i * bq + bq - 1)
    if window:
        # window skip: KV block ends before the window of the first query row
        needed &= (j * bk + bk - 1) > (i * bq - window - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        dh = q.shape[-1]
        sc = jnp.dot(q / np.sqrt(dh), k.T, preferred_element_type=jnp.float32)
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        mask &= (kpos < seq)[None, :]
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bk", "window", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, S, H, dh]
    k: jnp.ndarray,  # [B, S, KV, dh]
    v: jnp.ndarray,  # [B, S, KV, dh]
    *,
    bq: int = 512,
    bk: int = 512,
    window: int = 0,
    interpret: bool,
) -> jnp.ndarray:
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    bq, bk = min(bq, S), min(bk, S)
    pad = (-S) % bq
    qt = jnp.moveaxis(q, 1, 2)  # [B, H, S, dh]
    kt = jnp.moveaxis(k, 1, 2)  # [B, KV, S, dh]
    vt = jnp.moveaxis(v, 1, 2)
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nq, nk = Sp // bq, Sp // bk
    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, window=window, seq=S)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :S], 2, 1)  # [B, S, H, dh]
