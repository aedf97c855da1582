"""GQA attention with qk-norm / qkv-bias / sliding-window / RoPE variants.

Two execution paths:
  * ``attention_full``   — chunked online-softmax ("XLA-flash") for train/prefill;
    memory is O(S * chunk), never materializes the S x S score matrix.
  * ``attention_decode`` — one query token vs a KV cache (dense fallback path;
    the memory-processing pipeline replaces this with sparse retrieval).

TP note (DESIGN.md §5): query heads are padded to a multiple of the model axis
with *dead heads* — their q/k/v rows and o-proj columns are zero-initialized
and an explicit static head mask keeps their gradients identically zero.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.page_pool import kv_view
from repro.models import layers as L

Params = Dict[str, jnp.ndarray]

NEG_INF = -1e30


def attn_init(key, cfg: ArchConfig, tp: int = 16) -> Params:
    d, hd, kv = cfg.d_model, cfg.hd, cfg.n_kv_heads
    hp = cfg.padded_heads(tp)
    dt = L.dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": L.dense_init(ks[0], d, hp * hd, dt),
        "wk": L.dense_init(ks[1], d, kv * hd, dt),
        "wv": L.dense_init(ks[2], d, kv * hd, dt),
        "wo": L.dense_init(ks[3], hp * hd, d, dt, scale=1.0 / np.sqrt(2 * cfg.n_layers * hp * hd)),
    }
    if hp != cfg.n_heads:  # zero the dead-head slices
        wq = p["wq"].reshape(d, hp, hd).at[:, cfg.n_heads:, :].set(0.0)
        wo = p["wo"].reshape(hp, hd, d).at[cfg.n_heads:, :, :].set(0.0)
        p["wq"] = wq.reshape(d, hp * hd)
        p["wo"] = wo.reshape(hp * hd, d)
    if cfg.qkv_bias:
        p["bq"] = L.zeros((hp * hd,), dt)
        p["bk"] = L.zeros((kv * hd,), dt)
        p["bv"] = L.zeros((kv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = L.ones((hd,), jnp.float32)
        p["k_norm"] = L.ones((hd,), jnp.float32)
    return p


def head_mask(cfg: ArchConfig, tp: int = 16) -> jnp.ndarray:
    hp = cfg.padded_heads(tp)
    return jnp.asarray((np.arange(hp) < cfg.n_heads).astype(np.float32))


def head_to_kv(cfg: ArchConfig, tp: int = 16) -> np.ndarray:
    """Static map padded-query-head -> kv head (dead heads map to kv 0)."""
    hp, h, kv = cfg.padded_heads(tp), cfg.n_heads, cfg.n_kv_heads
    g = max(h // kv, 1)
    m = np.minimum(np.arange(hp) // g, kv - 1)
    m[h:] = 0
    return m.astype(np.int32)


def project_qkv(
    p: Params,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cfg: ArchConfig,
    tp: int = 16,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, S, d] -> q [B, S, Hp, hd], k/v [B, S, KV, hd] (rope applied)."""
    B, S, _ = x.shape
    hd, kv = cfg.hd, cfg.n_kv_heads
    hp = cfg.padded_heads(tp)
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, hp, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = L.rms_norm({"w": p["q_norm"]}, q, cfg.norm_eps)
        k = L.rms_norm({"w": p["k_norm"]}, k, cfg.norm_eps)
    if cfg.rope_style != "none":
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    return q, k, v


def expand_kv(kv_arr: jnp.ndarray, cfg: ArchConfig, tp: int = 16) -> jnp.ndarray:
    """[..., KV, hd] -> [..., Hp, hd] by group broadcast (or gather)."""
    hp, kv = cfg.padded_heads(tp), cfg.n_kv_heads
    if hp % kv == 0:
        reps = hp // kv
        return jnp.repeat(kv_arr, reps, axis=-2)
    return jnp.take(kv_arr, jnp.asarray(head_to_kv(cfg, tp)), axis=-2)


# ---------------------------------------------------------------------------
# Full-sequence causal attention (train / prefill): chunked online softmax.
# ---------------------------------------------------------------------------


def attention_full(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: ArchConfig,
    *,
    q_chunk: int = 256,
    window: Optional[int] = None,
    tp: int = 16,
) -> jnp.ndarray:
    """Causal attention; q [B,S,Hp,hd], k/v [B,S,KV,hd] -> [B,S,Hp,hd].

    Scans QUERY chunks: each step materializes only a transient
    [B, H, q_chunk, S] score tile (no running-softmax carry — a carried
    (m, l, acc) formulation makes XLA hoist S^2-sized loop invariants into
    the while carry; see EXPERIMENTS.md §Perf iteration log).
    """
    B, S, HP, hd = q.shape
    window = window if window is not None else (cfg.sliding_window or None)
    kexp = expand_kv(k, cfg, tp).astype(jnp.float32)  # [B, S, Hp, hd]
    vexp = expand_kv(v, cfg, tp).astype(jnp.float32)
    bq = min(q_chunk, S)
    pad = (-S) % bq
    scale = 1.0 / np.sqrt(hd)
    q32 = q.astype(jnp.float32) * scale
    if pad:
        q32 = jnp.pad(q32, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (S + pad) // bq
    q32 = jnp.moveaxis(q32.reshape(B, nq, bq, HP, hd), 1, 0)
    kpos = jnp.arange(S)

    def step(i, qc):
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qc, kexp)  # [B,Hp,bq,S]
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        sc = jnp.where(mask[None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vexp)  # [B,bq,Hp,hd]

    outs = jax.lax.map(lambda args: step(*args), (jnp.arange(nq), q32))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S + pad, HP, hd)
    return out[:, :S].astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (dense fallback): 1 query token vs KV cache.
# ---------------------------------------------------------------------------


def attention_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    length: jnp.ndarray,
    cfg: ArchConfig,
    *,
    window: Optional[int] = None,
    tp: int = 16,
) -> jnp.ndarray:
    """q [B,1,Hp,hd]; caches [B,Smax,KV,hd] (or ``PooledKV``, gathered
    here); length [] or [B] -> [B,1,Hp,hd]."""
    k_cache, v_cache = kv_view(k_cache), kv_view(v_cache)
    B, Smax = k_cache.shape[0], k_cache.shape[1]
    hd = q.shape[-1]
    window = window if window is not None else (cfg.sliding_window or None)
    kexp = expand_kv(k_cache, cfg, tp)
    vexp = expand_kv(v_cache, cfg, tp)
    scale = 1.0 / np.sqrt(hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                    kexp.astype(jnp.float32))  # [B,Hp,1,Smax]
    pos = jnp.arange(Smax)
    length = jnp.asarray(length)
    lb = length if length.ndim else length[None].repeat(B)
    mask = pos[None, :] < lb[:, None]  # [B, Smax]
    if window:
        mask &= pos[None, :] >= (lb[:, None] - window)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vexp.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_decode_chunk(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    start: jnp.ndarray,
    cfg: ArchConfig,
    *,
    window: Optional[int] = None,
    tp: int = 16,
) -> jnp.ndarray:
    """Chunked-prefill attention: C new query tokens vs an in-place cache.

    q [B,C,Hp,hd]; caches [B,Smax,KV,hd] (the C new keys are already written
    at positions start[b]..start[b]+C-1); start [B] -> [B,C,Hp,hd]. Query i
    of row b sits at position start[b]+i and attends causally to everything
    at or before it. Padding queries (beyond a row's real span) just produce
    garbage rows the caller ignores.
    """
    B, C = q.shape[:2]
    Smax = k_cache.shape[1]
    hd = q.shape[-1]
    window = window if window is not None else (cfg.sliding_window or None)
    kexp = expand_kv(k_cache, cfg, tp)
    vexp = expand_kv(v_cache, cfg, tp)
    scale = 1.0 / np.sqrt(hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                    kexp.astype(jnp.float32))          # [B,Hp,C,Smax]
    kpos = jnp.arange(Smax)
    qpos = start[:, None] + jnp.arange(C)[None, :]     # [B, C]
    mask = kpos[None, None, :] <= qpos[:, :, None]     # [B, C, Smax]
    if window:
        mask &= kpos[None, None, :] > (qpos[:, :, None] - window)
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vexp.astype(jnp.float32))
    return out.astype(q.dtype)
