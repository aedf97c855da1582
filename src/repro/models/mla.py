"""DeepSeek-V3.2 on the paged serving path: multi-head latent attention
(MLA) with YaRN rope, the published lightning indexer choosing the tokens
each decode step attends, and a stack of ``first_k_dense`` dense layers
followed by mixture-of-experts layers.

Per layer, for the residual x (DeepSeek-V3's equations):

    h            = rmsnorm(x)
    c_q          = rmsnorm(h W_dq)                          [q_lora]
    [q_n | q_r]  = c_q W_uq                                 per head [dn | dr]
    [c_kv | k_r] = h W_dkv,  c_kv = rmsnorm(c_kv)           [dl | dr], shared
    q_r, k_r     = rope(q_r), rope(k_r)                     YaRN frequencies
    score(t)     = (q_n . k_n(t) + q_r . k_r(t)) * scale,   k_n = c_kv W_uk
    x            = x + (softmax(score) v) W_o,              v   = c_kv W_uv
    x            = x + ffn(rmsnorm(x))        dense MLP, or MoE (models/moe.py)

Attention is absorbed: q_n . (c_kv W_uk) = (q_n W_uk^T) . c_kv and
softmax(.) (c_kv W_uv) = (softmax(.) c_kv) W_uv, so a token's cache is one
latent row [c_kv | k_r] of dl + dr values a layer, shared by every head, and
no key or value is expanded per head. Decode attends to the rows the indexer
chose (core/methods/dsa.py, token top-k); prompt positions attend to every
earlier token.

The paged pool keeps its two page arrays under the engine's keys:
``k_pages`` holds the latent rows [L, P, ps, 1, dl + dr] and ``v_pages``
the indexer's keys [L, P, ps, 1, di], written when the token is.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.methods import dsa
from repro.kernels import ops
from repro.kernels.page_pool import (pool_gather, pool_gather_rows,
                                     pool_scatter, span_dest, token_dest)
from repro.models import layers as L
from repro.models import moe as M

Params = Dict
KEY_BLOCK = 1024       # keys per step of chunked-prefill attention
NEG = -1e30


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def yarn_inv_freq(cfg: ArchConfig) -> np.ndarray:
    """Rope inverse frequencies of the ``qk_rope_head_dim`` dims: the base
    frequencies, divided by ``rope_factor`` below the beta_slow correction
    dim, kept above beta_fast's, ramped linearly in between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if cfg.rope_factor <= 1.0:
        return freqs.astype(np.float32)

    def corr(rotations):
        return (dim * math.log(cfg.rope_original_max_len
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (freqs / cfg.rope_factor * (1 - smooth)
            + freqs * smooth).astype(np.float32)


def softmax_scale(cfg: ArchConfig) -> float:
    """(dn + dr)^-1/2, times YaRN's mscale squared."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor > 1.0:
        m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
        s *= m * m
    return s


def rope_tables(cfg: ArchConfig, positions):
    """positions [B, T] -> cos, sin [B, T, dr/2] (unscaled: mscale ==
    mscale_all_dim)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(cfg))
    return jnp.cos(ang), jnp.sin(ang)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg: ArchConfig) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, dl = cfg.q_lora_rank, cfg.kv_lora_rank
    dt = L.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    return {
        "wq_a": L.dense_init(ks[0], d, ql, dt),
        "q_norm": jnp.ones((ql,), jnp.float32),
        "wq_b": L.dense_init(ks[1], ql, H * (dn + dr), dt),
        "wkv_a": L.dense_init(ks[2], d, dl + dr, dt),
        "kv_norm": jnp.ones((dl,), jnp.float32),
        "wkv_b": L.dense_init(ks[3], dl, H * (dn + dv), dt),
        "wo": L.dense_init(ks[4], H * dv, d, dt,
                           scale=1.0 / np.sqrt(2 * cfg.n_layers * H * dv)),
    }


def _layer_init(key, cfg: ArchConfig, moe: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"attn": _attn_init(k1, cfg),
         "attn_norm": L.rms_norm_init(cfg.d_model, None),
         "mlp_norm": L.rms_norm_init(cfg.d_model, None)}
    if moe:
        p["moe"] = M.noaux_moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


@functools.partial(jax.jit, static_argnums=1)
def _body_init(key, cfg: ArchConfig) -> Params:
    kd, km = jax.random.split(key)
    k = cfg.first_k_dense
    return {
        "dense_layers": jax.lax.map(lambda r: _layer_init(r, cfg, False),
                                    jax.random.split(kd, k)),
        "moe_layers": jax.lax.map(lambda r: _layer_init(r, cfg, True),
                                  jax.random.split(km, cfg.n_layers - k)),
    }


def init_params(cfg: ArchConfig, key) -> Params:
    """Random params: embedding, ``dense_layers`` [first_k_dense, ...],
    ``moe_layers`` [n_layers - first_k_dense, ...], final norm, lm_head."""
    ke, kb, kh = jax.random.split(key, 3)
    params = {
        "embed": jax.jit(L.embed_init, static_argnums=1)(ke, cfg),
        "final_norm": L.rms_norm_init(cfg.d_model, None),
        "lm_head": jax.jit(L.lm_head_init, static_argnums=1)(kh, cfg),
    }
    params.update(_body_init(kb, cfg))
    return params


def make_page_pool(cfg: ArchConfig, n_slots: int, max_len: int, *,
                   page_size: int, total_pages: int) -> Dict:
    """Latent rows (``k_pages``) and index keys (``v_pages``), page 0 the
    reserved zero page as in ``models.make_page_pool``."""
    dt = L.dtype_of(cfg)
    assert max_len % page_size == 0, (max_len, page_size)
    shape = (cfg.n_layers, total_pages, page_size, 1)
    return {
        "k_pages": jnp.zeros(
            shape + (cfg.kv_lora_rank + cfg.qk_rope_head_dim,), dt),
        "v_pages": jnp.zeros(shape + (cfg.memory.index_dim,), dt),
        "page_table": jnp.zeros((n_slots, max_len // page_size), jnp.int32),
        "lengths": jnp.zeros((n_slots,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# one layer's pieces
# ---------------------------------------------------------------------------


def project(a: Params, h, cos, sin, cfg: ArchConfig):
    """h [B, T, d] -> (c_q [B, T, q_lora], q_nope [B, T, H, dn], q_rope
    [B, T, H, dr], latent row [B, T, dl + dr])."""
    B, T, _ = h.shape
    dn, dl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = L.rms_norm({"w": a["q_norm"]}, h @ a["wq_a"], cfg.norm_eps)
    q = (c_q @ a["wq_b"]).reshape(B, T, cfg.n_heads, -1)
    q_rope = L.apply_rope(q[..., dn:], cos, sin)
    kv = h @ a["wkv_a"]
    c_kv = L.rms_norm({"w": a["kv_norm"]}, kv[..., :dl], cfg.norm_eps)
    k_rope = L.apply_rope(kv[..., None, dl:], cos, sin)[..., 0, :]
    return c_q, q[..., :dn], q_rope, jnp.concatenate([c_kv, k_rope], -1)


def _w_ukv(a: Params, cfg: ArchConfig):
    """W_uk [dl, H, dn] and W_uv [dl, H, dv] out of ``wkv_b``."""
    w = a["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorb(a: Params, q_nope, q_rope, cfg: ArchConfig):
    """Queries in the latent: [q_nope W_uk^T | q_rope] [B, T, H, dl + dr]
    float32."""
    w_uk, _ = _w_ukv(a, cfg)
    q_lat = jnp.einsum("bthn,lhn->bthl", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], -1)


def unabsorb(a: Params, out_lat, cfg: ArchConfig):
    """Attention output out of the latent: [B, T, H, dl] -> [B, T, H * dv]
    through W_uv."""
    _, w_uv = _w_ukv(a, cfg)
    o = jnp.einsum("bthl,lhv->bthv", out_lat.astype(w_uv.dtype), w_uv)
    return o.reshape(o.shape[0], o.shape[1], -1)


def chunk_attention(q_lat, view, start, scale: float, dl: int):
    """Causal attention of a prefill chunk over its slot's latent view.

    q_lat [B, C, H, W] float32; view [B, S, W] latent rows (the chunk's
    own rows already written); start [B]: query i of row b sits at
    start[b] + i and attends to every t <= start[b] + i. Online softmax over
    blocks of ``KEY_BLOCK`` keys, so no [B, H, C, S] score array is held.
    -> [B, C, H, dl] float32."""
    B, C, H, _ = q_lat.shape
    S = view.shape[1]
    kb = math.gcd(S, KEY_BLOCK)
    qpos = start[:, None] + jnp.arange(C)[None]                 # [B, C]

    def step(carry, j):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(view, j * kb, kb, 1).astype(
            jnp.float32)                                        # [B, kb, W]
        s = jnp.einsum("bchw,bkw->bhck", q_lat, blk) * scale
        ok = (j * kb + jnp.arange(kb))[None, None] <= qpos[:, :, None]
        s = jnp.where(ok[:, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum("bhck,bkd->bhcd", p,
                                                 blk[..., :dl])
        return (m_new, l * corr + p.sum(-1), acc), None

    init = (jnp.full((B, H, C), NEG, jnp.float32),
            jnp.zeros((B, H, C), jnp.float32),
            jnp.zeros((B, H, C, dl), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(step, init, jnp.arange(S // kb))
    return jnp.moveaxis(acc / l[..., None], 1, 2)


def _ffn(lp: Params, x, cfg: ArchConfig, moe: bool):
    """Residual add of the layer's FFN (under ``dense``; the MoE under a
    nested ``moe`` scope)."""
    h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
    if not moe:
        return x + L.mlp(lp["mlp"], h)
    with jax.named_scope("moe"):
        return x + M.noaux_moe_apply(lp["moe"], h, cfg)


def _stack(params: Params, sp, carry, layer_fn, cfg: ArchConfig):
    """The dense layers, then the MoE layers, each kind in a scan whose
    carry starts with the [B, T, d] residual; ``layer`` (the layer's index
    in the whole model) addresses the pools and the indexer's stack."""
    for name, first, moe in (("dense_layers", 0, False),
                             ("moe_layers", cfg.first_k_dense, True)):
        n = params[name]["attn_norm"]["w"].shape[0]
        if not n:
            continue

        def body(c, xs, moe=moe):
            lp, layer = xs
            spl = jax.tree.map(lambda a: a[layer], sp)
            return layer_fn(lp, spl, c, layer, moe), None

        carry, _ = jax.lax.scan(body, carry,
                                (params[name], first + jnp.arange(n)))
    return carry


def _logits(params: Params, cfg: ArchConfig, x):
    with jax.named_scope("dense"):
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return L.lm_head(params["lm_head"], x, cfg)[:, 0]


# ---------------------------------------------------------------------------
# paged decode and chunked prefill
# ---------------------------------------------------------------------------


def decode_step_paged(params: Params, cfg: ArchConfig, token, pool, live, *,
                      sparse_params):
    """One decode step of every slot, as ``models.decode_step_paged``.

    Each slot attends to the min(top_k, context) tokens that the published
    indexer (``sparse_params``, stacked [L, ...]) ranks best. -> (logits
    [B, V], pool')."""
    lengths, table = pool["lengths"], pool["page_table"]
    live = live.astype(bool)
    ps = pool["k_pages"].shape[2]
    dl, top_k = cfg.kv_lora_rank, cfg.memory.top_k
    scale = softmax_scale(cfg)
    with jax.named_scope("dense"):
        x = L.embed(params["embed"], token[:, None])
    cos, sin = rope_tables(cfg, lengths[:, None])
    dest = token_dest(table, lengths, live, ps)
    ctx = lengths + 1

    def layer_fn(lp, spl, carry, layer, moe):
        x, lat, idx = carry
        a = lp["attn"]
        with jax.named_scope("dense"):
            h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            c_q, q_nope, q_rope, row = project(a, h, cos, sin, cfg)
            q_lat = absorb(a, q_nope, q_rope, cfg)[:, 0]        # [B, H, W]
        with jax.named_scope("kv_write"):
            lat = pool_scatter(lat, layer, dest, row)            # [B, 1, W]
        with jax.named_scope("prepare"):
            k_i = dsa.index_key(spl, h, cos, sin, cfg.norm_eps)
            idx = pool_scatter(idx, layer, dest, k_i)            # [B, 1, di]
        with jax.named_scope("relevancy"):
            q_i, w = dsa.index_query(spl, c_q[:, 0], h[:, 0], cos, sin)
            keys = pool_gather(idx, layer, table)[:, :, 0]
            ids, n = dsa.token_topk(q_i, w, keys, ctx, top_k)
        with jax.named_scope("retrieve"):
            rows = pool_gather_rows(lat, layer, table, ids)[:, :, 0]
        with jax.named_scope("apply"):
            o = ops.mla_sparse_decode_attention(q_lat, rows, n, dv=dl,
                                                scale=scale)
            o = unabsorb(a, o[:, None], cfg)
        with jax.named_scope("dense"):
            x = _ffn(lp, x + o @ a["wo"], cfg, moe)
        return x, lat, idx

    x, lat, idx = _stack(params, sparse_params,
                         (x, pool["k_pages"], pool["v_pages"]), layer_fn, cfg)
    pool = dict(pool, k_pages=lat, v_pages=idx,
                lengths=lengths + live.astype(jnp.int32))
    return _logits(params, cfg, x), pool


def extend_paged(params: Params, cfg: ArchConfig, tokens, pool, n_valid, *,
                 sparse_params):
    """Chunked prefill, as ``models.extend_paged``: each slot's span of C
    tokens (``n_valid`` of them real) appended to the pool, with the span's
    index keys by the indexer ``sparse_params``; queries attend causally to
    the prefix and the span. -> (logits [B, V] at each row's last real
    token, pool')."""
    B, C = tokens.shape
    lengths, table = pool["lengths"], pool["page_table"]
    ps = pool["k_pages"].shape[2]
    scale = softmax_scale(cfg)
    with jax.named_scope("dense"):
        x = L.embed(params["embed"], tokens)
    cos, sin = rope_tables(cfg, lengths[:, None] + jnp.arange(C)[None])
    dest = span_dest(table, lengths, n_valid, C, ps)

    def layer_fn(lp, spl, carry, layer, moe):
        x, lat, idx = carry
        a = lp["attn"]
        with jax.named_scope("dense"):
            h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            _, q_nope, q_rope, row = project(a, h, cos, sin, cfg)
            q_lat = absorb(a, q_nope, q_rope, cfg)
        with jax.named_scope("kv_write"):
            lat = pool_scatter(lat, layer, dest, row[:, :, None])
        with jax.named_scope("prepare"):
            k_i = dsa.index_key(spl, h, cos, sin, cfg.norm_eps)
            idx = pool_scatter(idx, layer, dest, k_i[:, :, None])
        with jax.named_scope("retrieve"):
            view = pool_gather(lat, layer, table)[:, :, 0]
        with jax.named_scope("apply"):
            o = chunk_attention(q_lat, view, lengths, scale,
                                cfg.kv_lora_rank)
            o = unabsorb(a, o, cfg)
        with jax.named_scope("dense"):
            x = _ffn(lp, x + o @ a["wo"], cfg, moe)
        return x, lat, idx

    x, lat, idx = _stack(params, sparse_params,
                         (x, pool["k_pages"], pool["v_pages"]), layer_fn, cfg)
    last = jnp.clip(n_valid - 1, 0, C - 1)
    xg = jnp.take_along_axis(x, last[:, None, None], axis=1)      # [B, 1, d]
    pool = dict(pool, k_pages=lat, v_pages=idx, lengths=lengths + n_valid)
    return _logits(params, cfg, xg), pool
