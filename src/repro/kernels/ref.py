"""Pure-jnp oracles for every Pallas kernel (the source of truth in tests).

All oracles use fp32 math and XLA-native ops (lax.top_k, einsum, softmax).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# 1. Fused relevancy scoring + top-k (DeepSeek lightning-indexer style)
# ---------------------------------------------------------------------------


def relevancy_scores(q: jnp.ndarray, keys: jnp.ndarray,
                     weights: jnp.ndarray) -> jnp.ndarray:
    """q [B,Hq,dk]; keys [B,S,dk]; weights [B,Hq] -> scores [B,S].

    score_s = sum_h w_h * relu(q_h . k_s)   (DSA indexer, paper App. D)
    """
    dots = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                      keys.astype(jnp.float32))
    return jnp.einsum("bh,bhs->bs", weights.astype(jnp.float32),
                      jax.nn.relu(dots))


def relevancy_topk(q, keys, weights, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact oracle: (vals [B,k], idx [B,k]) sorted descending.

    ``k`` is clamped to the key count, matching the fused kernel path
    (ops.relevancy_topk passes ``min(k, S)`` to the candidate merge)."""
    scores = relevancy_scores(q, keys, weights)
    return jax.lax.top_k(scores, min(k, keys.shape[1]))


# ---------------------------------------------------------------------------
# 2. Paged sparse decode attention (apply-to-inference stage)
# ---------------------------------------------------------------------------


def paged_decode_attention(
    q: jnp.ndarray,            # [B, Hq, dh]
    k_cache: jnp.ndarray,      # [B, S, KV, dh]
    v_cache: jnp.ndarray,      # [B, S, KV, dh]
    page_ids: jnp.ndarray,     # [B, P] int32, -1 = invalid
    page_size: int,
    length,                    # [] or [B]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attention of one query over the selected pages -> (out [B,Hq,dh],
    lse [B,Hq])."""
    B, S, KV, dh = k_cache.shape
    Hq = q.shape[1]
    G = Hq // KV
    P = page_ids.shape[1]
    ps = page_size
    safe = jnp.maximum(page_ids, 0)
    # gather pages: [B, P, ps, KV, dh]
    kp = k_cache.reshape(B, S // ps, ps, KV, dh)
    vp = v_cache.reshape(B, S // ps, ps, KV, dh)
    kg = jnp.take_along_axis(kp, safe[:, :, None, None, None], axis=1)
    vg = jnp.take_along_axis(vp, safe[:, :, None, None, None], axis=1)
    qg = q.reshape(B, KV, G, dh).astype(jnp.float32) / np.sqrt(dh)
    sc = jnp.einsum("bkgd,bptkd->bkgpt", qg, kg.astype(jnp.float32))
    tok_pos = safe[:, :, None] * ps + jnp.arange(ps)[None, None, :]  # [B,P,ps]
    length = jnp.asarray(length)
    lb = length if length.ndim else jnp.broadcast_to(length, (B,))
    valid = (page_ids[:, :, None] >= 0) & (tok_pos < lb[:, None, None])
    sc = jnp.where(valid[:, None, None], sc, NEG_INF)
    sc = sc.reshape(B, KV, G, P * ps)
    m = sc.max(-1)
    p = jnp.exp(sc - m[..., None])
    l = p.sum(-1)
    out = jnp.einsum("bkgn,bnkd->bkgd", p.reshape(B, KV, G, P * ps),
                     vg.reshape(B, P * ps, KV, dh).astype(jnp.float32))
    out = out / l[..., None]
    lse = m + jnp.log(l)
    return out.reshape(B, Hq, dh), lse.reshape(B, Hq)


# ---------------------------------------------------------------------------
# 3. Causal flash attention (train / prefill)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jnp.ndarray,   # [B, S, H, dh]
    k: jnp.ndarray,   # [B, S, KV, dh]
    v: jnp.ndarray,   # [B, S, KV, dh]
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    kexp = jnp.repeat(k, G, axis=2)
    vexp = jnp.repeat(v, G, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) / np.sqrt(dh),
                    kexp.astype(jnp.float32))
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vexp.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# 4. LServe page-wise min/max pooling (prepare-memory stage)
# ---------------------------------------------------------------------------


def page_minmax(k_cache: jnp.ndarray, page_size: int):
    """[B, S, KV, dh] -> (min, max) [B, S/ps, KV, dh]."""
    B, S, KV, dh = k_cache.shape
    kp = k_cache.reshape(B, S // page_size, page_size, KV, dh).astype(jnp.float32)
    return kp.min(axis=2), kp.max(axis=2)


def lserve_page_scores(q: jnp.ndarray, pmin: jnp.ndarray, pmax: jnp.ndarray):
    """LServe relevancy: per page max(q . min, q . max) summed over channels.

    q [B,Hq,dh] -> scores [B, n_pages] (mean over query heads).
    score = sum_c max(q_c * min_c, q_c * max_c)   per (head, page) -> mean_h
    """
    qf = q.astype(jnp.float32)
    # channel-wise max of the two products, then sum over channels
    prod_min = qf[:, :, None, None, :] * pmin.astype(jnp.float32)[:, None]  # [B,H,P,KV,dh]
    prod_max = qf[:, :, None, None, :] * pmax.astype(jnp.float32)[:, None]
    sc = jnp.maximum(prod_min, prod_max).sum(-1)  # [B, H, P, KV]
    return sc.max(-1).mean(1)  # max over kv heads, mean over q heads -> [B, P]


# ---------------------------------------------------------------------------
# 5. BM25 scoring + top-k (RAG relevancy+retrieval)
# ---------------------------------------------------------------------------


def bm25_scores(tf: jnp.ndarray, doc_len: jnp.ndarray, idf: jnp.ndarray,
                *, k1: float = 1.5, b: float = 0.75, avgdl: float = 100.0):
    """tf [B, D, T] term counts; doc_len [B, D]; idf [B, T] -> scores [B, D]."""
    tff = tf.astype(jnp.float32)
    denom = tff + k1 * (1.0 - b + b * doc_len.astype(jnp.float32)[..., None] / avgdl)
    return jnp.einsum("bt,bdt->bd", idf.astype(jnp.float32),
                      tff * (k1 + 1.0) / denom)


def bm25_topk(tf, doc_len, idf, k: int, **kw):
    scores = bm25_scores(tf, doc_len, idf, **kw)
    return jax.lax.top_k(scores, min(k, scores.shape[-1]))  # match ops path


# ---------------------------------------------------------------------------
# Sparse MLA decode attention over selected latent rows
# ---------------------------------------------------------------------------


def mla_sparse_decode_attention(q, rows, n_valid, dv: int, scale: float):
    """q [B,H,W]; rows [B,N,W]; n_valid [B] -> [B,H,dv]: softmax over the
    first n_valid rows of (q . row) * scale, values the rows' first dv."""
    sc = jnp.einsum("bhw,bnw->bhn", q.astype(jnp.float32),
                    rows.astype(jnp.float32)) * scale
    ok = jnp.arange(rows.shape[1])[None] < n_valid[:, None]
    sc = jnp.where(ok[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhn,bnd->bhd", p, rows[..., :dv].astype(jnp.float32))
