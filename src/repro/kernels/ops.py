"""Public jit'd kernel API. On CPU the Pallas kernels run in interpret mode
(exact same kernel body, validated against ref.py); on TPU they compile via
Mosaic. ``_interp`` is the one place that decides: the kernel modules take
``interpret`` as a required argument. ``use_pallas(False)`` routes
everything through the ref oracles (useful under 512-device dry-run
lowering where interpret-mode overhead in the traced graph is unwanted).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import relevancy_topk as _rt
from repro.kernels import sparse_decode_attention as _sda
from repro.kernels import flash_attention as _fa
from repro.kernels import page_pool as _pp
from repro.kernels import bm25_topk as _bm

_STATE = {"pallas": True}


def use_pallas(flag: bool) -> None:
    _STATE["pallas"] = flag


def pallas_enabled() -> bool:
    return _STATE["pallas"]


def _interp() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------


def _pow2_block(n: int, want: int) -> int:
    """Largest power-of-two block <= want, and at least 8: Mosaic refuses a
    partial block below the 8-row sublane tile, so a shorter axis is padded
    up to one whole block instead."""
    b = 1
    while b * 2 <= min(n, want):
        b *= 2
    return max(b, 8)


def relevancy_topk(q, keys, weights, k: int, *, block: int = 2048,
                   c: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused score + top-k. Exact when c=0 (c -> min(block, S)).

    Pads the key axis to a power-of-two block multiple (the kernel masks the
    pad with -inf via valid_len), so any context length is accepted.
    """
    if not _STATE["pallas"]:
        return ref.relevancy_topk(q, keys, weights, k)
    B, S, dk = keys.shape
    blk = _pow2_block(max(S, 2), block)
    pad = (-S) % blk
    if pad:
        keys = jnp.pad(keys, ((0, 0), (0, pad), (0, 0)))
    vals, idx = _rt.relevancy_topk_candidates(
        q, keys, weights, block=blk, c=c, valid_len=S, interpret=_interp())
    return _rt.merge_candidates(vals, idx, min(k, S))


def paged_decode_attention(q, k_cache, v_cache, page_ids, length, *,
                           page_size: int = 64):
    if not _STATE["pallas"]:
        return ref.paged_decode_attention(q, k_cache, v_cache, page_ids,
                                          page_size, length)
    return _sda.paged_decode_attention(q, k_cache, v_cache, page_ids, length,
                                       page_size=page_size,
                                       interpret=_interp())


lse_merge = _sda.lse_merge


def flash_attention(q, k, v, *, window: int = 0, bq: int = 512, bk: int = 512):
    if not _STATE["pallas"]:
        return ref.flash_attention(q, k, v, window=window or None)
    return _fa.flash_attention(q, k, v, bq=bq, bk=bk, window=window,
                               interpret=_interp())


def page_minmax(k_cache, *, page_size: int = 64):
    if not _STATE["pallas"]:
        return ref.page_minmax(k_cache, page_size)
    return _pp.page_minmax(k_cache, page_size=page_size, interpret=_interp())


def bm25_topk(tf, doc_len, idf, k: int, *, block: int = 4096, c: int = 0,
              k1: float = 1.5, b: float = 0.75, avgdl: float = 100.0,
              valid=None):
    """Fused BM25 score + top-k. ``valid`` restricts scoring to the first
    ``valid`` documents (traced ok — the serving corpus store passes its
    live doc count so ingest never re-jits); None scores all D docs."""
    B, D, T = tf.shape
    if not _STATE["pallas"]:
        if valid is None:
            return ref.bm25_topk(tf, doc_len, idf, k, k1=k1, b=b, avgdl=avgdl)
        scores = ref.bm25_scores(tf, doc_len, idf, k1=k1, b=b, avgdl=avgdl)
        scores = jnp.where(jnp.arange(D)[None] < valid, scores, -jnp.inf)
        return jax.lax.top_k(scores, min(k, D))
    blk = _pow2_block(max(D, 2), block)
    pad = (-D) % blk
    if pad:
        tf = jnp.pad(tf, ((0, 0), (0, pad), (0, 0)))
        doc_len = jnp.pad(doc_len, ((0, 0), (0, pad)), constant_values=1.0)
    c = c or min(k, blk)
    vals, idx = _bm.bm25_topk_candidates(
        tf, doc_len, idf, block=blk, c=c, k1=k1, b=b, avgdl=avgdl,
        valid=D if valid is None else valid, interpret=_interp())
    return _rt.merge_candidates(vals, idx, min(k, D))
