"""LServe — paper Table 1 row 3.

  prepare   — page-wise min/max pooling of the key cache (Pallas page_pool
              kernel); logical pages grouped into physical pages
  relevancy — per-channel max(q*min, q*max) bound, max-reduced over logical
              pages within each physical page
  retrieve  — top-k physical pages
  apply     — block-sparse attention over the logical pages of the selected
              physical pages (+ optional sliding-window locality, Mixtral)
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MemoryConfig
from repro.core.pipeline import MemoryPipeline
from repro.kernels import ops, ref as kref
from repro.kernels.page_pool import kv_view

Params = Dict

# Hetero offload metadata: the page min/max summaries are the only inputs
# to relevancy/retrieve; sparse apply stays with the KV pool.
OFFLOAD_STAGES = ("prepare", "relevancy", "retrieve")


def lserve_init(key, cfg: ArchConfig, mem: MemoryConfig, stacked: bool = True):
    # LServe's prepare/relevancy are projection-free (min/max pooling of raw
    # keys) — no learned parameters; a dummy leaf keeps the scan signature.
    n = cfg.n_layers if stacked else 1
    return {"_": jnp.zeros((n,), jnp.int32)} if stacked else {"_": jnp.zeros((), jnp.int32)}


def _physical_scores(q, pmin, pmax, ppp: int):
    """Logical page scores max-reduced to physical pages. -> [B, n_phys]."""
    sc = kref.lserve_page_scores(q, pmin, pmax)  # [B, n_logical]
    B, nl = sc.shape
    pad = (-nl) % ppp
    if pad:
        sc = jnp.pad(sc, ((0, 0), (0, pad)), constant_values=-1e30)
    return sc.reshape(B, (nl + pad) // ppp, ppp).max(axis=-1)


def make_sparse_fn(cfg: ArchConfig, mem: MemoryConfig, *, tp: int = 16):
    ps = mem.block_size                   # logical page size
    ppp = mem.pages_per_physical
    n_phys_sel = max(mem.token_budget // (ps * ppp), 1)

    def sparse_fn(q, kc, vc, length, sp, k_new=None):
        B = q.shape[0]
        S = kc.shape[1]
        with jax.named_scope("prepare"):
            # page min/max pooling (Pallas kernel)
            pmin, pmax = ops.page_minmax(kv_view(kc), page_size=ps)
            pmin = pmin.max(axis=2)  # reduce kv-head dim for the bound
            pmax = pmax.max(axis=2)
        with jax.named_scope("relevancy"):
            sc = _physical_scores(q[:, 0], pmin[:, :, None], pmax[:, :, None],
                                  ppp)
        with jax.named_scope("retrieve"):
            # top physical pages, expanded to logical pages
            n_sel = min(n_phys_sel, sc.shape[1])  # small caches: all of them
            _, phys = jax.lax.top_k(sc, n_sel)                 # [B, n_sel]
            logical = (phys[..., None] * ppp +
                       jnp.arange(ppp)[None, None, :]).reshape(B, -1)
            lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
            live = (logical * ps < lb[:, None]) & (logical < S // ps)
            logical = jnp.where(live, logical, -1)
        from repro.core.methods.dsa import strip_dead_heads, repad_dead_heads
        with jax.named_scope("apply"):
            out, _ = ops.paged_decode_attention(
                strip_dead_heads(q, cfg), kc, vc, logical.astype(jnp.int32),
                lb, page_size=ps)
            return repad_dead_heads(out, q, cfg)

    return sparse_fn


def build_pipeline(cfg: ArchConfig, mem: MemoryConfig, sp: Params, *,
                   fused: bool = False) -> MemoryPipeline:
    ps = mem.block_size
    ppp = mem.pages_per_physical
    n_phys_sel = max(mem.token_budget // (ps * ppp), 1)

    def prepare(M):
        kc, _ = M
        if fused:
            pmin, pmax = ops.page_minmax(kc, page_size=ps)
        else:
            pmin, pmax = kref.page_minmax(kc, ps)
        return pmin.max(axis=2), pmax.max(axis=2)

    def relevancy(I, q):
        pmin, pmax = I
        return _physical_scores(q[:, 0], pmin[:, :, None], pmax[:, :, None], ppp)

    def retrieve(M, sc):
        kc, vc = M
        _, phys = jax.lax.top_k(sc, n_phys_sel)
        B = sc.shape[0]
        logical = (phys[..., None] * ppp +
                   jnp.arange(ppp)[None, None, :]).reshape(B, -1)
        return (kc, vc, logical)

    def apply(Mp, q):
        kc, vc, logical = Mp
        B = q.shape[0]
        length = jnp.full((B,), kc.shape[1], jnp.int32)
        out, _ = ops.paged_decode_attention(
            q[:, 0], kc, vc, logical.astype(jnp.int32), length, page_size=ps)
        return out

    return MemoryPipeline(
        name="lserve-fused" if fused else "lserve",
        prepare=prepare, relevancy=relevancy, retrieve=retrieve, apply=apply,
    )
