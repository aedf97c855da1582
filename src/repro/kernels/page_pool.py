"""Paged KV-pool primitives + LServe page-wise min/max pooling.

Two groups of device code live here:

* Paged-pool access (``pool_gather`` / ``pool_scatter_token`` /
  ``pool_scatter_span``): the serving engine stores KV in a shared pool of
  fixed-size physical pages ``[n_pages, page_size, KV, dh]`` and addresses it
  through per-slot page tables, so HBM scales with *live* tokens instead of
  ``n_slots * max_len``. On CPU/XLA the gather materializes a contiguous
  per-slot view (advanced-indexing gather — XLA lowers it to a DMA-friendly
  dynamic-gather); on TPU the paged Pallas kernel in
  ``sparse_decode_attention.py`` consumes the page table directly via
  scalar-prefetch block index maps, so the materialized view is never needed
  on the sparse path.

* ``page_minmax``: the LServe Prepare-Memory stage. Each logical page of the
  key cache is summarized by its channel-wise min and max vectors; the
  relevancy stage then bounds q.k over the page by max(q*min, q*max) per
  channel. One grid step per (batch, page).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# ---------------------------------------------------------------------------
# Paged-pool gather / scatter
# ---------------------------------------------------------------------------


def pool_gather(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize contiguous per-slot views from the shared page pool.

    pages [P, ps, KV, dh]; page_table [B, NP] int32 (physical page id per
    logical page; unallocated entries point at the reserved zero page 0)
    -> [B, NP * ps, KV, dh].
    """
    P, ps, KV, dh = pages.shape
    B, NP = page_table.shape
    view = pages[page_table]                      # [B, NP, ps, KV, dh]
    return view.reshape(B, NP * ps, KV, dh)


def pool_scatter_token(pages: jnp.ndarray, page_table: jnp.ndarray,
                       positions: jnp.ndarray, values: jnp.ndarray,
                       live: jnp.ndarray) -> jnp.ndarray:
    """Write one new token per slot into the pool.

    pages [P, ps, KV, dh]; page_table [B, NP]; positions [B] (logical token
    position being written); values [B, KV, dh]; live [B] bool. Dead slots
    write ZEROS to the reserved trash page 0 so the pool stays clean (the
    zero page is part of every unallocated page-table entry and must remain
    zero for pooled decode to match per-request decode exactly).
    """
    ps = pages.shape[1]
    B = positions.shape[0]
    NP = page_table.shape[1]
    logical = jnp.clip(positions // ps, 0, NP - 1)  # dead slots can sit at NP
    dest = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    dest = jnp.where(live, dest, 0)
    off = positions % ps
    vals = values * live[:, None, None].astype(values.dtype)
    return pages.at[dest, off].set(vals)


def pool_scatter_span(pages: jnp.ndarray, page_table: jnp.ndarray,
                      start: jnp.ndarray, values: jnp.ndarray,
                      n_valid: jnp.ndarray) -> jnp.ndarray:
    """Write a span of C new tokens per slot (chunked prefill).

    pages [P, ps, KV, dh]; page_table [B, NP]; start [B] (first logical
    position of the span); values [B, C, KV, dh]; n_valid [B] (tokens of the
    span that are real — the rest are padding and are routed, zeroed, to the
    trash page 0).
    """
    ps = pages.shape[1]
    B, C = values.shape[:2]
    tok_pos = start[:, None] + jnp.arange(C)[None, :]          # [B, C]
    valid = jnp.arange(C)[None, :] < n_valid[:, None]          # [B, C]
    logical = jnp.clip(tok_pos // ps, 0, page_table.shape[1] - 1)
    dest = jnp.take_along_axis(page_table, logical, axis=1)    # [B, C]
    dest = jnp.where(valid, dest, 0)
    off = tok_pos % ps
    vals = values * valid[:, :, None, None].astype(values.dtype)
    return pages.at[dest, off].set(vals)


def _kernel(k_ref, min_ref, max_ref):
    blk = k_ref[0, 0].astype(jnp.float32)  # [ps, KV, dh]
    min_ref[0, 0] = blk.min(axis=0)
    max_ref[0, 0] = blk.max(axis=0)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def page_minmax(k_cache: jnp.ndarray, *, page_size: int = 64,
                interpret: bool):
    """[B, S, KV, dh] -> (min, max) [B, S/ps, KV, dh] fp32."""
    B, S, KV, dh = k_cache.shape
    ps = page_size
    assert S % ps == 0
    n_pages = S // ps
    kp = k_cache.reshape(B, n_pages, ps, KV, dh)
    return pl.pallas_call(
        _kernel,
        grid=(B, n_pages),
        in_specs=[pl.BlockSpec((1, 1, ps, KV, dh), lambda b, p: (b, p, 0, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, KV, dh), lambda b, p: (b, p, 0, 0)),
            pl.BlockSpec((1, 1, KV, dh), lambda b, p: (b, p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_pages, KV, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pages, KV, dh), jnp.float32),
        ],
        interpret=interpret,
        name="page_minmax",
    )(kp)
