"""Paged KV-pool primitives + LServe page-wise min/max pooling.

Two groups of device code live here:

* Paged-pool access (``pool_gather`` / ``pool_scatter`` at the locations
  ``token_dest`` / ``span_dest`` give): the serving engine stores KV in a
  shared pool of fixed-size physical pages, stacked over layers as
  ``[L, n_pages, page_size, KV, dh]``, and addresses it through per-slot
  page tables, so HBM scales with *live* tokens instead of
  ``n_slots * max_len``. The model's layer loop carries the whole stacked
  pool and hands these helpers a layer index, which becomes part of the
  gather's or scatter's indices: no ``[n_pages, page_size, KV, dh]`` slice
  of a layer is ever materialized, and the scatter updates the carried
  pool in place. ``pool_gather`` materializes a contiguous per-slot view of
  one layer (an advanced-indexing gather). The pooled decode step hands
  its attention a ``PooledKV`` (pool, layer, table) in place of that view:
  the paged Pallas kernel in ``sparse_decode_attention.py`` reads the
  selected pages straight from the pool, and only readers that need every
  cached token (the dense fallback, an indexer's key projection) build the
  view, with ``kv_view``. Chunked prefill still reads the view.

* ``page_minmax``: the LServe Prepare-Memory stage. Each logical page of the
  key cache is summarized by its channel-wise min and max vectors; the
  relevancy stage then bounds q.k over the page by max(q*min, q*max) per
  channel. One grid step per (batch, page).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# ---------------------------------------------------------------------------
# Paged-pool gather / scatter
# ---------------------------------------------------------------------------


def pool_gather(pages: jnp.ndarray, layer, page_table: jnp.ndarray
                ) -> jnp.ndarray:
    """Materialize contiguous per-slot views of one layer of the pool.

    pages [L, P, ps, KV, dh]; layer: int32 scalar (may be traced);
    page_table [B, NP] int32 (physical page id per logical page;
    unallocated entries point at the reserved zero page 0)
    -> [B, NP * ps, KV, dh].
    """
    _, _, ps, KV, dh = pages.shape
    B, NP = page_table.shape
    view = pages[layer, page_table]               # [B, NP, ps, KV, dh]
    return view.reshape(B, NP * ps, KV, dh)


class PooledKV(NamedTuple):
    """One layer of the stacked pool as the slots address it, standing in
    for the view ``pool_gather(pages, layer, table)`` [B, NP * ps, KV, dh]
    without building it. ``shape`` and ``dtype`` are the view's."""
    pages: jnp.ndarray          # [L, P, ps, KV, dh]
    layer: jnp.ndarray          # int32 scalar (may be traced)
    table: jnp.ndarray          # [B, NP] int32

    @property
    def shape(self):
        _, _, ps, kv, dh = self.pages.shape
        B, NP = self.table.shape
        return (B, NP * ps, kv, dh)

    @property
    def dtype(self):
        return self.pages.dtype


def kv_view(kv) -> jnp.ndarray:
    """The contiguous per-slot view [B, S, KV, dh] of a view (returned as
    it is) or of a ``PooledKV`` (gathered, in the ``retrieve`` stage)."""
    if isinstance(kv, PooledKV):
        with jax.named_scope("retrieve"):
            return pool_gather(kv.pages, kv.layer, kv.table)
    return kv


def as_pool(kv, page_size: int) -> PooledKV:
    """A ``PooledKV`` as it is, or a view [B, S, KV, dh] as a one-layer
    pool of ``B * S / page_size`` pages with the identity table
    ``b * NP + p`` (a reshape, no copy)."""
    if isinstance(kv, PooledKV):
        return kv
    B, S, KV, dh = kv.shape
    NP = S // page_size
    assert NP * page_size == S, (S, page_size)
    return PooledKV(kv.reshape(1, B * NP, page_size, KV, dh), jnp.int32(0),
                    jnp.arange(B * NP, dtype=jnp.int32).reshape(B, NP))


def pool_gather_rows(pages: jnp.ndarray, layer, page_table: jnp.ndarray,
                     tokens: jnp.ndarray) -> jnp.ndarray:
    """Chosen tokens of one layer of the pool, without the per-slot view.

    pages [L, P, ps, KV, dh]; page_table [B, NP]; tokens [B, N] int32
    logical positions -> [B, N, KV, dh]. Reads only the chosen rows.
    """
    ps = pages.shape[2]
    page = jnp.take_along_axis(page_table, tokens // ps, axis=1)   # [B, N]
    return pages[layer, page, tokens % ps]


def token_dest(page_table: jnp.ndarray, positions: jnp.ndarray,
               live: jnp.ndarray, page_size: int):
    """Where one new token per slot is written: ``(page, row, keep)``, each
    [B], for ``pool_scatter``.

    page_table [B, NP]; positions [B] (logical token position being
    written); live [B] bool. Dead slots go to the reserved trash page 0 and
    write zeros there (``keep`` False), so the pool stays clean: the zero
    page is part of every unallocated page-table entry and must remain zero
    for pooled decode to match per-request decode exactly. The location is
    the same in every layer.
    """
    NP = page_table.shape[1]
    logical = jnp.clip(positions // page_size, 0, NP - 1)  # dead can be NP
    dest = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    return jnp.where(live, dest, 0), positions % page_size, live


def span_dest(page_table: jnp.ndarray, start: jnp.ndarray,
              n_valid: jnp.ndarray, span: int, page_size: int):
    """Where a span of ``span`` new tokens per slot is written (chunked
    prefill): ``(page, row, keep)``, each [B, span], for ``pool_scatter``.

    start [B] (first logical position of the span); n_valid [B] (tokens of
    the span that are real — the rest are padding and go, zeroed, to the
    trash page 0).
    """
    tok_pos = start[:, None] + jnp.arange(span)[None, :]       # [B, C]
    valid = jnp.arange(span)[None, :] < n_valid[:, None]       # [B, C]
    logical = jnp.clip(tok_pos // page_size, 0, page_table.shape[1] - 1)
    dest = jnp.take_along_axis(page_table, logical, axis=1)    # [B, C]
    return jnp.where(valid, dest, 0), tok_pos % page_size, valid


def pool_scatter(pages: jnp.ndarray, layer, dest,
                 values: jnp.ndarray) -> jnp.ndarray:
    """Write new tokens into one layer of the pool, in place.

    pages [L, P, ps, KV, dh]; layer: int32 scalar (may be traced); dest
    ``(page, row, keep)`` from ``token_dest`` ([B]) or ``span_dest``
    ([B, C]); values [B, KV, dh] or [B, C, KV, dh]. Tokens not kept write
    zeros.
    """
    page, row, keep = dest
    vals = values * keep[..., None, None].astype(values.dtype)
    return pages.at[layer, page, row].set(vals)


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
