"""Distributed fused relevancy+top-k over a sequence-sharded index.

The paper's PCIe principle — "transfer only the top-k indices" (§5.2) —
becomes the ICI principle: every model-axis shard runs the fused Pallas
kernel over ITS slice of the compressed keys, then the mesh all-gathers only
(k values, k indices) pairs per shard (8 B * k per shard, ~16 KB for k=2048)
and merges locally. All-gathering raw scores would move O(S) bytes; all-
gathering KV would move O(S * kv * hd) — this moves O(k * shards).

``batch_axis`` optionally shards the batch dim over the data axes (decode_32k
layout: batch on data, sequence on model); ``axis`` may be a tuple for the
long-context layout where the sequence spans (data, model) jointly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops


def _axes_tuple(axis):
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _n_shards(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_index(mesh, axes):
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def distributed_relevancy_topk(
    q: jnp.ndarray,        # [B, Hq, dk]
    keys: jnp.ndarray,     # [B, S, dk]  sharded on S over `axis`
    weights: jnp.ndarray,  # [B, Hq]
    k: int,
    mesh: Mesh,
    axis="model",
    *,
    block: int = 2048,
    batch_axis=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact global top-k with index-only exchange. Returns (vals, idx) in
    GLOBAL sequence coordinates."""
    axes = _axes_tuple(axis)
    n_shards = _n_shards(mesh, axes)
    S = keys.shape[1]
    assert S % n_shards == 0, (S, n_shards)
    local_S = S // n_shards
    k_local = min(k, local_S)
    ba = batch_axis

    def local_fn(q_l, keys_l, w_l):
        shard = _shard_index(mesh, axes)
        vals, idx = ops.relevancy_topk(q_l, keys_l, w_l, k_local, block=block)
        idx = idx + shard * local_S
        # index-only exchange: gather [n_shards, B, k_local] pairs
        vals_g = jax.lax.all_gather(vals, axes)
        idx_g = jax.lax.all_gather(idx, axes)
        B = vals.shape[0]
        vals_f = jnp.moveaxis(vals_g, 0, 1).reshape(B, -1)
        idx_f = jnp.moveaxis(idx_g, 0, 1).reshape(B, -1)
        top_v, pos = jax.lax.top_k(vals_f, min(k, n_shards * k_local))
        top_i = jnp.take_along_axis(idx_f, pos, axis=1)
        if top_v.shape[1] < k:  # pad (can't select more than exist)
            pad = k - top_v.shape[1]
            top_v = jnp.pad(top_v, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            top_i = jnp.pad(top_i, ((0, 0), (0, pad)), constant_values=-1)
        return top_v, top_i

    seq_spec = axes if len(axes) > 1 else axes[0]
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(ba), P(ba, seq_spec, None), P(ba)),
        out_specs=(P(ba), P(ba)),
        check_vma=False,
    )
    return fn(q, keys, weights)


def sharded_page_add(kidx: jnp.ndarray, delta: jnp.ndarray, pg,
                     mesh: Mesh, axis="model", batch_axis=None):
    """Add ``delta`` [B, di] into page ``pg`` of the page-sharded index cache
    ``kidx`` [B, n_pages, di] WITHOUT gathering it: only the shard owning the
    page updates (masked local dynamic-update)."""
    axes = _axes_tuple(axis)
    n_shards = _n_shards(mesh, axes)
    n_pages = kidx.shape[1]
    local_np = n_pages // n_shards
    ba = batch_axis
    seq_spec = axes if len(axes) > 1 else axes[0]

    def local_fn(kx, d, pg_arr):
        shard = _shard_index(mesh, axes)
        lpg = pg_arr[0] - shard * local_np
        ok = (lpg >= 0) & (lpg < local_np)
        idx = jnp.clip(lpg, 0, local_np - 1)
        cur = jax.lax.dynamic_slice(kx, (0, idx, 0),
                                    (kx.shape[0], 1, kx.shape[2]))
        new = cur + jnp.where(ok, 1.0, 0.0) * d[:, None]
        return jax.lax.dynamic_update_slice(kx, new.astype(kx.dtype),
                                            (0, idx, 0))

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(ba, seq_spec, None), P(ba), P()),
        out_specs=P(ba, seq_spec, None),
        check_vma=False,
    )
    return fn(kidx, delta, jnp.asarray(pg, jnp.int32)[None])


def distributed_sparse_decode(
    q: jnp.ndarray,         # [B, Hq, dh]
    k_cache: jnp.ndarray,   # [B, S, KV, dh] sharded on S
    v_cache: jnp.ndarray,
    page_ids: jnp.ndarray,  # [B, P] GLOBAL page ids
    length: jnp.ndarray,    # [B]
    mesh: Mesh,
    axis="model",
    *,
    page_size: int = 64,
    batch_axis=None,
):
    """Sequence-parallel sparse decode: each shard attends to ITS selected
    pages; only (out, lse) pairs cross the mesh (FlashDecoding LSE merge).
    Exchanged bytes: O(B * Hq * dh * n_shards) — independent of S and k.

    Thin dense-contract wrapper over ``distributed_paged_sparse_decode``
    (ONE shard body for both: a second copy of the merge math drifted once
    and could not feed LSE-merging callers) — the LSE is dropped for
    callers that only want the merged output."""
    out, _ = distributed_paged_sparse_decode(
        q, k_cache, v_cache, page_ids, length, mesh, axis,
        page_size=page_size, batch_axis=batch_axis)
    return out


def distributed_paged_sparse_decode(
    q: jnp.ndarray,         # [B, Hq, dh]
    k_cache: jnp.ndarray,   # [B, S, KV, dh] paged-pool VIEW, sharded on S
    v_cache: jnp.ndarray,
    page_ids: jnp.ndarray,  # [B, P] GLOBAL logical page ids, -1 invalid
    lengths: jnp.ndarray,   # [B] per-slot live lengths
    mesh: Mesh,
    axis="model",
    *,
    page_size: int = 64,
    batch_axis=None,
):
    """The ONE LSE-merged sequence-parallel apply core (paper Fig. 6a),
    stated for the SERVING pool contract — the dense per-request layout of
    ``distributed_sparse_decode`` is the special case where lengths are
    broadcast and the view has no holes:

      * ``k_cache``/``v_cache`` are the gathered paged-pool view
        (``kernels.page_pool.pool_gather`` of one layer over the slot's
        page table) —
        positions outside a slot's live region are exact zeros by the
        pool's zero-page invariant, so cutting the view into sequence
        shards never exposes stale data;
      * ``lengths`` is PER SLOT (continuous batching: every slot attends
        at its own offset); each shard clips it to its window;
      * ``page_ids`` may carry ``-1`` holes anywhere (merged sharded
        selections, threshold selection) — holes are masked locally.

    Each shard attends to ITS selected pages only; the mesh exchanges
    (out, lse) pairs — O(B * Hq * dh * n_shards) bytes, independent of S
    and k — and FlashDecoding-merges them. Returns (out [B, Hq, dh],
    lse [B, Hq]), the same contract as ``ops.paged_decode_attention`` so it
    drops into ``models.decode_step_paged_presel``'s ``page_attn`` seam.
    """
    axes = _axes_tuple(axis)
    n_shards = _n_shards(mesh, axes)
    S = k_cache.shape[1]
    assert S % (n_shards * page_size) == 0, (S, n_shards, page_size)
    local_S = S // n_shards
    local_pages = local_S // page_size
    ba = batch_axis

    def local_fn(q_l, kc_l, vc_l, pids, len_g):
        shard = _shard_index(mesh, axes)
        local = pids - shard * local_pages
        mine = (pids >= 0) & (local >= 0) & (local < local_pages)
        local = jnp.where(mine, local, -1)
        len_l = jnp.clip(len_g - shard * local_S, 0, local_S)
        out, lse = ops.paged_decode_attention(
            q_l, kc_l, vc_l, local.astype(jnp.int32), len_l,
            page_size=page_size)
        outs = jax.lax.all_gather(out, axes)   # [n_shards, B, Hq, dh]
        lses = jax.lax.all_gather(lse, axes)
        return ops.lse_merge(outs, lses)

    seq_spec = axes if len(axes) > 1 else axes[0]
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(ba), P(ba, seq_spec, None, None),
                  P(ba, seq_spec, None, None), P(ba), P(ba)),
        out_specs=(P(ba), P(ba)),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, page_ids, lengths)
