"""Bitonic sort primitive usable INSIDE Pallas TPU kernels.

Mosaic has no ``lax.sort``/``lax.top_k`` lowering, so the fused
relevancy+retrieval kernels sort with a bitonic compare-exchange network
built from lane rotations + ``jnp.where``: the partner element ``x[i ^ j]``
for power-of-two ``j`` is ``x[i + j]`` where bit ``j`` of ``i`` is clear and
``x[i - j]`` where it is set — two ``pltpu.roll``s and a select, no gathers
and no reshapes of the lane axis (Mosaic lowers neither ``rev`` nor
sub-lane reshapes).

Ties are broken lexicographically on the integer payload (ascending index),
which makes the network a strict total order — exchanges stay consistent and
no payload is ever duplicated or dropped.

This mirrors the paper's FPGA "parallel reduction tree" top-k retriever
(Fig. 7b): same O(n log^2 n) compare network, vectorized over VPU lanes
instead of unrolled into LUTs.

Inputs are 2-D ``[rows, n]`` (Mosaic needs at least 2-D vectors). The sort
is jitted so that it also runs outside a kernel: ``pltpu.roll`` has a
lowering on every backend but no eager evaluation rule.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def _bit(shape, j: int) -> jnp.ndarray:
    """int32 [rows, n]: bit ``j`` (a power of two) of the lane index, 0/1.

    Built from ``broadcasted_iota`` (not a numpy constant) so the
    expression is legal inside a kernel body — Pallas rejects captured
    constants and Mosaic rejects 1-D iota."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (i // j) % 2


def _partner_swap(x: jnp.ndarray, j: int) -> jnp.ndarray:
    """Return y with y[..., i] = x[..., i ^ j] (j a power of two < n)."""
    n = x.shape[-1]
    axis = x.ndim - 1
    up = pltpu.roll(x, n - j, axis)      # up[i] = x[i + j]
    down = pltpu.roll(x, j, axis)        # down[i] = x[i - j]
    return jnp.where(_bit(x.shape, j) == 0, up, down)


@jax.jit
def bitonic_sort_desc(keys: jnp.ndarray, vals: jnp.ndarray):
    """Sort descending along the last axis. keys fp, vals int payload.

    Shapes [rows, n] with n a power of two. Returns (keys_sorted,
    vals_sorted).
    """
    n = keys.shape[-1]
    assert n & (n - 1) == 0, f"bitonic sort needs power-of-two n, got {n}"
    assert keys.ndim == 2, f"bitonic sort needs [rows, n], got {keys.shape}"
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            pk = _partner_swap(keys, j)
            pv = _partner_swap(vals, j)
            # runs with (i & k) == 0 sort DESCENDING (for i < n=k this covers
            # the whole array, giving a descending final merge); within a
            # descending run the lower index of the pair takes the max, so
            # a lane takes the max iff bit k and bit j of its index agree
            bk, bj = _bit(keys.shape, k), _bit(keys.shape, j)
            # strict self-wins predicate (lexicographic on (key, -val)) and
            # its complement, both spelled out: Mosaic lowers neither the
            # negation of a vector mask nor a select between masks
            self_gt = (keys > pk) | ((keys == pk) & (vals < pv))
            self_le = (keys < pk) | ((keys == pk) & (vals >= pv))
            sel_self = ((bk == bj) & self_gt) | ((bk != bj) & self_le)
            keys = jnp.where(sel_self, keys, pk)
            vals = jnp.where(sel_self, vals, pv)
            j //= 2
        k *= 2
    return keys, vals


def bitonic_topk(keys: jnp.ndarray, vals: jnp.ndarray, k: int):
    """Top-k by full descending sort + slice (exact when k <= n)."""
    ks, vs = bitonic_sort_desc(keys, vals)
    return ks[..., :k], vs[..., :k]
