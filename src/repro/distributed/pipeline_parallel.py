"""Optional GPipe-style pipeline parallelism over the 'pod' axis.

At multi-pod scale the cross-pod links are the thin pipe; PP turns them into
point-to-point boundary-activation transfers (collective_permute) instead of
full gradient all-reduces. The schedule is classic GPipe: M microbatches
flow through ``n_stages`` stage groups; bubble fraction (n_stages-1)/(M +
n_stages - 1).

Implementation: shard_map over the pod axis; each stage owns a
layer-contiguous slice of the (stacked) layer params; boundary activations
move with lax.ppermute inside a fori over (M + n_stages - 1) ticks.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(
    layer_group_fn: Callable,  # (stage_params, x) -> x
    mesh: Mesh,
    axis: str = "pod",
):
    """Build fn(stage_params_stacked, x_microbatches) -> y_microbatches.

    ``stage_params_stacked`` leading dim = n_stages (sharded over `axis`);
    ``x_microbatches`` [M, mb, ...] replicated; output from the LAST stage.
    """
    n_stages = mesh.shape[axis]

    def local(stage_params, xs):
        # stage_params: this stage's slice (leading dim 1) ; xs [M, mb, ...]
        sp = jax.tree.map(lambda a: a[0], stage_params)
        stage = jax.lax.axis_index(axis)
        M = xs.shape[0]
        ticks = M + n_stages - 1
        buf = jnp.zeros_like(xs)  # holds this stage's outputs per microbatch

        def tick(t, carry):
            inflight, buf = carry  # inflight: activation entering this stage
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 reads fresh microbatches; others consume the permuted
            src = jnp.where(stage == 0,
                            jnp.clip(t, 0, M - 1),
                            jnp.clip(mb_idx, 0, M - 1))
            x_in = jnp.where(stage == 0, xs[src], inflight)
            y = layer_group_fn(sp, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            buf = jnp.where(active,
                            buf.at[jnp.clip(mb_idx, 0, M - 1)].set(y), buf)
            # ship boundary activation to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, buf)

        inflight0 = jnp.zeros_like(xs[0])
        _, buf = jax.lax.fori_loop(0, ticks, tick, (inflight0, buf))
        # only the last stage's buffer is the model output; broadcast it
        # (ppermute is a permutation — multicast needs all_gather + select)
        return jax.lax.all_gather(buf, axis)[n_stages - 1]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
