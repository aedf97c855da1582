"""Fused Compute-Relevancy + Retrieval kernel (the paper's FPGA "General
Setup" engine, Fig. 7, adapted to TPU).

One pallas_call fuses, per key block:
  1. multi-head inner-product scoring against the compressed key/index
     vectors (MXU matmul, keys streamed HBM->VMEM exactly once),
  2. head-weighted ReLU reduction (DSA lightning indexer),
  3. an in-VMEM bitonic top-c selection — scores never round-trip to HBM.

Only (c values, c indices) per block leave the kernel (the paper's
"transfer only the top-k indices over PCIe" principle — here it bounds both
HBM writeback and the cross-device exchange of the distributed top-k).

TPU adaptation note (DESIGN.md §2): the FPGA maintains ONE running top-k list
sequentially; a TPU prefers the two-stage data-parallel form — exact per-block
top-c (bitonic network on the VPU) + a cheap global merge of nb*c candidates.
Exactness: global top-k is a subset of the union of per-block
top-min(k, block) candidates, so c >= min(k, block) => exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic import bitonic_topk


def _kernel(q_ref, k_ref, w_ref, vals_ref, idx_ref, *, block: int, c: int,
            valid_len: int):
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)          # [Hq, dk]
    keys = k_ref[0].astype(jnp.float32)       # [block, dk]
    w = w_ref[0].astype(jnp.float32)          # [1, Hq]
    # 1) multi-head inner product on the MXU, keys on the lane axis;
    #    float32 passes (the default rounds the operands to bf16, which
    #    reorders near-tied scores against the float32 oracle)
    exact = jax.lax.Precision.HIGHEST
    dots = jax.lax.dot_general(
        q, keys, (((1,), (1,)), ((), ())), precision=exact,
        preferred_element_type=jnp.float32)   # [Hq, block]
    # 2) weighted ReLU reduction -> one lane-dense score row
    scores = jnp.dot(w, jax.nn.relu(dots), precision=exact,
                     preferred_element_type=jnp.float32)  # [1, block]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    scores = jnp.where(j * block + pos < valid_len, scores, -jnp.inf)
    # 3) in-VMEM bitonic top-c (no HBM writeback of raw scores)
    top_v, top_pos = bitonic_topk(scores, pos, c)
    vals_ref[0, 0] = top_v
    idx_ref[0, 0] = j * block + top_pos


@functools.partial(
    jax.jit,
    static_argnames=("block", "c", "valid_len", "interpret"),
)
def relevancy_topk_candidates(
    q: jnp.ndarray,        # [B, Hq, dk]
    keys: jnp.ndarray,     # [B, S, dk]  compressed key / index vectors
    weights: jnp.ndarray,  # [B, Hq]     per-head query weights
    *,
    block: int = 2048,
    c: int = 0,            # candidates per block; 0 -> min(block, S)
    valid_len: int = 0,    # 0 -> S (static; dynamic masking happens on merge)
    interpret: bool,
):
    """Per-block candidates: (vals [B, nb, c], idx [B, nb, c]).

    Every block's last two dims are either full array dims or (block, dk),
    so the call is legal for Mosaic: weights ride as [B, 1, Hq] and the
    candidates leave as [B, nb, 1, c] rows."""
    B, S, dk = keys.shape
    Hq = q.shape[1]
    block = min(block, S)
    assert S % block == 0, (S, block)
    nb = S // block
    c = c or block
    c = min(c, block)
    valid_len = valid_len or S
    kern = functools.partial(_kernel, block=block, c=c, valid_len=valid_len)
    vals, idx = pl.pallas_call(
        kern,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, dk), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, Hq), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, c), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, c), lambda b, j: (b, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((B, nb, 1, c), jnp.int32),
        ],
        interpret=interpret,
        name="relevancy_topk",
    )(q, keys, weights.reshape(B, 1, Hq))
    return vals.reshape(B, nb, c), idx.reshape(B, nb, c)


def merge_candidates(vals: jnp.ndarray, idx: jnp.ndarray, k: int):
    """Global merge: [B, nb, c] -> exact top-k over all candidates."""
    B = vals.shape[0]
    flat_v = vals.reshape(B, -1)
    flat_i = idx.reshape(B, -1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    return top_v, jnp.take_along_axis(flat_i, pos, axis=1)
