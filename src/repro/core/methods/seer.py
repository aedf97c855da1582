"""SeerAttention-R — paper Table 1 row 2.

  prepare   — linear down-projection of queries + average pooling of keys
              over blocks (block 64)
  relevancy — inner product (pooled q . pooled k per block)
  retrieve  — top-k blocks (token budget 4096) OR threshold (5e-4 on
              softmax-normalized block scores)
  apply     — block-sparse attention over selected blocks

Threshold mode keeps static shapes: the engine still materializes
``budget/block`` slots but invalidates (-1) every block whose normalized
score is below the threshold — matching the paper's variable-sparsity
semantics with TPU-legal shapes.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MemoryConfig
from repro.core.pipeline import MemoryPipeline
from repro.kernels import ops
from repro.kernels.page_pool import kv_view
from repro.models import layers as L

Params = Dict

# Hetero offload metadata: gate pooling + block scoring touch only the
# pooled gate cache; block-sparse apply stays with the KV pool.
OFFLOAD_STAGES = ("prepare", "relevancy", "retrieve")


def seer_init(key, cfg: ArchConfig, mem: MemoryConfig, stacked: bool = True):
    hd = cfg.hd
    hp_in = cfg.n_heads * hd
    kv_in = cfg.n_kv_heads * hd

    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "wq_gate": L.dense_init(k1, hp_in, mem.index_dim, jnp.bfloat16),
            "wk_gate": L.dense_init(k2, kv_in, mem.index_dim, jnp.bfloat16),
        }

    n = cfg.n_layers if stacked else 1
    p = jax.vmap(one)(jax.random.split(key, n))
    return p if stacked else jax.tree.map(lambda a: a[0], p)


def make_sparse_fn(cfg: ArchConfig, mem: MemoryConfig, *, tp: int = 16):
    bs = mem.block_size
    n_sel = max(mem.token_budget // bs, 1)

    def sparse_fn(q, kc, vc, length, sp, k_new=None):
        B = q.shape[0]
        S = kc.shape[1]
        with jax.named_scope("prepare"):
            # pooled block keys
            k_gate = (kv_view(kc).reshape(B, S, -1) @ sp["wk_gate"])
            k_blk = k_gate.reshape(B, S // bs, bs, -1).mean(axis=2)
        with jax.named_scope("relevancy"):
            # gated query; the kernel fuses the top-k blocks in
            q_gate = (q[:, 0].reshape(B, -1) @ sp["wq_gate"])[:, None, :]
            w = jnp.ones((B, 1), jnp.float32)
            vals, bidx = ops.relevancy_topk(
                q_gate, k_blk, w, n_sel, block=max(min(4096, S // bs), n_sel))
        with jax.named_scope("retrieve"):
            lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
            live = bidx * bs < lb[:, None]
            if mem.selection == "threshold":
                # normalize: block softmax over selected candidates, drop < tau
                probs = jax.nn.softmax(vals, axis=-1)
                live &= probs >= mem.threshold
            bidx = jnp.where(live, bidx, -1)
        from repro.core.methods.dsa import strip_dead_heads, repad_dead_heads
        with jax.named_scope("apply"):
            out, _ = ops.paged_decode_attention(
                strip_dead_heads(q, cfg), kc, vc, bidx.astype(jnp.int32), lb,
                page_size=bs)
            return repad_dead_heads(out, q, cfg)

    return sparse_fn


def build_pipeline(cfg: ArchConfig, mem: MemoryConfig, sp: Params, *,
                   fused: bool = False) -> MemoryPipeline:
    from repro.kernels import ref as kref
    bs = mem.block_size
    n_sel = max(mem.token_budget // bs, 1)

    def prepare(M):
        kc, _ = M
        B, S = kc.shape[0], kc.shape[1]
        kg = kc.reshape(B, S, -1) @ sp["wk_gate"]
        return kg.reshape(B, S // bs, bs, -1).mean(axis=2)

    def relevancy(k_blk, q):
        B = q.shape[0]
        qg = (q[:, 0].reshape(B, -1) @ sp["wq_gate"])[:, None, :]
        w = jnp.ones((B, 1), jnp.float32)
        if fused:
            _, bidx = ops.relevancy_topk(
                qg, k_blk, w, n_sel, block=max(min(4096, k_blk.shape[1]), n_sel))
            return ("fused", bidx)
        return ("scores", kref.relevancy_scores(qg, k_blk, w))

    def retrieve(M, S):
        kc, vc = M
        tag, val = S
        if tag == "fused":
            return (kc, vc, val)
        _, bidx = jax.lax.top_k(val, n_sel)
        return (kc, vc, bidx)

    def apply(Mp, q):
        kc, vc, bidx = Mp
        B = q.shape[0]
        length = jnp.full((B,), kc.shape[1], jnp.int32)
        out, _ = ops.paged_decode_attention(
            q[:, 0], kc, vc, bidx.astype(jnp.int32), length, page_size=bs)
        return out

    return MemoryPipeline(
        name="seer-fused" if fused else "seer",
        prepare=prepare, relevancy=relevancy, retrieve=retrieve, apply=apply,
        fused={"relevancy": ("relevancy", "retrieve")} if fused else {},
    )
