"""DeepSeek Sparse Attention (lightning indexer) — paper Table 1 row 1.

Pipeline:
  prepare   — project hidden/KV into compact index vectors (+ partial RoPE)
  relevancy — 64-head inner product, per-head ReLU, query-weighted sum
  retrieve  — top-k tokens (k = 2048)
  apply     — attention restricted to the retrieved tokens

TPU adaptation: retrieval is quantized to micro-pages of ``page`` tokens
(default 16) so the apply stage gathers page-aligned DMA blocks (the paper's
own LServe/SeerAttention rows make the same granularity trade). Token-exact
mode (page=1) is kept for parity tests. Relevancy+retrieval run in the fused
Pallas kernel (FPGA General Setup analogue).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, MemoryConfig
from repro.core.pipeline import MemoryPipeline
from repro.kernels import ops
from repro.kernels.page_pool import kv_view
from repro.models import layers as L

Params = Dict

# Stages the hetero subsystem may move off the KV-owning device (paper
# §5.2): the indexer reads only compressed index vectors; apply gathers raw
# KV pages and must stay with the pool.
OFFLOAD_STAGES = ("prepare", "relevancy", "retrieve")


def dsa_init(key, cfg: ArchConfig, mem: MemoryConfig, stacked: bool = True):
    """Per-layer lightning-indexer params, stacked [L, ...] for the scan
    (the published indexer for a latent-attention model)."""
    if cfg.is_mla:
        return published_init(key, cfg, mem)
    hd = cfg.hd
    hp_in = cfg.n_heads * hd  # from query heads (pre-o-proj activations)
    kv_in = cfg.n_kv_heads * hd

    def one(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wq_idx": L.dense_init(k1, hp_in, mem.index_heads * mem.index_dim,
                                   jnp.bfloat16),
            "wk_idx": L.dense_init(k2, kv_in, mem.index_dim, jnp.bfloat16),
            "w_wgt": L.dense_init(k3, hp_in, mem.index_heads, jnp.float32,
                                  scale=0.02),
        }

    n = cfg.n_layers if stacked else 1
    keys = jax.random.split(key, n)
    p = jax.lax.map(one, keys)       # one layer's float32 draw at a time
    return p if stacked else jax.tree.map(lambda a: a[0], p)


def _index_q(sp: Params, q: jnp.ndarray):
    """Query half of the lightning indexer (relevancy): q [B, Hp, hd] ->
    (q_idx [B, Hi, di], w [B, Hi]); TP dead-head padding is cut off."""
    B = q.shape[0]
    qf = q.reshape(B, -1)[:, : sp["wq_idx"].shape[0]]
    q_idx = (qf @ sp["wq_idx"]).reshape(B, -1, sp["wk_idx"].shape[1])
    w = jax.nn.softmax((qf.astype(jnp.float32) @ sp["w_wgt"]), axis=-1)
    return q_idx, w


def _index_k(sp: Params, k_cache: jnp.ndarray):
    """Key half of the lightning indexer (prepare): k_cache [B, S, KV, hd]
    (or ``PooledKV``, gathered here) -> k_idx [B, S, di]."""
    B, S = k_cache.shape[:2]
    return kv_view(k_cache).reshape(B, S, -1) @ sp["wk_idx"]


def strip_dead_heads(q: jnp.ndarray, cfg: ArchConfig):
    """[B, 1, Hp, hd] -> [B, n_heads, hd]: drop TP dead-head padding before
    the paged attention kernel (it requires Hq % KV == 0; dead heads are
    zero-masked afterwards anyway)."""
    return q[:, 0, : cfg.n_heads]


def repad_dead_heads(out: jnp.ndarray, q_like: jnp.ndarray, cfg: ArchConfig):
    """[B, n_heads, hd] -> [B, 1, Hp, hd] (zeros in the dead-head slots)."""
    B, _, HP, hd = q_like.shape
    pad = HP - cfg.n_heads
    if pad:
        out = jnp.pad(out, ((0, 0), (0, pad), (0, 0)))
    return out.astype(q_like.dtype)[:, None]


def make_sparse_fn(cfg: ArchConfig, mem: MemoryConfig, *, tp: int = 16,
                   page: int = 16, max_context: int = 0):
    """Returns sparse_fn(q, kc, vc, length, sp) for model.decode_step.

    kc / vc are views [B, S, KV, hd] or ``PooledKV`` handles: only the
    index projection gathers the K view; the paged kernel reads the selected
    pages straight from the pool."""
    from repro.models import attention as A

    n_pages_sel = max(mem.top_k // page, 1)

    def sparse_fn(q, kc, vc, length, sp, k_new=None):
        B = q.shape[0]
        S = kc.shape[1]
        with jax.named_scope("prepare"):
            # index projection of the cached keys, mean-pooled to
            # micro-pages (page-level scores score the pooled keys)
            kp = _index_k(sp, kc).reshape(B, S // page, page, -1).mean(axis=2)
        with jax.named_scope("relevancy"):
            # the Pallas kernel fuses relevancy with the top-k retrieval;
            # its time counts here
            q_idx, w = _index_q(sp, q[:, 0])
            vals, pidx = ops.relevancy_topk(
                q_idx, kp, w, n_pages_sel,
                block=max(min(4096, S // page), n_pages_sel))
        with jax.named_scope("retrieve"):
            # mask pages beyond the live context (length [] or per-slot [B])
            lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
            live = pidx * page < lb[:, None]
            pidx = jnp.where(live, pidx, -1)
        with jax.named_scope("apply"):
            # paged sparse attention over the retrieved pages
            out, _ = ops.paged_decode_attention(
                strip_dead_heads(q, cfg), kc, vc, pidx.astype(jnp.int32), lb,
                page_size=page)
            return repad_dead_heads(out, q, cfg)  # [B,1,Hp,hd]

    return sparse_fn


def make_sparse_fn_distributed(cfg: ArchConfig, mem: MemoryConfig, mesh, *,
                               axis="model", batch_axis=None, tp: int = 16,
                               page: int = 64):
    """Sequence-parallel sparse decode (the beyond-paper optimized path):
    shard_map distributed top-k (index-only exchange) + per-shard paged
    attention with LSE merge. See distributed/topk.py."""
    from repro.distributed.topk import (distributed_relevancy_topk,
                                        distributed_sparse_decode)

    n_pages_sel = max(mem.top_k // page, 1)

    def sparse_fn(q, kc, vc, length, sp, k_new=None):
        B = q.shape[0]
        S = kc.shape[1]
        q_idx, w = _index_q(sp, q[:, 0])
        kp = _index_k(sp, kc).reshape(B, S // page, page, -1).mean(axis=2)
        vals, pidx = distributed_relevancy_topk(
            q_idx, kp, w, n_pages_sel, mesh, axis, block=2048,
            batch_axis=batch_axis)
        lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
        live = pidx * page < lb[:, None]
        pidx = jnp.where(live, pidx, -1)
        out = distributed_sparse_decode(
            strip_dead_heads(q, cfg), kc, vc, pidx.astype(jnp.int32), lb,
            mesh, axis, page_size=page, batch_axis=batch_axis)
        return repad_dead_heads(out, q, cfg)

    return sparse_fn


def idx_cache_init(cfg: ArchConfig, mem: MemoryConfig, batch: int,
                   max_len: int, *, page: int = 64, stacked: bool = True):
    """Incremental pooled-index cache: per-page SUM of index vectors (the
    mean is recovered at score time from `length`). Prepare-memory runs once
    per token instead of re-projecting the whole context every step."""
    n_pages = max_len // page
    shape = (batch, n_pages, mem.index_dim)
    if stacked:
        shape = (cfg.n_layers,) + shape
    return jnp.zeros(shape, jnp.float32)


def make_sparse_fn_cached(cfg: ArchConfig, mem: MemoryConfig, mesh, *,
                          axis="model", batch_axis=None, tp: int = 16,
                          page: int = 64):
    """Stateful sequence-parallel sparse decode (§Perf iteration 3):
    sparse_params = {"p": indexer weights, "kidx_sum": pooled index cache}.
    Per step: project ONLY the new token's key into the index, update one
    page of the cache, score the 128-dim compressed index (not the raw KV),
    distributed top-k + LSE-merged paged attention.
    """
    from repro.distributed.topk import (distributed_relevancy_topk,
                                        distributed_sparse_decode)

    n_pages_sel = max(mem.top_k // page, 1)

    def sparse_fn(q, kc, vc, length, sp, k_new=None):
        B = q.shape[0]
        S = kc.shape[1]
        p, kidx_sum = sp["p"], sp["kidx_sum"]
        # --- prepare (incremental): index the ONE new key. k_new is the key
        # computed THIS step (replicated) — slicing it back out of the
        # seq-sharded cache forces a full-cache all-gather (refuted
        # iteration, §Perf log). The page update is shard-local. ---
        k_idx_new = (k_new.reshape(B, -1) @ p["wk_idx"]).astype(jnp.float32)
        from repro.distributed.topk import sharded_page_add
        kidx_sum = sharded_page_add(kidx_sum, k_idx_new, (length - 1) // page,
                                    mesh, axis, batch_axis=batch_axis)
        # --- relevancy over the compressed pooled index ---
        q_idx, w = _index_q(p, q[:, 0])
        n_pages = kidx_sum.shape[1]
        counts = jnp.clip(length - jnp.arange(n_pages) * page, 0, page)
        kp = kidx_sum * (1.0 / jnp.maximum(counts, 1))[None, :, None]
        vals, pidx = distributed_relevancy_topk(
            q_idx, kp, w, n_pages_sel, mesh, axis, block=2048,
            batch_axis=batch_axis)
        live = pidx * page < length
        pidx = jnp.where(live, pidx, -1)
        lb = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
        out = distributed_sparse_decode(
            strip_dead_heads(q, cfg), kc, vc, pidx.astype(jnp.int32), lb,
            mesh, axis, page_size=page, batch_axis=batch_axis)
        return repad_dead_heads(out, q, cfg), dict(sp, kidx_sum=kidx_sum)

    return sparse_fn


def build_pipeline(cfg: ArchConfig, mem: MemoryConfig, sp: Params, *,
                   page: int = 16, fused: bool = False) -> MemoryPipeline:
    """Concrete 4-stage pipeline over (memory=(kc, vc), query=q [B,1,Hp,hd]).

    ``fused=False`` runs each stage as separate XLA ops (the paper's GPU
    baseline); ``fused=True`` routes relevancy+retrieval through the fused
    Pallas kernel (the FPGA analogue). Benchmarks compare the two — the
    structural reproduction of paper Fig. 9.
    """
    from repro.kernels import ref as kref

    n_pages_sel = max(mem.top_k // page, 1)

    def prepare(M):
        kc, vc = M
        B, S = kc.shape[0], kc.shape[1]
        return _index_k(sp, kc).reshape(B, S // page, page, -1).mean(axis=2)

    def relevancy(kp, q):
        q_idx, w = _index_q(sp, q[:, 0])
        if fused:
            vals, pidx = ops.relevancy_topk(
                q_idx, kp, w, n_pages_sel,
                block=max(min(4096, kp.shape[1]), n_pages_sel))
            return ("fused", pidx)
        return ("scores", kref.relevancy_scores(q_idx, kp, w))

    def retrieve(M, S):
        """ret(M, S) = M' — the refined memory is (KV, selected page ids)."""
        kc, vc = M
        tag, val = S
        if tag == "fused":
            return (kc, vc, val)
        _, pidx = jax.lax.top_k(val, n_pages_sel)
        return (kc, vc, pidx)

    def apply(Mp, q):
        kc, vc, pidx = Mp
        B = q.shape[0]
        length = jnp.full((B,), kc.shape[1], jnp.int32)
        out, _ = ops.paged_decode_attention(
            q[:, 0], kc, vc, pidx.astype(jnp.int32), length, page_size=page)
        return out

    pipe = MemoryPipeline(
        name="dsa-fused" if fused else "dsa",
        prepare=prepare, relevancy=relevancy, retrieve=retrieve, apply=apply,
        fused={"relevancy": ("relevancy", "retrieve")} if fused else {},
    )
    return pipe


# ---------------------------------------------------------------------------
# The published lightning indexer (DeepSeek-V3.2-Exp), for models/mla.py:
# its own cache of one index key per token, index queries from the query
# latent, and token-level top-k.
# ---------------------------------------------------------------------------


def published_init(key, cfg: ArchConfig, mem: MemoryConfig):
    """Per-layer indexer weights, stacked [L, ...]: ``wq_b`` (query latent
    -> index queries), ``wk`` (hidden -> index key), ``k_norm`` (a
    LayerNorm's gain and bias), ``w_proj`` (hidden -> head weights)."""
    d, Hi, di = cfg.d_model, mem.index_heads, mem.index_dim

    def one(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wq_b": L.dense_init(k1, cfg.q_lora_rank, Hi * di, jnp.bfloat16),
            "wk": L.dense_init(k2, d, di, jnp.bfloat16),
            "k_norm": {"w": jnp.ones((di,), jnp.float32),
                       "b": jnp.zeros((di,), jnp.float32)},
            "w_proj": L.dense_init(k3, d, Hi, jnp.bfloat16),
        }

    return jax.lax.map(one, jax.random.split(key, cfg.n_layers))


def _layer_norm(p, x, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return (xf - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


def index_key(sp: Params, h, cos, sin, eps: float):
    """Prepare: the index key of each new token, h [B, T, d] ->
    [B, T, di] = LayerNorm(h W_k), rope on its first 2 * cos.shape[-1]
    dims."""
    k = _layer_norm(sp["k_norm"], h @ sp["wk"], eps)
    return L.apply_rope(k[:, :, None], cos, sin)[:, :, 0].astype(h.dtype)


def index_query(sp: Params, c_q, h, cos, sin):
    """Relevancy, query side: c_q [B, q_lora] (the query latent), h [B, d]
    -> (index queries [B, Hi, di] roped like the keys, head weights [B, Hi]
    = h W_w * Hi^-1/2 * di^-1/2)."""
    B = c_q.shape[0]
    Hi = sp["w_proj"].shape[-1]
    q = (c_q @ sp["wq_b"]).reshape(B, 1, Hi, -1)
    q = L.apply_rope(q, cos, sin)[:, 0]
    w = (h @ sp["w_proj"]).astype(jnp.float32)
    return q, w * (Hi ** -0.5) * (q.shape[-1] ** -0.5)


def token_topk(q, w, keys, context, top_k: int):
    """Relevancy and selection over every cached index key: score
    s_t = sum_h w_h relu(q_h . k_t) for t < context, the rest never chosen;
    q [B, Hi, di], w [B, Hi], keys [B, S, di], context [B].
    -> (token ids [B, n], chosen [B]): the n = min(top_k, S) best ids, best
    first, of which the first ``chosen`` = min(top_k, context) are real."""
    S = keys.shape[1]
    exact = jax.lax.Precision.HIGHEST      # float32 passes, as the oracle
    dots = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                      keys.astype(jnp.float32), precision=exact)
    scores = jnp.einsum("bh,bhs->bs", w, jax.nn.relu(dots), precision=exact)
    scores = jnp.where(jnp.arange(S)[None] < context[:, None], scores,
                       -jnp.inf)
    n = min(top_k, S)
    _, ids = jax.lax.top_k(scores, n)
    return ids.astype(jnp.int32), jnp.minimum(context, n).astype(jnp.int32)

