"""Offload-side memory index + selection (the emulated FPGA bitstream).

For each sparse method the offload device keeps a compact, incrementally
maintained SUMMARY of the key cache in logical (slot, page) space — the
paper's "compressed memory resides on the accelerator" — and answers
lookahead queries with top-k page indices:

  dsa    : per-micro-page SUM of lightning-indexer key projections
           (mean recovered at score time; score = w-weighted ReLU inner
           product, identical math to the fused relevancy kernel);
  seer   : per-block SUM of gate-projected keys (mean-pooled block keys),
           optional threshold selection on softmax-normalized scores;
  lserve : per-logical-page channel-wise MIN/MAX of raw keys, max-reduced
           over physical-page groups.

Summaries are updated from the SAME per-layer keys the main device writes
into the KV pool (one token per decode step, spans at prefill), so summary
state is a pure function of the token stream — which is what makes the
overlapped executor bit-match its synchronous schedule. Zero-initialized
summaries mirror the paged pool's zero-page invariant: a page the pool
considers zero scores exactly like an all-zero key page.

SHARDING (paper §5.2 / Fig. 6a at scale): every bundle is built over a
WINDOW ``(tok_lo, n_tok)`` of the logical token space — the full window by
default, one contiguous KV-sequence shard per offload device under the
sharded executor. Ingest masks tokens outside the window (so each shard's
index covers exactly its pages), ``select_partial`` returns the shard's
top candidates as ``(vals, idx)`` pairs in GLOBAL page coordinates — the
index-only exchange unit, 8 bytes per candidate — and ``finalize`` merges
candidate lists into the final page selection on the compute side.
``select = finalize ∘ select_partial``: the single-device path is the
one-shard special case of the same math, and because per-page scores are
independent of the window extent and ``jax.lax.top_k`` breaks ties by
ascending index on shard-ordered candidates, the merged selection is
bit-identical to the unsharded one.

All functions are pure jnp so the executor can jit them once and pin them
to the offload device via committed inputs. Their ops carry the inline
path's stage names (``jax.named_scope``): ingest is ``prepare``, scoring
``relevancy``, top-k and the candidate merge ``retrieve``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MemoryConfig

NEG_INF = -1e30
BIG = 3e30  # finite min/max sentinel (inf would poison 0 * inf -> nan)


@dataclasses.dataclass(frozen=True)
class OffloadSelect:
    """Per-method offload-side implementation bundle (one per window)."""

    method: str
    page: int                 # selection granularity (tokens per page)
    n_sel: int                # width of the FINAL merged index vector
    n_pages: int              # logical pages in THIS bundle's window
    summary_init: Callable    # () -> summary pytree
    reset: Callable           # (summary, slot_ids) -> summary
    ingest: Callable          # (summary, sp, k_new, pos, live) -> summary
    ingest_span: Callable     # (summary, sp, k_span, slots, start, n_valid)
    select: Callable          # (sp, summary, q_layers, lengths) -> pidx
    # --- sharded protocol ---
    select_partial: Optional[Callable] = None
    #   (sp, summary, q_layers, lengths) -> (vals [L,B,n_part],
    #   idx [L,B,n_part] in GLOBAL page/physical-page coordinates)
    finalize: Optional[Callable] = None
    #   (vals [L,B,K], idx [L,B,K], lengths [B]) -> pidx [L,B,n_sel]
    n_part: int = 0           # candidate width of select_partial
    tok_lo: int = 0           # global token offset of the window
    n_tok: int = 0            # tokens covered by the window


def _qf_layers(q_layers: jnp.ndarray, n_in: int) -> jnp.ndarray:
    """[L, B, Hp, hd] -> [L, B, n_in]: flatten heads, strip TP dead-head
    padding (matches the inline ``qf[:, :n_in]`` slice)."""
    L, B = q_layers.shape[:2]
    return q_layers.reshape(L, B, -1)[:, :, :n_in]


def _win_mask(P: int, page: int, tok_lo: int, lengths: jnp.ndarray):
    """[L?, B, P] page-liveness mask for a window starting at ``tok_lo``:
    page p covers global tokens [tok_lo + p*page, ...), live iff its first
    token is inside the slot's live region."""
    return ((tok_lo + jnp.arange(P)[None, None, :] * page)
            < lengths[None, :, None])


def merge_shard_topk(vals: jnp.ndarray, idx: jnp.ndarray, k: int):
    """Top-k over (shard-ordered) candidate lists. Candidates within a
    shard are index-ascending among ties (lax.top_k is stable) and shards
    concatenate in ascending-window order, so tie-breaking here matches a
    global top-k exactly — the merged selection is bit-identical to the
    unsharded one."""
    k = min(k, vals.shape[-1])
    top_v, pos = jax.lax.top_k(vals, k)
    top_i = jnp.take_along_axis(idx, pos, axis=-1)
    return top_v, top_i


# ---------------------------------------------------------------------------
# shared per-page SUM summary (dsa indexer projections / seer gate
# projections differ only in page size and projection-weight key)
# ---------------------------------------------------------------------------


def _sum_summary(key: str, weight: str, page: int, L: int, n_slots: int,
                 P: int, di: int, tok_lo: int):
    """(summary_init, reset, ingest, ingest_span) for a summary that holds,
    per logical page of the window [tok_lo, tok_lo + P*page), the SUM of
    ``k @ sp[weight]`` over its live tokens. Tokens outside the window are
    masked out (their contribution lands on a clipped page as exact zero),
    so a sharded bundle ingests the same stream as the full one and simply
    ignores what it does not own."""
    tok_hi = tok_lo + P * page

    def summary_init():
        return {key: jnp.zeros((L, n_slots, P, di), jnp.float32)}

    def reset(s, slot_ids):
        return {key: s[key].at[:, slot_ids].set(0.0)}

    def _contrib(sp, k):  # [L, ..., KV, hd] -> [L, ..., di]
        kf = k.reshape(*k.shape[:-2], -1)
        return jnp.einsum("l...f,lfd->l...d", kf,
                          sp[weight]).astype(jnp.float32)

    @jax.named_scope("prepare")
    def ingest(s, sp, k_new, pos, live):
        B = pos.shape[0]
        own = live & (pos >= tok_lo) & (pos < tok_hi)
        c = _contrib(sp, k_new) * own.astype(jnp.float32)[None, :, None]
        pages = jnp.clip((pos - tok_lo) // page, 0, P - 1)
        return {key: s[key].at[:, jnp.arange(B), pages].add(c)}

    @jax.named_scope("prepare")
    def ingest_span(s, sp, k_span, slot_ids, start, n_valid):
        S = k_span.shape[2]
        gpos = start[:, None] + jnp.arange(S)[None, :]           # [Bg, S]
        valid = ((jnp.arange(S)[None, :] < n_valid[:, None])
                 & (gpos >= tok_lo) & (gpos < tok_hi))
        c = _contrib(sp, k_span) * valid[None, :, :, None]
        pages = jnp.clip((gpos - tok_lo) // page, 0, P - 1)      # [Bg, S]
        return {key: s[key].at[:, slot_ids[:, None], pages].add(c)}

    return summary_init, reset, ingest, ingest_span


# ---------------------------------------------------------------------------
# dsa — lightning-indexer micro-page sums
# ---------------------------------------------------------------------------


def _dsa(cfg: ArchConfig, mem: MemoryConfig, page: int, n_slots: int,
         max_len: int, window: Optional[Tuple[int, int]] = None
         ) -> OffloadSelect:
    tok_lo, n_tok = window or (0, max_len)
    P = n_tok // page                         # pages in this window
    n_sel = min(max(mem.top_k // page, 1), max_len // page)
    n_part = min(n_sel, P)
    L = cfg.n_layers
    di = mem.index_dim
    n_in = cfg.n_heads * cfg.hd
    summary_init, reset, ingest, ingest_span = _sum_summary(
        "kidx_sum", "wk_idx", page, L, n_slots, P, di, tok_lo)

    @jax.named_scope("relevancy")
    def select_partial(sp, s, q_layers, lengths):
        qf = _qf_layers(q_layers, n_in)
        q_idx = jnp.einsum("lbf,lfe->lbe", qf, sp["wq_idx"])
        q_idx = q_idx.reshape(*q_idx.shape[:2], -1, di).astype(jnp.float32)
        w = jax.nn.softmax(
            jnp.einsum("lbf,lfh->lbh", qf.astype(jnp.float32), sp["w_wgt"]),
            axis=-1)
        kp = s["kidx_sum"] * (1.0 / page)         # page means, [L, B, P, di]
        dots = jnp.einsum("lbhd,lbpd->lbhp", q_idx, kp)
        scores = jnp.einsum("lbh,lbhp->lbp", w, jax.nn.relu(dots))
        scores = jnp.where(_win_mask(P, page, tok_lo, lengths), scores,
                           NEG_INF)
        with jax.named_scope("retrieve"):
            vals, idx = jax.lax.top_k(scores, n_part)
            return vals, (idx + tok_lo // page).astype(jnp.int32)

    @jax.named_scope("retrieve")
    def finalize(vals, idx, lengths):
        top_v, top_i = merge_shard_topk(vals, idx, n_sel)
        return jnp.where(top_v > NEG_INF / 2, top_i, -1).astype(jnp.int32)

    def select(sp, s, q_layers, lengths):
        vals, idx = select_partial(sp, s, q_layers, lengths)
        return finalize(vals, idx, lengths)

    return OffloadSelect("dsa", page, n_sel, P, summary_init, reset, ingest,
                         ingest_span, select, select_partial, finalize,
                         n_part, tok_lo, n_tok)


# ---------------------------------------------------------------------------
# seer — gate-projected block sums (+ threshold selection)
# ---------------------------------------------------------------------------


def _seer(cfg: ArchConfig, mem: MemoryConfig, n_slots: int,
          max_len: int, window: Optional[Tuple[int, int]] = None
          ) -> OffloadSelect:
    bs = mem.block_size
    tok_lo, n_tok = window or (0, max_len)
    P = n_tok // bs
    n_sel = min(max(mem.token_budget // bs, 1), max_len // bs)
    n_part = min(n_sel, P)
    L = cfg.n_layers
    di = mem.index_dim
    n_in = cfg.n_heads * cfg.hd
    summary_init, reset, ingest, ingest_span = _sum_summary(
        "kgate_sum", "wk_gate", bs, L, n_slots, P, di, tok_lo)

    @jax.named_scope("relevancy")
    def select_partial(sp, s, q_layers, lengths):
        qf = _qf_layers(q_layers, n_in)
        q_gate = jnp.einsum("lbf,lfd->lbd", qf,
                            sp["wq_gate"]).astype(jnp.float32)
        k_blk = s["kgate_sum"] * (1.0 / bs)                 # block means
        scores = jax.nn.relu(
            jnp.einsum("lbd,lbpd->lbp", q_gate, k_blk))
        scores = jnp.where(_win_mask(P, bs, tok_lo, lengths), scores,
                           NEG_INF)
        with jax.named_scope("retrieve"):
            vals, idx = jax.lax.top_k(scores, n_part)
            return vals, (idx + tok_lo // bs).astype(jnp.int32)

    @jax.named_scope("retrieve")
    def finalize(vals, idx, lengths):
        top_v, top_i = merge_shard_topk(vals, idx, n_sel)
        out = jnp.where(top_v > NEG_INF / 2, top_i, -1)
        if mem.selection == "threshold":
            probs = jax.nn.softmax(top_v, axis=-1)
            out = jnp.where(probs >= mem.threshold, out, -1)
        return out.astype(jnp.int32)

    def select(sp, s, q_layers, lengths):
        vals, idx = select_partial(sp, s, q_layers, lengths)
        return finalize(vals, idx, lengths)

    return OffloadSelect("seer", bs, n_sel, P, summary_init, reset, ingest,
                         ingest_span, select, select_partial, finalize,
                         n_part, tok_lo, n_tok)


# ---------------------------------------------------------------------------
# lserve — per-page channel min/max bounds, physical-page grouping
# ---------------------------------------------------------------------------


def _lserve(cfg: ArchConfig, mem: MemoryConfig, n_slots: int,
            max_len: int, window: Optional[Tuple[int, int]] = None
            ) -> OffloadSelect:
    ps = mem.block_size
    ppp = mem.pages_per_physical
    tok_lo, n_tok = window or (0, max_len)
    P = n_tok // ps
    Pphys = max(P // ppp, 1)
    Pphys_full = max(max_len // ps // ppp, 1)
    n_phys = min(max(mem.token_budget // (ps * ppp), 1), Pphys_full)
    n_sel = n_phys * ppp
    n_part = min(n_phys, Pphys)               # candidates are PHYSICAL pages
    assert P % ppp == 0 and tok_lo % (ps * ppp) == 0, \
        "lserve shard windows must align to physical-page groups"
    L = cfg.n_layers
    kv, hd = cfg.n_kv_heads, cfg.hd
    tok_hi = tok_lo + n_tok

    def summary_init():
        return {"pmin": jnp.full((L, n_slots, P, kv, hd), BIG, jnp.float32),
                "pmax": jnp.full((L, n_slots, P, kv, hd), -BIG, jnp.float32)}

    def reset(s, slot_ids):
        return {"pmin": s["pmin"].at[:, slot_ids].set(BIG),
                "pmax": s["pmax"].at[:, slot_ids].set(-BIG)}

    @jax.named_scope("prepare")
    def ingest(s, sp, k_new, pos, live):
        B = pos.shape[0]
        kf = k_new.astype(jnp.float32)
        own = live & (pos >= tok_lo) & (pos < tok_hi)
        m = own[None, :, None, None]
        lo = jnp.where(m, kf, BIG)
        hi = jnp.where(m, kf, -BIG)
        pages = jnp.clip((pos - tok_lo) // ps, 0, P - 1)
        b = jnp.arange(B)
        return {"pmin": s["pmin"].at[:, b, pages].min(lo),
                "pmax": s["pmax"].at[:, b, pages].max(hi)}

    @jax.named_scope("prepare")
    def ingest_span(s, sp, k_span, slot_ids, start, n_valid):
        S = k_span.shape[2]
        kf = k_span.astype(jnp.float32)
        gpos = start[:, None] + jnp.arange(S)[None, :]           # [Bg, S]
        valid = ((jnp.arange(S)[None, :] < n_valid[:, None])
                 & (gpos >= tok_lo)
                 & (gpos < tok_hi))[None, :, :, None, None]
        lo = jnp.where(valid, kf, BIG)
        hi = jnp.where(valid, kf, -BIG)
        pages = jnp.clip((gpos - tok_lo) // ps, 0, P - 1)
        return {"pmin": s["pmin"].at[:, slot_ids[:, None], pages].min(lo),
                "pmax": s["pmax"].at[:, slot_ids[:, None], pages].max(hi)}

    @jax.named_scope("relevancy")
    def select_partial(sp, s, q_layers, lengths):
        # reduce the kv-head axis for the bound (same as the inline path)
        pmin = s["pmin"].max(axis=3)                       # [L, B, P, hd]
        pmax = s["pmax"].max(axis=3)
        qf = q_layers.astype(jnp.float32)                  # [L, B, Hp, hd]
        pm = jnp.maximum(qf[:, :, :, None, :] * pmin[:, :, None],
                         qf[:, :, :, None, :] * pmax[:, :, None])
        sc = pm.sum(-1).mean(axis=2)                       # [L, B, P]
        sc = jnp.where(_win_mask(P, ps, tok_lo, lengths), sc, NEG_INF)
        phys = sc.reshape(*sc.shape[:2], Pphys, ppp).max(-1)
        with jax.named_scope("retrieve"):
            vals, pidx = jax.lax.top_k(phys, n_part)       # [L, B, n_part]
            return vals, (pidx + tok_lo // (ps * ppp)).astype(jnp.int32)

    @jax.named_scope("retrieve")
    def finalize(vals, idx, lengths):
        top_v, top_i = merge_shard_topk(vals, idx, n_phys)
        logical = (top_i[..., None] * ppp + jnp.arange(ppp)
                   ).reshape(*top_i.shape[:2], -1)          # [L, B, n_sel]
        live = ((logical * ps < lengths[None, :, None])
                & jnp.repeat(top_v > NEG_INF / 2, ppp, axis=-1))
        return jnp.where(live, logical, -1).astype(jnp.int32)

    def select(sp, s, q_layers, lengths):
        vals, idx = select_partial(sp, s, q_layers, lengths)
        return finalize(vals, idx, lengths)

    return OffloadSelect("lserve", ps, n_sel, P, summary_init, reset, ingest,
                         ingest_span, select, select_partial, finalize,
                         n_part, tok_lo, n_tok)


# ---------------------------------------------------------------------------


def make_offload_select(method: str, cfg: ArchConfig, mem: MemoryConfig, *,
                        dsa_page: int, n_slots: int, max_len: int,
                        corpus=None, mac=None, rag_k: int = 4,
                        capacity: int = 0,
                        window: Optional[Tuple[int, int]] = None
                        ) -> OffloadSelect:
    """One bundle per OFFLOAD_STAGES declarer. The sparse-attention family
    (dsa/seer/lserve) keeps KV-page summaries; the document-memory family
    (rag/mac, built in ``repro.retrieval.select``) keeps the corpus index /
    per-slot memory banks — same protocol, different state. ``corpus`` /
    ``mac`` configure the retrieval-family builders and are ignored by the
    sparse ones. ``window=(tok_lo, n_tok)`` builds the bundle over one
    contiguous KV-sequence shard of the logical token space (sparse family
    only; the document-memory state has no sequence axis to shard)."""
    builders: Dict[str, Callable] = {
        "dsa": lambda: _dsa(cfg, mem, dsa_page, n_slots, max_len, window),
        "seer": lambda: _seer(cfg, mem, n_slots, max_len, window),
        "lserve": lambda: _lserve(cfg, mem, n_slots, max_len, window),
    }
    if method in ("rag", "mac"):
        assert window is None, "document-memory bundles do not shard"
        from repro.retrieval.select import make_retrieval_select
        return make_retrieval_select(method, cfg, n_slots=n_slots,
                                     corpus=corpus, mac=mac, k=rag_k,
                                     capacity=capacity)
    if method not in builders:
        raise KeyError(f"method {method!r} has no offload-side selection: "
                       f"{sorted(builders) + ['rag', 'mac']}")
    return builders[method]()
