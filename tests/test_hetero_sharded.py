"""Sharded hetero offload (src/repro/hetero/sharded.py).

Load-bearing properties:

  * pooled decode with ``offload_shards=2`` (sync AND overlap) emits token
    streams BIT-IDENTICAL to ``offload_shards=1`` and to the fully
    synchronous configuration with inline retrieval, for dsa / seer /
    lserve, on a mixed pool containing a retrieval-enabled slot — the
    per-shard candidate merge is exact (index-only exchange loses nothing);
  * each shard's TransferLedger reports at most 8 bytes per candidate per
    step on the up link (k (val, idx) pairs — never scores, never KV), and
    per-shard per-step traffic stays below one KV page;
  * the sharded top-k merge equals the exact reference top-k for random
    shard counts and ragged/empty/all-masked shards (hypothesis property);
  * per-slot lookahead invalidation: membership events (staggered
    admission, retrieval splice) PATCH the affected rows instead of
    discarding the pending lookahead — cold starts stay at 1 per fallback
    window entry (the reuse-count regression for PR 4's satellite fix);
  * ``distributed_paged_sparse_decode`` (LSE-merged sequence-parallel
    apply over the paged-pool view) matches the single-device paged
    attention, including through ``decode_step_paged_presel``'s
    ``page_attn`` seam.

CI runs this file under ``XLA_FLAGS=--xla_force_host_platform_device_count``
of 2 AND 4 (the ``test-sharded`` matrix) so every topology — shards
sharing one offload device, one device per shard — is exercised; with one
device all transfers degenerate to no-ops and the properties still hold.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch
from repro.data import build_corpus
from repro.distributed.topk import distributed_paged_sparse_decode
from repro.hetero.select import make_offload_select, merge_shard_topk
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from repro.models import init_params, model as M
from repro.retrieval import RetrievalConfig
from repro.serving import Engine, OffloadConfig, Request, ServeConfig, \
    Scheduler

NEG_INF = -1e30


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    corpus = build_corpus(48, retrieval_vocab=128, doc_max=8,
                          gen_vocab=cfg.vocab_size, embed_dim=16, seed=0)
    return cfg, params, corpus


def _drain(eng, n_steps):
    got = {}
    for _ in range(n_steps):
        for rid, _slot, tok in eng.poll():
            got.setdefault(rid, []).append(tok)
    return got


def _free_pages_zero(pool) -> bool:
    idx = np.asarray([0] + pool.free, np.int32)
    k = np.asarray(pool.device["k_pages"][:, idx], np.float32)
    v = np.asarray(pool.device["v_pages"][:, idx], np.float32)
    return not k.any() and not v.any()


def _rcfg(corpus, mode):
    return RetrievalConfig(mode=mode, kind="rag", corpus=corpus, k=2,
                           trigger="flare", tau=1.1, min_interval=3,
                           max_retrievals=1, query_window=6)


# ---------------------------------------------------------------------------
# serving bit-exactness: shards=2 == shards=1 == inline-retrieval pairing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["dsa", "seer", "lserve"])
def test_sharded_bitmatches_single_and_inline(setup, method):
    """Mixed pool (one retrieval-enabled slot + one sparse slot): the
    sharded topologies serve the same tokens as the single-offload-device
    executor and the fully synchronous inline-retrieval schedule, and the
    per-shard up link carries at most 8 bytes per candidate per step."""
    cfg, params, corpus = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (16, 24)]
    streams, events = {}, {}
    sharded_eng = None
    for off, rmode, shards in (("sync", "inline", 1),
                               ("sync", "sync", 2),
                               ("overlap", "overlap", 2)):
        sc = ServeConfig(max_len=128, n_slots=2, method=method, tp=4,
                         page=8, kv_page_size=16,
                         offload_cfg=OffloadConfig(
                             mode=off, shards=shards,
                             validate=(off == "overlap")),
                         retrieval=_rcfg(corpus, rmode))
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, 6, retrieval=(i == 0)))
        key = (off, rmode, shards)
        streams[key] = _drain(eng, 24)
        events[key] = [(e["slot"], tuple(e["ids"])) for e in
                       eng.retrieval.events]
        assert events[key], "no retrieval fired"
        assert eng.pool.pages_in_use() == 0
        assert _free_pages_zero(eng.pool)      # zero-page invariant
        if shards == 2:
            sharded_eng = eng
    first = streams[("sync", "inline", 1)]
    assert all(s == first for s in streams.values())
    assert len(set(map(tuple, events.values()))) == 1

    # index-only invariant: per shard, the up link moved exactly k
    # (val, idx) pairs per offloaded step — 8 bytes per candidate, less
    # than one KV page (what a page-shipping design would move)
    hx = sharded_eng.hetero
    L, B = cfg.n_layers, sc.n_slots
    kv_page = sc.kv_page_size * cfg.n_kv_heads * cfg.hd * 2 * 2  # bf16, K+V
    for led, shard in zip(hx.ledgers, hx.shards):
        assert led.up_bytes <= led.steps * 8 * L * B * shard.n_part
        assert led.up_bytes / led.steps < kv_page
    rep = hx.report()
    assert rep["shards"]["n_shards"] == 2
    assert len(rep["shards"]["per_shard_transfer"]) == 2


def test_sharded_under_scheduler(setup):
    """Chunked admission + staggered completion through the Scheduler:
    overlapped 2-shard serving bit-matches the synchronous single-shard
    executor end to end."""
    cfg, params, _ = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (10, 40, 16, 33)]
    streams = {}
    for off, shards in (("sync", 1), ("overlap", 2)):
        sc = ServeConfig(max_len=128, n_slots=2, method="dsa", tp=4, page=8,
                         kv_page_size=16, prefill_chunk=16,
                         chunk_threshold=32,
                         offload_cfg=OffloadConfig(mode=off,
                                                   shards=shards))
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
        sch = Scheduler(eng, prefill_token_budget=32)
        rids = [sch.submit(p, max_new=4) for p in prompts]
        done = sch.run()
        assert sorted(done) == sorted(rids)
        streams[(off, shards)] = {r: done[r].tokens for r in done}
        assert eng.pool.pages_in_use() == 0
        assert _free_pages_zero(eng.pool)
    assert streams[("sync", 1)] == streams[("overlap", 2)]


def test_shard_ownership_alignment(setup):
    """The paged pool's page->shard map agrees with the executor's static
    ingest windows, and ServeConfig aligns max_len so every shard covers a
    whole number of selection and KV pages."""
    cfg, params, _ = setup
    sc = ServeConfig(max_len=100, n_slots=2, method="dsa", tp=4, page=8,
                     kv_page_size=16,
                     offload_cfg=OffloadConfig(mode="sync", shards=2))
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    assert eng.sc.max_len % (2 * 16) == 0 and eng.sc.max_len >= 100
    eng._ensure_pool()
    owners = eng.pool.shard_owners(2)
    local = eng.sc.max_len // 2
    for s, shard in enumerate(eng.hetero.shards):
        assert shard.tok_lo == s * local and shard.n_tok == local
        pages = np.flatnonzero(owners == s) * sc.kv_page_size
        assert pages.min() == shard.tok_lo
        assert pages.max() + sc.kv_page_size == shard.tok_lo + shard.n_tok
        view = eng.pool.shard_table_view(2, s)
        assert view.shape == (sc.n_slots, local // sc.kv_page_size)


# ---------------------------------------------------------------------------
# per-slot lookahead invalidation (reuse-count regression)
# ---------------------------------------------------------------------------


def test_lookahead_survives_membership_events(setup):
    """Staggered admission and a retrieval splice no longer discard the
    pending lookahead: the executor patches only the affected slots' rows,
    so the whole run pays exactly ONE cold start (pool entry) and every
    other step reuses the overlapped selection."""
    cfg, params, corpus = setup
    rng = np.random.default_rng(3)
    sc = ServeConfig(max_len=128, n_slots=2, method="dsa", tp=4, page=8,
                     kv_page_size=16,
                     offload_cfg=OffloadConfig(mode="overlap",
                                               validate=True),
                     retrieval=_rcfg(corpus, "overlap"))
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, size=16), 8,
                       retrieval=True))
    got = {}
    for step in range(26):
        for rid, _s, tok in eng.poll():
            got.setdefault(rid, []).append(tok)
        if step == 2:    # staggered admission: membership change mid-decode
            eng.submit(Request(
                1, rng.integers(0, cfg.vocab_size, size=12), 6,
                retrieval=False))
    assert len(got[0]) == 8 and len(got[1]) == 6
    assert eng.retrieval.events, "no splice landed — regression unexercised"
    p = eng.hetero.profiler
    assert p.lookahead_cold == 1, \
        f"membership events cold-started the lookahead: {p.lookahead_cold}"
    # at least the admission and the splice completion were row-patches
    assert p.lookahead_patched >= 2
    assert p.lookahead_hits + p.lookahead_cold == p.offload_steps
    assert p.lookahead_hits > p.lookahead_patched


# ---------------------------------------------------------------------------
# sharded top-k merge == exact reference top-k (property)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 5), st.integers(1, 24),
       st.booleans())
def test_sharded_topk_merge_matches_ref(seed, n_shards, k, masked):
    """Per-shard exact top-k over ragged contiguous score slices +
    candidate merge == ``ref.relevancy_topk`` over the whole key axis, bit
    for bit — values, indices, AND tie order (ReLU scores tie at exact 0.0
    often). Empty shards and shards entirely past the live length
    (all-masked) contribute nothing / NEG_INF candidates and must not
    perturb the merge. Scores are computed once and sliced — the property
    of the MERGE is that it loses nothing whenever the per-shard scores
    equal the global ones, which is what the executor's per-page summary
    einsums provide (each page's score depends only on its own summary
    row)."""
    rng = np.random.default_rng(seed)
    B, Hq, dk = int(rng.integers(1, 4)), 2, 8
    S = int(rng.integers(n_shards, 40))
    q = jnp.asarray(rng.normal(size=(B, Hq, dk)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(B, S, dk)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.0, 1.0, size=(B, Hq)), jnp.float32)
    length = int(rng.integers(0, S + 1)) if masked else S

    scores = np.asarray(ref.relevancy_scores(q, keys, w))
    scores = np.where(np.arange(S)[None, :] < length, scores, NEG_INF)

    # oracle: global masked scores -> exact top-k (== ref.relevancy_topk
    # composed with the live mask)
    ref_v, ref_i = jax.lax.top_k(jnp.asarray(scores), min(k, S))
    if not masked:
        rv2, ri2 = ref.relevancy_topk(q, keys, w, k)
        np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(rv2))
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(ri2))

    # ragged contiguous shard cuts (possibly empty)
    bounds = [0] + sorted(rng.integers(0, S + 1,
                                       size=n_shards - 1).tolist()) + [S]
    vals, idx = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi == lo:
            continue                      # empty shard: nothing to send
        v, i = jax.lax.top_k(jnp.asarray(scores[:, lo:hi]),
                             min(k, hi - lo))
        vals.append(np.asarray(v))
        idx.append(np.asarray(i) + lo)    # global coordinates
    mv, mi = merge_shard_topk(jnp.asarray(np.concatenate(vals, -1)),
                              jnp.asarray(np.concatenate(idx, -1)),
                              min(k, S))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(ref_v))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
def test_windowed_bundles_match_full_select(seed, n_shards):
    """End-to-end bundle property: ingesting one key stream through ragged
    window bundles and merging their partial selections reproduces the full
    bundle's selection exactly (windowed ingest routes every token to the
    owning shard and drops the rest)."""
    cfg = get_arch("llama3.2-1b").smoke()
    mem = cfg.memory
    rng = np.random.default_rng(seed)
    page, max_len, n_slots = 8, 64, 2
    from repro.core.methods import get_sparse_method
    sp = get_sparse_method("dsa")[0](jax.random.PRNGKey(seed % 97), cfg,
                                     mem, stacked=True)
    full = make_offload_select("dsa", cfg, mem, dsa_page=page,
                               n_slots=n_slots, max_len=max_len)
    # ragged page-aligned windows covering [0, max_len)
    P = max_len // page
    cuts = sorted(set([0, P] + rng.integers(0, P + 1,
                                            size=n_shards - 1).tolist()))
    windows = [(lo * page, (hi - lo) * page)
               for lo, hi in zip(cuts[:-1], cuts[1:]) if hi > lo]
    shards = [make_offload_select("dsa", cfg, mem, dsa_page=page,
                                  n_slots=n_slots, max_len=max_len,
                                  window=w) for w in windows]

    lens = rng.integers(1, max_len + 1, size=n_slots).astype(np.int32)
    S = int(lens.max())
    kv, hd = cfg.n_kv_heads, cfg.hd
    k_span = jnp.asarray(rng.normal(size=(cfg.n_layers, n_slots, S, kv, hd)),
                         jnp.float32)
    q = jnp.asarray(rng.normal(
        size=(cfg.n_layers, n_slots, cfg.padded_heads(4), hd)), jnp.float32)
    slot_ids = jnp.arange(n_slots, dtype=jnp.int32)
    start = jnp.zeros((n_slots,), jnp.int32)
    n_valid = jnp.asarray(lens)
    lengths = jnp.asarray(lens)

    s_full = full.ingest_span(full.summary_init(), sp, k_span, slot_ids,
                              start, n_valid)
    want = np.asarray(full.select(sp, s_full, q, lengths))

    vals, idx = [], []
    for sh in shards:
        s_sh = sh.ingest_span(sh.summary_init(), sp, k_span, slot_ids,
                              start, n_valid)
        v, i = sh.select_partial(sp, s_sh, q, lengths)
        vals.append(np.asarray(v))
        idx.append(np.asarray(i))
    got = np.asarray(full.finalize(jnp.asarray(np.concatenate(vals, -1)),
                                   jnp.asarray(np.concatenate(idx, -1)),
                                   lengths))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# LSE-merged sequence-parallel apply over the paged-pool view
# ---------------------------------------------------------------------------


def test_distributed_paged_sparse_decode_matches_single():
    """Sequence-parallel sparse decode over the gathered pool view (zero
    pages outside live regions, per-slot lengths, -1 holes from merged
    selections) matches single-device paged attention, directly and through
    the ``decode_step_paged_presel`` page_attn seam."""
    rng = np.random.default_rng(0)
    B, S, KV, dh, Hq, ps = 2, 128, 2, 16, 4, 8
    lengths = np.asarray([70, 33], np.int32)
    k = np.zeros((B, S, KV, dh), np.float32)
    v = np.zeros((B, S, KV, dh), np.float32)
    for b in range(B):   # zero-page invariant: dead region is exact zeros
        k[b, : lengths[b]] = rng.normal(size=(lengths[b], KV, dh))
        v[b, : lengths[b]] = rng.normal(size=(lengths[b], KV, dh))
    q = rng.normal(size=(B, Hq, dh)).astype(np.float32)
    pids = np.full((B, 6), -1, np.int32)     # -1 holes mid-selection
    pids[0, :4] = [0, 3, 8, 2]
    pids[1, :3] = [4, 1, 0]

    ref_out, ref_lse = ops.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pids),
        jnp.asarray(lengths), page_size=ps)
    mesh = make_mesh((jax.device_count(),), ("model",))
    out, lse = distributed_paged_sparse_decode(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pids),
        jnp.asarray(lengths), mesh, "model", page_size=ps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-6)

    # page_attn seam: the serving apply step accepts the distributed
    # implementation and produces the same logits (LSE merge is exact up
    # to fp reassociation)
    cfg = get_arch("llama3.2-1b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    pool = M.make_page_pool(cfg, 2, 64, page_size=8, total_pages=17, tp=4)
    table = np.zeros((2, 8), np.int32)
    table[0, :4] = [1, 2, 3, 4]
    table[1, :2] = [5, 6]
    pool["page_table"] = jnp.asarray(table)
    pool["lengths"] = jnp.asarray([20, 9], jnp.int32)
    tok = jnp.asarray([3, 7], jnp.int32)
    live = jnp.asarray([True, True])
    pidx = jnp.tile(jnp.asarray([[0, 1, -1]], jnp.int32)[None],
                    (cfg.n_layers, 2, 1))
    want = M.decode_step_paged_presel(params, cfg, tok, dict(pool), live,
                                      pidx, cfg.memory, page_size=8, tp=4)
    dist = functools.partial(distributed_paged_sparse_decode,
                             mesh=mesh, axis="model")

    def page_attn(q, kc, vc, p, lb, page_size):
        return dist(q, kc, vc, p, lb, page_size=page_size)

    got = M.decode_step_paged_presel(params, cfg, tok, dict(pool), live,
                                     pidx, cfg.memory, page_size=8, tp=4,
                                     page_attn=page_attn)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-5)
