"""Sequence-parallel apply on the main mesh (``ServeConfig(main_mesh=N)``).

Load-bearing properties:

  * pooled decode with ``main_mesh=2`` emits token streams BIT-IDENTICAL
    to ``main_mesh=1`` — and to the fully synchronous inline-retrieval
    schedule — for dsa / seer / lserve on a mixed pool with a
    retrieval-enabled slot, both standalone and composed with
    ``offload_shards=2`` (selection AND apply sharded, the paper's
    Fig. 6a end to end);
  * the scheduler path (chunked prefill, staggered completion) holds the
    same bit-match, and the DENSE fallback branch of the traced cond runs
    through the same sequence-parallel seam (a window crossing mid-decode
    exercises both branches on the mesh);
  * pow2-bucketed decode views stay aligned to the shard granularity
    ``main_mesh * page_size`` — the regression for the bucket size that
    used to trip ``distributed_paged_sparse_decode``'s divisibility
    assert;
  * the unified LSE-merge core matches the single-device paged attention
    for duplicate-free page ids with ``-1`` holes anywhere and ragged
    per-slot lengths (hypothesis property), through the
    ONE shard body the dense wrapper shares.

CI runs this file under 2 host devices (the fast split) and in the
dedicated ``main-mesh`` leg of the ``test-sharded`` matrix under 4 — the
full 2-mesh + 2-selection-shard topology; with one device the mesh clamps
to a single device and every property still holds.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch
from repro.data import build_corpus
from repro.distributed.topk import (distributed_paged_sparse_decode,
                                    distributed_sparse_decode)
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.retrieval import RetrievalConfig
from repro.serving import Engine, OffloadConfig, Request, ServeConfig, \
    Scheduler


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


def _rcfg(corpus, mode):
    return RetrievalConfig(mode=mode, kind="rag", corpus=corpus, k=2,
                           trigger="flare", tau=1.1, min_interval=3,
                           max_retrievals=1, query_window=6)


# ---------------------------------------------------------------------------
# serving bit-exactness: mesh=2 == mesh=1 == inline retrieval, incl. the
# combined selection x apply topology
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["dsa", "seer", "lserve"])
def test_main_mesh_bitmatches_single(setup, method):
    """Mixed pool (one retrieval-enabled slot + one sparse slot): the
    2-device apply mesh serves the same tokens as the single-device apply,
    standalone AND composed with 2 selection shards, and the merged
    selection still reaches the mesh as indices only."""
    cfg, params, corpus = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (16, 24)]
    streams, events = {}, {}
    mesh_eng = None
    for off, rmode, shards, mesh_n in (("sync", "inline", 1, 1),
                                       ("sync", "sync", 1, 2),
                                       ("overlap", "overlap", 2, 2)):
        sc = ServeConfig(max_len=128, n_slots=2, method=method, tp=4,
                         page=8, kv_page_size=16,
                         offload_cfg=OffloadConfig(
                             mode=off, shards=shards, main_mesh=mesh_n,
                             validate=(off == "overlap")),
                         retrieval=_rcfg(corpus, rmode))
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, 6, retrieval=(i == 0)))
        key = (off, rmode, shards, mesh_n)
        streams[key] = _drain(eng, 24)
        events[key] = [(e["slot"], tuple(e["ids"])) for e in
                       eng.retrieval.events]
        assert events[key], "no retrieval fired"
        assert eng.pool.pages_in_use() == 0
        if mesh_n > 1:
            mesh_eng = eng
    first = streams[("sync", "inline", 1, 1)]
    assert all(s == first for s in streams.values())
    assert len(set(map(tuple, events.values()))) == 1

    # the apply side saw only merged page indices (up link) — 8 B per
    # candidate per step PER MESH COPY (replication to N mesh devices
    # moves N physical copies; the ledger counts every one)
    hx = mesh_eng.hetero
    L, B = cfg.n_layers, 2
    n_copies = hx.main_mesh.size
    for led, shard in zip(hx.ledgers, hx.shards):
        assert led.up_bytes <= led.steps * 8 * L * B * shard.n_part * \
            n_copies
    rep = hx.report()
    if jax.device_count() >= 2:
        assert len(set(rep["devices"]["main_mesh"])) == 2


def test_main_mesh_under_scheduler(setup):
    """Chunked admission + staggered completion through the Scheduler:
    the combined offload_shards=2 + main_mesh=2 topology bit-matches the
    synchronous single-device executor end to end."""
    cfg, params, _ = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (10, 40, 16, 33)]
    streams = {}
    for off, shards, mesh_n in (("sync", 1, 1), ("overlap", 2, 2)):
        sc = ServeConfig(max_len=128, n_slots=2, method="dsa", tp=4, page=8,
                         kv_page_size=16, prefill_chunk=16,
                         chunk_threshold=32,
                         offload_cfg=OffloadConfig(mode=off, shards=shards,
                                                   main_mesh=mesh_n))
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
        sch = Scheduler(eng, prefill_token_budget=32)
        rids = [sch.submit(p, max_new=4) for p in prompts]
        done = sch.run()
        assert sorted(done) == sorted(rids)
        streams[(off, shards, mesh_n)] = {r: done[r].tokens for r in done}
        assert eng.pool.pages_in_use() == 0
    assert streams[("sync", 1, 1)] == streams[("overlap", 2, 2)]


def test_main_mesh_dense_fallback_window(setup):
    """The dynamic-fallback dense branch also runs on the mesh: a run that
    starts BELOW min_context (dense apply) and crosses into the sparse
    window mid-decode exercises both cond branches sequence-parallel and
    still bit-matches the single-device engine."""
    cfg, params, _ = setup
    mem = cfg.memory.replace(method="dsa", min_context=48)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (40, 16)]                 # slot 0 crosses 48 mid-run
    streams = {}
    for mesh_n in (1, 2):
        sc = ServeConfig(max_len=128, n_slots=2, method="dsa", tp=4,
                         page=8, kv_page_size=16,
                         offload_cfg=OffloadConfig(mode="sync",
                                                   main_mesh=mesh_n))
        eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0), mem=mem)
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, 12))
        streams[mesh_n] = _drain(eng, 14)
        assert eng.hetero.profiler.offload_steps > 0, \
            "run never entered the sparse window"
    assert streams[1] == streams[2]
    assert all(len(v) == 12 for v in streams[1].values())


# ---------------------------------------------------------------------------
# bucket / shard granularity (regression: satellite of the mesh apply)
# ---------------------------------------------------------------------------


def test_view_buckets_align_to_mesh_granularity(setup):
    """pow2-bucketed view lengths are multiples of main_mesh * page_size.
    Pre-fix, the granule ignored the mesh: the smallest dsa bucket was 16
    tokens (lcm of page=8 and kv_page=16), which trips the shard assert
    ``S % (n_shards * page_size) == 0`` at main_mesh=4 — 16 % 32 != 0."""
    cfg, params, _ = setup
    sc = ServeConfig(max_len=512, n_slots=2, method="dsa", tp=4, page=8,
                     kv_page_size=16,
                     offload_cfg=OffloadConfig(mode="sync", main_mesh=4))
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    ps = eng.hetero.sel.page
    old_gran = eng._gran // sc.main_mesh          # what PR 4 would bucket by
    assert old_gran % (sc.main_mesh * ps) != 0    # the tripping bucket size
    for needed in range(1, eng.sc.max_len + 1, 7):
        vl = eng._view_len(needed)
        assert vl % (sc.main_mesh * ps) == 0, (needed, vl)
        assert vl % (sc.main_mesh * sc.kv_page_size) == 0, (needed, vl)

    # functional: the smallest bucket actually decodes through the mesh
    # (pre-fix this step raised in distributed_paged_sparse_decode)
    rng = np.random.default_rng(0)
    eng.submit(Request(0, rng.integers(0, cfg.vocab_size, size=8), 4))
    got = _drain(eng, 6)
    assert len(got[0]) == 4


def test_unaligned_view_trips_shard_assert():
    """The contract the engine alignment protects: a view that is NOT a
    multiple of n_shards * page_size is rejected loudly, not mis-sharded."""
    if jax.device_count() < 2:
        pytest.skip("needs a >=2-device mesh for a real shard count")
    mesh = make_mesh((2,), ("seq",))
    q = jnp.zeros((1, 2, 8), jnp.float32)
    kc = jnp.zeros((1, 24, 1, 8), jnp.float32)    # 24 % (2 * 8) != 0
    with pytest.raises(AssertionError):
        distributed_paged_sparse_decode(
            q, kc, kc, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), mesh, "seq", page_size=8)


# ---------------------------------------------------------------------------
# unified LSE-merge core == single-device reference (property)
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3), st.booleans())
def test_lse_merge_core_matches_reference(seed, B, holes):
    """Duplicate-free page ids with -1 holes anywhere + ragged per-slot
    lengths through the unified (out, lse) core == ``ops.
    paged_decode_attention`` on one device — and the dense wrapper built on
    the SAME shard body agrees with the core's out to the last bit.

    Every slot keeps at least one LIVE pick (the page holding its last
    live token) — the serving contract: ``decode_step_paged_presel``
    force-includes the current page, so an EFFECTIVELY EMPTY selection
    (all -1 / all picks past the live region) never reaches the apply.
    On an empty selection the softmax is degenerate and single-device vs
    shard-merged garbage legitimately differ."""
    rng = np.random.default_rng(seed)
    ps, Hq, KV, dh = 8, 4, 2, 16
    mesh = make_mesh((jax.device_count(),), ("seq",))
    n_sh = jax.device_count()
    S = int(rng.integers(2, 6)) * n_sh * ps       # shard-aligned view
    P = S // ps
    lengths = rng.integers(1, S + 1, size=B).astype(np.int32)
    k = np.zeros((B, S, KV, dh), np.float32)
    v = np.zeros((B, S, KV, dh), np.float32)
    for b in range(B):   # zero-page invariant: dead region is exact zeros
        k[b, : lengths[b]] = rng.normal(size=(lengths[b], KV, dh))
        v[b, : lengths[b]] = rng.normal(size=(lengths[b], KV, dh))
    q = rng.normal(size=(B, Hq, dh)).astype(np.float32)
    n_pick = int(rng.integers(1, P + 1))
    pids = np.full((B, n_pick + 1), -1, np.int32)
    for b in range(B):                            # duplicate-free picks
        cur = (lengths[b] - 1) // ps              # page of last live token
        picks = rng.choice(P, size=n_pick, replace=False)
        if holes:
            picks = np.where(rng.random(n_pick) < 0.4, -1, picks)
        picks = np.where(picks == cur, -1, picks)  # engine recency dedup
        pids[b, :n_pick] = picks
        pids[b, n_pick] = cur                      # force-included page
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pids), jnp.asarray(lengths))
    ref_out, ref_lse = ops.paged_decode_attention(*args[:3], args[3],
                                                  args[4], page_size=ps)
    out, lse = distributed_paged_sparse_decode(*args, mesh, "seq",
                                               page_size=ps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-6)
    dense = distributed_sparse_decode(*args, mesh, "seq", page_size=ps)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(out))
