"""Paged continuous batching: pooled decode with per-slot lengths must emit
token streams identical to per-request ``Engine.generate`` (dense and
sparse), pages must not leak across admit/release cycles, chunked prefill
must match one-shot prefill, and the scheduler must drain mixed workloads
over the paged pool. All admission goes through the request-level API
(``Engine.submit(Request)`` + ``poll``)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import init_params
from repro.serving import Engine, PagedKVPool, Request, ServeConfig, \
    Scheduler


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    return cfg, params


def _drain(eng, n_steps):
    got = {}
    for _ in range(n_steps):
        for rid, _slot, tok in eng.poll():
            got.setdefault(rid, []).append(tok)
    return got


@pytest.mark.parametrize("method", ["none", "dsa", "dsa-sparse"])
def test_pooled_decode_matches_per_request_generate(setup, method):
    """Mixed-length slots (incl. a ragged non-pow2 prompt) admitted through
    the bucketed batched prefill decode EXACTLY like per-request generate.
    ``dsa`` selects pages of 8 inside pool pages of 16; ``dsa-sparse``
    selects 2 pages of 16 from every context past 16 tokens, so each step
    takes the sparse branch, whose kernel reads the pool (pooled) or the
    view (per request)."""
    cfg, params = setup
    mem, page, lens = None, 8, (16, 32, 9)
    if method == "dsa-sparse":
        mem = cfg.memory.replace(method="dsa", min_context=16, top_k=32)
        page, lens = 16, (16, 40, 20)
    sc = ServeConfig(max_len=96, n_slots=3, method=method.split("-")[0],
                     tp=4, page=page, kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0), mem=mem)
    ref = Engine(cfg, params, sc, key=jax.random.PRNGKey(0), mem=mem)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    max_new = 6
    refs = [ref.generate(jnp.asarray(p)[None], max_new)[0] for p in prompts]
    hs = [eng.submit(Request(i, p, max_new)) for i, p in enumerate(prompts)]
    got = _drain(eng, max_new + 1)
    assert all(h.done for h in hs)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(np.asarray(got[i][:max_new]), refs[i])
        np.testing.assert_array_equal(np.asarray(hs[i].tokens), refs[i])
    assert eng.pool.pages_in_use() == 0  # all pages released at completion


def test_staggered_admission_and_page_reuse(setup):
    """Admission mid-decode reuses released pages; token streams stay exact
    even though slots sit at heterogeneous positions. Requests beyond the
    slot count queue at submit and admit as slots free."""
    cfg, params = setup
    sc = ServeConfig(max_len=96, n_slots=2, method="none", tp=4,
                     kv_page_size=16, pool_pages=2 * (96 // 16) + 1)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    ref = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (16, 24, 40, 8)]
    refs = [ref.generate(jnp.asarray(p)[None], 5)[0] for p in prompts]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, 5))
    got = {}
    for rid, _slot, tok in eng.poll():
        got.setdefault(rid, []).append(tok)
    # only two slots: requests 2 and 3 stay queued (clean rejection,
    # re-queued at the front in FCFS order)
    assert eng.queue_depth() == 2
    assert sorted(got) == [0, 1]
    for _ in range(15):
        for rid, _slot, tok in eng.poll():
            got.setdefault(rid, []).append(tok)
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(got[i][:5]), refs[i])
    assert eng.pool.pages_in_use() == 0


def test_pages_do_not_leak_across_admit_release_cycles(setup):
    """Repeated admit/decode/complete cycles return every page: the free
    list ends at full capacity with no duplicate page ids."""
    cfg, params = setup
    sc = ServeConfig(max_len=64, n_slots=2, method="none", tp=4,
                     kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    rid = 0
    for cycle in range(3):
        for n in (10, 20):
            eng.submit(Request(
                rid, rng.integers(0, cfg.vocab_size, size=n), 3))
            rid += 1
        eng.poll()   # admits both queued requests, then one decode step
        assert eng.queue_depth() == 0
        in_use = eng.pool.pages_in_use()
        assert in_use == eng.pool.pages_needed(10 + 3) + \
            eng.pool.pages_needed(20 + 3)
        _drain(eng, 3)
        assert eng.pool.pages_in_use() == 0
        free = eng.pool.free
        assert len(free) == len(set(free)) == eng.pool.total_pages - 1
        assert 0 not in free  # the zero page is never handed out


def test_pool_oversubscription_blocks_then_admits(setup):
    """With an arena smaller than full backing, admission waits for pages
    (not slots) and proceeds once a release frees them."""
    cfg, params = setup
    # 3 pages of 16: one request of 33..48 tokens takes all three
    sc = ServeConfig(max_len=64, n_slots=2, method="none", tp=4,
                     kv_page_size=16, pool_pages=4)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    h0 = eng.submit(Request(0, rng.integers(0, cfg.vocab_size, size=40), 4))
    h1 = eng.submit(Request(1, rng.integers(0, cfg.vocab_size, size=10), 4))
    eng.poll()
    assert eng.pool.n_free() == 0
    # a free slot exists but no pages: request 1 must stay queued
    assert eng.slots.free_slots()
    assert eng.queue_depth() == 1 and not h1.tokens
    done = eng.drain()
    assert sorted(done) == [0, 1]
    assert h0.done and h1.done
    assert eng.pool.n_free() == 3


def test_chunked_prefill_matches_one_shot(setup):
    """A long prompt streamed in chunks (interleaved with another slot's
    decode) produces the same tokens as one-shot prefill + generate; the
    admission path picks chunked mode from ``chunk_threshold`` (and a
    ``method_overrides`` pin can force it)."""
    cfg, params = setup
    sc = ServeConfig(max_len=128, n_slots=2, method="none", tp=4,
                     kv_page_size=16, prefill_chunk=16, chunk_threshold=24)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    ref = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(0, cfg.vocab_size, size=50).astype(np.int32)
    short = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    r_long = ref.generate(jnp.asarray(long_prompt)[None], 5)[0]
    r_short = ref.generate(jnp.asarray(short)[None], 5)[0]
    eng.submit(Request(0, long_prompt, 5,
                       method_overrides={"chunked": True}))
    eng.submit(Request(1, short, 5))
    got = _drain(eng, 12)
    np.testing.assert_array_equal(np.asarray(got[0][:5]), r_long)
    np.testing.assert_array_equal(np.asarray(got[1][:5]), r_short)
    assert eng.pool.pages_in_use() == 0


def test_scheduler_paged_mixed_lengths(setup):
    """End-to-end: bucketed + chunked admission under the scheduler, with an
    oversubscribed arena, drains everything."""
    cfg, params = setup
    sc = ServeConfig(max_len=128, n_slots=3, method="none", tp=4,
                     kv_page_size=16, prefill_chunk=16, chunk_threshold=32,
                     pool_pages=3 * (128 // 16) + 1)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    sch = Scheduler(eng, prefill_token_budget=64)
    rng = np.random.default_rng(5)
    lens = [10, 40, 16, 33, 8, 50, 12]
    rids = [sch.submit(rng.integers(0, cfg.vocab_size, size=n), max_new=4)
            for n in lens]
    done = sch.run()
    assert sorted(done) == sorted(rids)
    assert all(len(r.tokens) == 4 for r in done.values())
    assert sch.throughput_tokens_per_s() > 0
    assert eng.pool.pages_in_use() == 0
