"""Request-level serving API (serving/api): submit/poll/drain semantics
and the ``generate()`` thin-wrapper guarantee.

``Engine.generate`` is now a wrapper over ``submit + drain`` whenever the
prompt batch fits the paged pool — it must stay BIT-IDENTICAL to the
dense-cache loop it replaced (``_generate_batched``), leave no residue in
the engine, and fall back to the dense loop whenever the pool cannot take
the batch (ssm families, oversized, pool busy)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import init_params
from repro.serving import Engine, Request, ServeConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    return cfg, params


@pytest.mark.parametrize("method", ["none", "dsa"])
def test_generate_wrapper_bitmatches_dense_loop(setup, method):
    cfg, params = setup
    sc = ServeConfig(max_len=64, n_slots=3, method=method, tp=4, page=8,
                     kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(3, 16)),
                          jnp.int32)
    want = eng._generate_batched(prompts, 5)       # the old dense loop
    got = eng.generate(prompts, 5)                 # routes through the pool
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # no residue: the pool is drained, no handles or done entries linger
    assert not eng.busy() and not eng.done and not eng._handles
    assert eng.pool.pages_in_use() == 0


def test_generate_falls_back_when_pool_busy(setup):
    """A generate() call while requests are resident must not disturb the
    pool — it takes the dense-cache path and the resident stream finishes
    unchanged."""
    cfg, params = setup
    sc = ServeConfig(max_len=64, n_slots=2, method="none", tp=4,
                     kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    ref = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    resident = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    want_resident = ref.generate(jnp.asarray(resident)[None], 6)[0]
    other = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)),
                        jnp.int32)
    want_other = ref.generate(other, 4)

    h = eng.submit(Request(0, resident, 6))
    eng.poll()                                     # resident mid-decode
    got_other = eng.generate(other, 4)             # dense fallback
    np.testing.assert_array_equal(np.asarray(got_other),
                                  np.asarray(want_other))
    eng.drain()
    assert h.done
    np.testing.assert_array_equal(np.asarray(h.tokens, np.int32),
                                  want_resident)


def test_submit_rejects_duplicates_and_wrong_types(setup):
    cfg, params = setup
    sc = ServeConfig(max_len=64, n_slots=2, method="none", tp=4,
                     kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    with pytest.raises(TypeError):
        eng.submit((0, p, 3))                      # legacy tuple shape
    eng.submit(Request(0, p, 3))
    with pytest.raises(ValueError):
        eng.submit(Request(0, p, 3))               # rid already in flight
    eng.drain()
    eng.submit(Request(0, p, 3))                   # done rids are reusable
    done = eng.drain()
    assert sorted(done) == [0]


def test_handle_timing_and_result(setup):
    cfg, params = setup
    sc = ServeConfig(max_len=64, n_slots=2, method="none", tp=4,
                     kv_page_size=16)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    h = eng.submit(Request(0, rng.integers(0, cfg.vocab_size, size=8), 4))
    assert not h.done and h.ttft_s() is None
    eng.drain()
    assert h.done and len(h.tokens) == 4
    assert h.admitted is not None and h.first_token_t is not None
    assert h.finished >= h.first_token_t >= h.submitted
    assert h.ttft_s() >= 0 and h.per_token_s() >= 0
    d = h.as_dict()
    assert d["rid"] == 0 and d["n_tokens"] == 4
    assert h.text == " ".join(str(t) for t in h.tokens)
    np.testing.assert_array_equal(h.result(),
                                  np.asarray(h.tokens, np.int32))
