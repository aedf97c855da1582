"""Memory-pipeline invariants: stage bypass, fused == unfused, full-budget
sparse == dense, placement policy, profiler attribution."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch
from repro.core.pipeline import MemoryPipeline, StageProfiler
from repro.core import placement
from repro.core.methods import (dsa, seer, lserve, get_sparse_method,
                                make_decode_attention)
from repro.models import attention as A
from repro.models import init_params, prefill, decode_step

TP = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("llama3.2-1b").smoke()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key, tp=TP)
    B, S = 2, 64
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    _, caches = jax.jit(lambda p, t: prefill(p, cfg, t, max_len=S + 8, tp=TP))(
        params, toks)
    dense_logits, _ = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c, tp=TP))(
        params, toks[:, 0], caches)
    return cfg, params, toks, caches, dense_logits


def test_stage_bypass_is_identity():
    """§3.1: a skipped stage costs nothing and passes data through."""
    pipe = MemoryPipeline("id-test", prepare=None, relevancy=None,
                          retrieve=None, apply=lambda Mp, x: Mp + x)
    out = pipe.run(jnp.asarray(2.0), jnp.asarray(3.0))
    assert float(out) == 5.0
    # fully-empty pipeline returns the memory untouched
    pipe2 = MemoryPipeline("empty")
    assert float(pipe2.run(jnp.asarray(7.0), None)) == 7.0


@pytest.mark.parametrize("method", ["dsa", "seer", "lserve"])
def test_full_budget_sparse_equals_dense(setup, method):
    """When the budget covers the whole context, the sparse pipeline must be
    EXACTLY dense attention (retrieval selects everything)."""
    cfg, params, toks, caches, dense_logits = setup
    mem = cfg.memory.replace(method=method, top_k=128, token_budget=128,
                             selection="topk", min_context=0)
    init_fn, mk = get_sparse_method(method)
    sp = init_fn(jax.random.PRNGKey(7), cfg, mem)
    kw = {"page": 8} if method == "dsa" else {}
    sfn = mk(cfg, mem, tp=TP, **kw)
    logits, _ = jax.jit(lambda p, t, c, s: decode_step(
        p, cfg, t, c, tp=TP, sparse_fn=sfn, sparse_params=s))(
        params, toks[:, 0], caches, sp)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(dense_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_fused_equals_unfused_pipeline(setup):
    """Pallas-fused relevancy+retrieval == XLA unfused (paper Fig. 9 setup)."""
    cfg, params, toks, caches, _ = setup
    mem = cfg.memory.replace(method="dsa", top_k=32)
    sp_all = dsa.dsa_init(jax.random.PRNGKey(9), cfg, mem)
    sp = jax.tree.map(lambda a: a[0], sp_all)
    kc, vc = caches["k"][0], caches["v"][0]
    B = kc.shape[0]
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (B, 1, cfg.padded_heads(TP), cfg.hd), jnp.float32)
    M = (kc, vc)
    out_u = dsa.build_pipeline(cfg, mem, sp, page=8, fused=False).run(M, q)
    out_f = dsa.build_pipeline(cfg, mem, sp, page=8, fused=True).run(M, q)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_f),
                               rtol=1e-4, atol=1e-4)


def test_threshold_mode_subset_of_topk(setup):
    """Seer threshold retrieval only ever drops blocks vs top-k mode."""
    cfg, params, toks, caches, dense_logits = setup
    base = cfg.memory.replace(method="seer", token_budget=32, block_size=8,
                              min_context=0)
    init_fn, mk = get_sparse_method("seer")
    sp = init_fn(jax.random.PRNGKey(7), cfg, base)
    step = lambda mem: jax.jit(lambda p, t, c, s: decode_step(
        p, cfg, t, c, tp=TP, sparse_fn=mk(cfg, mem, tp=TP),
        sparse_params=s))(params, toks[:, 0], caches, sp)
    l_topk = step(base.replace(selection="topk"))[0]
    l_thr = step(base.replace(selection="threshold", threshold=1.0))[0]
    # tau=1.0 drops everything -> must differ from topk
    assert not np.allclose(np.asarray(l_topk, np.float32),
                           np.asarray(l_thr, np.float32))


def test_profiler_attribution():
    prof = StageProfiler()
    pipe = MemoryPipeline(
        "p", prepare=lambda M: M, relevancy=lambda I, x: I,
        retrieve=lambda M, S: S, apply=lambda Mp, x: Mp,
        fused={"relevancy": ("relevancy", "retrieve")})
    pipe.run(jnp.zeros(4), jnp.zeros(4), profiler=prof)
    prof.record_total("p", sum(prof.stage_seconds["p"].values()) * 2)
    bd = prof.breakdown("p")
    assert abs(sum(bd.values()) - 1.0) < 1e-6
    assert 0.0 < prof.memory_fraction("p") <= 0.5 + 1e-9


# ---------------------------------------------------------------------------
# placement policy properties (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 21))
def test_placement_monotone_windows(log_ctx):
    """dense below min_context, dense above fallback, sparse allowed between."""
    cfg = get_arch("qwen3-32b")
    ctx = 1 << log_ctx
    path = placement.choose_path(cfg, cfg.memory, ctx)
    if ctx < cfg.memory.min_context:
        assert path == "dense"
    if ctx > cfg.memory.fallback_context:
        assert path == "dense"


def test_placement_prefers_sparse_at_long_context():
    cfg = get_arch("qwen3-32b")
    assert placement.choose_path(cfg, cfg.memory, 1 << 19) == "sparse"


@pytest.mark.parametrize("length,branch", [(24, "dense"), (56, "sparse")],
                         ids=["below_min_context", "inside_window"])
def test_decode_attention_is_the_placement_fallback(length, branch):
    """``make_decode_attention`` runs dense ``attention_decode`` below
    ``min_context`` and the method's own sparse function inside
    [min_context, fallback_context] (per-slot lengths, max decides)."""
    cfg = get_arch("llama3.2-1b").smoke()
    mem = cfg.memory.replace(method="dsa", top_k=16, min_context=32)
    B, S, page = 2, 64, 8
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(kq, (B, 1, cfg.padded_heads(TP), cfg.hd),
                          jnp.bfloat16)
    kc = jax.random.normal(kk, (B, S, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    vc = jax.random.normal(kv, (B, S, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    lengths = jnp.asarray([length - 8, length], jnp.int32)
    sp = dsa.dsa_init(ks, cfg, mem, stacked=False)
    raw = dsa.make_sparse_fn(cfg, mem, tp=TP, page=page)
    expect = {
        "dense": lambda: A.attention_decode(q, kc, vc, lengths, cfg, tp=TP),
        "sparse": lambda: raw(q, kc, vc, lengths, sp)}
    fn = make_decode_attention(cfg, mem, "dsa", tp=TP, page=page)
    got = np.asarray(jax.jit(lambda: fn(q, kc, vc, lengths, sp))(),
                     np.float32)
    want = {b: np.asarray(jax.jit(f)(), np.float32) for b, f in expect.items()}
    np.testing.assert_array_equal(got, want[branch])
    other = "sparse" if branch == "dense" else "dense"
    assert not np.array_equal(got, want[other])


def test_stage_costs_match_paper_table2_decades():
    """Arithmetic intensities land in the paper's order-of-magnitude bands
    (Table 2) for sparse attention at long context: relevancy/retrieval are
    memory-bound (low AI), apply/rest sit higher."""
    cfg = get_arch("qwen3-32b")
    costs = placement.sparse_attention_stage_costs(cfg, cfg.memory, 1 << 20)
    assert costs["retrieve"].intensity < 10
    assert costs["relevancy"].intensity < 100
    assert costs["apply"].intensity > costs["retrieve"].intensity
    assert costs["rest"].intensity > costs["retrieve"].intensity
    # relevancy+retrieval dominate the pipeline time at 1M context (Fig. 3)
    mem_s = {k: v.seconds() for k, v in costs.items()}
    assert mem_s["relevancy"] + mem_s["retrieve"] > mem_s["prepare"]
