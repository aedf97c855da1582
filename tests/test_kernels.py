"""Per-kernel allclose sweeps vs the pure-jnp oracles (ref.py), across
shapes and dtypes, in Pallas interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.page_pool import PooledKV, pool_gather

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# relevancy_topk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,dk,S,k,block", [
    (1, 4, 16, 128, 8, 32),
    (2, 8, 32, 512, 32, 128),
    (3, 64, 128, 1024, 128, 256),   # DSA-like indexer shape
    (2, 4, 16, 96, 16, 64),         # non-power-of-two S (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_relevancy_topk_exact(B, Hq, dk, S, k, block, dtype):
    q = _arr((B, Hq, dk), dtype)
    keys = _arr((B, S, dk), dtype)
    w = jnp.abs(_arr((B, Hq), jnp.float32))
    v1, i1 = ops.relevancy_topk(q, keys, w, k, block=block)
    v2, i2 = ref.relevancy_topk(q, keys, w, k)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=tol, atol=tol)
    # discrete outputs: compare as sets (ties may reorder)
    for b in range(B):
        assert set(np.asarray(i1[b]).tolist()) == set(np.asarray(i2[b]).tolist())


def test_relevancy_topk_approximate_recall():
    """c < min(k, block): approximate mode must keep high recall."""
    B, Hq, dk, S, k = 2, 8, 32, 2048, 64
    q, keys = _arr((B, Hq, dk)), _arr((B, S, dk))
    w = jnp.abs(_arr((B, Hq), jnp.float32))
    v1, i1 = ops.relevancy_topk(q, keys, w, k, block=256, c=48)
    _, i2 = ref.relevancy_topk(q, keys, w, k)
    recall = np.mean([
        len(set(np.asarray(i1[b]).tolist()) & set(np.asarray(i2[b]).tolist())) / k
        for b in range(B)])
    assert recall > 0.9, recall


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


def _pool_form(kc, vc, pool_page, n_layers=3, layer=1):
    """The views as layer ``layer`` of a pool of ``n_layers`` layers: pages
    of ``pool_page`` tokens behind a shuffled page table, every third entry
    unallocated (on the zero page), other layers and spare pages random.
    -> (pool K, pool V, table, the views the pool gives)."""
    B, S, KV, dh = kc.shape
    NP = S // pool_page
    P = B * NP + 4                                   # zero page + spares
    table = RNG.permutation(np.arange(1, P))[:B * NP].reshape(B, NP)
    table[:, ::3] = 0                                # unallocated entries

    def pool(view):
        pages = np.asarray(RNG.standard_normal(
            (n_layers, P, pool_page, KV, dh)), np.float32)
        pages[layer, table] = np.asarray(view, np.float32).reshape(
            B, NP, pool_page, KV, dh)
        pages[:, 0] = 0                              # the zero page
        return jnp.asarray(pages, kc.dtype)

    kp, vp = pool(kc), pool(vc)
    table = jnp.asarray(table, jnp.int32)
    return kp, vp, table, (pool_gather(kp, layer, table),
                           pool_gather(vp, layer, table))


@pytest.mark.parametrize("B,KV,G,dh,S,ps,nsel,pool_page", [
    pytest.param(1, 1, 1, 32, 128, 16, 4, None, id="1-1-1-32-128-16-4"),
    pytest.param(2, 2, 4, 64, 512, 16, 8, None, id="2-2-4-64-512-16-8"),
    pytest.param(2, 8, 8, 128, 1024, 64, 8, None,     # GQA 64 heads / 8 kv
                 id="2-8-8-128-1024-64-8"),
    # the pool form: pages read straight from a stacked pool
    pytest.param(2, 8, 8, 128, 512, 16, 9, 16, id="pool-kv8-g8"),
    pytest.param(2, 4, 7, 128, 512, 16, 9, 16, id="pool-kv4-g7"),
    pytest.param(2, 2, 4, 64, 256, 8, 6, 16, id="pool-select8-of-16"),
    pytest.param(2, 2, 4, 64, 256, 32, 4, 16, id="pool-select32-of-16"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, KV, G, dh, S, ps, nsel, pool_page, dtype):
    """Against the oracle over the contiguous view; in the pool form the
    kernel reads the pool under a traced layer index and is compared with
    the oracle over the view ``pool_gather`` builds (zero-page entries
    included), with selected pages smaller and larger than a pool page."""
    Hq = KV * G
    q = _arr((B, Hq, dh), dtype)
    kc = _arr((B, S, KV, dh), dtype)
    vc = _arr((B, S, KV, dh), dtype)
    pages = jnp.asarray(
        np.stack([RNG.choice(S // ps, nsel, replace=False) for _ in range(B)]),
        jnp.int32)
    pages = pages.at[0, -1].set(-1)  # invalid page masking
    length = jnp.asarray(RNG.integers(S // 2, S + 1, B), jnp.int32)
    if pool_page is None:
        o1, l1 = ops.paged_decode_attention(q, kc, vc, pages, length,
                                            page_size=ps)
    else:
        pages = pages.at[1, 0].set(-1)
        kp, vp, table, (kc, vc) = _pool_form(kc, vc, pool_page)
        o1, l1 = jax.jit(lambda layer: ops.paged_decode_attention(
            q, PooledKV(kp, layer, table), PooledKV(vp, layer, table), pages,
            length, page_size=ps))(jnp.int32(1))
    o2, l2 = ref.paged_decode_attention(q, kc, vc, pages, ps, length)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=tol, atol=tol)


def test_lse_merge_equals_joint_attention():
    """Two disjoint half-contexts LSE-merged == attention over the union."""
    B, KV, G, dh, S, ps = 1, 2, 2, 32, 256, 16
    Hq = KV * G
    q, kc, vc = _arr((B, Hq, dh)), _arr((B, S, KV, dh)), _arr((B, S, KV, dh))
    all_pages = jnp.arange(S // ps, dtype=jnp.int32)[None]
    length = jnp.asarray([S], jnp.int32)
    o_all, _ = ref.paged_decode_attention(q, kc, vc, all_pages, ps, length)
    lo = all_pages[:, : S // ps // 2]
    hi = all_pages[:, S // ps // 2:]
    o1, l1 = ref.paged_decode_attention(q, kc, vc, lo, ps, length)
    o2, l2 = ref.paged_decode_attention(q, kc, vc, hi, ps, length)
    merged, _ = ops.lse_merge(jnp.stack([o1, o2]), jnp.stack([l1, l2]))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(o_all),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,KV,dh,bq,window", [
    (1, 128, 4, 4, 32, 64, 0),
    (2, 200, 8, 2, 64, 64, 0),      # GQA + ragged block
    (2, 256, 4, 4, 32, 64, 48),     # sliding window (Mixtral)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, KV, dh, bq, window, dtype):
    q, k, v = _arr((B, S, H, dh), dtype), _arr((B, S, KV, dh), dtype), \
        _arr((B, S, KV, dh), dtype)
    o1 = ops.flash_attention(q, k, v, bq=bq, bk=bq, window=window)
    o2 = ref.flash_attention(q, k, v, window=window or None)
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# page pool + bm25
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,KV,dh,ps", [(1, 128, 2, 32, 16),
                                          (2, 512, 8, 64, 64)])
def test_page_minmax(B, S, KV, dh, ps):
    kc = _arr((B, S, KV, dh))
    mn1, mx1 = ops.page_minmax(kc, page_size=ps)
    mn2, mx2 = ref.page_minmax(kc, ps)
    np.testing.assert_allclose(np.asarray(mn1), np.asarray(mn2))
    np.testing.assert_allclose(np.asarray(mx1), np.asarray(mx2))


@pytest.mark.parametrize("B,D,T,k,block", [
    (1, 128, 8, 8, 64), (2, 1000, 16, 32, 256),   # non-pow2 doc count
])
def test_bm25_topk(B, D, T, k, block):
    tf = jnp.asarray(RNG.poisson(1.0, (B, D, T)), jnp.float32)
    dl = jnp.asarray(RNG.integers(20, 200, (B, D)), jnp.float32)
    idf = jnp.asarray(RNG.random((B, T)), jnp.float32)
    v1, i1 = ops.bm25_topk(tf, dl, idf, k, block=block)
    v2, i2 = ref.bm25_topk(tf, dl, idf, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-5)
