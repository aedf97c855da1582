"""The paged pool is addressed as one stacked ``[L, P, ps, KV, dh]`` array
with a layer index inside each gather and scatter:

  * ``pool_gather`` / ``pool_scatter`` at layer ``l`` equal slicing layer
    ``l`` out, gathering or writing it, and putting it back;
  * dead slots and padded span rows write zeros to page 0 of layer ``l``
    only, and every other layer stays bit-unchanged;
  * the compiled decode and chunked-prefill programs, with the pool donated
    as the engine donates it, neither slice a layer out of the pool nor
    restack or copy it: the layer loop carries the pool and updates it in
    place; with DSA the sparse branch gathers one view (K, for the index
    projection) and the paged kernel reads the pool.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.page_pool import (PooledKV, pool_gather, pool_scatter,
                                     span_dest, token_dest)
from repro.models import init_params
from repro.models import model as M
from repro.serving import Engine, ServeConfig

from hlo_checks import DSA_VIEW_OPS, view_ops

L, P, PS, KV, DH = 3, 9, 4, 2, 8
B, NP, C = 3, 4, 5


def _pool(seed):
    """Random bf16 pages; page 0 is nonzero too, so a write there shows."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (L, P, PS, KV, DH))
    return x.astype(jnp.bfloat16)


def _table(seed):
    rng = np.random.default_rng(seed)
    t = np.stack([rng.permutation(np.arange(1, P))[:NP] for _ in range(B)])
    t[0, -1] = 0                           # an unallocated entry
    return jnp.asarray(t, jnp.int32)


def _values(seed, shape):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    return x.astype(jnp.bfloat16)


def _slice_form_token(pages, table, positions, values, live):
    """One layer's pages [P, ps, KV, dh], written the unstacked way."""
    logical = jnp.clip(positions // PS, 0, NP - 1)
    dest = jnp.take_along_axis(table, logical[:, None], axis=1)[:, 0]
    dest = jnp.where(live, dest, 0)
    vals = values * live[:, None, None].astype(values.dtype)
    return pages.at[dest, positions % PS].set(vals)


def _slice_form_span(pages, table, start, values, n_valid):
    tok_pos = start[:, None] + jnp.arange(C)[None, :]
    valid = jnp.arange(C)[None, :] < n_valid[:, None]
    logical = jnp.clip(tok_pos // PS, 0, NP - 1)
    dest = jnp.where(valid, jnp.take_along_axis(table, logical, axis=1), 0)
    vals = values * valid[:, :, None, None].astype(values.dtype)
    return pages.at[dest, tok_pos % PS].set(vals)


# each write: (table, dest, values, the unstacked write of one layer);
# slot 1 is dead (token) or has no valid row (span), slot 2 a padded tail
def _token_case():
    positions = jnp.asarray([5, 0, 14], jnp.int32)
    live = jnp.asarray([True, False, True])
    values = _values(1, (B, KV, DH))
    table = _table(0)
    return (table, token_dest(table, positions, live, PS), values,
            lambda pg: _slice_form_token(pg, table, positions, values, live))


def _span_case():
    start = jnp.asarray([2, 0, 9], jnp.int32)
    n_valid = jnp.asarray([C, 0, 3], jnp.int32)
    values = _values(2, (B, C, KV, DH))
    table = _table(0)
    return (table, span_dest(table, start, n_valid, C, PS), values,
            lambda pg: _slice_form_span(pg, table, start, values, n_valid))


CASES = {"token": _token_case, "span": _span_case}
_scatter = jax.jit(pool_scatter)           # the layer index is traced


@pytest.mark.parametrize("layer", range(L))
def test_gather_equals_layer_slice(layer):
    pages, table = _pool(0), _table(1)
    got = jax.jit(pool_gather)(pages, jnp.int32(layer), table)
    want = pages[layer][table].reshape(B, NP * PS, KV, DH)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("kind", sorted(CASES))
def test_scatter_equals_slice_then_write(kind, layer):
    pages = _pool(3)
    _, dest, values, slice_form = CASES[kind]()
    got = _scatter(pages, jnp.int32(layer), dest, values)
    want = pages.at[layer].set(slice_form(pages[layer]))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("kind", sorted(CASES))
def test_dead_rows_zero_page0_of_their_layer_only(kind, layer):
    pages = _pool(4)
    table, (dest, off, keep), values, _ = CASES[kind]()
    got = np.asarray(_scatter(pages, jnp.int32(layer), (dest, off, keep),
                              values))
    before = np.asarray(pages)
    dest, off, keep = map(np.asarray, (dest, off, keep))
    assert (~keep).any() and (dest[~keep] == 0).all()
    for p, r in zip(dest[~keep], off[~keep]):
        assert not got[layer, p, r].any()              # zeros on page 0
    other = [m for m in range(L) if m != layer]
    np.testing.assert_array_equal(got[other], before[other])
    # within the layer only the written rows changed
    written = np.zeros((P, PS), bool)
    written[dest, off] = True
    np.testing.assert_array_equal(got[layer][~written],
                                  before[layer][~written])


# ---------------------------------------------------------------------------
# the compiled programs: no layer slice, restack or copy of the pool
# ---------------------------------------------------------------------------


def _pool_ops(hlo, pool_shape):
    """(opcode, result) of every dynamic-slice, dynamic-update-slice or copy
    whose result holds as many elements as one layer of the pool or the
    whole pool (in whatever shape), fusion bodies included."""
    sizes = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    hits = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (dynamic-slice|"
                         r"dynamic-update-slice|copy)\(", hlo):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) in sizes:
            hits.append((m.group(2), m.group(1)))
    return hits


def _smoke():
    cfg = get_arch("llama3.2-1b").smoke()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                                tp=4))
    n_slots, max_len, ps = 2, 64, 16
    pool = jax.eval_shape(lambda: M.make_page_pool(
        cfg, n_slots, max_len, page_size=ps,
        total_pages=n_slots * max_len // ps + 1, tp=4))
    return cfg, params, pool


def _decode_hlo(cfg, params, pool, sparse_fn=None, sp=None):
    def decode_paged(p, tok, kp, vp, table, lengths, live, sp):
        return M.decode_step_paged(
            p, cfg, tok, {"k_pages": kp, "v_pages": vp, "page_table": table,
                          "lengths": lengths}, live, tp=4,
            sparse_fn=sparse_fn, sparse_params=sp)

    B = pool["lengths"].shape[0]
    return jax.jit(decode_paged, donate_argnums=(2, 3)).lower(
        params, jax.ShapeDtypeStruct((B,), jnp.int32), pool["k_pages"],
        pool["v_pages"], pool["page_table"], pool["lengths"],
        jax.ShapeDtypeStruct((B,), jnp.bool_), sp).compile().as_text()


def _dsa_decode_hlo(cfg, params, pool):
    """The decode program with the engine's DSA ``sparse_fn``: the sparse
    branch and the dense fallback under one cond."""
    B, NP = pool["page_table"].shape
    ps = pool["k_pages"].shape[2]
    sc = ServeConfig(max_len=NP * ps, n_slots=B, method="dsa", tp=4, page=ps,
                     kv_page_size=ps)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0), tp=4), sc,
                 key=jax.random.PRNGKey(0))
    return _decode_hlo(cfg, params, pool, eng._sparse_fn,
                       jax.eval_shape(lambda: eng.sparse_params))


def _extend_hlo(cfg, params, pool):
    def extend_paged(p, toks, kp, vp, table, lengths, nv):
        return M.extend_paged(
            p, cfg, toks, {"k_pages": kp, "v_pages": vp, "page_table": table,
                           "lengths": lengths}, nv, tp=4)

    B = pool["lengths"].shape[0]
    return jax.jit(extend_paged, donate_argnums=(2, 3)).lower(
        params, jax.ShapeDtypeStruct((B, 8), jnp.int32), pool["k_pages"],
        pool["v_pages"], pool["page_table"], pool["lengths"],
        pool["lengths"]).compile().as_text()


@pytest.mark.parametrize("program", ["decode", "extend", "dsa"])
def test_layer_loop_keeps_the_pool_in_place(program, monkeypatch):
    """With the DSA ``sparse_fn`` the sparse branch hands the paged kernel
    the pool: only the index projection gathers a (K) view, and no view is
    copied head-major; the dense fallback gathers K and V. On the CPU the
    kernel would run in interpret mode, whose block emulation copies its
    operands, so here it is a stand-in that takes the ``PooledKV`` handles;
    test_chip_compile.py checks the same program with the Mosaic kernel."""
    handed = []

    def kernel(q, kc, vc, page_ids, length, *, page_size):
        handed.append((kc, vc))
        picked = page_ids.sum(axis=1).astype(jnp.float32)  # keeps prepare
        return (jnp.broadcast_to(picked[:, None, None], q.shape),
                jnp.broadcast_to(picked[:, None], q.shape[:2]))

    monkeypatch.setattr(ops, "paged_decode_attention", kernel)
    cfg, params, pool = _smoke()
    hlo = {"decode": _decode_hlo, "extend": _extend_hlo,
           "dsa": _dsa_decode_hlo}[program](cfg, params, pool)
    assert all(isinstance(kv, PooledKV) for pair in handed for kv in pair)
    assert len(handed) == (program == "dsa")
    assert "scatter(" in hlo
    assert _pool_ops(hlo, pool["k_pages"].shape) == []
    if program == "dsa":
        assert view_ops(hlo, pool["k_pages"].shape,
                        *pool["page_table"].shape) == DSA_VIEW_OPS
