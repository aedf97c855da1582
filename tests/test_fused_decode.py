"""Fused multi-step decode (``ServeConfig(fused_steps=K)``, serving/fused).

Load-bearing properties:

  * ORACLE BIT-MATCH: for every memory method (none/dsa/seer/lserve) and
    every offload pipeline (inline, sync, overlap — incl. validate mode,
    2 selection shards, and the 2-device apply mesh), ``fused(K)`` emits
    token-for-token what K separate ``step_pool()`` calls emit, while
    consuming several device steps per host dispatch;
  * EARLY EXIT: a window hands control back to the host at the exact step
    a slot finishes (admission timing unchanged) or a FLARE trigger fires
    (retrieval launch timing unchanged), in every retrieval mode;
  * the new ``StepEvents`` result iterates like the legacy tuple list, and
    the nested ``OffloadConfig`` surface validates at construction time
    and round-trips through ``dataclasses.replace`` on either surface;
  * the page-table view cache re-slices only when the bucket or the pool's
    host table actually changed;
  * hypothesis property: arbitrary window widths x slot-length mixes stay
    bit-exact against the stepped loop.

CI runs this file under 1, 2 and 4 host devices (the hetero matrix legs);
meshes clamp to the available device count, so every property holds at any
topology.
"""
import dataclasses
import functools
import warnings

import numpy as np
import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_arch
from repro.data import build_corpus
from repro.retrieval import RetrievalConfig
from repro.serving import Engine, OffloadConfig, Request, ServeConfig, \
    StepEvents


@functools.lru_cache(maxsize=1)
def _setup_cached():
    from repro.models import init_params

    cfg = get_arch("llama3.2-1b").smoke()
    params = init_params(cfg, jax.random.PRNGKey(0), tp=4)
    corpus = build_corpus(48, retrieval_vocab=128, doc_max=8,
                          gen_vocab=cfg.vocab_size, embed_dim=16, seed=0)
    return cfg, params, corpus


@pytest.fixture(scope="module")
def setup():
    return _setup_cached()


BASE = dict(max_len=128, n_slots=2, tp=4, page=8, kv_page_size=16)


def _run(cfg, params, sc, prompts, max_new, max_dispatches=200):
    """Drive the engine to drain; returns (streams, fired, window_steps)."""
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    for i, (p, mn) in enumerate(zip(prompts, max_new)):
        eng.submit(Request(i, p, mn))
    streams, fired, windows = {}, [], []
    for _ in range(max_dispatches):
        ev = eng.poll()
        for rid, _slot, tok in ev:
            streams.setdefault(rid, []).append(tok)
        fired.extend(ev.fired)
        if ev.steps:
            windows.append(ev.steps)
        if all(s.done for s in eng.slots.slots) and \
                not eng.has_prefill_work() and not eng.has_retrieval_work():
            break
    return streams, fired, windows, eng


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


# ---------------------------------------------------------------------------
# oracle matrix: fused(K) == K x step_pool() for every method x pipeline
# ---------------------------------------------------------------------------


MATRIX = [
    ("none", dict()),
    ("dsa", dict()),
    ("seer", dict()),
    ("lserve", dict()),
    ("dsa", dict(offload_cfg=OffloadConfig(mode="sync", validate=True))),
    ("dsa", dict(offload_cfg=OffloadConfig(mode="overlap"))),
    ("seer", dict(offload_cfg=OffloadConfig(mode="overlap",
                                            validate=True))),
    ("lserve", dict(offload_cfg=OffloadConfig(mode="sync"))),
]


@pytest.mark.parametrize("method,extra", MATRIX)
def test_fused_matches_stepped(setup, method, extra):
    cfg, params, _ = setup
    prompts = _prompts(cfg, (16, 9))
    max_new = (6, 9)
    ref, _, _, _ = _run(cfg, params,
                        ServeConfig(method=method, **extra, **BASE),
                        prompts, max_new)
    got, _, windows, eng = _run(
        cfg, params, ServeConfig(method=method, fused_steps=4,
                                 **extra, **BASE),
        prompts, max_new)
    assert got == ref
    # the windows actually amortized host dispatches
    assert eng.stats["host_steps"] < eng.stats["decode_steps"]
    assert max(windows) > 1
    assert eng.pool.pages_in_use() == 0


def test_fused_composes_shards_and_mesh(setup):
    """fused windows x 2 selection shards x 2-device apply mesh x validate:
    the full PR-4/PR-5 topology behind one dispatch per window."""
    cfg, params, _ = setup
    prompts = _prompts(cfg, (16, 24), seed=5)
    max_new = (6, 6)
    oc = OffloadConfig(mode="overlap", validate=True, shards=2, main_mesh=2)
    ref, _, _, _ = _run(
        cfg, params, ServeConfig(method="dsa", offload_cfg=oc, **BASE),
        prompts, max_new)
    got, _, _, eng = _run(
        cfg, params,
        ServeConfig(method="dsa", fused_steps=4, offload_cfg=oc, **BASE),
        prompts, max_new)
    assert got == ref
    f = eng.hetero.profiler.summary()["fused"]
    assert f["windows"] >= 1 and f["steps_per_dispatch"] > 1


# ---------------------------------------------------------------------------
# early exit
# ---------------------------------------------------------------------------


def test_early_exit_on_finish(setup):
    """Staggered max_new: the first window must stop AT the finishing step
    (3), not run the full K=4 — admission/release timing stays identical
    to the stepped loop."""
    cfg, params, _ = setup
    prompts = _prompts(cfg, (16, 9), seed=2)
    ref, _, _, _ = _run(cfg, params, ServeConfig(method="dsa", **BASE),
                        prompts, (3, 7))
    got, _, windows, _ = _run(
        cfg, params, ServeConfig(method="dsa", fused_steps=4, **BASE),
        prompts, (3, 7))
    assert got == ref
    assert windows[0] == 3          # early exit at slot 0's last token
    assert sum(windows) == 7        # no wasted device steps


@pytest.mark.parametrize("rmode", ["inline", "sync", "overlap"])
def test_early_exit_on_trigger(setup, rmode):
    """tau=1.1 FLARE fires as soon as the cooldown opens; the window must
    exit at the trigger step so the retrieval launches on the same step it
    would have under the stepped loop — same fired slots, same doc ids,
    same spliced streams."""
    cfg, params, corpus = setup
    rcfg = RetrievalConfig(kind="rag", mode=rmode, corpus=corpus, k=2,
                           trigger="flare", tau=1.1, min_interval=3,
                           max_retrievals=1, query_window=6)
    prompts = _prompts(cfg, (16, 9), seed=3)
    ref, rfired, _, reng = _run(
        cfg, params, ServeConfig(method="dsa", retrieval=rcfg, **BASE),
        prompts, (10, 10))
    got, gfired, _, geng = _run(
        cfg, params,
        ServeConfig(method="dsa", retrieval=rcfg, fused_steps=4, **BASE),
        prompts, (10, 10))
    assert got == ref
    assert gfired == rfired and gfired
    assert [e["ids"] for e in geng.retrieval.events] == \
           [e["ids"] for e in reng.retrieval.events]


def test_trigger_composed_with_offload(setup):
    """Retrieval triggers + hetero offload inside fused windows: the
    armed/arm_after countdown gates must reproduce the host gate decisions
    exactly when both services share the pool."""
    cfg, params, corpus = setup
    rcfg = RetrievalConfig(kind="rag", mode="overlap", corpus=corpus, k=2,
                           trigger="flare", tau=1.1, min_interval=3,
                           max_retrievals=1, query_window=6)
    prompts = _prompts(cfg, (16, 9), seed=4)
    ref, rf, _, _ = _run(
        cfg, params,
        ServeConfig(method="dsa", retrieval=rcfg,
                    offload_cfg=OffloadConfig(mode="overlap"), **BASE),
        prompts, (10, 10))
    got, gf, _, _ = _run(
        cfg, params,
        ServeConfig(method="dsa", retrieval=rcfg,
                    offload_cfg=OffloadConfig(mode="overlap"),
                    fused_steps=4, **BASE),
        prompts, (10, 10))
    assert got == ref and gf == rf and gf


# ---------------------------------------------------------------------------
# API surface: StepEvents shim, OffloadConfig validation, view cache
# ---------------------------------------------------------------------------


def test_step_events_legacy_shim():
    ev = StepEvents(emissions=[(7, 0, 11), (8, 1, 12)], finished=[1],
                    fired=[0], steps=2)
    assert list(ev) == [(7, 0, 11), (8, 1, 12)]
    assert len(ev) == 2 and bool(ev) and ev[0] == (7, 0, 11)
    assert not StepEvents() and len(StepEvents()) == 0


def test_offload_config_validation():
    with pytest.raises(ValueError):
        OffloadConfig(mode="bogus")
    with pytest.raises(ValueError):
        OffloadConfig(shards=0)
    with pytest.raises(ValueError):
        OffloadConfig(mode="off", shards=2)
    with pytest.raises(ValueError):
        OffloadConfig(mode="off", main_mesh=2)
    with pytest.raises(ValueError), pytest.warns(DeprecationWarning):
        ServeConfig(offload="nope")
    with pytest.raises(ValueError):
        ServeConfig(fused_steps=0)


def test_offload_config_precedence_and_replace():
    # nested populates the flat mirror, silently — the supported surface
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sc = ServeConfig(offload_cfg=OffloadConfig(mode="overlap",
                                                   shards=2))
    assert (sc.offload, sc.offload_shards) == ("overlap", 2)
    # flat kwargs are DEPRECATED: they warn, and still win over a
    # conflicting nested config (pre-existing call sites unchanged)
    with pytest.warns(DeprecationWarning, match="offload_cfg"):
        sc = ServeConfig(offload="sync",
                         offload_cfg=OffloadConfig(mode="overlap"))
    assert sc.offload == "sync" and sc.offload_cfg.mode == "sync"
    with pytest.warns(DeprecationWarning, match="deprecated"):
        sc = ServeConfig(offload="overlap", offload_shards=2)
    assert sc.offload_cfg == OffloadConfig(mode="overlap", shards=2)
    # replace on the FLAT surface re-derives the nested view (and warns)
    with pytest.warns(DeprecationWarning):
        sc = dataclasses.replace(ServeConfig(), offload="overlap")
    assert sc.offload_cfg.mode == "overlap"
    # replace on the NESTED surface updates the flat mirror silently, and
    # an unrelated replace() carries the coherent pair without warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sc = dataclasses.replace(ServeConfig(),
                                 offload_cfg=OffloadConfig(mode="sync"))
        assert sc.offload == "sync"
        sc2 = dataclasses.replace(sc, fused_steps=2)
    assert sc2.offload_cfg.mode == "sync" and sc2.offload == "sync"
    assert sc2.fused_steps == 2


def test_table_view_cache(setup):
    """Steady-state decode reuses the sliced table view; admissions and
    releases (host-table pushes) invalidate it."""
    cfg, params = setup[0], setup[1]
    sc = ServeConfig(method="none", **BASE)
    eng = Engine(cfg, params, sc, key=jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (16, 9), seed=6)
    eng.submit(Request(0, prompts[0], 4))
    eng.submit(Request(1, prompts[1], 4))
    eng.poll()                             # admit both (one decode step)
    lengths = np.where(eng._decode_live(), eng.slots.lengths(),
                       0).astype(np.int32)
    v1 = eng._table_view(lengths)
    v2 = eng._table_view(lengths)
    assert v1 is v2                        # cache hit: same buffer object
    ver = eng.pool.table_version
    eng.step_pool()                        # decode does not edit the table
    assert eng.pool.table_version == ver
    for _ in range(8):                     # drain to release (table push)
        eng.step_pool()
    assert eng.pool.table_version > ver
    v3 = eng._table_view(lengths)
    assert v3 is not v1                    # version bump invalidated it


# ---------------------------------------------------------------------------
# property: arbitrary window widths x slot-length mixes
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=6)
@given(st.integers(2, 6), st.integers(4, 20), st.integers(4, 20),
       st.integers(1, 7), st.integers(1, 7))
def test_fused_property_bitmatch(K, n1, n2, m1, m2):
    cfg, params, _ = _setup_cached()
    prompts = _prompts(cfg, (n1, n2), seed=n1 * 29 + n2)
    ref, _, _, _ = _run(cfg, params, ServeConfig(method="dsa", **BASE),
                        prompts, (m1, m2))
    got, _, _, _ = _run(
        cfg, params, ServeConfig(method="dsa", fused_steps=K, **BASE),
        prompts, (m1, m2))
    assert got == ref
