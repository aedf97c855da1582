"""The decode programs name their work (``jax.named_scope``), so a device
trace can split a step into the memory pipeline's stages:

  * the engine's decode programs — the stepped ``decode_paged``, the
    fused ``fused_decode`` window, and under offload the apply step
    ``decode_paged_presel`` with the offload side's ``select`` and
    ``ingest`` — carry ``core.pipeline.SCOPES`` in their ops' ``op_name``
    metadata, each scope the program runs on some op;
  * the two Pallas kernels sit where the stage split counts them:
    ``relevancy_topk`` under ``relevancy``, ``paged_decode_attention``
    under ``apply``;
  * the jitted programs are named after their functions (``jit_<name>``),
    not ``jit__lambda``.

Checked on the compiled HLO's metadata (what the profiler attributes an
op by) and on the jaxpr, where a kernel is one ``pallas_call`` whether it
lowers to Mosaic or runs in interpret mode.
"""
import functools
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.pipeline import SCOPES, STAGES
from repro.serving import Engine, OffloadConfig, Request, ServeConfig

BASE = dict(max_len=128, n_slots=2, tp=4, page=8, kv_page_size=16,
            method="dsa")


@functools.lru_cache(maxsize=1)
def _setup():
    from repro.models import init_params

    cfg = get_arch("llama3.2-1b").smoke()
    return cfg, init_params(cfg, jax.random.PRNGKey(0), tp=4)


def innermost(op_name: str) -> str:
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def _decode_paged(eng):
    lengths = np.where(eng.slots.live_mask(), eng.slots.lengths(), 0)
    lengths = lengths.astype(np.int32)
    d = eng.pool.device
    return eng._decode_paged, (
        eng.params, jnp.asarray(eng._pending), d["k_pages"], d["v_pages"],
        eng._table_view(lengths), jnp.asarray(lengths),
        jnp.asarray(eng.slots.live_mask()), eng.sparse_params)


def _fused_decode(eng):
    fn = next(iter(eng._fused_fns.values()))
    n = eng.sc.n_slots
    live = eng.slots.live_mask()
    lengths = np.where(live, eng.slots.lengths(), 0).astype(np.int32)
    zeros = jnp.zeros((n,), jnp.int32)
    d = eng.pool.device
    return fn, (eng.params, eng.sparse_params, jnp.asarray(eng._pending),
                d["k_pages"], d["v_pages"], eng._table_view(lengths, 4),
                jnp.asarray(lengths), jnp.asarray(live), zeros, zeros + 6,
                jnp.zeros((n,), bool), zeros)


def _decode_paged_presel(eng):
    fn = next(iter(eng.hetero._apply_jits.values()))
    live = eng.slots.live_mask()
    lengths = np.where(live, eng.slots.lengths(), 0).astype(np.int32)
    d = eng.pool.device
    return fn, (eng.params, jnp.asarray(eng._pending), d["k_pages"],
                d["v_pages"], eng._table_view(lengths), jnp.asarray(lengths),
                jnp.asarray(live), eng.hetero._neg_sel)


def _select(eng):
    hx = eng.hetero
    return hx._select_jit, (hx.sp_off, hx.summary, hx.q_buf,
                            jnp.asarray(eng.slots.lengths(), jnp.int32))


def _ingest(eng):
    hx, cfg, n = eng.hetero, eng.cfg, eng.sc.n_slots
    k_new = jnp.zeros((cfg.n_layers, n, cfg.n_kv_heads, cfg.hd),
                      hx.q_buf.dtype)
    return hx._ingest_jit, (hx.summary, hx.sp_off, k_new,
                            jnp.zeros((n,), jnp.int32),
                            jnp.ones((n,), bool))


ENGINES = {"stepped": dict(), "fused": dict(fused_steps=4),
           "offload": dict(offload_cfg=OffloadConfig(mode="sync"))}
# the scopes of a dense model's decode step (no ``moe``)
DENSE = STAGES + ("kv_write", "dense")
# program -> (its engine, its function and arguments, the scopes it runs)
PROGRAMS = {
    "decode_paged": ("stepped", _decode_paged, DENSE),
    "fused_decode": ("fused", _fused_decode, DENSE),
    # the offload split: the apply step on the KV pool's device, selection
    # and index upkeep on the offload side
    "decode_paged_presel": ("offload", _decode_paged_presel,
                            ("retrieve", "apply", "kv_write", "dense")),
    "select": ("offload", _select, ("relevancy", "retrieve")),
    "ingest": ("offload", _ingest, ("prepare",)),
}
KERNELS = {"decode_paged": {"relevancy_topk": "relevancy",
                            "paged_decode_attention": "apply"},
           "fused_decode": {"relevancy_topk": "relevancy",
                            "paged_decode_attention": "apply"},
           "decode_paged_presel": {"paged_decode_attention": "apply"}}


@functools.lru_cache(maxsize=None)
def _engine(kind):
    cfg, params = _setup()
    eng = Engine(cfg, params, ServeConfig(**BASE, **ENGINES[kind]),
                 key=jax.random.PRNGKey(1))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    eng.submit(Request(0, prompt.astype(np.int32), 6))
    for _ in range(3):
        eng.poll()
    return eng


@functools.lru_cache(maxsize=None)
def _program(name):
    kind, get, _ = PROGRAMS[name]
    fn, args = get(_engine(kind))
    return fn, args, fn.lower(*args).compile().as_text()


def _kernel_scopes(fn, args):
    """{kernel name: {innermost scope of each of its calls}} over the
    program's jaxpr and every jaxpr nested in it."""
    found = {}

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = "/".join(p for p in (stack,
                                        str(eqn.source_info.name_stack)) if p)
            if eqn.primitive.name == "pallas_call":
                info = eqn.params.get("name_and_src_info")
                name = getattr(info, "name", None) or eqn.params.get("name")
                found.setdefault(name, set()).add(innermost(here))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr, here)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub, here)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_program_is_named(name):
    _, _, hlo = _program(name)
    assert hlo.startswith(f"HloModule jit_{name}"), hlo.splitlines()[0]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_every_scope_claims_ops(name):
    _, _, hlo = _program(name)
    scopes = {innermost(p) for p in re.findall(r'op_name="([^"]*)"', hlo)}
    assert set(PROGRAMS[name][2]) <= scopes, sorted(scopes)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernels_sit_under_their_stage(name):
    fn, args, _ = _program(name)
    found = _kernel_scopes(fn, args)
    assert found == {k: {v} for k, v in KERNELS[name].items()}


def test_stage_names_are_the_pipeline_stages():
    assert SCOPES[:4] == STAGES == ("prepare", "relevancy", "retrieve",
                                    "apply")
    assert set(SCOPES[4:]) == {"kv_write", "dense", "moe"}
