"""DeepSeek-V3.2 on the serving path (models/mla.py, the published indexer
in core/methods/dsa.py, the noaux_tc MoE in models/moe.py, the sparse MLA
decode kernel), at a tiny size on the CPU in float32: the engine against
the plain reference (bench/reference/deepseek_v32.py), absorbed against
expanded attention, YaRN and the router against hand values, the expert
shares against the uncut layer, dropless routing, the kernel and the
token top-k against plain jnp."""
import functools
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MemoryConfig
from repro.configs.deepseek_v32 import CONFIG as PUBLISHED
from repro.core.methods import dsa
from repro.kernels import ops, ref
from repro.models import init_params, mla
from repro.models import moe as M
from repro.serving import Engine, Request, ServeConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]

TINY = ArchConfig(
    name="deepseek-v3.2-tiny", family="moe", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=300, head_dim=24,
    norm_eps=1e-6, n_experts=16, experts_per_token=4,
    n_expert_groups=4, topk_expert_groups=2, routed_scaling=2.5,
    moe_d_ff=32, n_shared_experts=1, n_held_experts=4, first_k_dense=1,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_factor=40.0,
    rope_original_max_len=64, dtype="float32",
    memory=MemoryConfig(index_heads=4, index_dim=16, top_k=32,
                        min_context=32))

# the same model as the reference reads it (published config.json keys)
TINY_FILE = {
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"factor": 40, "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "memory": {"index_heads": 4, "index_dim": 16, "top_k": 32},
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "program": {"first_held_expert": 0}, "vocab_size": 300,
}


@functools.lru_cache(maxsize=1)
def _reference():
    path = ROOT / "bench" / "reference" / "deepseek_v32.py"
    spec = importlib.util.spec_from_file_location("deepseek_v32_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _perturbed(tree, key):
    """Norm gains and biases moved off 1 and 0, so skipping one shows."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "bias" in name:
            leaf = leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=1)
def _weights():
    params = _perturbed(init_params(TINY, jax.random.PRNGKey(0)),
                        jax.random.PRNGKey(1))
    idx = dsa.dsa_init(jax.random.PRNGKey(2), TINY, TINY.memory)
    idx = jax.tree.map(lambda a: a.astype(jnp.float32), idx)
    return params, _perturbed(idx, jax.random.PRNGKey(3))


@functools.lru_cache(maxsize=1)
def _served():
    """Two requests through Engine.submit / poll (chunked prefill, then
    pooled decode past top_k), with the logits of every decode step."""
    params, idx = _weights()
    sc = ServeConfig(max_len=256, n_slots=2, method="dsa", tp=1, page=16,
                     prefill_chunk=16)
    eng = Engine(TINY, params, sc, key=jax.random.PRNGKey(4))
    eng.sparse_params = idx
    steps = []
    decode = eng._decode_paged

    def recorded(*args):
        out = decode(*args)
        steps.append((np.asarray(args[5]), np.asarray(args[6]),
                      np.asarray(out[0])))
        return out

    eng._decode_paged = recorded
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY.vocab_size, n).astype(np.int32)
               for n in (40, 57)]
    handles = [eng.submit(Request(i, p, 30)) for i, p in enumerate(prompts)]
    eng.drain()
    assert all(h.done for h in handles)
    return prompts, [np.asarray(h.tokens) for h in handles], steps


@pytest.mark.parametrize("request_index", [0, 1])
def test_engine_matches_reference(request_index):
    """Served tokens are the reference's first choice at every position,
    and every decode step's logits are the reference's logits there."""
    params, idx = _weights()
    prompts, served, steps = _served()
    prompt, out = prompts[request_index], served[request_index]
    P, V = len(prompt), TINY.vocab_size
    seq = np.concatenate([prompt, out[:-1]])
    rows = np.arange(P - 1, P - 1 + len(out), dtype=np.int32)
    query = np.broadcast_to(np.arange(V, dtype=np.int32), (len(out), V))
    r = _reference().score(params, idx, TINY_FILE, seq, P, rows, query)
    np.testing.assert_array_equal(r["argmax"], out)
    assert P + len(out) > TINY.memory.top_k      # the sparse branch ran
    slot = request_index                          # admitted in order
    compared = 0
    for lengths, live, logits in steps:
        row = lengths[slot] - (P - 1)
        if not live[slot] or row >= len(out):     # the last step's logits
            continue                              # pick no served token
        np.testing.assert_allclose(logits[slot, :V], r["at"][row],
                                   rtol=1e-4, atol=1e-4)
        compared += 1
    assert compared == len(out) - 1


def test_absorbed_decode_equals_expanded_mla():
    """q . (c_kv W_uk) over the latent equals attention with every head's
    keys and values expanded from it."""
    params, _ = _weights()
    a = jax.tree.map(lambda x: x[0], params["moe_layers"]["attn"])
    B, T, H = 2, 50, TINY.n_heads
    dn, dr, dv, dl = 16, 8, 16, 32
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(k[0], (B, T, TINY.d_model))
    cos, sin = mla.rope_tables(TINY, jnp.broadcast_to(jnp.arange(T), (B, T)))
    _, q_nope, q_rope, row = mla.project(a, h, cos, sin, TINY)
    scale = mla.softmax_scale(TINY)
    n = jnp.array([50, 23], jnp.int32)
    # absorbed, as the decode step runs it: the last position's query
    q_lat = mla.absorb(a, q_nope[:, -1:], q_rope[:, -1:], TINY)[:, 0]
    o = ref.mla_sparse_decode_attention(q_lat, row, n, dl, scale)
    got = mla.unabsorb(a, o[:, None], TINY)[:, 0].reshape(B, H, dv)
    # expanded
    w = a["wkv_b"].reshape(dl, H, dn + dv)
    kv = jnp.einsum("btl,lhe->bthe", row[..., :dl], w)
    k_full = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        row[:, :, None, dl:], (B, T, H, dr))], -1)
    q_full = jnp.concatenate([q_nope[:, -1], q_rope[:, -1]], -1)
    s = jnp.einsum("bhe,bthe->bht", q_full, k_full) * scale
    s = jnp.where(jnp.arange(T)[None, None] < n[:, None, None], s, -1e30)
    want = jnp.einsum("bht,bthv->bhv", jax.nn.softmax(s, -1), kv[..., dn:])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_yarn_matches_hand_values():
    """Published rope: theta 10000 over 64 dims, factor 40 from 4096
    positions, beta_fast 32 -> correction dim 10, beta_slow 1 -> 23."""
    inv = mla.yarn_inv_freq(PUBLISHED)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = 6 / 13                                # (16 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv[16], base[16] / 40 * ramp + base[16] * (1 - ramp), rtol=1e-6)
    assert mla.softmax_scale(PUBLISHED) == pytest.approx(
        192 ** -0.5 * (1 + 0.1 * math.log(40)) ** 2)
    assert mla.softmax_scale(PUBLISHED) == pytest.approx(0.1352338, rel=1e-6)


def test_router_matches_hand_example():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts a token. The
    bias turns the group choice (g3 over g1) and the expert choice inside
    it; e0 has the best score but sits in a dropped group; the weights are
    the un-biased scores 0.3 and 0.2, normalised, times 2.5."""
    cfg = TINY.replace(d_model=8, n_experts=8, n_expert_groups=4,
                       topk_expert_groups=2, experts_per_token=2,
                       n_held_experts=8)
    s = np.array([0.9, 0.1, 0.6, 0.5, 0.7, 0.65, 0.2, 0.3], np.float32)
    p = {"gate": jnp.eye(8, dtype=jnp.float32),
         "bias": jnp.array([0, 0, 0, 0, 0, 0, 0.55, 0.5], jnp.float32)}
    x = jnp.asarray(np.log(s / (1 - s)))[None]
    w, idx = M.noaux_route(p, x, cfg)
    assert idx.tolist() == [[7, 6]]
    np.testing.assert_allclose(w, [[1.5, 1.0]], rtol=1e-5)
    held = M.held_expert_weights(p, x, cfg)
    np.testing.assert_allclose(held, [[0, 0, 0, 0, 0, 0, 1.0, 1.5]],
                               rtol=1e-5, atol=1e-7)


def _uncut_layer():
    cfg = TINY.replace(n_held_experts=0)          # all 16 experts held
    p = M.noaux_moe_init(jax.random.PRNGKey(6), cfg)
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 7, cfg.d_model))
    return cfg, p, x


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: their held parts, plus the shared
    expert counted once, are the layer with all 16 experts."""
    cfg, p, x = _uncut_layer()
    flat = x.reshape(-1, cfg.d_model)
    total = M.L.mlp(p["shared"], flat)
    for share in range(4):
        part = dict(p, **{n: p[n][4 * share: 4 * share + 4]
                          for n in ("w1", "w3", "w2")})
        scfg = cfg.replace(n_held_experts=4, first_held_expert=4 * share)
        total = total + M.held_experts_apply(part, flat, scfg)
    whole = M.noaux_moe_apply(p, x, cfg).reshape(-1, cfg.d_model)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_moe_is_dropless():
    """A token's output alone equals its output beside other tokens."""
    cfg, p, x = _uncut_layer()
    together = M.noaux_moe_apply(p, x, cfg)
    for b, t in ((0, 0), (3, 5), (4, 6)):
        alone = M.noaux_moe_apply(p, x[b:b + 1, t:t + 1], cfg)
        np.testing.assert_allclose(alone[0, 0], together[b, t], rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("n_rows,block,n_valid", [
    (64, 16, (64, 17)), (40, 16, (1, 40)), (2048, 512, (2048, 300))])
def test_mla_kernel_matches_jnp(n_rows, block, n_valid):
    B, H, W, dl = 2, 8, 48, 32
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(k[0], (B, H, W), jnp.float32)
    rows = jax.random.normal(k[1], (B, n_rows, W)).astype(jnp.bfloat16)
    n = jnp.asarray(n_valid, jnp.int32)
    got = ops.mla_sparse_decode_attention(q, rows, n, dv=dl, scale=0.3,
                                          block=block)
    want = ref.mla_sparse_decode_attention(q, rows, n, dl, 0.3)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,context", [(256, (256, 100)), (64, (64, 10))])
def test_token_topk_matches_plain_topk(S, context):
    B, Hi, di, top_k = 2, 4, 16, 32
    k = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(k[0], (B, Hi, di))
    w = jax.random.normal(k[1], (B, Hi))
    keys = jax.random.normal(k[2], (B, S, di))
    ctx = jnp.asarray(context, jnp.int32)
    ids, n = dsa.token_topk(q, w, keys, ctx, top_k)
    scores = np.einsum("bh,bhs->bs", np.asarray(w), np.maximum(
        np.einsum("bhd,bsd->bhs", np.asarray(q), np.asarray(keys)), 0))
    for b in range(B):
        c = int(context[b])
        want = np.argsort(-scores[b, :c], kind="stable")[:min(top_k, c)]
        assert int(n[b]) == min(top_k, c)
        assert set(np.asarray(ids[b, :int(n[b])]).tolist()) == set(
            want.tolist())


def test_decode_step_names_its_scopes():
    """Every scope of core.pipeline.SCOPES claims ops of the decode step,
    the MoE's under ``dense``, and the kernel sits under ``apply``."""
    import re

    from repro.core.pipeline import SCOPES
    from repro.models import model

    params, idx = _weights()
    pool = model.make_page_pool(TINY, 2, 128, page_size=16, total_pages=17)
    pool["page_table"] = jnp.arange(1, 17, dtype=jnp.int32).reshape(2, 8)
    pool["lengths"] = jnp.array([40, 70], jnp.int32)
    fn = jax.jit(lambda p, tok, pool, live, sp: model.decode_step_paged(
        p, TINY, tok, pool, live, sparse_params=sp))
    args = (params, jnp.array([3, 4], jnp.int32), pool,
            jnp.array([True, True]), idx)
    names = re.findall(r'op_name="([^"]*)"',
                       fn.lower(*args).compile().as_text())
    innermost = {next((p for p in reversed(n.split("/")) if p in SCOPES), "")
                 for n in names}
    assert set(SCOPES) <= innermost
    assert any("dense/moe/" in n for n in names)

    def kernel_scopes(jaxpr, stack=""):
        for eqn in jaxpr.eqns:
            here = f"{stack}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                info = eqn.params.get("name_and_src_info")
                yield getattr(info, "name", None) or eqn.params.get(
                    "name"), here
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield from kernel_scopes(sub.jaxpr, here)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        yield from kernel_scopes(sub, here)

    found = list(kernel_scopes(jax.make_jaxpr(fn)(*args).jaxpr))
    assert found and all(
        name == "mla_sparse_decode_attention" and next(
            p for p in reversed(where.split("/")) if p in SCOPES) == "apply"
        for name, where in found), found
