"""Top-k MoE with GShard/Switch-style capacity dispatch (TPU-native, dense
einsum dispatch — no data-dependent shapes, shardable under GSPMD).

Tokens are processed in fixed-size groups (``group_size``); each group builds a
[t, E, C] one-hot dispatch tensor (bounded < ~100 MB), experts run as a batched
[E, C, d] x [E, d, ff] einsum whose ff dim TP-shards on the model axis, and a
Switch-style load-balancing aux loss is returned. The same path serves both
training (t = sequence chunk) and batched decode (t = batch).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L

Params = Dict[str, jnp.ndarray]

# §Perf iteration (granite train cell): when set to a mesh axis name, the
# dispatch/combine tensors get expert-dim sharding constraints so each EP
# shard computes ONLY its experts' slices (otherwise GSPMD all-gathers the
# [t, E, C] dispatch one-hot to every shard — 1.9 TiB/step at granite scale).
EP_CONSTRAINT = {"axis": None}


def set_ep_constraint(axis):
    EP_CONSTRAINT["axis"] = axis


def _ep(x, spec_fn):
    axis = EP_CONSTRAINT["axis"]
    if axis is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, spec_fn(axis))


def moe_init(key, cfg: ArchConfig) -> Params:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = L.dtype_of(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": L.dense_init(ks[0], d, E, jnp.float32, scale=0.02),
        "w1": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) / np.sqrt(d)).astype(dt),
        "w3": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) / np.sqrt(d)).astype(dt),
        "w2": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
               / np.sqrt(2 * cfg.n_layers * ff)).astype(dt),
    }


def capacity(t: int, cfg: ArchConfig) -> int:
    c = int(np.ceil(t * cfg.experts_per_token / cfg.n_experts * cfg.capacity_factor))
    return max(4 * ((c + 3) // 4), 4)


def _moe_group(p: Params, x: jnp.ndarray, cfg: ArchConfig, cap: int):
    """x [t, d] -> (y [t, d], aux scalar). One dispatch group."""
    t, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ p["router"]  # [t, E]
    probs = jax.nn.softmax(logits, axis=-1)
    wgt, widx = jax.lax.top_k(probs, k)  # [t, k]
    wgt = wgt / jnp.maximum(wgt.sum(-1, keepdims=True), 1e-9)

    # assignment mask [t, E] (top-k experts are distinct so sum over k is 0/1)
    assign = jax.nn.one_hot(widx, E, dtype=jnp.float32).sum(axis=1)  # [t, E]
    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(assign, axis=0) - assign  # [t, E]
    keep = (pos < cap) * assign
    # weighted expert coefficient per token
    wgt_e = (jax.nn.one_hot(widx, E, dtype=jnp.float32) * wgt[..., None]).sum(1)  # [t, E]

    from jax.sharding import PartitionSpec as P
    disp = keep[..., None] * jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                            dtype=jnp.float32)  # [t, E, C]
    disp = _ep(disp, lambda ax: P(None, ax, None))
    disp_b = disp.astype(x.dtype)
    xe = jnp.einsum("tec,td->ecd", disp_b, x)  # [E, C, d]
    xe = _ep(xe, lambda ax: P(ax, None, None))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["w3"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w2"])  # [E, C, d]
    ye = _ep(ye, lambda ax: P(ax, None, None))
    comb = (disp * (wgt_e * keep.astype(jnp.float32))[..., None]).astype(x.dtype)
    y = jnp.einsum("tec,ecd->td", comb, ye)

    # Switch load-balance aux: E * sum_e f_e * mean_prob_e
    frac = assign.mean(axis=0)
    mean_p = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return y, aux


def moe_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig,
              group_size: int = 2048) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, d] -> (y [B, S, d], aux). Groups scan over flattened tokens."""
    B, S, d = x.shape
    tokens = B * S
    g = min(group_size, tokens)
    n_groups = (tokens + g - 1) // g
    pad = n_groups * g - tokens
    flat = x.reshape(tokens, d)
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    flat = flat.reshape(n_groups, g, d)
    cap = capacity(g, cfg)

    def body(carry, xg):
        y, aux = _moe_group(p, xg, cfg, cap)
        return carry + aux, y

    aux_total, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32), flat)
    y = ys.reshape(n_groups * g, d)[:tokens].reshape(B, S, d)
    return y, aux_total / n_groups


# ---------------------------------------------------------------------------
# DeepSeek-V3 routing (``noaux_tc``): dropless, over the experts this chip
# holds, plus the shared expert.
# ---------------------------------------------------------------------------


def noaux_moe_init(key, cfg: ArchConfig) -> Params:
    """Router over all ``n_experts`` (float32 weight and correction bias),
    the ``held_experts`` this chip holds, and the shared expert."""
    d, ff, Eh = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    dt = L.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    expert = lambda k, a, b, s: (jax.random.normal(k, (Eh, a, b), jnp.float32)
                                 * s).astype(dt)
    return {
        "gate": L.dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w1": expert(ks[1], d, ff, 1 / np.sqrt(d)),
        "w3": expert(ks[2], d, ff, 1 / np.sqrt(d)),
        "w2": expert(ks[3], ff, d, 1 / np.sqrt(2 * cfg.n_layers * ff)),
        "shared": L.mlp_init(ks[4], cfg, d_ff=ff * cfg.n_shared_experts),
    }


def noaux_route(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """x [T, d] -> (weights [T, k], expert ids [T, k]) over all n_experts.

    Scores are sigmoids of a full float32 product, as the published gate
    computes them; the correction bias is added only to choose: a
    group's score is the sum of its two best biased scores, the
    ``topk_expert_groups`` best groups stay, and the k best biased experts
    inside them are chosen. The weights are the un-biased scores of the
    chosen experts, normalised to sum 1 and scaled by ``routed_scaling``."""
    T = x.shape[0]
    E, G = cfg.n_experts, cfg.n_expert_groups
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["gate"],   # [T, E]
                               precision=jax.lax.Precision.HIGHEST))
    b = s + p["bias"]
    group = jax.lax.top_k(b.reshape(T, G, E // G), 2)[0].sum(-1)   # [T, G]
    _, keep = jax.lax.top_k(group, cfg.topk_expert_groups)
    kept = jax.nn.one_hot(keep, G, dtype=jnp.float32).sum(1) > 0   # [T, G]
    b = jnp.where(jnp.repeat(kept, E // G, axis=1), b, -jnp.inf)
    _, idx = jax.lax.top_k(b, cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / w.sum(-1, keepdims=True)
    return w * cfg.routed_scaling, idx


def held_expert_weights(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """x [T, d] -> [T, held_experts]: each token's routing weight on each
    expert this chip holds (0 where the router did not choose it)."""
    w, idx = noaux_route(p, x, cfg)
    held = cfg.first_held_expert + jnp.arange(cfg.held_experts)
    return ((idx[:, :, None] == held[None, None]) * w[:, :, None]).sum(1)


def held_experts_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """The held experts' part of the routed output, x [T, d] -> [T, d]: one
    grouped product over every held expert for every token, each scaled by
    its routing weight (0 for experts not chosen). A token's output depends
    on its own routing alone, and each held expert is read once."""
    w = held_expert_weights(p, x, cfg)                              # [T, Eh]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, p["w1"])) * jnp.einsum(
        "td,edf->tef", x, p["w3"])                                  # [T,Eh,f]
    h = h * w[:, :, None].astype(h.dtype)
    return jnp.einsum("tef,efd->td", h, p["w2"])


def noaux_moe_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig):
    """x [B, S, d] -> [B, S, d]: the held experts' part plus the shared
    expert. Under expert parallelism each chip adds its own part; here the
    chip's part goes on to the next layer as it is."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    y = held_experts_apply(p, flat, cfg) + L.mlp(p["shared"], flat)
    return y.reshape(B, S, d)
