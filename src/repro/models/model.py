"""Model builder: init / forward / prefill / decode for all 10 assigned
architectures, with scan-over-layers (stacked params) so HLO size and compile
time stay flat in depth.

Families:
  dense | moe | audio | vlm : transformer (GQA attn + SwiGLU-or-MoE FFN)
  hybrid (zamba2)           : 13 x (6 Mamba2 + shared attn/MLP block) + 3 Mamba2
  ssm (xlstm)               : (mLSTM, sLSTM) pairs

Caches are dataclass-free pytrees (dicts) so they cross jit boundaries and
shard cleanly. ``length`` is a traced scalar.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Params = Dict

# §Perf (train cells): Megatron-style sequence-parallel residual stream.
# When set to a PartitionSpec, the residual activations between layers are
# constrained to it (sequence sharded over the model axis) — GSPMD then
# lowers the TP boundary as all-gather + reduce-scatter pairs instead of
# full fp32 all-reduces of [B, S, d]. Variant-gated from launch/dryrun.py.
SP_RESIDUAL = {"spec": None}


def set_sp_residual(spec):
    SP_RESIDUAL["spec"] = spec


def _sp(x):
    if SP_RESIDUAL["spec"] is None:
        return x
    return jax.lax.with_sharding_constraint(x, SP_RESIDUAL["spec"])


def _sp_gather(h):
    """Megatron-SP boundary: explicitly all-gather the normed activations
    entering the TP projections (bf16), instead of letting GSPMD pick an
    interior resharding point."""
    spec = SP_RESIDUAL["spec"]
    if spec is None:
        return h
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(h, P(spec[0], None, None))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _tf_layer_init(key, cfg: ArchConfig, tp: int) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "attn": A.attn_init(k1, cfg, tp),
        "attn_norm": L.rms_norm_init(cfg.d_model, None),
        "mlp_norm": L.rms_norm_init(cfg.d_model, None),
    }
    if cfg.n_experts:
        p["moe"] = M.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def _stacked(init_fn, key, n: int):
    """Layer-stacked params [n, ...], drawn one layer at a time (lax.map),
    so no [n, ...] float32 draw is ever materialized."""
    return jax.lax.map(init_fn, jax.random.split(key, n))


def init_params(cfg: ArchConfig, key, tp: int = 16) -> Params:
    """Random params from ``key``. Each part is drawn by its own jitted
    call, so at published widths the float32 draw behind a bf16 weight is
    never held for more than one matrix at a time."""
    if cfg.is_mla:
        return mla.init_params(cfg, key)
    ke, kl, kf, kh = jax.random.split(key, 4)
    params: Params = {
        "embed": _embed_init(ke, cfg),
        "final_norm": L.rms_norm_init(cfg.d_model, None),
        "lm_head": _lm_head_init(kh, cfg),
    }
    params.update(_body_init(kl, cfg, tp))
    return params


_embed_init = jax.jit(L.embed_init, static_argnums=1)
_lm_head_init = jax.jit(L.lm_head_init, static_argnums=1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _body_init(key, cfg: ArchConfig, tp: int) -> Params:
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.shared_attn_every  # 13
        per = cfg.shared_attn_every                      # 6
        tail = cfg.n_layers - n_super * per              # 3
        kb, kt, ks = jax.random.split(key, 3)
        body_keys = jax.random.split(kb, n_super * per).reshape(n_super, per, 2)
        mamba = lambda k: _mamba_layer_init(k, cfg)
        return {"body": jax.lax.map(lambda row: jax.lax.map(mamba, row),
                                    body_keys),
                "tail": _stacked(mamba, kt, tail),
                "shared": _tf_layer_init(ks, cfg, tp)}
    if cfg.xlstm_pattern:
        nb = cfg.n_layers // len(cfg.xlstm_pattern)
        km, ks = jax.random.split(key)
        return {
            "mlstm": _stacked(
                lambda k: {"pre": L.rms_norm_init(cfg.d_model, None),
                           "blk": X.mlstm_init(k, cfg)}, km, nb),
            "slstm": _stacked(
                lambda k: {"pre": L.rms_norm_init(cfg.d_model, None),
                           "blk": X.slstm_init(k, cfg)}, ks, nb),
        }
    return {"layers": _stacked(lambda k: _tf_layer_init(k, cfg, tp), key,
                               cfg.n_layers)}


def _mamba_layer_init(key, cfg: ArchConfig) -> Params:
    return {"norm": L.rms_norm_init(cfg.d_model, None),
            "mamba": S.mamba_init(key, cfg)}


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def _rope_tables(cfg: ArchConfig, positions, positions3=None):
    if cfg.rope_style == "none":
        return None, None
    if cfg.rope_style == "mrope":
        assert positions3 is not None
        return L.mrope_cos_sin(positions3, cfg.hd, cfg.rope_theta,
                               cfg.mrope_sections)
    return L.rope_cos_sin(positions, cfg.hd, cfg.rope_theta)


def _attn_out(lp: Params, out: jnp.ndarray, cfg: ArchConfig, tp: int):
    """Apply dead-head mask then o-projection. out [B,S,Hp,hd] -> [B,S,d]."""
    hm = A.head_mask(cfg, tp)
    out = out * hm[None, None, :, None].astype(out.dtype)
    B, Sq, HP, hd = out.shape
    return out.reshape(B, Sq, HP * hd) @ lp["wo"]


# Decode-path scopes (``jax.named_scope``): the memory-pipeline stages of
# core/pipeline.STAGES, plus ``kv_write`` (the step's K/V into the cache) and
# ``dense`` (embedding, norms, projections, MLP, lm_head). They only name the
# ops in the HLO metadata; the compiled code is the same.


def _qkv(lp: Params, x, cos, sin, cfg: ArchConfig, tp: int):
    """Pre-attention norm and q/k/v projection of one layer."""
    with jax.named_scope("dense"):
        h = L.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        return A.project_qkv(lp["attn"], h, cos, sin, cfg, tp)


def _out_mlp(lp: Params, x, attn, cfg: ArchConfig, tp: int):
    """o-projection, residual and MLP (or MoE) of one layer."""
    with jax.named_scope("dense"):
        x = x + _attn_out(lp["attn"], attn, cfg, tp)
        h = L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        if cfg.n_experts:
            y, _ = M.moe_apply(lp["moe"], h, cfg)
        else:
            y = L.mlp(lp["mlp"], h)
        return x + y


def _logits(params: Params, cfg: ArchConfig, x):
    """Final norm and lm_head at the last position."""
    with jax.named_scope("dense"):
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return last_logits(params, cfg, x)


def _tf_layer_full(lp, x, cos, sin, cfg, tp):
    """Full-sequence transformer layer; returns (x, aux, (k, v, q))."""
    h = _sp_gather(L.rms_norm(lp["attn_norm"], x, cfg.norm_eps))
    q, k, v = A.project_qkv(lp["attn"], h, cos, sin, cfg, tp)
    attn = A.attention_full(q, k, v, cfg, tp=tp)
    x = x + _attn_out(lp["attn"], attn, cfg, tp)
    x = _sp(x)
    h = _sp_gather(L.rms_norm(lp["mlp_norm"], x, cfg.norm_eps))
    if cfg.n_experts:
        y, aux = M.moe_apply(lp["moe"], h, cfg)
    else:
        y, aux = L.mlp(lp["mlp"], h), jnp.zeros((), jnp.float32)
    return x + y, aux, (k, v, q)


def _tf_layer_decode(lp, x, cos, sin, cfg, tp, kc, vc, length, sparse_fn=None,
                     sparse_params=None):
    """One-token transformer layer vs cache; returns (x, kc, vc, sp_new).

    A stateful sparse_fn may return (attn, new_sparse_params) — the
    incremental index cache of the prepare-memory stage lives there."""
    q, k, v = _qkv(lp, x, cos, sin, cfg, tp)
    with jax.named_scope("kv_write"):
        kc = jax.lax.dynamic_update_slice(kc, k, (0, length, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, length, 0, 0))
    sp_new = sparse_params
    if sparse_fn is not None:
        res = sparse_fn(q, kc, vc, length + 1, sparse_params, k_new=k)
        attn, sp_new = res if isinstance(res, tuple) else (res, sparse_params)
    else:
        with jax.named_scope("apply"):
            attn = A.attention_decode(q, kc, vc, length + 1, cfg, tp=tp)
    return _out_mlp(lp, x, attn, cfg, tp), kc, vc, sp_new


def _maybe_ckpt(fn, remat: bool):
    return jax.checkpoint(fn) if remat else fn


# ---------------------------------------------------------------------------
# forward (train / prefill full-sequence pass)
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: jnp.ndarray,
    *,
    positions: Optional[jnp.ndarray] = None,
    positions3: Optional[jnp.ndarray] = None,
    img_embeds: Optional[jnp.ndarray] = None,
    collect_cache: bool = False,
    collect_q: bool = False,
    remat: bool = False,
    tp: int = 16,
):
    """tokens [B, S] -> (hidden [B,S,d], aux, caches-or-None).

    ``collect_q`` additionally stashes the per-layer query activations in
    ``caches["q"]`` ([L, B, S, Hp, hd]) — consumed by the hetero offload
    executor to seed the lookahead relevancy query after prefill. It is a
    prefill-only option; the cache dict handed to decode must not carry it.
    """
    B, Sq = tokens.shape
    x = L.embed(params["embed"], tokens)
    if img_embeds is not None:  # vlm stub: patch embeddings overwrite prefix
        x = jax.lax.dynamic_update_slice(x, img_embeds.astype(x.dtype), (0, 0, 0))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    cos, sin = _rope_tables(cfg, positions, positions3)

    if cfg.family == "hybrid":
        return _hybrid_forward(params, cfg, x, cos, sin, collect_cache, remat, tp)
    if cfg.xlstm_pattern:
        return _xlstm_forward(params, cfg, x, collect_cache, remat)

    def layer_fn(carry, lp):
        x, aux = carry
        x, aux_l, kvq = _tf_layer_full(lp, x, cos, sin, cfg, tp)
        out = kvq if collect_q else kvq[:2]
        return (_sp(x), aux + aux_l), out if collect_cache else None

    (x, aux), kvs = jax.lax.scan(_maybe_ckpt(layer_fn, remat), (x, jnp.zeros((), jnp.float32)),
                                 params["layers"])
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    caches = None
    if collect_cache:
        caches = {"k": kvs[0], "v": kvs[1], "length": jnp.asarray(Sq, jnp.int32)}
        if collect_q:
            caches["q"] = kvs[2]
    return x, aux, caches


def _hybrid_forward(params, cfg, x, cos, sin, collect_cache, remat, tp):
    def super_fn(carry, lp):
        x, aux = carry
        body_lp, shared_kv_unused = lp, None

        def mamba_fn(x, mlp):
            h = L.rms_norm(mlp["norm"], x, cfg.norm_eps)
            y, st = S.mamba_forward(mlp["mamba"], h, cfg)
            return x + y, st if collect_cache else None

        x, states = jax.lax.scan(mamba_fn, x, body_lp)
        x, aux_l, kvq = _tf_layer_full(params["shared"], x, cos, sin, cfg, tp)
        return (x, aux + aux_l), (states, kvq[:2] if collect_cache else None)

    (x, aux), (body_states, shared_kvs) = jax.lax.scan(
        _maybe_ckpt(super_fn, remat), (x, jnp.zeros((), jnp.float32)), params["body"])

    def tail_fn(x, mlp):
        h = L.rms_norm(mlp["norm"], x, cfg.norm_eps)
        y, st = S.mamba_forward(mlp["mamba"], h, cfg)
        return x + y, st if collect_cache else None

    x, tail_states = jax.lax.scan(_maybe_ckpt(tail_fn, remat), x, params["tail"])
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    caches = None
    if collect_cache:
        caches = {
            "body_ssm": body_states[0], "body_conv": body_states[1],
            "tail_ssm": tail_states[0], "tail_conv": tail_states[1],
            "shared_k": shared_kvs[0], "shared_v": shared_kvs[1],
            "length": jnp.asarray(x.shape[1], jnp.int32),
        }
    return x, aux, caches


def _xlstm_forward(params, cfg, x, collect_cache, remat, states=None):
    nb = cfg.n_layers // 2

    def pair_fn(carry, lp):
        x = carry
        mlp, slp, st_in = lp
        y, mstate = X.mlstm_forward(
            mlp["blk"], L.rms_norm(mlp["pre"], x, cfg.norm_eps), cfg,
            None if st_in is None else st_in[0])
        x = x + y
        y, sstate = X.slstm_forward(
            slp["blk"], L.rms_norm(slp["pre"], x, cfg.norm_eps), cfg,
            None if st_in is None else st_in[1])
        x = x + y
        return x, (mstate, sstate) if collect_cache else None

    xs = (params["mlstm"], params["slstm"], states)
    if states is None:
        xs = (params["mlstm"], params["slstm"])
        fn = lambda c, lp: pair_fn(c, (lp[0], lp[1], None))
    else:
        fn = pair_fn
    x, new_states = jax.lax.scan(_maybe_ckpt(fn, remat), x, xs)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    caches = None
    if collect_cache:
        caches = {"states": new_states,
                  "length": jnp.asarray(x.shape[1], jnp.int32)}
    return x, jnp.zeros((), jnp.float32), caches


# ---------------------------------------------------------------------------
# losses / logits
# ---------------------------------------------------------------------------

MOE_AUX_COEF = 0.01


def train_loss(params, cfg: ArchConfig, batch: Dict, *, remat: bool = True,
               tp: int = 16) -> jnp.ndarray:
    x, aux, _ = forward(params, cfg, batch["tokens"],
                        positions3=batch.get("positions3"),
                        img_embeds=batch.get("img_embeds"),
                        remat=remat, tp=tp)
    logits = L.lm_head(params["lm_head"], x, cfg)
    loss = L.cross_entropy(logits, batch["labels"])
    return loss + MOE_AUX_COEF * aux


def last_logits(params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    return L.lm_head(params["lm_head"], x[:, -1:], cfg)[:, 0]


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def make_cache(cfg: ArchConfig, batch: int, max_len: int, tp: int = 16,
               dtype=None) -> Dict:
    dt = dtype or L.dtype_of(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.shared_attn_every
        per = cfg.shared_attn_every
        tail = cfg.n_layers - n_super * per
        ssm, conv = S.mamba_state_init(cfg, batch)
        stack = lambda lead, t: jax.tree.map(
            lambda a: jnp.zeros(lead + a.shape, a.dtype), t)
        return {
            "body_ssm": stack((n_super, per), ssm),
            "body_conv": stack((n_super, per), conv),
            "tail_ssm": stack((tail,), ssm),
            "tail_conv": stack((tail,), conv),
            "shared_k": jnp.zeros((n_super, batch, max_len, kv, hd), dt),
            "shared_v": jnp.zeros((n_super, batch, max_len, kv, hd), dt),
            "length": jnp.zeros((), jnp.int32),
        }
    if cfg.xlstm_pattern:
        nb = cfg.n_layers // 2
        m = X.mlstm_state_init(cfg, batch)
        s = X.slstm_state_init(cfg, batch)
        stack = lambda t: tuple(jnp.zeros((nb,) + a.shape, a.dtype) for a in t)
        return {"states": (stack(m), stack(s)), "length": jnp.zeros((), jnp.int32)}
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, kv, hd), dt),
        "length": jnp.zeros((), jnp.int32),
    }


def prefill(params, cfg: ArchConfig, tokens, *, max_len: Optional[int] = None,
            positions3=None, img_embeds=None, remat: bool = False, tp: int = 16):
    """Full prompt pass -> (last_logits [B, V], caches).

    Caches are padded to ``max_len`` (>= S) so decode can continue in place.
    """
    B, Sq = tokens.shape
    max_len = max_len or Sq
    x, _, caches = forward(params, cfg, tokens, positions3=positions3,
                           img_embeds=img_embeds, collect_cache=True,
                           remat=remat, tp=tp)
    if caches is not None and "k" in caches and max_len > Sq:
        pad = max_len - Sq
        caches["k"] = jnp.pad(caches["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        caches["v"] = jnp.pad(caches["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    if caches is not None and "shared_k" in caches and max_len > Sq:
        pad = max_len - Sq
        caches["shared_k"] = jnp.pad(
            caches["shared_k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        caches["shared_v"] = jnp.pad(
            caches["shared_v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return last_logits(params, cfg, x), caches


def decode_step(params, cfg: ArchConfig, token, caches, *, tp: int = 16,
                sparse_fn=None, sparse_params=None, sparse_stateful=False,
                positions3=None):
    """token [B] int32 + caches -> (logits [B, V], caches).

    ``sparse_fn(q, kcache, vcache, length, sparse_params_l) -> attn_out``
    lets the memory pipeline replace dense decode attention (DESIGN.md §2).
    ``sparse_params`` is a layer-stacked pytree scanned alongside the layers
    (per-layer indexer weights, e.g. the DSA lightning indexer). With
    ``sparse_stateful=True`` the sparse_fn returns (attn, new_params) —
    carrying an incremental index cache (prepare-once) — and decode_step
    returns (logits, caches, new_sparse_params).
    """
    B = token.shape[0]
    length = caches["length"]
    x = L.embed(params["embed"], token[:, None])
    positions = jnp.broadcast_to(length[None, None], (B, 1))
    if cfg.rope_style == "mrope" and positions3 is None:
        positions3 = jnp.broadcast_to(length[None, None, None], (3, B, 1))
    cos, sin = _rope_tables(cfg, positions, positions3)

    if cfg.family == "hybrid":
        x, caches = _hybrid_decode(params, cfg, x, cos, sin, caches, tp,
                                   sparse_fn, sparse_params)
    elif cfg.xlstm_pattern:
        # _xlstm_forward applies final_norm itself — return directly.
        x, _, new = _xlstm_forward(params, cfg, x, True, False,
                                   states=caches["states"])
        caches = dict(caches, states=new["states"], length=length + 1)
        return last_logits(params, cfg, x), caches
    else:
        stateful = sparse_stateful

        def layer_fn(x, lp_kv):
            lp, kc, vc, sp = lp_kv
            x, kc, vc, sp_new = _tf_layer_decode(lp, x, cos, sin, cfg, tp, kc,
                                                 vc, length, sparse_fn, sp)
            return x, ((kc, vc, sp_new) if stateful else (kc, vc))

        sp_stack = sparse_params
        if sp_stack is None:
            sp_stack = jnp.zeros((cfg.n_layers,), jnp.int32)  # dummy scan leaf
        x, ys = jax.lax.scan(
            layer_fn, x, (params["layers"], caches["k"], caches["v"], sp_stack))
        if stateful:
            k_new, v_new, sp_new = ys
        else:
            (k_new, v_new), sp_new = ys, sparse_params
        caches = dict(caches, k=k_new, v=v_new, length=length + 1)
        logits = _logits(params, cfg, x)
        return (logits, caches, sp_new) if stateful else (logits, caches)

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return last_logits(params, cfg, x), caches


# ---------------------------------------------------------------------------
# Paged continuous-batching decode (serving): per-slot lengths + page pool
# ---------------------------------------------------------------------------


def make_page_pool(cfg: ArchConfig, n_slots: int, max_len: int, *,
                   page_size: int, total_pages: int, tp: int = 16,
                   dtype=None) -> Dict:
    """Device-side paged KV pool for transformer families.

    Physical page 0 is reserved as the zero/trash page: every unallocated
    page-table entry points at it, dead-slot writes are routed (zeroed) to
    it, and it must stay zero so pooled decode equals per-request decode.
    """
    if cfg.is_mla:
        return mla.make_page_pool(cfg, n_slots, max_len, page_size=page_size,
                                  total_pages=total_pages)
    dt = dtype or L.dtype_of(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd
    assert max_len % page_size == 0, (max_len, page_size)
    return {
        "k_pages": jnp.zeros((cfg.n_layers, total_pages, page_size, kv, hd), dt),
        "v_pages": jnp.zeros((cfg.n_layers, total_pages, page_size, kv, hd), dt),
        "page_table": jnp.zeros((n_slots, max_len // page_size), jnp.int32),
        "lengths": jnp.zeros((n_slots,), jnp.int32),
    }


def decode_step_paged(params, cfg: ArchConfig, token, pool, live, *,
                      tp: int = 16, sparse_fn=None, sparse_params=None,
                      positions3=None):
    """One decode step over the paged pool with PER-SLOT lengths.

    token [B] int32; pool from ``make_page_pool`` (lengths [B] must be
    pre-masked to 0 for dead slots); live [B] bool. Each slot gets its own
    RoPE position, its own cache-write offset, and its own attention mask —
    no slot pays for the longest sequence's watermark, and the sparse-method
    fallback cond sees the true max over live slots instead of a shared
    scalar. Returns (logits [B, V], pool') with live lengths advanced by one.

    The layer loop carries the whole stacked pool: each layer writes its
    token into the carried ``[L, P, ps, KV, dh]`` pages and hands its
    attention the pool, the layer index and the page table
    (``PooledKV``) in place of a view, so no layer's pages are sliced out or
    restacked, and with the pool donated the step updates it in place. The
    paged kernel reads the selected pages from the pool; a reader that needs
    every cached token gathers the view itself (``kv_view``).

    A latent-attention config runs ``mla.decode_step_paged``, whose own
    indexer (``sparse_params``) replaces ``sparse_fn``.
    """
    if cfg.is_mla:
        return mla.decode_step_paged(params, cfg, token, pool, live,
                                     sparse_params=sparse_params)
    from repro.kernels.page_pool import PooledKV, pool_scatter, token_dest

    B = token.shape[0]
    lengths = pool["lengths"]
    table = pool["page_table"]
    live = live.astype(bool)
    with jax.named_scope("dense"):
        x = L.embed(params["embed"], token[:, None])
    positions = lengths[:, None]                           # [B, 1] per-slot
    if cfg.rope_style == "mrope" and positions3 is None:
        positions3 = jnp.broadcast_to(lengths[None, :, None], (3, B, 1))
    cos, sin = _rope_tables(cfg, positions, positions3)
    dest = token_dest(table, lengths, live, pool["k_pages"].shape[2])

    def layer_fn(carry, xs):
        x, kp, vp = carry
        lp, sp, layer = xs
        q, k, v = _qkv(lp, x, cos, sin, cfg, tp)
        with jax.named_scope("kv_write"):
            kp = pool_scatter(kp, layer, dest, k[:, 0])
            vp = pool_scatter(vp, layer, dest, v[:, 0])
        kc, vc = PooledKV(kp, layer, table), PooledKV(vp, layer, table)
        if sparse_fn is not None:
            res = sparse_fn(q, kc, vc, lengths + 1, sp, k_new=k)
            attn = res[0] if isinstance(res, tuple) else res
        else:
            with jax.named_scope("apply"):
                attn = A.attention_decode(q, kc, vc, lengths + 1, cfg, tp=tp)
        return (_out_mlp(lp, x, attn, cfg, tp), kp, vp), None

    sp_stack = sparse_params
    if sp_stack is None:
        sp_stack = jnp.zeros((cfg.n_layers,), jnp.int32)   # dummy scan leaf
    (x, k_new, v_new), _ = jax.lax.scan(
        layer_fn, (x, pool["k_pages"], pool["v_pages"]),
        (params["layers"], sp_stack, jnp.arange(cfg.n_layers)))
    pool = dict(pool, k_pages=k_new, v_pages=v_new,
                lengths=lengths + live.astype(jnp.int32))
    return _logits(params, cfg, x), pool


def decode_step_paged_presel(params, cfg: ArchConfig, token, pool, live,
                             pidx, mem, *, page_size: int, tp: int = 16,
                             page_attn=None):
    """Apply-phase decode over the paged pool with PRE-SELECTED pages.

    The hetero offload split (paper §5): prepare/relevancy/retrieve ran
    elsewhere (offload device, one step of lookahead) and handed back only
    page indices — this step is the compute-dense remainder that stays on
    the main device. ``pidx [L, B, n_sel]`` holds per-layer selected page
    ids in logical (per-slot) space, -1 = no selection.

    Semantics vs the inline sparse path:
      * the page currently being written (``lengths // page_size``) is
        always force-included so the newest tokens are never invisible to
        a stale selection (the paper's recency guarantee); a stale pick of
        the same page is deduplicated to avoid double-counted softmax mass,
      * indices outside the live region are dropped (stale-lookahead
        validity mask),
      * the paper's dynamic fallback stays a traced cond: outside
        [min_context, fallback_context] the step runs dense attention and
        ignores the selection entirely (single-device execution).

    ``page_attn`` overrides the selected-page attention implementation
    (same contract as ``ops.paged_decode_attention``: (q, kc, vc, pids,
    lengths, page_size=) -> (out, lse)). The main-mesh serving stack uses
    it to run ``distributed.topk.distributed_paged_sparse_decode`` when the
    main side is itself a mesh (LSE-merged sequence-parallel apply). With a
    ``page_attn`` installed, the DENSE fallback branch runs through the
    SAME seam — every view page selected is dense attention — so both
    sides of the traced cond are sequence-parallel and the step never
    collapses to a single device of the mesh.

    Returns (logits [B, V], pool', q_layers [L, B, Hp, hd], k_layers
    [L, B, KV, hd]) — the per-layer query/key of THIS step feed the next
    lookahead selection and the offload-side index update.
    """
    from repro.core import placement
    from repro.core.methods.dsa import strip_dead_heads, repad_dead_heads
    from repro.kernels import ops
    from repro.kernels.page_pool import pool_gather, pool_scatter, token_dest

    B = token.shape[0]
    ps = page_size
    lengths = pool["lengths"]
    table = pool["page_table"]
    live = live.astype(bool)
    with jax.named_scope("dense"):
        x = L.embed(params["embed"], token[:, None])
    positions = lengths[:, None]
    positions3 = None
    if cfg.rope_style == "mrope":
        positions3 = jnp.broadcast_to(lengths[None, :, None], (3, B, 1))
    cos, sin = _rope_tables(cfg, positions, positions3)

    lb = lengths + 1                       # context incl. this step's token
    cur_page = lengths // ps               # page receiving this step's write
    use_sparse = placement.traced_use_sparse(lb, mem)
    dest = token_dest(table, lengths, live, pool["k_pages"].shape[2])

    def layer_fn(carry, xs):
        x, kp, vp = carry
        lp, sel, layer = xs
        q, k, v = _qkv(lp, x, cos, sin, cfg, tp)
        with jax.named_scope("kv_write"):
            kp = pool_scatter(kp, layer, dest, k[:, 0])
            vp = pool_scatter(vp, layer, dest, v[:, 0])
        with jax.named_scope("retrieve"):
            kc = pool_gather(kp, layer, table)
            vc = pool_gather(vp, layer, table)

        def sparse(_):
            with jax.named_scope("retrieve"):
                s = jnp.where(sel == cur_page[:, None], -1, sel)  # dedup
                s = jnp.where(s * ps < lb[:, None], s, -1)  # validity mask
                s_full = jnp.concatenate([s, cur_page[:, None]], axis=1)
            with jax.named_scope("apply"):
                attn_fn = page_attn or ops.paged_decode_attention
                out, _ = attn_fn(
                    strip_dead_heads(q, cfg), kc, vc,
                    s_full.astype(jnp.int32), lb, page_size=ps)
                return repad_dead_heads(out, q, cfg)

        def dense(_):
            with jax.named_scope("apply"):
                if page_attn is None:
                    return A.attention_decode(q, kc, vc, lb, cfg, tp=tp)
                # distributed dense fallback: all view pages selected
                # through the same sequence-parallel seam (lb masks the
                # live region)
                n_pages = kc.shape[1] // ps
                allp = jnp.broadcast_to(
                    jnp.arange(n_pages, dtype=jnp.int32)[None], (B, n_pages))
                out, _ = page_attn(strip_dead_heads(q, cfg), kc, vc, allp,
                                   lb, page_size=ps)
                return repad_dead_heads(out, q, cfg)

        attn = jax.lax.cond(use_sparse, sparse, dense, None)
        return (_out_mlp(lp, x, attn, cfg, tp), kp, vp), (q[:, 0], k[:, 0])

    (x, k_new, v_new), (q_layers, k_layers) = jax.lax.scan(
        layer_fn, (x, pool["k_pages"], pool["v_pages"]),
        (params["layers"], pidx, jnp.arange(cfg.n_layers)))
    pool = dict(pool, k_pages=k_new, v_pages=v_new,
                lengths=lengths + live.astype(jnp.int32))
    return _logits(params, cfg, x), pool, q_layers, k_layers


def extend_paged(params, cfg: ArchConfig, tokens, pool, n_valid, *,
                 tp: int = 16, collect_kq: bool = False, x_embeds=None,
                 emb_rows=None, sparse_params=None):
    """Chunked prefill: append a span of C tokens per slot to the paged pool.

    tokens [B, C] int32 (rows padded past ``n_valid[b]``); pool from
    ``make_page_pool``; n_valid [B] int32 (0 = slot not prefilling this
    step). Queries attend causally to the existing prefix plus the chunk.
    Returns (logits [B, V] at each row's last valid token, pool').

    With ``collect_kq`` two more outputs follow: k_span [L, B, C, KV, hd]
    (the span's raw new keys, unmasked past ``n_valid``; consumers mask)
    and q_last [L, B, Hp, hd] (the query at each row's last valid chunk
    token) — consumed by the hetero offload executor to keep its
    device-resident memory index coherent with the pool.
    ``decode_step_paged`` is the C=1 specialization of this, kept separate
    so the decode path can thread the sparse-method fallback.

    ``x_embeds [B, C, d]`` + ``emb_rows [B]`` feed rows with PRE-EMBEDDED
    context instead of token ids: the MaC retrieval service splices
    retrieved memory embeddings into a slot's context through the exact
    same chunked path its documents would take.

    A latent-attention config runs ``mla.extend_paged``, which also writes
    the span's index keys with the indexer ``sparse_params``.
    """
    if cfg.is_mla:
        return mla.extend_paged(params, cfg, tokens, pool, n_valid,
                                sparse_params=sparse_params)
    from repro.kernels.page_pool import pool_gather, pool_scatter, span_dest

    B, C = tokens.shape
    lengths = pool["lengths"]
    table = pool["page_table"]
    with jax.named_scope("dense"):
        x = L.embed(params["embed"], tokens)
        if x_embeds is not None:
            x = jnp.where(emb_rows[:, None, None], x_embeds.astype(x.dtype),
                          x)
    positions = lengths[:, None] + jnp.arange(C)[None, :]  # [B, C]
    positions3 = None
    if cfg.rope_style == "mrope":
        positions3 = jnp.broadcast_to(positions[None], (3, B, C))
    cos, sin = _rope_tables(cfg, positions, positions3)
    dest = span_dest(table, lengths, n_valid, C, pool["k_pages"].shape[2])

    def layer_fn(carry, xs):
        x, kp, vp = carry
        lp, layer = xs
        q, k, v = _qkv(lp, x, cos, sin, cfg, tp)
        with jax.named_scope("kv_write"):
            kp = pool_scatter(kp, layer, dest, k)
            vp = pool_scatter(vp, layer, dest, v)
        with jax.named_scope("retrieve"):
            kc = pool_gather(kp, layer, table)
            vc = pool_gather(vp, layer, table)
        with jax.named_scope("apply"):
            attn = A.attention_decode_chunk(q, kc, vc, lengths, cfg, tp=tp)
        x = _out_mlp(lp, x, attn, cfg, tp)
        return (x, kp, vp), ((k, q) if collect_kq else None)

    (x, k_new, v_new), kq = jax.lax.scan(
        layer_fn, (x, pool["k_pages"], pool["v_pages"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    last = jnp.clip(n_valid - 1, 0, C - 1)
    with jax.named_scope("dense"):
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        xg = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B,1,d]
        logits = L.lm_head(params["lm_head"], xg, cfg)[:, 0]
    pool = dict(pool, k_pages=k_new, v_pages=v_new, lengths=lengths + n_valid)
    if not collect_kq:
        return logits, pool
    k_span, q_span = kq
    q_last = jnp.take_along_axis(
        q_span, last[None, :, None, None, None], axis=2)[:, :, 0]
    return logits, pool, k_span, q_last


def prefill_bucketed(params, cfg: ArchConfig, tokens, true_lens, *,
                     tp: int = 16, collect_q: bool = False):
    """Batched admission prefill over a length bucket.

    tokens [B, Sb] right-padded prompts; true_lens [B] real lengths.
    Returns (logits [B, V] at each row's last REAL token, k, v) where
    k/v [L, B, Sb, KV, hd] are zero-masked past ``true_lens`` so splicing
    them into the page pool leaves the dead region exactly zero (page-level
    relevancy scores must see the same zeros a per-request cache has).

    With ``collect_q`` a fourth output ``q_last [L, B, Hp, hd]`` carries each
    row's query activations at its last real token — the hetero offload
    executor seeds its lookahead relevancy query with it so the first decode
    step after admission selects pages with a real (one-step-stale) query.
    """
    B, Sb = tokens.shape
    x, _, caches = forward(params, cfg, tokens, collect_cache=True,
                           collect_q=collect_q, tp=tp)
    last = jnp.clip(true_lens - 1, 0, Sb - 1)
    xg = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = L.lm_head(params["lm_head"], xg, cfg)[:, 0]
    mask = (jnp.arange(Sb)[None, :] < true_lens[:, None])      # [B, Sb]
    m = mask[None, :, :, None, None]
    k = caches["k"] * m.astype(caches["k"].dtype)
    v = caches["v"] * m.astype(caches["v"].dtype)
    if not collect_q:
        return logits, k, v
    q_last = jnp.take_along_axis(
        caches["q"], last[None, :, None, None, None], axis=2)[:, :, 0]
    return logits, k, v, q_last


def _hybrid_decode(params, cfg, x, cos, sin, caches, tp, sparse_fn,
                   sparse_params=None):
    length = caches["length"]

    def super_fn(x, lp):
        body_lp, ssm_st, conv_st, kc, vc = lp

        def mamba_fn(x, mlp_st):
            mlp, sst, cst = mlp_st
            h = L.rms_norm(mlp["norm"], x, cfg.norm_eps)
            y, (sst, cst) = S.mamba_decode(mlp["mamba"], h, cfg, (sst, cst))
            return x + y, (sst, cst)

        x, (ssm_new, conv_new) = jax.lax.scan(
            mamba_fn, x, (body_lp, ssm_st, conv_st))
        x, kc, vc, _ = _tf_layer_decode(params["shared"], x, cos, sin, cfg,
                                        tp, kc, vc, length, sparse_fn,
                                        sparse_params)
        return x, (ssm_new, conv_new, kc, vc)

    x, (bs, bc, sk, sv) = jax.lax.scan(
        super_fn, x,
        (params["body"], caches["body_ssm"], caches["body_conv"],
         caches["shared_k"], caches["shared_v"]))

    def tail_fn(x, mlp_st):
        mlp, sst, cst = mlp_st
        h = L.rms_norm(mlp["norm"], x, cfg.norm_eps)
        y, (sst, cst) = S.mamba_decode(mlp["mamba"], h, cfg, (sst, cst))
        return x + y, (sst, cst)

    x, (ts, tc) = jax.lax.scan(
        tail_fn, x, (params["tail"], caches["tail_ssm"], caches["tail_conv"]))
    caches = dict(caches, body_ssm=bs, body_conv=bc, tail_ssm=ts, tail_conv=tc,
                  shared_k=sk, shared_v=sv, length=length + 1)
    return x, caches
