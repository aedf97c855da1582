"""Weights of a dense GQA transformer with a DSA lightning indexer, drawn
from the seed on the device in one jitted call, in the type they are served
in and in the layout the serving engine takes (layer-stacked ``layers``,
``embed``/``lm_head``/``final_norm``; the indexer stack apart).

The benchmark makes these; the program under test and the plain reference
both read them. Scales follow the usual init of such models (std 1/sqrt(fan
in), residual outputs scaled down by depth), so activations keep the size
they have in the program's own init. Gains and biases are drawn too, so the
reference has to apply them.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 256  # the engine pads the vocabulary to a multiple of this


def seed_key(seed: int, salt: int = 0):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(salt)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def sizes(config: Dict[str, Any]) -> Tuple:
    """The hashable size tuple the generator is specialised on."""
    c, prog, mem = config, config["program"], config["memory"]
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    heads, tp = c["num_attention_heads"], int(prog["tp"])
    padded = heads if heads % tp == 0 else -(-heads // tp) * tp
    vocab_p = -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    return (c["num_hidden_layers"], c["hidden_size"], heads, padded,
            c["num_key_value_heads"], hd, c["intermediate_size"], vocab_p,
            bool(prog["qk_norm"]), bool(prog["qkv_bias"]),
            mem["index_heads"], mem["index_dim"],
            c.get("torch_dtype", "bfloat16"))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _gain(key, shape):
    return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=1)
def _generate(key, sz):
    (L, d, H, Hp, KV, hd, ff, Vp, qk_norm, qkv_bias, Hi, di, dtype) = sz
    bf = jnp.dtype(dtype)
    k_emb, k_head, k_norm, k_layers, k_idx = jax.random.split(key, 5)
    live = (jnp.arange(Hp) < H).astype(jnp.float32)   # padded heads are dead

    def layer(k):
        ks = jax.random.split(k, 12)
        wq = _normal(ks[0], (d, Hp, hd), 1 / math.sqrt(d), jnp.float32)
        wo = _normal(ks[3], (Hp, hd, d), 1 / math.sqrt(2 * L * Hp * hd),
                     jnp.float32)
        attn = {
            "wq": (wq * live[None, :, None]).reshape(d, Hp * hd).astype(bf),
            "wk": _normal(ks[1], (d, KV * hd), 1 / math.sqrt(d), bf),
            "wv": _normal(ks[2], (d, KV * hd), 1 / math.sqrt(d), bf),
            "wo": (wo * live[:, None, None]).reshape(Hp * hd, d).astype(bf),
        }
        if qkv_bias:
            attn["bq"] = (_normal(ks[4], (Hp, hd), 0.02, jnp.float32)
                          * live[:, None]).reshape(Hp * hd).astype(bf)
            attn["bk"] = _normal(ks[5], (KV * hd,), 0.02, bf)
            attn["bv"] = _normal(ks[6], (KV * hd,), 0.02, bf)
        if qk_norm:
            attn["q_norm"] = _gain(ks[7], (hd,))
            attn["k_norm"] = _gain(ks[8], (hd,))
        return {
            "attn": attn,
            "attn_norm": {"w": _gain(ks[9], (d,))},
            "mlp_norm": {"w": _gain(ks[10], (d,))},
            "mlp": {
                "w1": _normal(jax.random.fold_in(ks[11], 1), (d, ff),
                              1 / math.sqrt(d), bf),
                "w3": _normal(jax.random.fold_in(ks[11], 3), (d, ff),
                              1 / math.sqrt(d), bf),
                "w2": _normal(jax.random.fold_in(ks[11], 2), (ff, d),
                              1 / math.sqrt(2 * L * ff), bf),
            },
        }

    def indexer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wq_idx": _normal(k1, (H * hd, Hi * di), 1 / math.sqrt(H * hd), bf),
            "wk_idx": _normal(k2, (KV * hd, di), 1 / math.sqrt(KV * hd), bf),
            "w_wgt": _normal(k3, (H * hd, Hi), 0.02, jnp.float32),
        }

    params = {
        "embed": {"w": _normal(k_emb, (Vp, d), 0.02, bf)},
        "lm_head": {"w": _normal(k_head, (d, Vp), 1 / math.sqrt(d), bf)},
        "final_norm": {"w": _gain(k_norm, (d,))},
        "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
    }
    return params, jax.lax.map(indexer, jax.random.split(k_idx, L))


def generate(config: Dict[str, Any], seed: int):
    """(params, indexer params) for a configuration file, on the default
    device, from ``seed``."""
    return _generate(seed_key(seed, salt=1), sizes(config))
