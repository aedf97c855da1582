"""A dense GQA decoder (Qwen2 / Qwen3 layers) served with a DSA lightning
indexer: everything the harness knows about this architecture's shape.

A configuration file names this module with ``"arch": "dense_gqa"``; the
harness finds it by that name (bench/benchlib/spec.py ``arch``) and calls:

``program_config``  the program's ArchConfig from the file's published keys
                    and its ``program`` / ``memory`` blocks;
``draw``, ``shapes`` the seeded weights (params, indexer params) in the
                    layout the engine takes, and their shapes alone;
``decode_flops``    model FLOPs of one decoded token (``decode_mfu``);
``tiny``            the configuration scaled down for a CPU test.

Nothing here imports the program: ``program_config`` is handed its classes.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchlib.weights import VOCAB_PAD, gain, normal

# published config.json key -> field of the program's ArchConfig
ARCH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}

# the CPU scale-down: every width and length small, the mechanisms kept
TINY_MODEL = {"hidden_size": 256, "intermediate_size": 512,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 64, "num_hidden_layers": 2, "vocab_size": 1000}
TINY_MEMORY = {"index_heads": 4, "index_dim": 32, "top_k": 64, "page": 16,
               "min_context": 256}


def _head_dim(c: Dict[str, Any]) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program_config(config: Dict[str, Any], ArchConfig, MemoryConfig):
    """The program's ArchConfig for a configuration file: every size from
    the file, the mechanisms the file's ``program`` block names."""
    prog = config["program"]
    mem = config["memory"]
    kw = {field: config[key] for key, field in ARCH_KEYS.items()}
    kw["head_dim"] = _head_dim(config)
    kw["rope_theta"] = float(kw["rope_theta"])
    kw["norm_eps"] = float(kw["norm_eps"])
    return ArchConfig(
        name=config["name"], family=prog["family"],
        qk_norm=bool(prog["qk_norm"]), qkv_bias=bool(prog["qkv_bias"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        memory=MemoryConfig(method=prog["method"],
                            index_heads=mem["index_heads"],
                            index_dim=mem["index_dim"], top_k=mem["top_k"],
                            min_context=mem["min_context"]),
        **kw)


def sizes(config: Dict[str, Any]) -> Tuple:
    """The hashable size tuple the generator is specialised on."""
    c, prog, mem = config, config["program"], config["memory"]
    hd = _head_dim(c)
    heads, tp = c["num_attention_heads"], int(prog["tp"])
    padded = heads if heads % tp == 0 else -(-heads // tp) * tp
    vocab_p = -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    return (c["num_hidden_layers"], c["hidden_size"], heads, padded,
            c["num_key_value_heads"], hd, c["intermediate_size"], vocab_p,
            bool(prog["qk_norm"]), bool(prog["qkv_bias"]),
            mem["index_heads"], mem["index_dim"],
            c.get("torch_dtype", "bfloat16"))


@functools.partial(jax.jit, static_argnums=1)
def _generate(key, sz):
    """Layer-stacked ``layers``, ``embed`` / ``lm_head`` / ``final_norm``,
    and the indexer stack apart. Scales follow the usual init of such models
    (std 1/sqrt(fan in), residual outputs scaled down by depth); gains and
    biases are drawn too, so the reference has to apply them; padded query
    heads are dead."""
    (L, d, H, Hp, KV, hd, ff, Vp, qk_norm, qkv_bias, Hi, di, dtype) = sz
    bf = jnp.dtype(dtype)
    k_emb, k_head, k_norm, k_layers, k_idx = jax.random.split(key, 5)
    live = (jnp.arange(Hp) < H).astype(jnp.float32)   # padded heads are dead

    def layer(k):
        ks = jax.random.split(k, 12)
        wq = normal(ks[0], (d, Hp, hd), 1 / math.sqrt(d), jnp.float32)
        wo = normal(ks[3], (Hp, hd, d), 1 / math.sqrt(2 * L * Hp * hd),
                    jnp.float32)
        attn = {
            "wq": (wq * live[None, :, None]).reshape(d, Hp * hd).astype(bf),
            "wk": normal(ks[1], (d, KV * hd), 1 / math.sqrt(d), bf),
            "wv": normal(ks[2], (d, KV * hd), 1 / math.sqrt(d), bf),
            "wo": (wo * live[:, None, None]).reshape(Hp * hd, d).astype(bf),
        }
        if qkv_bias:
            attn["bq"] = (normal(ks[4], (Hp, hd), 0.02, jnp.float32)
                          * live[:, None]).reshape(Hp * hd).astype(bf)
            attn["bk"] = normal(ks[5], (KV * hd,), 0.02, bf)
            attn["bv"] = normal(ks[6], (KV * hd,), 0.02, bf)
        if qk_norm:
            attn["q_norm"] = gain(ks[7], (hd,))
            attn["k_norm"] = gain(ks[8], (hd,))
        return {
            "attn": attn,
            "attn_norm": {"w": gain(ks[9], (d,))},
            "mlp_norm": {"w": gain(ks[10], (d,))},
            "mlp": {
                "w1": normal(jax.random.fold_in(ks[11], 1), (d, ff),
                             1 / math.sqrt(d), bf),
                "w3": normal(jax.random.fold_in(ks[11], 3), (d, ff),
                             1 / math.sqrt(d), bf),
                "w2": normal(jax.random.fold_in(ks[11], 2), (ff, d),
                             1 / math.sqrt(2 * L * ff), bf),
            },
        }

    def indexer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wq_idx": normal(k1, (H * hd, Hi * di), 1 / math.sqrt(H * hd), bf),
            "wk_idx": normal(k2, (KV * hd, di), 1 / math.sqrt(KV * hd), bf),
            "w_wgt": normal(k3, (H * hd, Hi), 0.02, jnp.float32),
        }

    params = {
        "embed": {"w": normal(k_emb, (Vp, d), 0.02, bf)},
        "lm_head": {"w": normal(k_head, (d, Vp), 1 / math.sqrt(d), bf)},
        "final_norm": {"w": gain(k_norm, (d,))},
        "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
    }
    return params, jax.lax.map(indexer, jax.random.split(k_idx, L))


def draw(key, config: Dict[str, Any]):
    """(params, indexer params) from ``key``, on the default device, in one
    jitted call."""
    return _generate(key, sizes(config))


def shapes(config: Dict[str, Any]):
    """``draw``'s tree as shapes and dtypes, with nothing drawn."""
    return jax.eval_shape(_generate, jax.random.PRNGKey(0), sizes(config))


def decode_flops(config: Dict[str, Any], context: int) -> int:
    """Model FLOPs of one decoded token on the DSA sparse branch at
    ``context`` live tokens:

        per layer:
          weights   2 * (d*Hq*dh + 2*d*KV*dh + Hq*dh*d + 3*d*ff)
          indexer   2 * (Hq*dh*Hi*di + Hq*dh*Hi)       # index query, weights
                  + 2 * KV*dh*di                       # the new key's index
                  + pages * (2*Hi*di + 3*Hi)           # page scores
          attention 4 * Hq * dh * min(top_k, context)  # over the selection
        once:       2 * d * vocab                      # lm_head

    Keys of earlier tokens are indexed once, when they are written; a
    program that projects the whole cache again every step does work this
    count leaves out (recomputed operations do not count)."""
    c, mem = config, config["memory"]
    d, ff, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    Hq, KV = c["num_attention_heads"], c["num_key_value_heads"]
    dh = _head_dim(c)
    Hi, di, page = mem["index_heads"], mem["index_dim"], mem["page"]
    pages = math.ceil(context / page)
    weights = 2 * (d * Hq * dh + 2 * d * KV * dh + Hq * dh * d + 3 * d * ff)
    indexer = (2 * (Hq * dh * Hi * di + Hq * dh * Hi) + 2 * KV * dh * di
               + pages * (2 * Hi * di + 3 * Hi))
    attention = 4 * Hq * dh * min(mem["top_k"], context)
    return c["num_hidden_layers"] * (weights + indexer + attention) + 2 * d * V


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with every width and length at a size a CPU test
    can hold (a new dict; the published keys it leaves alone stay)."""
    out = dict(config, **TINY_MODEL)
    out["memory"] = dict(config["memory"], **TINY_MEMORY)
    return out
