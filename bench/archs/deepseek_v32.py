"""DeepSeek-V3.2: multi-head latent attention (MLA) with YaRN rope, the
published lightning indexer, leading dense layers, then MoE layers of which
one chip of an expert-parallel deployment holds a share: everything the
harness knows about this architecture's shape.

A configuration file names this module with ``"arch": "deepseek_v32"``;
the harness calls (bench/benchlib/spec.py ``arch``):

``program_config``  the program's ArchConfig from the file's published keys
                    (the router's width from ``published``) and its
                    ``program`` / ``memory`` blocks;
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
    "num_experts_per_tok": "experts_per_token",
    "n_group": "n_expert_groups",
    "topk_group": "topk_expert_groups",
    "routed_scaling_factor": "routed_scaling",
    "moe_intermediate_size": "moe_d_ff",
    "n_shared_experts": "n_shared_experts",
    "n_routed_experts": "n_held_experts",
    "first_k_dense_replace": "first_k_dense",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}
# rope_scaling key -> field
YARN_KEYS = {
    "factor": "rope_factor",
    "original_max_position_embeddings": "rope_original_max_len",
    "beta_fast": "rope_beta_fast",
    "beta_slow": "rope_beta_slow",
    "mscale_all_dim": "rope_mscale_all_dim",
}

# the CPU scale-down: every width and length small, the mechanisms kept
TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 4, "q_lora_rank": 48,
              "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_hidden_layers": 3, "first_k_dense_replace": 1,
              "n_routed_experts": 4, "num_experts_per_tok": 4, "n_group": 4,
              "topk_group": 2, "vocab_size": 1000}
TINY_ROUTER = 16          # the router's width at the tiny size
TINY_YARN = {"original_max_position_embeddings": 64}
TINY_MEMORY = {"index_heads": 4, "index_dim": 16, "top_k": 64, "page": 16,
               "min_context": 64}


def _router_width(c: Dict[str, Any]) -> int:
    return c["published"]["n_routed_experts"]


def program_config(config: Dict[str, Any], ArchConfig, MemoryConfig):
    """The program's ArchConfig for a configuration file: every size from
    the file, the router over the published expert count, this chip's
    experts from ``program.first_held_expert`` on."""
    prog, mem = config["program"], config["memory"]
    kw = {field: config[key] for key, field in ARCH_KEYS.items()}
    kw.update({field: config["rope_scaling"][key]
               for key, field in YARN_KEYS.items()})
    for k in ("rope_theta", "norm_eps", "routed_scaling", "rope_factor",
              "rope_beta_fast", "rope_beta_slow", "rope_mscale_all_dim"):
        kw[k] = float(kw[k])
    yarn = config["rope_scaling"]
    if not (config["scoring_func"] == "sigmoid"
            and config["topk_method"] == "noaux_tc"
            and config["norm_topk_prob"]
            and yarn["mscale"] == yarn["mscale_all_dim"]):
        raise ValueError(f"{config['name']}: the program routes as noaux_tc "
                         "(sigmoid, normalised) and keeps the rope unscaled")
    return ArchConfig(
        name=config["name"], family=prog["family"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        n_experts=_router_width(config),
        first_held_expert=int(prog["first_held_expert"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        memory=MemoryConfig(method=prog["method"],
                            index_heads=mem["index_heads"],
                            index_dim=mem["index_dim"], top_k=mem["top_k"],
                            min_context=mem["min_context"]),
        **kw)


def sizes(config: Dict[str, Any]) -> Tuple:
    """The hashable size tuple the generator is specialised on."""
    c, mem = config, config["memory"]
    vocab_p = -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    return (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["n_routed_experts"],
            c["n_shared_experts"], _router_width(c), vocab_p,
            mem["index_heads"], mem["index_dim"],
            c.get("torch_dtype", "bfloat16"))


def _rows(key, shape, std, dtype, block: int):
    """``normal`` drawn ``block`` rows at a time (lax.map), so no float32
    copy of the whole matrix is ever held."""
    n = shape[0] // block
    out = jax.lax.map(lambda k: normal(k, (block,) + shape[1:], std, dtype),
                      jax.random.split(key, n))
    return out.reshape(shape)


def _row_block(n: int, want: int = 1024) -> int:
    return max(b for b in range(1, want + 1) if n % b == 0)


@functools.partial(jax.jit, static_argnums=1)
def _generate(key, sz):
    """``dense_layers`` / ``moe_layers`` stacks, ``embed`` / ``lm_head`` /
    ``final_norm``, and the indexer stack [L, ...] apart. Scales follow the
    usual init (std 1/sqrt(fan in), residual outputs scaled down by depth);
    norm gains, the router's correction bias and the index key's LayerNorm
    bias are drawn too, so the reference has to apply them."""
    (L, K, d, H, ql, dl, dn, dr, dv, ff, fe, Eh, ns, E, Vp, Hi, di,
     dtype) = sz
    bf = jnp.dtype(dtype)
    k_emb, k_head, k_norm, k_dense, k_moe, k_idx = jax.random.split(key, 6)

    def attn(k):
        ks = jax.random.split(k, 7)
        return {
            "wq_a": normal(ks[0], (d, ql), 1 / math.sqrt(d), bf),
            "q_norm": gain(ks[1], (ql,)),
            "wq_b": normal(ks[2], (ql, H * (dn + dr)), 1 / math.sqrt(ql), bf),
            "wkv_a": normal(ks[3], (d, dl + dr), 1 / math.sqrt(d), bf),
            "kv_norm": gain(ks[4], (dl,)),
            "wkv_b": normal(ks[5], (dl, H * (dn + dv)), 1 / math.sqrt(dl),
                            bf),
            "wo": normal(ks[6], (H * dv, d), 1 / math.sqrt(2 * L * H * dv),
                         bf),
        }

    def mlp(k, width):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"w1": normal(k1, (d, width), 1 / math.sqrt(d), bf),
                "w3": normal(k2, (d, width), 1 / math.sqrt(d), bf),
                "w2": normal(k3, (width, d), 1 / math.sqrt(2 * L * width),
                             bf)}

    def layer(k, moe):
        ks = jax.random.split(k, 4)
        p = {"attn": attn(ks[0]), "attn_norm": {"w": gain(ks[1], (d,))},
             "mlp_norm": {"w": gain(ks[2], (d,))}}
        if not moe:
            p["mlp"] = mlp(ks[3], ff)
            return p
        kg, kb, ke, kshared = jax.random.split(ks[3], 4)
        experts = jax.lax.map(lambda r: mlp(r, fe), jax.random.split(ke, Eh))
        p["moe"] = {"gate": normal(kg, (d, E), 1 / math.sqrt(d), jnp.float32),
                    "bias": normal(kb, (E,), 0.05, jnp.float32),
                    **experts, "shared": mlp(kshared, fe * ns)}
        return p

    def indexer(k):
        ks = jax.random.split(k, 5)
        return {
            "wq_b": normal(ks[0], (ql, Hi * di), 1 / math.sqrt(ql), bf),
            "wk": normal(ks[1], (d, di), 1 / math.sqrt(d), bf),
            "k_norm": {"w": gain(ks[2], (di,)),
                       "b": normal(ks[3], (di,), 0.05, jnp.float32)},
            "w_proj": normal(ks[4], (d, Hi), 1 / math.sqrt(d), bf),
        }

    params = {
        "embed": {"w": _rows(k_emb, (Vp, d), 0.02, bf, _row_block(Vp))},
        "lm_head": {"w": _rows(k_head, (d, Vp), 1 / math.sqrt(d), bf,
                               _row_block(d, 128))},
        "final_norm": {"w": gain(k_norm, (d,))},
        "dense_layers": jax.lax.map(lambda k: layer(k, False),
                                    jax.random.split(k_dense, K)),
        "moe_layers": jax.lax.map(lambda k: layer(k, True),
                                  jax.random.split(k_moe, L - K)),
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
    """Model FLOPs of one decoded token at ``context`` live tokens:

        every layer:
          MLA       2 * (d*ql + ql*H*(dn+dr) + d*(dl+dr) + H*dv*d)
                  + 2 * H*dn*dl + 2 * H*dl*dv        # W_uk, W_uv absorbed
          attention 2 * H*(dl+dr)*n + 2 * H*dl*n,    n = min(top_k, context)
          indexer   2 * (ql*Hi*di + d*di + d*Hi)     # query, key, weights
                  + context * (2*Hi*di + 3*Hi)       # every cached key
        dense layers: 2 * 3*d*ff
        MoE layers:   2 * d*E                        # the router, E = 256
                    + 2 * 3*d*fe * (ns + k*Eh/E)     # shared, routed here
        once:         2 * d * vocab                  # lm_head

    The routed work is what the tokens routed to this chip's Eh experts
    cost, k*Eh/E experts a token (0.25 at 8 of 256); held experts multiplied
    by a zero weight are not counted."""
    c, mem = config, config["memory"]
    d, H, ql, dl = (c["hidden_size"], c["num_attention_heads"],
                    c["q_lora_rank"], c["kv_lora_rank"])
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    Hi, di = mem["index_heads"], mem["index_dim"]
    L, K = c["num_hidden_layers"], c["first_k_dense_replace"]
    E, Eh, k = _router_width(c), c["n_routed_experts"], c["num_experts_per_tok"]
    fe, ns = c["moe_intermediate_size"], c["n_shared_experts"]
    n = min(mem["top_k"], context)
    mla = (2 * (d * ql + ql * H * (dn + dr) + d * (dl + dr) + H * dv * d)
           + 2 * H * dn * dl + 2 * H * dl * dv)
    attention = 2 * H * (dl + dr) * n + 2 * H * dl * n
    indexer = (2 * (ql * Hi * di + d * di + d * Hi)
               + context * (2 * Hi * di + 3 * Hi))
    dense = 2 * 3 * d * c["intermediate_size"]
    moe = 2 * d * E + 2 * 3 * d * fe * ns + 2 * 3 * d * fe * k * Eh / E
    return (L * (mla + attention + indexer) + K * dense + (L - K) * moe
            + 2 * d * c["vocab_size"])


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with every width and length at a size a CPU test
    can hold (a new dict; the published keys it leaves alone stay)."""
    out = dict(config, **TINY_MODEL)
    out["rope_scaling"] = dict(config["rope_scaling"], **TINY_YARN)
    out["published"] = dict(config["published"], n_routed_experts=TINY_ROUTER)
    out["memory"] = dict(config["memory"], **TINY_MEMORY)
    return out
