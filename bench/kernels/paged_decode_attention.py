"""Algorithmic work of one ``paged_decode_attention`` call (DSA apply for
one layer of one decode step): each slot's one query attends to the tokens
of its selected pages.

    n_b   = min(top_k, context_b)          tokens attended
    flops = sum_b 4 * Hq * dh * n_b        # q.k and p.v
    bytes = sum_b (2 * n_b * KV * dh * 2   # bf16 keys and values read
                   + Hq * dh * 2           # bf16 query
                   + Hq * (dh + 1) * 4     # f32 output and lse
                   + (top_k // page) * 4)  # int32 page ids

Each key and value is counted once, however many query heads share it.
"""
from __future__ import annotations


def cost(config, contexts):
    mem = config["memory"]
    Hq, KV = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config.get("head_dim") or config["hidden_size"] // Hq
    n_sel = max(mem["top_k"] // mem["page"], 1)
    flops = nbytes = 0.0
    for ctx in contexts:
        n = min(mem["top_k"], ctx)
        flops += 4 * Hq * dh * n
        nbytes += 2 * n * KV * dh * 2 + Hq * dh * 2 + Hq * (dh + 1) * 4 \
            + n_sel * 4
    return flops, nbytes
