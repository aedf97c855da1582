"""Algorithmic work of one ``mla_sparse_decode_attention`` call (the apply
stage of a latent-attention layer for one decode step): each slot's H
query heads, absorbed into the latent (dl + dr wide), attend to the
latent rows the indexer chose; values are the rows' first dl columns.

    n_b   = min(top_k, context_b)             rows attended
    flops = sum_b (2 * H * (dl + dr) * n_b    # q . row
                   + 2 * H * dl * n_b)        # p . latent
    bytes = sum_b (n_b * (dl + dr) * 2        # bf16 rows, each read once
                   + H * (dl + dr) * 4        # f32 absorbed query
                   + H * dl * 4 + 4)          # f32 output, the row count

Each row is counted once, however many heads share it.
"""
from __future__ import annotations


def cost(config, contexts):
    top_k = config["memory"]["top_k"]
    H = config["num_attention_heads"]
    dl, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for ctx in contexts:
        n = min(top_k, ctx)
        flops += 2 * H * (dl + dr) * n + 2 * H * dl * n
        nbytes += n * (dl + dr) * 2 + H * (dl + dr) * 4 + H * dl * 4 + 4
    return flops, nbytes
