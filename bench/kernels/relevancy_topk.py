"""Algorithmic work of one ``relevancy_topk`` call (DSA relevancy + retrieve
for one layer of one decode step): every page summary of each slot's live
context is scored by the 64-head index query, and the top pages are kept.

    pages_b = ceil(context_b / page)
    flops   = sum_b pages_b * (2 * Hi * di    # q_idx . kp over the heads
                               + 3 * Hi)      # relu, weight, sum
    bytes   = sum_b (pages_b * di * 2         # bf16 page summaries
                     + Hi * di * 2 + Hi * 4   # bf16 query, f32 weights
                     + n_sel * 8)             # f32 scores + int32 ids out

Only live pages count: scoring padding or a view wider than the context is
work the algorithm does not need, whatever implements it.
"""
from __future__ import annotations

import math


def cost(config, contexts):
    mem = config["memory"]
    Hi, di, page = mem["index_heads"], mem["index_dim"], mem["page"]
    n_sel = max(mem["top_k"] // page, 1)
    flops = nbytes = 0.0
    for ctx in contexts:
        pages = math.ceil(ctx / page)
        flops += pages * (2 * Hi * di + 3 * Hi)
        nbytes += pages * di * 2 + Hi * di * 2 + Hi * 4 + n_sel * 8
    return flops, nbytes
