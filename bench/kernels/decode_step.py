"""Model FLOPs of one decoded token on the DSA sparse branch, for the
whole step's share of the chip's peak (``decode_mfu``).

    per layer:
      weights   2 * (d*Hq*dh + 2*d*KV*dh + Hq*dh*d + 3*d*ff)
      indexer   2 * (Hq*dh*Hi*di + Hq*dh*Hi)       # index query, weights
              + 2 * KV*dh*di                       # the new key's index
              + pages * (2*Hi*di + 3*Hi)           # page scores
      attention 4 * Hq * dh * min(top_k, context)  # over the selection
    once:       2 * d * vocab                      # lm_head

Keys of earlier tokens are indexed once, when they are written; a program
that projects the whole cache again every step does work this count leaves
out (recomputed operations do not count).
"""
from __future__ import annotations

import math


def cost(config, context):
    c, mem = config, config["memory"]
    d, ff, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    Hq, KV = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // Hq
    Hi, di, page = mem["index_heads"], mem["index_dim"], mem["page"]
    pages = math.ceil(context / page)
    weights = 2 * (d * Hq * dh + 2 * d * KV * dh + Hq * dh * d + 3 * d * ff)
    indexer = (2 * (Hq * dh * Hi * di + Hq * dh * Hi) + 2 * KV * dh * di
               + pages * (2 * Hi * di + 3 * Hi))
    attention = 4 * Hq * dh * min(mem["top_k"], context)
    return c["num_hidden_layers"] * (weights + indexer + attention) + 2 * d * V
