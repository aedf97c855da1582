"""The whole decode step's share of the chip's bf16 peak: model FLOPs per
token at the sessions' mean context (the configuration's architecture
module, bench/archs/<arch>.py ``decode_flops``) times the tokens per second
of the traced run's window, over the peak."""

from benchlib import spec


def read(ctx):
    if not ctx.contexts or not ctx.stats["tokens"]:
        return None
    cost = spec.arch(ctx.cell.config).decode_flops
    flops = sum(cost(ctx.cell.config, c) for c in ctx.contexts) / len(
        ctx.contexts)
    return 100.0 * flops * ctx.stats["tok_s"] / ctx.peak["bf16_flops_per_s"]
