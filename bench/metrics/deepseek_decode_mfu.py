"""The whole decode step's share of the chip's bf16 peak in a DeepSeek-V3.2
cell: ``decode_mfu``'s reading (model FLOPs per token at the sessions' mean
context, by the architecture module's ``decode_flops``, times the traced
window's tokens per second, over the peak) under a name of its own, for the
cells that ``decode_mfu`` does not list."""

from benchlib import readers


def read(ctx):
    return readers.module("metrics", "decode_mfu").read(ctx)
