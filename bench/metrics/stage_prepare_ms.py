"""Device ms per decode step of the memory pipeline's ``prepare`` stage
(the index projection of the cached keys, the page pooling): the ops under
the program's ``prepare`` scope, outside prefill programs, over the
engine's decode steps in the traced window (bench/benchlib/scopes.py
``stage_ms``)."""

from benchlib import scopes


def read(ctx):
    return scopes.stage_ms(ctx.trace, ctx.decode_steps, "prepare",
                           ctx.cell.name)
