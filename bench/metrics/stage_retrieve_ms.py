"""Device ms per decode step of the memory pipeline's ``retrieve`` stage
(``pool_gather`` of the view, the live-page mask): the ops under the
program's ``retrieve`` scope, outside prefill programs, over the engine's
decode steps in the traced window (bench/benchlib/scopes.py
``stage_ms``)."""

from benchlib import scopes


def read(ctx):
    return scopes.stage_ms(ctx.trace, ctx.decode_steps, "retrieve",
                           ctx.cell.name)
