"""Device ms per decode step of the memory pipeline's ``relevancy`` stage
(the index query's projection, ``relevancy_topk``): the ops under the
program's ``relevancy`` scope, outside prefill programs, over the engine's
decode steps in the traced window (bench/benchlib/scopes.py
``stage_ms``)."""

from benchlib import scopes


def read(ctx):
    return scopes.stage_ms(ctx.trace, ctx.decode_steps, "relevancy",
                           ctx.cell.name)
