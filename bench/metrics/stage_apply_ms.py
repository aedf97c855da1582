"""Device ms per decode step of the memory pipeline's ``apply`` stage
(``paged_decode_attention``, the dense fallback): the ops under the
program's ``apply`` scope, outside prefill programs, over the engine's
decode steps in the traced window (bench/benchlib/scopes.py
``stage_ms``)."""

from benchlib import scopes


def read(ctx):
    return scopes.stage_ms(ctx.trace, ctx.decode_steps, "apply",
                           ctx.cell.name)
