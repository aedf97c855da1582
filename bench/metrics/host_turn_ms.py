"""Device idle ms per decode step whose innermost host span is one of the
engine's (``engine.poll`` and the phases inside it: admission, prefill,
decode launch, sync and emission, dispatch) in the traced window
(bench/benchlib/scopes.py ``host_turn_ms``)."""

from benchlib import scopes


def read(ctx):
    return scopes.host_turn_ms(ctx.trace, ctx.decode_steps, ctx.cell.name)
