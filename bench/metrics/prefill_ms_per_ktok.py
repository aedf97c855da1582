"""Device time of the prefill programs (bucketed admission prefill and
chunked prefill, told apart in bench/benchlib/trace.py by the [B, T > 1,
hidden] residual stream of their layer loop) in the traced window,
per thousand prompt tokens of the requests whose first token landed in that
window."""


def read(ctx):
    if ctx.trace is None or not ctx.prompt_tokens_traced:
        return None
    busy, runs = ctx.trace.program("prefill")
    if not runs:
        return None
    return 1e3 * busy / (ctx.prompt_tokens_traced / 1e3)
