"""Device time per decode step in the traced window: the seconds in which
some operation ran on the device, leaving out the prefill programs (told
apart in bench/benchlib/trace.py), over the decode steps the engine counted
in the window. The steps come from the engine and not from the trace, so
the reading holds whether a dispatch carries one step or several, and
whatever the decode program is called or how its layers are laid out."""


def read(ctx):
    if ctx.trace is None:
        return None
    if not ctx.decode_steps or not ctx.trace.ops:
        raise RuntimeError(
            f"decode_step_ms: {ctx.decode_steps} decode steps and "
            f"{len(ctx.trace.ops)} device operations in the traced window "
            f"of {ctx.cell.name}, which decodes in every window")
    return 1e3 * ctx.trace.busy_s(exclude=("prefill",)) / ctx.decode_steps
