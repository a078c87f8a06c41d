"""Mean device time of one served step program: the `jit_step(...)`
events on the trace's `XLA Modules` line, summed / counted. A mean over
every step shape the traced span used."""


def read(ctx, _):
    if ctx.trace is None:
        return None
    count, seconds = ctx.trace["modules"].get("jit_step", (0, 0.0))
    return seconds * 1e3 / count if count else None
