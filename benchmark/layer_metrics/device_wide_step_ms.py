"""Mean device time a served batch spends in the wide program: the
`jit_step_wide(...)` events on the trace's `XLA Modules` line, their
summed duration over the BATCHES of the traced span (the `jit_step`
programs: a batch launches the narrow program once for its short rows
and the wide one as often as its long rows need), not over the wide
programs, so it adds to `device_step_ms`. A trace without the wide
program reads nothing."""


def read(ctx, _):
    if ctx.trace is None:
        return None
    modules = ctx.trace["modules"]
    _, seconds = modules.get("jit_step_wide", (0, 0.0))
    batches, _ = modules.get("jit_step", (0, 0.0))
    return seconds * 1e3 / batches if seconds and batches else None
