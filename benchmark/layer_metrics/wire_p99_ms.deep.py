"""99th percentile of the native front's own wire histogram (C++,
frame decode -> response write), over the window. Bucketed: the
landing bucket's geometric midpoint, within 4.5 % by construction."""


def begin(ctx):
    return ctx.native.latency_raw()


def read(ctx, base):
    snap = ctx.native.latency_snapshot(since=base)
    return snap["p99"] if snap["n"] else None
