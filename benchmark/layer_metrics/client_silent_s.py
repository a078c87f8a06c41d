"""Seconds the server had answered everything and the client sent
nothing, in silences of 0.2 s or more: the native front's
gaps()["silent"] (httpd.cpp enqueue_request: at a request that arrives
with nothing in flight, the time since the last response was written),
delta of `sum_ns` over the whole window. A stall this reads is the
HARNESS's (the load generator or the loopback), not the server's, and
the progress line it prints then says so. 0.0 in a window without one;
nothing on a front without the counter."""
import json


def _silent(ctx):
    gaps = getattr(ctx.native, "gaps", None)
    return gaps()["silent"] if gaps else None


def begin(ctx):
    return _silent(ctx)


def read(ctx, base):
    if base is None:
        return None
    now = _silent(ctx)
    seconds = (now["sum_ns"] - base["sum_ns"]) / 1e9
    if seconds:
        print(json.dumps({
            "phase": "client_silent", "seconds": seconds,
            "silences": now["count"] - base["count"],
            "whose": "the harness's: the server had nothing in flight "
                     "and the client sent nothing"}), flush=True)
    return seconds
