"""Seconds rows waited in the C++ queue while no pump came, in waits
of 0.2 s or more: the native front's gaps()["starved"] (httpd.cpp
take_impl: at a handover, the time since the first row arrived or since
the previous handover left rows queued), delta of `sum_ns` over the
whole window. No python runs where it is counted. 0.0 in a window
without such a wait; nothing on a front without the counter."""


def _starved_ns(ctx):
    gaps = getattr(ctx.native, "gaps", None)
    return gaps()["starved"]["sum_ns"] if gaps else None


def begin(ctx):
    return _starved_ns(ctx)


def read(ctx, base):
    if base is None:
        return None
    return (_starved_ns(ctx) - base) / 1e9
