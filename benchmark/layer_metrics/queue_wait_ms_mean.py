"""Mean wait of a request in the C++ queue for a free pump: the native
front's queue_wait() counter (httpd.cpp take_impl: take time less the
row's enqueue stamp), delta of the sum over delta of the rows."""


def _queue_wait(ctx):
    read = getattr(ctx.native, "queue_wait", None)
    return read() if read else None


def begin(ctx):
    return _queue_wait(ctx)


def read(ctx, base):
    now = _queue_wait(ctx)
    if base is None or now is None or now["rows"] == base["rows"]:
        return None
    return (now["sum_ns"] - base["sum_ns"]) / (
        now["rows"] - base["rows"]) / 1e6
