"""Share of the window's wall spent in young (generation 0 and 1)
garbage collections of the serving process, which hold the interpreter
lock as full ones do, some 300 times a second in a deep cell: the
program's gc.callbacks hook (monitor.gc_pause_snapshot()["young"] <-
`mixer_gc_young_seconds`) over this reader's own begin -> read clock, as
gc_pause_share does for full collections. A program whose hook does not
count them reads nothing."""
import time

from istio_tpu.runtime import monitor


def begin(ctx):
    snapshot = getattr(monitor, "gc_pause_snapshot", None)
    before = snapshot() if snapshot else {}
    return (time.perf_counter(), before) if "young" in before else None


def read(ctx, base):
    if base is None:
        return None
    started, before = base
    young = monitor.gc_pause_snapshot(since=before)["young"]["sum_s"]
    return 100.0 * young / (time.perf_counter() - started)
