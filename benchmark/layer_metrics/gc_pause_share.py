"""Share of the window's wall spent in full (generation 2) garbage
collections of the serving process, which stop both pumps: the program's
gc.callbacks hook (monitor.gc_pause_snapshot) over this reader's own
begin -> read clock."""
import time

from istio_tpu.runtime import monitor


def begin(ctx):
    snapshot = getattr(monitor, "gc_pause_snapshot", None)
    return (time.perf_counter(), snapshot()) if snapshot else None


def read(ctx, base):
    if base is None:
        return None
    started, before = base
    paused = monitor.gc_pause_snapshot(since=before)["sum_s"]
    return 100.0 * paused / (time.perf_counter() - started)
