"""Objects in the collector's permanent generation the instant the
window opens (monitor.gc_pause_snapshot()["frozen"], the gauge
mixer_gc_frozen_objects set by monitor.settle_heap): whether the
long-lived heap was out of the full collections' way before the window,
and how much of it. 0 beside an unchanged gc_pause_share: a call site
of settle_heap was missed. A program without the key is not read."""
from istio_tpu.runtime import monitor


def begin(ctx):
    snapshot = getattr(monitor, "gc_pause_snapshot", None)
    return snapshot().get("frozen") if snapshot else None


def read(ctx, base):
    return base
