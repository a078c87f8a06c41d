"""Host wall per batch of the h2d staging stage (Dispatcher._stage_h2d)."""
from istio_tpu.runtime import monitor

from observe import stage_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return stage_ms_per_batch(base, "h2d")
