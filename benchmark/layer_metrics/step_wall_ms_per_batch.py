"""Host wall per batch from dispatching the device step to the pulled result."""
from istio_tpu.runtime import monitor

from observe import stage_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return stage_ms_per_batch(base, "device_step")
