"""Host wall per batch of the fold and respond stages together."""
from istio_tpu.runtime import monitor

from observe import stage_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return stage_ms_per_batch(base, "fold", "respond")
