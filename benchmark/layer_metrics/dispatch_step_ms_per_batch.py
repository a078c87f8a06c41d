"""Host wall per batch of dispatching the engine step, its arguments'
implicit transfer included (span `dispatch.step`, FusedPlan.packed_check),
inside stage `h2d`."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "dispatch.step")
