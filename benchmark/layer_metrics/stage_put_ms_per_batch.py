"""Host wall per batch of the one explicit device_put: narrow the byte
plane to its tier, then stage it (span `tensorize.stage_put`,
Dispatcher._stage_h2d), inside stage `tensorize`."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "tensorize.stage_put")
