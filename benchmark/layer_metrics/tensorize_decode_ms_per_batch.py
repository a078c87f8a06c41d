"""Host wall per batch of the C++ wire -> tensor decode (span
`tensorize.decode`, plan.native.tensorize_wire in
Dispatcher._tensorize_for_device), inside stage `tensorize`."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "tensorize.decode")
