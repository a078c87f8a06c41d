"""Host wall per batch of the per-row namespace ids (span
`tensorize.ns_ids`, Dispatcher._ns_ids_from_batch), inside stage
`tensorize`, while the staged byte plane's transfer is in flight."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "tensorize.ns_ids")
