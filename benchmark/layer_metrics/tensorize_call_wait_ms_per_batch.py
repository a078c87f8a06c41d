"""Host wall per batch a pump waits for the tensorizer's one call lock
(span `tensorize.call_wait`, NativeTensorizer._tensorize: the
acquisition of `_call_lock` and nothing else), inside span
`tensorize.decode`. Both pumps decode through one NativeTensorizer, so
this is the queue a further pump would meet first."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "tensorize.call_wait")
