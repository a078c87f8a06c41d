"""Host wall per batch of _parse_take and the per-row LazyWireBag +
preprocess loop (span `wire_decode`, NativeMixerServer._run_batch)."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "wire_decode")
