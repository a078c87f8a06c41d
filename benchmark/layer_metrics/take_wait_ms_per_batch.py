"""Host wall per pump cycle blocked in h2srv_take waiting for rows (span
`take_wait`, NativeMixerServer._take)."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "take_wait")
