"""Host wall per batch of the per-row response build / SerializeToString
loop and of handing the completions to the C++ front (spans `serialize`
+ `send`, NativeMixerServer._serialize_rows / _send_completions)."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "serialize", "send")
