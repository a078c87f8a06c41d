"""Host wall per batch of dispatching the rule-telemetry fold (span
`dispatch.rulestats`, RuleTelemetry.observe: two programs), inside stage
`h2d`."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "dispatch.rulestats")
