"""Host wall per batch of span `wire_decode`
(NativeMixerServer._run_batch): one np.frombuffer over the pump's own
take buffer (api/take.TakenRows.read), the split of checks from reports
by the `kind` column and, only under an APA, a bag + preprocess a row."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "wire_decode")
