"""Host wall of deciding a batch's undecided rows on the host: span
`fold.undecided` (Dispatcher._fold_respond: the snapshot oracle's
programs on the rules that can match the row, then the generic path's
response), nested in stage `fold`, `sum_ms / count` over the batches
that had such a row since the window opened. A program without the
span reads nothing."""
from istio_tpu.runtime import monitor

from spans import span_ms_per_batch


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return span_ms_per_batch(base, "fold.undecided")
