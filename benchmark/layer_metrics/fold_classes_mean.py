"""Mean number of distinct referenced / presence signatures in a served
batch: the program's `mixer_fold_signature_classes_total` over the
count of the span `fold.signature`, both since the window opened. The
per-class part of `fold` (name tuples, presence dicts) is built once a
class. A program without the counter reads nothing."""
from istio_tpu.runtime import monitor

from spans import window_spans


def begin(ctx):
    counters = getattr(monitor, "check_decided_counters", None)
    if counters is None:
        return None
    return counters()["signature_classes_total"], monitor.stage_baseline()


def read(ctx, base):
    if base is None:
        return None
    classes, spans_base = base
    seen = window_spans(spans_base) or {}
    batches = seen.get("fold.signature", {}).get("count", 0)
    if not batches:
        return None
    now = monitor.check_decided_counters()["signature_classes_total"]
    return (now - classes) / batches
