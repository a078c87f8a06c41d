"""Mean number of verdict classes in a served batch: the program's
`mixer_respond_classes_total` (the distinct CheckResponse objects the
respond stage built: rows equal in all that decides a response share
one) over the count of the stage `respond`, both since the window
opened. Serialising and framing in the native front are paid once a
class. As much a traffic check as a cost: a batch under the stage's
class threshold builds a response a row and reads its rows. Also prints
one progress line with the share of rows that share their response
(`classed`) and that have one of their own (`row`). A program without
the counter reads nothing."""
import json

from istio_tpu.runtime import monitor

from spans import window_spans


def _counters():
    counters = getattr(monitor, "respond_class_counters", None)
    return counters() if counters else None


def begin(ctx):
    counters = _counters()
    if counters is None:
        return None
    return counters, monitor.stage_baseline()


def read(ctx, base):
    if base is None:
        return None
    was, spans_base = base
    seen = window_spans(spans_base) or {}
    batches = seen.get("respond", {}).get("count", 0)
    if not batches:
        return None
    now = _counters()
    rows = {path: n - was["rows"][path] for path, n in now["rows"].items()}
    total = sum(rows.values())
    print(json.dumps({"phase": "respond_rows", "rows": total, "share_pct": {
        path: 100.0 * n / max(total, 1) for path, n in rows.items()}}),
        flush=True)
    return (now["classes_total"] - was["classes_total"]) / batches
