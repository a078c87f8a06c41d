"""Share of the window's served rows whose verdict was not OK: the
program's `mixer_check_decided_total{by}` (one count a batch in
Dispatcher._fold_respond), rows not `ok` over rows. Also prints one
progress line with the share of each `by`. A program without the
counter reads nothing."""
import json

from istio_tpu.runtime import monitor


def _decided():
    counters = getattr(monitor, "check_decided_counters", None)
    return counters()["decided"] if counters else None


def begin(ctx):
    return _decided()


def read(ctx, base):
    if base is None:
        return None
    rows = {by: n - base[by] for by, n in _decided().items()}
    total = sum(rows.values())
    if not total:
        return None
    print(json.dumps({"phase": "decided_by", "rows": total, "share_pct": {
        by: 100.0 * n / total for by, n in rows.items()}}), flush=True)
    return 100.0 * (total - rows["ok"]) / total
