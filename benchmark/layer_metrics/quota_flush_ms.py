"""Mean wall of a quota pool worker's flush, in ms: the program's
`mixer_quota_flush_seconds` (runtime/device_quota.DeviceQuotaPool.
_flush: from the take of a batch of pending asks to the last of its
answers handed on — staging, the device trip, the grants, and the
native front's build and framing of the rows it completes), Δ sum over
Δ count since the window opened. Prints the flushes and the rows they
answered beside it. A program without the histogram reads nothing,
and so does a window with no flush."""
import json

from istio_tpu.runtime import monitor


def _flushes():
    counters = getattr(monitor, "quota_counters", None)
    if counters is None:
        return None
    seen = counters()
    return seen["flush_s"], seen["flushes"], seen["flush_rows"]


def begin(ctx):
    return _flushes()


def read(ctx, base):
    if base is None:
        return None
    seconds, flushes, rows = (now - then
                              for now, then in zip(_flushes(), base))
    if not flushes:
        return None
    print(json.dumps({"phase": "quota_flush", "flushes": flushes,
                      "rows": rows}), flush=True)
    return 1e3 * seconds / flushes
