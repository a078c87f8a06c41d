"""Share of the quota rows the native front answered with a response
built once a class of equal bytes (the verdict and each quota's grant:
api/native_server._answer_quota_rows), against rows whose response was
built from the row's own bag: the program's
`mixer_quota_rows_answered_total{path=class|row}`, class ÷ both, since
the window opened, in %. How often the grouped answer engages. A
program without the counter reads nothing, and so does a window with
no quota row answered."""
from istio_tpu.runtime import monitor


def _answered():
    counters = getattr(monitor, "quota_counters", None)
    return counters()["answered"] if counters else None


def begin(ctx):
    return _answered()


def read(ctx, base):
    if base is None:
        return None
    moved = {path: n - base[path] for path, n in _answered().items()}
    rows = sum(moved.values())
    if not rows:
        return None
    return 100.0 * moved["class"] / rows
