"""Share of the served Check rows the host decided: the program's
`mixer_check_undecided_rows_total{subject}` (rows a subject of which
fills the widest byte plane under a rule that reads it: the device
leaves them undecided and `fold` gives them the oracle's verdict) over
the rows of `mixer_check_rows_by_width_total`, both since the window
opened. A traffic check as much as a cost: about 0.5 % in
`routelong10k`; 0 there means the route is dead, more that the device
gave rows up. Also prints one progress line with the rows by subject.
A program without the counters reads nothing."""
import json

from istio_tpu.runtime import monitor


def _counters():
    counters = getattr(monitor, "length_split_counters", None)
    return counters() if counters else None


def begin(ctx):
    return _counters()


def read(ctx, base):
    if base is None:
        return None
    now = _counters()
    rows = sum(now["rows_by_width"].values()) \
        - sum(base["rows_by_width"].values())
    if not rows:
        return None
    by_subject = {subject: n - base["undecided"].get(subject, 0)
                  for subject, n in now["undecided"].items()}
    print(json.dumps({"phase": "undecided_rows", "rows": rows,
                      "by_subject": by_subject}), flush=True)
    return 100.0 * sum(by_subject.values()) / rows
