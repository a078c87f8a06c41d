"""Share of the native front's quota asks of a device pool whose
memquota key came from the plan's memo over the batch's interned ids
(runtime/quota_keys.py), against every ask keyed: the program's
`mixer_quota_keys_total{path=memo|built|row}`, memo over the three
paths, since the window opened, in %. `built`: a key the plan had not
seen, its instance built on the row's bag and stored; `row`: the
per-row build (a quota that reads what the step's APA binds, a value
that is no string, a build that raised). How often the memo engages.
Also prints the three counts. A program without the counter reads
nothing, and so does a window with no ask keyed."""
import json

from istio_tpu.runtime import monitor


def _keys():
    counters = getattr(monitor, "quota_key_counters", None)
    return counters() if counters else None


def begin(ctx):
    return _keys()


def read(ctx, base):
    if base is None:
        return None
    moved = {path: n - base[path] for path, n in _keys().items()}
    asks = sum(moved.values())
    if not asks:
        return None
    print(json.dumps({"phase": "quota_keys", **moved}), flush=True)
    return 100.0 * moved["memo"] / asks
