"""Share of the device's idle time in the traced span that lies under a
top-level host span of the program (`mixer/*` events on the trace's host
planes; spans.attribute_idle). Also prints one progress line with the
idle seconds by span and the ten longest gaps, each with its label."""
import json
import time

from spans import attribute_idle, find_window_trace


def begin(ctx):
    return time.time()


def read(ctx, since):
    if ctx.trace is None:
        return None
    path = find_window_trace(since)
    found = attribute_idle(path) if path else None
    if found is None:
        return None
    print(json.dumps({"phase": "idle_by_span", **found}), flush=True)
    return found["attributed_share_pct"]
