"""Readers of the program's host spans (`monitor.stage` / `monitor.span`,
istio_tpu/runtime/monitor.py): their histogram, and their
`mixer/<name>` events on the host planes of the window's trace, which
lie on the same clock as the device's `XLA Modules` line.

A program without these spans (the parent of the PR that added them)
makes every function here return None: a reader then reports nothing.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import tempfile

from observe import (DEVICE_PLANE, MODULES_LINE, NAME_CUT, TOP,
                     merge_intervals)

PREFIX = "mixer/"
STAGES = ("queue_wait", "tensorize", "h2d", "device_step", "fold",
          "respond")
PUMP_SPANS = ("take_wait", "wire_decode", "serialize", "send")
# the ten spans that tile a pump's cycle; nested spans (tensorize.*,
# dispatch.*, device, overlay, grant) and pump_cycle itself label nothing
TOP_LEVEL = STAGES + PUMP_SPANS
GC, UNATTRIBUTED = "gc", "host:unattributed"


def window_spans(base: dict) -> dict | None:
    """{name: {"count", "sum_ms", ...}} of every stage and span observed
    since the `monitor.stage_baseline()` token `base`; None when the
    program has no span histogram."""
    from istio_tpu.runtime import monitor

    snap = monitor.latency_snapshot(since=base)
    if "spans" not in snap:
        return None
    return {**snap["stages"], **snap["spans"]}


def span_ms_per_batch(base: dict, *names: str) -> float | None:
    """Mean host wall of the named spans (or stages) since `base`, each
    its own sum / count, summed over the names. None when one of them
    saw nothing."""
    seen = window_spans(base)
    if seen is None or any(n not in seen or not seen[n]["count"]
                           for n in names):
        return None
    return sum(seen[n]["sum_ms"] / seen[n]["count"] for n in names)


def pump_unaccounted_ms(base: dict) -> float | None:
    """What no top-level span covers of a pump's cycle, per cycle: the
    `pump_cycle` wall less the ten spans that tile it, over the cycles
    counted. A stage that saw nothing (the native front has no
    `queue_wait` stage) counts as zero."""
    seen = window_spans(base)
    if seen is None or not seen.get("pump_cycle", {}).get("count"):
        return None
    cycle = seen["pump_cycle"]
    inside = sum(seen[n]["sum_ms"] for n in TOP_LEVEL if n in seen)
    return (cycle["sum_ms"] - inside) / cycle["count"]


def find_window_trace(since: float) -> str | None:
    """The newest xplane file under the temporary directory written
    after `since` (time.time() at the reader's begin). run.py keeps the
    window's trace in a TemporaryDirectory that still exists while
    readers run, and does not hand them its path."""
    paths = [p for p in glob.glob(os.path.join(
        tempfile.gettempdir(), "*", "plugins", "profile", "*",
        "*.xplane.pb")) if os.path.getmtime(p) >= since]
    return max(paths, key=os.path.getmtime) if paths else None


def load_planes(path: str) -> tuple[list | None, dict, tuple]:
    """One xplane file -> (`XLA Modules` intervals of the first device
    plane, host spans {(thread, label): [(start, end)]} of every
    `mixer/*` event off the device planes, (first start, last end)
    over every plane), in ns."""
    from jax.profiler import ProfileData

    device, spans = None, collections.defaultdict(list)
    t0 = t1 = None
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            events = [(e.name, int(e.start_ns),
                       int(e.start_ns + e.duration_ns))
                      for e in line.events]
            if not events:
                continue
            lo = min(s for _, s, _ in events)
            hi = max(e for _, _, e in events)
            t0 = lo if t0 is None else min(t0, lo)
            t1 = hi if t1 is None else max(t1, hi)
            if on_device:
                if line.name == MODULES_LINE and device is None:
                    device = [(s, e) for _, s, e in events]
                continue
            thread = (plane.name, line.name)
            for name, s, e in events:
                if name.startswith(PREFIX):
                    spans[thread, name[len(PREFIX):]].append((s, e))
    return device, dict(spans), (t0, t1)


def idle_intervals(busy, t0: int, t1: int) -> list[tuple[int, int]]:
    """The complement in [t0, t1] of the union of `busy`."""
    gaps, at = [], t0
    for start, end in merge_intervals(
            (max(s, t0), min(e, t1)) for s, e in busy
            if e > t0 and s < t1):
        if start > at:
            gaps.append((at, start))
        at = end
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def _clipped(events, starts, g0: int, g1: int):
    """The events (sorted, disjoint: one thread's spans of one name)
    that overlap [g0, g1], clipped to it."""
    i = max(bisect.bisect_right(starts, g0) - 1, 0)
    while i < len(events) and events[i][0] < g1:
        s, e = events[i]
        if e > g0:
            yield max(s, g0), min(e, g1)
        i += 1


def attribute(gaps, spans: dict) -> dict:
    """Put each device-idle interval down to a host span. Per gap: the
    top-level span with the largest overlap, summed over threads; `gc`
    outright when `mixer/gc` events cover half the gap or more;
    UNATTRIBUTED when no top-level span touches it. Idle seconds by
    label share each gap's covered time (the union of its top-level
    spans) in proportion to the labels' overlaps; the rest of the gap
    is UNATTRIBUTED."""
    lists = {key: (sorted(events), sorted(s for s, _ in events))
             for key, events in spans.items()
             if key[1] in TOP_LEVEL or key[1] == GC}
    by_label: collections.Counter = collections.Counter()
    labelled = []
    for g0, g1 in gaps:
        overlap: collections.Counter = collections.Counter()
        pieces = []
        for (_, label), (events, starts) in lists.items():
            for s, e in _clipped(events, starts, g0, g1):
                overlap[label] += e - s
                pieces.append((s, e))
        length = g1 - g0
        if 2 * overlap[GC] >= length:
            label, shares = GC, {GC: length}
        elif overlap:
            label = max(overlap, key=overlap.get)
            covered = sum(e - s for s, e in merge_intervals(pieces))
            total = sum(overlap.values())
            shares = {name: covered * ns / total
                      for name, ns in overlap.items()}
        else:
            label, shares = UNATTRIBUTED, {}
        by_label.update(shares)
        by_label[UNATTRIBUTED] += length - sum(shares.values())
        labelled.append((label, length))
    idle = sum(length for _, length in labelled)
    return {
        "idle_s": idle / 1e9,
        "attributed_share_pct":
            100.0 * (idle - by_label[UNATTRIBUTED]) / idle if idle
            else None,
        "idle_s_by_label": {k[:NAME_CUT]: v / 1e9
                            for k, v in by_label.most_common() if v},
        "longest_gaps": [[label, ns / 1e9] for label, ns in sorted(
            labelled, key=lambda g: g[1], reverse=True)[:TOP]],
    }


def attribute_idle(xplane_path: str) -> dict | None:
    """attribute() over one trace: the idle intervals of the first
    device between the trace's first event and its last (the span
    `observe.reduce_trace` measures). None when no program ran on a
    device or the trace holds no `mixer/*` event."""
    device, spans, (t0, t1) = load_planes(xplane_path)
    if device is None or not spans:
        return None
    return attribute(idle_intervals(device, t0, t1), spans)
