"""The yardstick's reductions: from the profiler's trace and the
program's stage histograms to numbers. Per-layer readers import these;
nothing here is the program's.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
TOP = 10
NAME_CUT = 96


def merge_intervals(intervals) -> list[tuple[int, int]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def busy_and_gaps(intervals, t0: int, t1: int) -> tuple[int, list[int]]:
    """Busy time inside [t0, t1] (intervals clipped to it, overlaps
    counted once) and every idle gap in it, the one before the first
    interval and the one after the last included."""
    clipped = [(max(s, t0), min(e, t1)) for s, e in intervals
               if e > t0 and s < t1]
    busy, gaps, at = 0, [], t0
    for start, end in merge_intervals(clipped):
        if start > at:
            gaps.append(start - at)
        busy += end - start
        at = end
    if t1 > at:
        gaps.append(t1 - at)
    return busy, gaps


def idle_share_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def find_trace(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def reduce_trace(path: str) -> dict | None:
    """One xplane file -> the traced span (first start to last end over
    every plane, host threads included: they share the device's clock),
    device busy seconds (union of the `XLA Modules` events, averaged
    over the device planes), the programs by name, the ten operations
    with most device time and the ten longest idle gaps. None when no
    operation ran on a device."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    t0 = t1 = None
    per_device = []
    for plane in planes:
        lines = {}
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
            lines.setdefault(line.name, []).extend(events)
        if DEVICE_PLANE.match(plane.name) and MODULES_LINE in lines:
            per_device.append(lines)
    if not per_device:
        return None
    modules: dict[str, list] = collections.defaultdict(lambda: [0, 0.0])
    ops: collections.Counter = collections.Counter()
    busy_ns, gaps = 0, []
    for lines in per_device:
        busy, dev_gaps = busy_and_gaps(
            [(s, e) for _, s, e in lines[MODULES_LINE]], t0, t1)
        busy_ns += busy
        gaps.extend(dev_gaps)
        for name, s, e in lines[MODULES_LINE]:
            entry = modules[name.split("(")[0]]
            entry[0] += 1
            entry[1] += (e - s) / 1e9
        for name, s, e in lines.get(OPS_LINE, ()):
            ops[name[:NAME_CUT]] += e - s
    n = len(per_device)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "modules": {k: tuple(v) for k, v in modules.items()},
        "device_ops": [[name, ns / n / 1e9]
                       for name, ns in ops.most_common(TOP)],
        # what the host was doing in a gap is not knowable yet: the
        # program has no host spans on the profiler's clock
        "idle_gaps": [["host:unattributed", ns / 1e9]
                      for ns in sorted(gaps, reverse=True)[:TOP]],
    }


def stage_ms_per_batch(base: dict, *stages: str) -> float | None:
    """Mean host wall per batch of the named serving stages since the
    `monitor.stage_baseline()` token `base`, summed over the stages:
    exact sum / count of the program's stage histogram (its quantiles
    are bucket edges and are not used). None when a stage saw nothing."""
    from istio_tpu.runtime import monitor

    seen = monitor.latency_snapshot(since=base)["stages"]
    if any(s not in seen or not seen[s]["count"] for s in stages):
        return None
    return sum(seen[s]["sum_ms"] / seen[s]["count"] for s in stages)
