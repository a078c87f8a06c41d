"""Device time of the served step by its named sections, from the
window's trace.

The step program (istio_tpu/models/policy_engine.py) wraps its sections
in `jax.named_scope` — `match`, `deny`, `lists`, `rbac`, `quota`,
`combine` — which is metadata only: on a TPU each event of a device
plane's `XLA Ops` line carries the scope path of the operation it ran
in the `tf_op` stat of its metadata (`jit(step)/lists/select_n:`). A
fusion carries the path of the one operation the compiler named it
after, so a section's time is that of the fused operations named under
it.

`scope_ms_per_step(path, "lists")` is a reader's whole job: the time
the first device's `XLA Ops` events under the scope cover (their summed
durations, but for an event nested in another of the same scope, which
counts once), over the `jit_step` programs on its `XLA Modules` line.
A reader finds the window's trace as `idle_attributed_share` does
(`spans.find_window_trace`). A step without the section (no list
handler, no RBAC) reads None. An executable keeps the metadata it was
compiled with and a compile cache's key leaves metadata out, so a cache
written before the scopes were placed serves a step that carries none:
a traced step with no operation under `match` raises, and the run is
not correct, where a None would pass for "no such section".
"""
from __future__ import annotations

import functools
import os

from observe import DEVICE_PLANE, MODULES_LINE, OPS_LINE, merge_intervals
from spans import find_window_trace

SCOPE_STAT = "tf_op"
STEP_MODULE = "jit_step"
EVERY_STEP = "match"     # the one section no step is without


def scope_seconds(ops, scope: str) -> float | None:
    """`ops`: (scope path or None, start, end) per device operation, in
    ps -> seconds covered by the operations whose path has `scope` as a
    whole component; None when there is none."""
    found = [(start, end) for op_path, start, end in ops
             if op_path and scope in op_path.split("/")]
    if not found:
        return None
    return sum(e - s for s, e in merge_intervals(found)) / 1e12


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """tsl's XSpace message, from the generated module the installed
    tensorflow ships, loaded by its path: importing the `tensorflow`
    package takes 14 s and loads its own runtime into the process that
    holds the chip. jax.profiler.ProfileData (observe.py's reader of
    the same file) shows an event's own stats only; the scope path is a
    stat of the event's METADATA, which it does not show."""
    import importlib.util

    package = importlib.util.find_spec("tensorflow")
    if package is None:
        raise ImportError("no tensorflow installation to take "
                          "tsl/profiler/protobuf/xplane_pb2.py from")
    spec = importlib.util.spec_from_file_location(
        "benchmark_xplane_pb2", os.path.join(
            package.submodule_search_locations[0], "tsl", "profiler",
            "protobuf", "xplane_pb2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.XSpace


@functools.lru_cache(maxsize=1)
def load_step_ops(xplane_path: str) -> tuple[tuple, int]:
    """One xplane file -> ((scope path or None, start ps, end ps) of
    every `XLA Ops` event of the first device plane that ran a
    program, the count of `jit_step` programs on that plane's
    `XLA Modules` line). Cached: one reader per scope reads the same
    file."""
    space = _xspace_class()()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        lines = {line.name: line for line in plane.lines}
        if not DEVICE_PLANE.match(plane.name) or MODULES_LINE not in lines:
            continue
        stat_names = {key: meta.name
                      for key, meta in plane.stat_metadata.items()}
        names, paths = {}, {}
        for key, meta in plane.event_metadata.items():
            names[key] = meta.name
            for stat in meta.stats:
                if stat_names.get(stat.metadata_id) == SCOPE_STAT:
                    # a string, or a reference to a stat's name
                    paths[key] = stat.str_value or \
                        stat_names.get(stat.ref_value)
        steps = sum(names.get(e.metadata_id, "").split("(")[0]
                    == STEP_MODULE for e in lines[MODULES_LINE].events)
        ops = tuple(
            (paths.get(e.metadata_id), e.offset_ps,
             e.offset_ps + e.duration_ps)
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()))
        return ops, steps
    return (), 0


def scope_ms_per_step(xplane_path: str, scope: str) -> float | None:
    """Mean device milliseconds a served step spends in operations
    named under `scope`; None when the trace holds no step program or
    no operation under the scope. Raises on steps without scopes."""
    ops, steps = load_step_ops(xplane_path)
    if steps and ops and scope_seconds(ops, EVERY_STEP) is None:
        raise RuntimeError(
            f"{steps} {STEP_MODULE} programs ran and no operation lies "
            f"under `{EVERY_STEP}`, the scope every step has: the "
            "executable came from a compile cache written before the "
            "step had its scopes (the cache's key leaves metadata out)")
    seconds = scope_seconds(ops, scope)
    if not steps or seconds is None:
        return None
    return seconds * 1e3 / steps


def read_window(ctx, since: float, scope: str) -> float | None:
    """A reader's `read`: `scope` in the traced window's own trace,
    `since` being time.time() at the reader's `begin`."""
    if ctx.trace is None:
        return None
    path = find_window_trace(since)
    return scope_ms_per_step(path, scope) if path else None
