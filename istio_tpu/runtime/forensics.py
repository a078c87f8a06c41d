"""Tail-latency forensics — the "why was THAT request slow" plane.

PR 12 made the <1ms p99 a MEASURED per-request number at the wire;
this module makes a p99 *violation* attributable without a rerun.
Three legs, all bounded and lock-light:

  * FLIGHT RECORDER (FlightRecorder / RECORDER): requests whose
    end-to-end latency exceeds a threshold (default: the live SLO
    target, monitor.CHECK_P99_TARGET_MS; adaptive live-p99 mode
    opt-in) capture a complete per-stage timeline — queue wait,
    tensorize, h2d, device step, fold, grant decision, respond, plus
    per-handler host-action waits and the native front's wire-decode
    wall — into a bounded ring with the active trace id. The tape is
    THREAD-LOCAL: the batch worker opens it (batch_begin), every
    `with monitor.stage(...)` site feeds it through a registered tap
    (the native pump's wire-decode wall comes from its `wire_decode`
    span), and the executor's resolve() adds its deadline-bounded
    host waits, so the serving path pays one thread-local read per
    stage observation and nothing else. Served at /debug/slow.

  * MESH EVENT TIMELINE (EventTimeline / EVENTS): a timestamped ring
    of control-plane events — config publish generations, canary
    verdicts, bank rebuild/reuse, prewarm start/end per shape,
    breaker state transitions, quota flushes, grant revocations,
    provider refreshes, chaos arms, drains/quiesce, a device batch's
    absorbed retry (`device.retry`, with the first exception) and a
    pump's stall (`pump.stall`: monitor's pump watch, a residence of
    monitor.STALL_S = 0.2 s or more in one top-level span, with its
    `cause`: process | lock | client | device | front | host, see
    monitor.stall_cause) — recorded by the planes that own them. Served at /debug/events; every slow-request
    exemplar is annotated with the events that overlapped its
    lifetime (plus a short pre-window: the breaker that opened 50ms
    before a request explains it), so "why slow" is one HTTP GET.

  * ON-DEMAND DEVICE PROFILING (capture_profile / thread_stacks):
    /debug/profile?seconds=N drives a jax.profiler trace capture into
    a configurable directory (ServerArgs.profile_dir / mixs
    --profile-dir), serialized by a lock and fail-soft where the
    profiler is unavailable; /debug/threads dumps every thread's
    python stack for diagnosing wedged pumps/lanes without gdb.

Overflow on either ring is bounded AND typed:
mixer_forensics_dropped_total{ring=} in runtime/monitor.py,
zero-shaped before the first drop per the promtext doctrine. The
recorder's clean-traffic overhead is gated by the forensics smoke
(≤2 %, CPU) — the fast path is a threshold compare per batch, not
per-request work.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any

from istio_tpu.runtime import monitor

__all__ = ["FlightRecorder", "EventTimeline", "RECORDER", "EVENTS",
           "record_event", "capture_profile", "thread_stacks",
           "ProfileBusy"]

# events recorded up to this many seconds BEFORE a slow request's
# enqueue still annotate its exemplar: the control-plane cause often
# immediately precedes the victim (a breaker opens, THEN requests
# route slow) — a strict-overlap window would hide exactly the event
# an on-call needs
EVENT_PRE_WINDOW_S = 1.0


class EventTimeline:
    """Bounded ring of timestamped control-plane events.

    record() is safe from any thread and any lock context (the ring
    lock is a leaf; breaker transitions call it under the breaker
    lock). `coalesce_s` folds bursts of one kind into a single entry
    with an `n` count — quota flushes fire per window and must not
    evict the publish/prewarm history the ring exists to keep."""

    def __init__(self, capacity: int = 512):
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(
            maxlen=max(int(capacity), 8))

    @staticmethod
    def _mergeable(a: dict, b: dict) -> bool:
        """Two detail payloads may coalesce only when their IDENTITY
        fields (everything non-numeric: provider names, ok flags,
        shapes) are equal — a provider_refresh failure must never be
        masked by a neighboring success, and two distinct providers
        never fold into one entry. Numeric fields (counts) accumulate
        instead."""
        if a.keys() != b.keys():
            return False
        for k, v in a.items():
            w = b[k]
            if isinstance(v, bool) or isinstance(w, bool) \
                    or not isinstance(v, (int, float)) \
                    or not isinstance(w, (int, float)):
                if v != w:
                    return False
        return True

    def record(self, kind: str, coalesce_s: float = 0.0,
               **detail: Any) -> None:
        ev = {"wall": time.time(), "t": time.perf_counter(),
              "kind": kind, "n": 1, "detail": detail}
        monitor.FORENSICS_EVENTS.inc()
        with self._lock:
            if coalesce_s and self._buf:
                last = self._buf[-1]
                if last["kind"] == kind and \
                        ev["t"] - last["t"] < coalesce_s and \
                        self._mergeable(last["detail"], detail):
                    last["n"] += 1
                    last["t"] = ev["t"]
                    last["wall"] = ev["wall"]
                    for k, v in detail.items():
                        if not isinstance(v, bool) and \
                                isinstance(v, (int, float)) and \
                                not isinstance(last["detail"][k],
                                               bool):
                            last["detail"][k] = \
                                last["detail"][k] + v
                    return
            if len(self._buf) == self._buf.maxlen:
                monitor.note_forensics_drop("events")
            self._buf.append(ev)

    def snapshot(self, kind: str | None = None,
                 limit: int = 128) -> list[dict]:
        """Most-recent-last copy; `kind` filters, `limit` keeps the
        newest (after the filter — an old publish event must stay
        findable behind a burst of newer flushes)."""
        with self._lock:
            out = list(self._buf)
        if kind:
            out = [e for e in out if e["kind"] == kind]
        return out[-limit:] if limit else out

    def overlapping(self, t0: float, t1: float,
                    pre_s: float = EVENT_PRE_WINDOW_S,
                    limit: int = 16) -> list[dict]:
        """Events whose perf_counter stamp lands in
        [t0 - pre_s, t1] — the annotation set for a request that
        lived [t0, t1]. Newest-last, bounded."""
        lo = t0 - pre_s
        with self._lock:
            out = [e for e in self._buf if lo <= e["t"] <= t1]
        return out[-limit:] if limit else out

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def reset(self) -> None:
        with self._lock:
            self._buf.clear()


class FlightRecorder:
    """Per-request flight recorder over the serving path's own stage
    observations (see module docstring for the tape contract)."""

    def __init__(self, capacity: int = 256):
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=max(int(capacity), 4))
        self._local = threading.local()
        self._enabled = True
        # 0 → the live SLO target (monitor.CHECK_P99_TARGET_MS)
        self._threshold_ms = 0.0
        self._adaptive = False
        self._thr_cache_s = monitor.CHECK_P99_TARGET_MS / 1e3
        self._thr_refreshed = 0.0

    # -- config (RuntimeServer arms this; last writer wins, like the
    #    process-global monitor counters) ------------------------------

    def configure(self, enabled: bool | None = None,
                  threshold_ms: float | None = None,
                  adaptive: bool | None = None,
                  capacity: int | None = None) -> None:
        if enabled is not None:
            self._enabled = bool(enabled)
        if threshold_ms is not None:
            self._threshold_ms = max(float(threshold_ms), 0.0)
        if adaptive is not None:
            self._adaptive = bool(adaptive)
        self._thr_refreshed = 0.0
        if capacity is not None:
            capacity = max(int(capacity), 4)
            with self._lock:
                if capacity != self._ring.maxlen:
                    self._ring = collections.deque(self._ring,
                                                   maxlen=capacity)

    def reset(self) -> None:
        """Drop retained exemplars (smoke/test phase boundaries); the
        process-lifetime counters in monitor.py keep accumulating —
        readers delta against their own baseline."""
        with self._lock:
            self._ring.clear()

    @property
    def enabled(self) -> bool:
        return self._enabled

    def threshold_s(self) -> float:
        """The live capture threshold in seconds. Adaptive mode tracks
        the sliding-window p99 (never below the configured/SLO base),
        refreshed at most every 250ms — the window sort is scrape-rate
        work, not per-batch work."""
        base = (self._threshold_ms or monitor.CHECK_P99_TARGET_MS) \
            / 1e3
        if not self._adaptive:
            return base
        now = time.perf_counter()
        if now - self._thr_refreshed > 0.25:
            self._thr_refreshed = now
            try:
                p99 = monitor.CHECK_WINDOW.quantile(0.99)
            except Exception:
                p99 = 0.0
            self._thr_cache_s = max(base, p99)
        return self._thr_cache_s

    # -- the hot-path tape (thread-local, zero alloc when disabled) ----

    def batch_begin(self) -> None:
        """Open this thread's stage tape for the batch about to run.
        Absorbs any pre-marks the front staged (the native pump's
        wire-decode wall). Disabled → clears the tape so a stale one
        never attributes a previous batch's stages."""
        if not self._enabled:
            self._local.tape = None
            return
        tape = getattr(self._local, "pre", None) or []
        self._local.pre = None
        self._local.tape = tape

    def stage_mark(self, stage: str, seconds: float) -> None:
        """One stage observation on this thread's open tape (the
        monitor.stage / observe_stage tap target). No-op off-batch."""
        tape = getattr(self._local, "tape", None)
        if tape is not None:
            tape.append((stage, seconds))

    def host_wait(self, handler: str, seconds: float) -> None:
        """One executor-lane claim wait (AdapterExecutor.resolve) —
        the stage a wedged adapter shows up as."""
        tape = getattr(self._local, "tape", None)
        if tape is not None:
            tape.append(("host:" + handler, seconds))

    def note_wire_decode(self, seconds: float) -> None:
        """Front-side pre-mark: the wire→bag decode wall the next
        batch_begin on this thread folds into its tape (the native
        pump decodes, then dispatches, on one thread)."""
        if not self._enabled:
            return
        pre = getattr(self._local, "pre", None)
        if pre is None:
            pre = []
            self._local.pre = pre
        elif len(pre) >= 4:
            # bounded: if every chunk keeps expiring pre-dispatch no
            # batch_begin ever consumes these — never grow without
            # bound on a deadline-storm thread
            del pre[0]
        pre.append(("wire_decode", seconds))

    def clear_premarks(self) -> None:
        """Drop this thread's unconsumed pre-marks. The front calls
        it after a dispatch that ended in a typed rejection (no
        batch_begin ran) — a stale decode wall must never inflate the
        NEXT unrelated batch's wire_decode stage."""
        self._local.pre = None

    # -- capture -------------------------------------------------------

    def note_batch(self, e2e_s: float, rows: int,
                   trace: dict | None) -> None:
        """Batcher-path completion: called once per batch with the
        SLOWEST request's e2e and its submit-time trace. Consumes the
        tape; captures one exemplar when over threshold (one per
        batch — batch-mates share the stage timeline)."""
        tape = getattr(self._local, "tape", None)
        self._local.tape = None
        if tape is None or e2e_s < self.threshold_s():
            return
        self._capture(e2e_s, rows, tape, trace, "batcher")

    def note_direct(self, e2e_s: float, rows: int) -> None:
        """Pre-batched-path completion (check_many / BatchCheck /
        native pump chunks): every row shares the batch e2e; the
        current thread span (the pump's rpc.check root) is the
        trace."""
        tape = getattr(self._local, "tape", None)
        self._local.tape = None
        if tape is None or e2e_s < self.threshold_s():
            return
        trace = None
        try:
            from istio_tpu.utils import tracing
            tr = tracing.get_tracer()
            if tr.reporter is not None:
                trace = tr._current()
        except Exception:
            trace = None
        self._capture(e2e_s, rows, tape, trace, "prebatched")

    def _capture(self, e2e_s: float, rows: int, tape: list,
                 trace: dict | None, source: str) -> None:
        """Build + ring one exemplar. Runs only for over-threshold
        requests — bounded dict work off the common path."""
        now = time.perf_counter()
        stages: dict[str, float] = {}
        for stage, s in tape:
            stages[stage] = stages.get(stage, 0.0) + s
        # host-action claims AND the grant fold happen INSIDE the
        # dispatcher's respond window, so the respond stage wall
        # contains both — net them out (the report plane's
        # adapter_dispatch doctrine: a wedged adapter is blamed as
        # host:<handler> and a slow grant fold as grant, never
        # smeared into respond; stage sums stay <= e2e)
        inner_s = sum(v for k, v in stages.items()
                      if k.startswith("host:") or k == "grant")
        if inner_s and "respond" in stages:
            stages["respond"] = max(stages["respond"] - inner_s, 0.0)
        top = max(stages, key=stages.get) if stages else None
        entry = {
            "wall": time.time(),
            "e2e_ms": round(e2e_s * 1e3, 3),
            "threshold_ms": round(self.threshold_s() * 1e3, 3),
            "rows": int(rows),
            "source": source,
            "stages_ms": {k: round(v * 1e3, 3)
                          for k, v in sorted(stages.items())},
            "top_stage": top,
            "trace_id": trace.get("traceId")
            if isinstance(trace, dict) else None,
            "events": [
                {"wall": e["wall"], "kind": e["kind"], "n": e["n"],
                 "detail": e["detail"]}
                for e in EVENTS.overlapping(now - e2e_s, now)],
        }
        if entry["trace_id"]:
            entry["traces_link"] = \
                f"/debug/traces?trace={entry['trace_id']}"
        monitor.FORENSICS_SLOW.inc()
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                monitor.note_forensics_drop("slow")
            self._ring.append(entry)

    # -- read side -----------------------------------------------------

    def snapshot(self, top_k: int = 10) -> dict:
        """/debug/slow payload: config + counters + the top-K slowest
        exemplars still retained (sorted slowest-first; the ring is
        recency-bounded so a startup outlier ages out)."""
        with self._lock:
            ring = list(self._ring)
        ring.sort(key=lambda e: e["e2e_ms"], reverse=True)
        return {
            "enabled": self._enabled,
            "threshold_ms": round(self.threshold_s() * 1e3, 3),
            "threshold_configured_ms": self._threshold_ms,
            "adaptive": self._adaptive,
            "capacity": self._ring.maxlen,
            "retained": len(ring),
            "counters": monitor.forensics_counters(),
            "slowest": ring[:max(int(top_k), 1)],
        }


# process-wide singletons (the monitor-counter doctrine: one home,
# armed by the owning RuntimeServer, readable by every surface)
RECORDER = FlightRecorder()
EVENTS = EventTimeline()

# feed the stage observations into the thread-local tape — the serving
# path keeps its one `with monitor.stage(...)` site per stage
monitor.set_stage_tap(RECORDER.stage_mark)


def record_event(kind: str, coalesce_s: float = 0.0,
                 **detail: Any) -> None:
    """The one tap the control planes call. Never raises — forensics
    observes the mesh, it is not allowed to take it down."""
    try:
        EVENTS.record(kind, coalesce_s=coalesce_s, **detail)
    except Exception:
        pass


# -- on-demand device profiling ---------------------------------------

class ProfileBusy(RuntimeError):
    """A capture is already running (the profiler is process-global —
    two concurrent traces would corrupt each other's artifact)."""


_PROFILE_LOCK = threading.Lock()


def capture_profile(directory: str | None, seconds: float) -> dict:
    """Drive one jax.profiler trace capture of `seconds` wall into
    `directory` (None → a fresh mixs-profile-* tempdir, created only
    once the lock is held and the profiler imports — a polling probe
    on a busy or profiler-less rig must not litter /tmp) and return
    the artifact listing. Raises ProfileBusy when a capture is in
    flight; any profiler unavailability returns a fail-soft payload
    ({"available": False, "error": ...}) — a rig without the profiler
    must still serve the endpoint."""
    seconds = min(max(float(seconds), 0.1), 60.0)
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfileBusy("a profile capture is already running")
    try:
        try:
            import jax
            if directory is None:
                import tempfile
                directory = tempfile.mkdtemp(prefix="mixs-profile-")
            os.makedirs(directory, exist_ok=True)
            t0 = time.perf_counter()
            jax.profiler.start_trace(directory)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            wall = time.perf_counter() - t0
        except Exception as exc:
            return {"available": False, "dir": directory,
                    "error": f"{type(exc).__name__}: {exc}"}
        files = []
        total = 0
        for root, _dirs, names in os.walk(directory):
            for name in names:
                p = os.path.join(root, name)
                try:
                    size = os.path.getsize(p)
                except OSError:
                    continue
                files.append({"path": os.path.relpath(p, directory),
                              "bytes": size})
                total += size
        files.sort(key=lambda f: f["path"])
        record_event("profile_capture", seconds=seconds,
                     files=len(files))
        return {"available": True, "dir": directory,
                "seconds": seconds, "wall_s": round(wall, 3),
                "files": files[:64], "n_files": len(files),
                "bytes_total": total}
    finally:
        _PROFILE_LOCK.release()


def thread_stacks(idents=None) -> dict:
    """Every live thread's python stack (sys._current_frames) keyed
    by thread name — the /debug/threads payload. A wedged pump or
    executor lane names its blocking frame here without gdb.
    `idents`: only these threads (the pump watch asks for the pumps
    and the threads that are no daemons)."""
    import sys
    import traceback

    frames = sys._current_frames()
    names = {t.ident: (t.name, t.daemon)
             for t in threading.enumerate()}
    threads = []
    for ident, frame in frames.items():
        if idents is not None and ident not in idents:
            continue
        name, daemon = names.get(ident, (f"unknown-{ident}", None))
        stack = [f"{f.filename}:{f.lineno} {f.name}"
                 + (f" — {f.line.strip()}" if f.line else "")
                 for f in traceback.extract_stack(frame)]
        threads.append({"name": name, "ident": ident,
                        "daemon": daemon, "stack": stack})
    threads.sort(key=lambda t: t["name"])
    return {"n_threads": len(threads), "threads": threads}
