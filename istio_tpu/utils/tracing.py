"""Control-plane tracing (reference: pkg/tracing/config.go:87-135
Configure — zipkin HTTP / log-only span reporters composed and wired
into the servers). Spans are zipkin-v2-shaped dicts; reporters are
pluggable: log_reporter (the reference's LogTraceSpans option),
MemoryReporter (tests), and ZipkinReporter — the v2 wire format
(JSON array POSTed to /api/v2/spans) over an injectable transport
(this image has no egress; tests drive a local HTTP sink).

The serving pipeline emits per-BATCH stage spans (queue-wait /
tensorize / device / overlay — runtime/dispatcher.py), so a served
check's latency is decomposable the way the reference's interceptor
chain makes its RPCs (mixer/pkg/server/server.go).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import threading
import time
import urllib.request
import uuid
from typing import Any, Callable

log = logging.getLogger("istio_tpu.tracing")

Reporter = Callable[[dict], None]


def log_reporter(span: dict) -> None:
    log.info("span %s/%s %s %.3fms", span.get("traceId"),
             span.get("id"), span.get("name"),
             span.get("duration", 0) / 1000.0)


class MemoryReporter:
    def __init__(self) -> None:
        self.spans: list[dict] = []
        self._lock = threading.Lock()

    def __call__(self, span: dict) -> None:
        with self._lock:
            self.spans.append(span)


class RingReporter:
    """Bounded ring of the most recent finished spans — the backing
    store of the introspect server's /debug/traces endpoint (ControlZ's
    recent-activity role). Dropping the oldest under load is the
    point: introspection must never grow without bound."""

    def __init__(self, capacity: int = 256):
        import collections
        self._buf: "collections.deque[dict]" = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self._closed = False

    def __call__(self, span: dict) -> None:
        with self._lock:
            if self._closed:   # detached ring still in a live chain
                return
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(span)

    def snapshot(self, limit: int = 0) -> list[dict]:
        """Most-recent-last copy (capped at `limit` when > 0),
        ordered by span START time. The deque holds FINISH order —
        children land before their parents, and once the ring wraps a
        long-lived root can sit after spans that started (and
        finished) much later, so finish order is not chronological.
        Sorting by (timestamp, id) makes the view stable and
        chronological under wrap-around; the limit keeps the NEWEST
        spans, applied after the sort."""
        with self._lock:
            out = list(self._buf)
        out.sort(key=lambda s: (s.get("timestamp", 0),
                                str(s.get("id", ""))))
        return out[-limit:] if limit else out


def enable_ring(capacity: int = 256) -> RingReporter:
    """Attach a RingReporter to the GLOBAL tracer: composed with the
    existing reporter when one is configured, or installed as the sole
    reporter on the noop tracer (turning span recording ON — the
    introspect server wants recent spans even when no zipkin/log
    reporter is wired). A later configure() replaces the global tracer
    and detaches the ring; re-enable after reconfiguring. Undo with
    disable_ring(ring) — a closed introspect server must not leave
    span construction on the hot path."""
    global _global
    ring = RingReporter(capacity)
    prev = _global
    if prev.reporter is None:
        tracer = Tracer(service_name=prev.service_name, reporter=ring)
    else:
        tracer = Tracer(service_name=prev.service_name,
                        reporter=composite_reporter(ring,
                                                    prev.reporter))
    # restore tokens for disable_ring: the back-pointer chain lets a
    # later disable unwind past rings closed out of order. configure()
    # installs a tracer with no _ring back-pointer, so a newer owner's
    # stack is never unwound.
    ring._installed_over = prev
    tracer._ring = ring
    _global = tracer
    return ring


def disable_ring(ring: RingReporter) -> None:
    """Detach a ring installed by enable_ring: mark it closed (it may
    still sit inside a LIVE composite — a later-installed ring's
    chain) and unwind the global tracer past every tracer whose
    installing ring is closed. Handles non-LIFO close order: closing
    the last introspect server walks back past earlier-closed rings,
    so no dead ring is left constructing spans on the hot path. No-op
    when configure()/another owner has replaced the tracer."""
    global _global
    ring._closed = True
    while True:
        owner = getattr(_global, "_ring", None)
        if owner is None or not owner._closed:
            return
        _global = owner._installed_over


def parent_from_traceparent(header: str | None) -> dict | None:
    """W3C `traceparent` header → a parent-span dict usable as the
    `parent` of span()/start_span(), so server-side rpc.check roots
    (and every exemplar trace id hanging off them) join the CLIENT'S
    trace. Format (https://www.w3.org/TR/trace-context/):
    `version-traceid(32 hex)-parentid(16 hex)-flags`; malformed or
    all-zero ids return None and the caller self-generates ids as
    before."""
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    _ver, trace_id, span_id = parts[0], parts[1].lower(), \
        parts[2].lower()
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    if set(trace_id) == {"0"} or set(span_id) == {"0"}:
        return None
    return {"traceId": trace_id, "id": span_id}


def _http_post_json(url: str, payload: bytes,
                    timeout_s: float = 5.0) -> int:
    req = urllib.request.Request(
        url, data=payload, method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        return r.status


class ZipkinReporter:
    """zipkin-v2 HTTP reporter: spans buffer and flush as a JSON array
    to `url` (POST /api/v2/spans — the wire format
    zipkin.NewHTTPTransport speaks in pkg/tracing/config.go:99).

    `post` is injectable (default urllib); flushing happens on a
    background thread every `flush_interval_s` or `max_batch` spans,
    and close() drains. Failures drop the batch with a log line —
    tracing must never stall serving."""

    def __init__(self, url: str,
                 post: Callable[[str, bytes], Any] | None = None,
                 flush_interval_s: float = 1.0, max_batch: int = 100):
        self.url = url
        self._post = post or _http_post_json
        self._buf: list[dict] = []
        self._lock = threading.Lock()
        self._closed = False
        self._interval = flush_interval_s
        self._max = max_batch
        self._wake = threading.Condition(self._lock)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="zipkin-reporter")
        self._thread.start()

    def __call__(self, span: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._buf.append(span)
            if len(self._buf) >= self._max:
                self._wake.notify()

    def _run(self) -> None:
        while True:
            with self._lock:
                self._wake.wait(timeout=self._interval)
                batch, self._buf = self._buf, []
                closed = self._closed
            if batch:
                try:
                    self._post(self.url, json.dumps(batch).encode())
                except Exception as exc:
                    log.warning("zipkin flush of %d spans failed: %s",
                                len(batch), exc)
            if closed:
                return

    def flush(self) -> None:
        with self._lock:
            self._wake.notify()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._wake.notify()
        self._thread.join(timeout=self._interval + 6)


def composite_reporter(*reporters: Reporter) -> Reporter:
    """jaeger.NewCompositeReporter analog (config.go:120)."""
    def report(span: dict) -> None:
        for r in reporters:
            try:
                r(span)
            except Exception:
                log.exception("span reporter failed")
    return report


@dataclasses.dataclass
class Tracer:
    service_name: str = "istio-tpu"
    reporter: Reporter | None = log_reporter   # None → disabled (noop)
    _local: threading.local = dataclasses.field(
        default_factory=threading.local)

    def _current(self) -> dict | None:
        return getattr(self._local, "span", None)

    # start_span/finish_span are the ONLY span-construction and
    # report sites; span() and emit() are thin wrappers (one place to
    # change the span shape, one place that guards the reporter).

    def start_span(self, name: str, parent: dict | None = None,
                   **tags: Any) -> dict | None:
        """Detached open span — for code that cannot hold a `with`
        block (asyncio handlers: a thread-local span held across an
        await would leak onto interleaved tasks). Does NOT touch the
        thread-local stack; pass the dict around explicitly
        (span(parent=...), finish_span). None when tracing is off."""
        if self.reporter is None:
            return None
        span = {
            "traceId": parent["traceId"] if parent
            else uuid.uuid4().hex[:16],
            "id": uuid.uuid4().hex[:16],
            "name": name,
            "localEndpoint": {"serviceName": self.service_name},
            "timestamp": int(time.time() * 1e6),
            "tags": {k: str(v) for k, v in tags.items()},
            "_t0": time.perf_counter(),
        }
        if parent:
            span["parentId"] = parent["id"]
        return span

    def finish_span(self, span: dict | None, **tags: Any) -> None:
        """Close + report a start_span() span (None-safe). Duration is
        measured from the open timestamp unless the span already
        carries one (emit's backdated intervals)."""
        if span is None or self.reporter is None:
            return
        t0 = span.pop("_t0", None)
        if t0 is not None and "duration" not in span:
            span["duration"] = int((time.perf_counter() - t0) * 1e6)
        if tags:
            span["tags"].update(
                {k: str(v) for k, v in tags.items()})
        try:
            self.reporter(span)
        except Exception:
            log.exception("span reporter failed")

    @contextlib.contextmanager
    def span(self, name: str, parent: dict | None = None, **tags: Any):
        """`parent` overrides the thread-local parent — cross-thread
        attribution (the batcher parenting its serve.batch span under
        the API layer's rpc.check root, which lives on the handler
        thread)."""
        if self.reporter is None:   # disabled: zero hot-path work
            yield None
            return
        prev = self._current()      # this THREAD's restore point —
        if parent is None:          # distinct from the LINK parent,
            parent = prev           # which may come from another
        span = self.start_span(name, parent=parent, **tags)
        self._local.span = span
        try:
            yield span
        except Exception as exc:
            span["tags"]["error"] = str(exc)
            raise
        finally:
            self._local.span = prev
            self.finish_span(span)

    def emit(self, name: str, duration_s: float, **tags: Any) -> None:
        """Fire-and-forget span for an already-measured interval —
        exception-safe instrumentation of code that cannot nest in a
        `with` block (multiple exits, hot paths)."""
        span = self.start_span(name, parent=self._current(), **tags)
        if span is None:
            return
        span["timestamp"] = int((time.time() - duration_s) * 1e6)
        span["duration"] = int(duration_s * 1e6)
        self.finish_span(span)


# -- global tracer (pkg/tracing's ot.SetGlobalTracer side effect) -----

NOOP_TRACER = Tracer(reporter=None)
_global = NOOP_TRACER
_closers: list = []


def configure(service_name: str, zipkin_url: str = "",
              log_spans: bool = False,
              post: Callable[[str, bytes], Any] | None = None) -> Tracer:
    """pkg/tracing/config.go:87 Configure: compose zipkin/log
    reporters (none configured → noop tracer), install globally.
    Reconfiguring closes the reporters it replaces (the reference's
    io.Closer contract) — otherwise every reload leaks a flush
    thread."""
    global _global
    for c in _closers:
        try:
            c.close()
        except Exception:
            log.exception("reporter close failed")
    _closers.clear()
    reporters: list[Reporter] = []
    if zipkin_url:
        zr = ZipkinReporter(zipkin_url, post=post)
        _closers.append(zr)
        reporters.append(zr)
    if log_spans:
        reporters.append(log_reporter)
    if not reporters:
        tracer = Tracer(service_name=service_name, reporter=None)
    elif len(reporters) == 1:
        tracer = Tracer(service_name=service_name,
                        reporter=reporters[0])
    else:
        tracer = Tracer(service_name=service_name,
                        reporter=composite_reporter(*reporters))
    _global = tracer
    return tracer


def get_tracer() -> Tracer:
    return _global


def capture(service_name: str = "capture"):
    """Temporarily swap in a MemoryReporter-backed tracer →
    (reporter, restore_fn): a test reads the pipeline's stage spans
    (queue-wait / tensorize / device / overlay) without a zipkin
    endpoint."""
    global _global
    prev = _global
    mem = MemoryReporter()
    _global = Tracer(service_name=service_name, reporter=mem)

    def restore() -> None:
        global _global
        _global = prev
    return mem, restore


def shutdown() -> None:
    global _global
    for c in _closers:
        try:
            c.close()
        except Exception:
            log.exception("reporter close failed")
    _closers.clear()
    _global = NOOP_TRACER
