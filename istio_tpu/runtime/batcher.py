"""Check batcher — coalesce concurrent Check() calls into device steps.

The design piece with no reference counterpart (SURVEY.md §7 layer 4):
the reference evaluates per request on CPU; the TPU path amortizes one
device dispatch over a window of concurrent requests. Requests enqueue
(bag, Future); the flusher thread drains up to `max_batch` per step,
waiting at most `window_s` after the first request of a batch. Batch
shapes are BUCKETED (pad to the next power of two) so jit re-traces a
handful of shapes, not one per batch size.

p99 story: window (≤300µs) + step (~1-2ms small batches) keeps tail
latency in the BASELINE budget while throughput scales with load —
under light load a request waits at most window_s; under heavy load
batches fill instantly and the window never matters.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Sequence

from istio_tpu.attribute.bag import Bag
from istio_tpu.runtime import monitor
from istio_tpu.runtime.resilience import (DeadlineExceededError,
                                          ResourceExhaustedError,
                                          UnavailableError)

log = logging.getLogger("istio_tpu.runtime.batcher")


def default_buckets(max_batch: int) -> tuple[int, ...]:
    """Few, coarse bucket shapes: every bucket is one jit trace the
    server must pay (seconds on TPU), so a small fixed set beats
    power-of-two granularity — padding a 3-request batch to 256 rows
    costs microseconds of MXU time, a 12th trace costs seconds.
    Includes the 64-wide LATENCY TIER (profiled r4: B=64 lands under
    the 1 ms budget at 10k rules where B=256 does not) so light-load
    batches compile to a tight shape instead of padding to 256."""
    out = sorted({min(64, max_batch), min(256, max_batch), max_batch})
    return tuple(out)


def bucket_size(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pad_to_bucket(bags, buckets: tuple[int, ...]):
    """`bags` + PadBags up to the bucket for len(bags) — the single
    home of bucket padding (batcher, BatchCheck front, fused report
    resolve). Caller chunks to buckets[-1] first; an over-bucket
    length returns the bags unpadded. A batch that counts its padding
    (api/take.TakenRows) pads itself and makes no PadBag."""
    target = bucket_size(len(bags), buckets)
    pad_to = getattr(bags, "pad_to", None)
    if pad_to is not None:
        return pad_to(target)
    return list(bags) + [PadBag() for _ in range(target - len(bags))]


def trim_pads(bags):
    """`bags` without their trailing PadBag rows — the single inverse
    of pad_to_bucket (padding is always appended at the tail)."""
    if hasattr(bags, "pad_to"):
        return bags.real
    n = len(bags)
    while n and isinstance(bags[n - 1], PadBag):
        n -= 1
    return bags[:n] if n < len(bags) else bags


class PadBag(Bag):
    """Empty bag used to pad a batch to its bucket size."""

    # empty CompressedAttributes — keeps a padded batch on the C++
    # wire-decode path (dispatcher._check_fused requires every row to
    # carry wire bytes)
    wire = b""

    def get(self, name: str):
        return None, False

    def names(self):
        return []


class CheckBatcher:
    """check(bag) blocks until its batch's device step completes.

    `run_batch(bags) -> list[result]` is the dispatcher hook; padding
    rows are PadBags whose results are discarded.
    """

    def __init__(self, run_batch: Callable[[Sequence[Bag]], Sequence[Any]],
                 window_s: float = 0.0003, max_batch: int = 1024,
                 pipeline: int = 4,
                 buckets: tuple[int, ...] | None = None,
                 hold_at: int | None = None,
                 size_hist=None,
                 pad_batches: bool = True,
                 observe_latency: bool = True,
                 max_queue: int | None = None,
                 brownout: bool = False,
                 stage_observer: Callable[[float], None] | None = None,
                 continuous: bool = False,
                 continuous_depth: int = 2):
        self.run_batch = run_batch
        # continuous batching (the latency lane): the flusher
        # dispatches a batch the moment an in-flight slot under
        # `continuous_depth` is free — it absorbs whatever is ALREADY
        # queued but never waits for a window to expire or a batch to
        # fill. In-flight step pipelining stays bounded (default 2:
        # one step executing, one dispatching) so continuous mode
        # can't flood the device with 1-row trips while a fat batch
        # queues behind them. False = the occupancy-fill policy
        # (throughput-optimal on serialized transports).
        self.continuous = bool(continuous)
        self._continuous_depth = max(int(continuous_depth), 1)
        # deadline propagation (the adapter-executor plane): hooks
        # that accept it get the batch's min remaining deadline, so
        # host adapter actions inherit the request budget end to end
        from istio_tpu.runtime.resilience import _takes_deadline
        self._run_takes_deadline = _takes_deadline(run_batch)
        # bounded admission (DAGOR-style front-door shedding): a submit
        # that would push the queue past max_queue resolves
        # RESOURCE_EXHAUSTED instead of growing queue_wait without
        # bound. None = unbounded (the seed behavior; RuntimeServer
        # passes a cap).
        self.max_queue = max_queue if max_queue and max_queue > 0 \
            else None
        # brownout: while the LIVE p99 gauge is over the SLO target and
        # the queue is already half full, shed the newest arrivals
        # first — protecting the requests already queued instead of
        # growing everyone's tail (Tail at Scale §"latency-induced
        # brownout"). Off by default: it reads the global p99 window,
        # which is only meaningful on the check path.
        self.brownout = brownout
        self._p99_refreshed = 0.0
        # False for non-Check coalescers (the report batcher): their
        # batches must not feed the Check() stage decomposition or the
        # live p99 window
        self._observe_latency = observe_latency
        # queue-wait observer for coalescers with their OWN stage
        # decomposition (the report batcher feeds coalesce_wait into
        # the report pipeline histograms instead of the Check stages)
        self._stage_observer = stage_observer
        # False for hooks whose downstream re-pads anyway (the report
        # batcher: dispatcher._report_active_fused pads per chunk) —
        # skips allocate-then-trim churn on every light-load batch
        self._pad_batches = pad_batches
        # batch-size histogram to observe (default: the check path's;
        # the report batcher passes monitor.REPORT_BATCH_SIZE so the
        # two coalescers stay separately diagnosable)
        self._size_hist = size_hist if size_hist is not None \
            else monitor.CHECK_BATCH_SIZE
        self.window_s = window_s
        self.max_batch = max_batch
        # occupancy threshold for the adaptive window (see _loop):
        # batches accumulate while >= hold_at trips are in flight.
        # Default 1: on the rigs measured before PR 1 (a transport
        # that serialized trips, a 1-core CPU) fat batches beat trip
        # overlap — concurrent steps contend for the device/core
        # anyway (CPU rig: 756/s at 1 vs 520/s at 2 vs 203/s at
        # pipeline=8). Not re-measured on the local chip; a device
        # that truly executes trips in parallel can raise it.
        self._hold_at = max(hold_at if hold_at is not None else 1, 1)
        self.buckets = tuple(sorted(buckets)) if buckets \
            else default_buckets(max_batch)
        if self.buckets[-1] < max_batch:
            # every collectable batch size must land in a pre-warmable
            # bucket, or over-bucket batches run at arbitrary unpadded
            # shapes and re-trace in-band
            self.buckets = self.buckets + (max_batch,)
        self._queue: "queue.Queue[tuple[Bag, Future] | None]" = queue.Queue()
        # Bounded batch pipelining: the flusher hands each batch to a
        # worker and immediately starts collecting the next, so the
        # host↔device sync of batch N overlaps batch N+1's window and
        # dispatch. Worth more the longer a sync takes (chip_smoke.py
        # prints device_sync_ms); harmless (slightly better tail)
        # when it is short. pipeline=1 restores strictly serial
        # batches.
        from concurrent.futures import ThreadPoolExecutor
        self._pipeline = max(pipeline, 1)
        self._pool = ThreadPoolExecutor(max_workers=self._pipeline,
                                        thread_name_prefix="check-step")
        self._inflight = threading.Semaphore(self._pipeline)
        # occupancy counter for the adaptive window (the semaphore
        # can't be read): >0 → a device trip is in flight
        self._inflight_n = 0
        self._inflight_lock = threading.Lock()
        self._thread = threading.Thread(target=self._loop_guard,
                                        daemon=True,
                                        name="check-batcher")
        self._closed = False
        # admission stopped (graceful shutdown step 1): new submits
        # resolve typed UNAVAILABLE; queued/in-flight work drains
        self._draining = False
        # watchdog: set to the fatal exception if the flusher thread
        # ever dies — submit() then fails fast (an orphaned Future
        # would block its caller forever) and /healthz goes unhealthy
        self._dead: BaseException | None = None
        self._thread.start()

    def check(self, bag: Bag, deadline: float | None = None) -> Any:
        return self.submit(bag, deadline=deadline).result()

    def healthy(self) -> tuple[bool, str]:
        """(ok, reason) for /healthz: the flusher thread must be alive
        (or deliberately closed) and must not have died on an
        exception."""
        if self._dead is not None:
            return False, (f"check-batcher flusher died: "
                           f"{type(self._dead).__name__}: {self._dead}")
        if not self._closed and not self._thread.is_alive():
            return False, "check-batcher flusher thread not running"
        return True, ""

    def _admission_error(self, deadline: float | None
                         ) -> Exception | None:
        """Front-door shedding decision for one submit(). Returns the
        typed rejection to resolve the future with, or None to admit.
        Counter increments are gated on _observe_latency so the report
        coalescer (which shares this class) never pollutes the CHECK
        resilience counters."""
        observe = self._observe_latency
        if self._draining:
            # ordered shutdown: admission is OFF — a typed rejection
            # the fronts map to UNAVAILABLE (clients retry a peer),
            # while already-admitted work keeps draining below
            if observe:
                monitor.CHECK_SHED.labels(reason="draining").inc()
            return UnavailableError("server shutting down")
        if self._dead is not None or \
                (not self._closed and not self._thread.is_alive()):
            if observe:
                monitor.CHECK_SHED.labels(reason="batcher_dead").inc()
            return UnavailableError(
                "check batcher flusher thread is dead")
        if deadline is not None and time.perf_counter() >= deadline:
            if observe:
                monitor.CHECK_DEADLINE_EXPIRED.inc()
            return DeadlineExceededError(
                "deadline expired before enqueue")
        depth = self._queue.qsize()
        if self.max_queue is not None and depth >= self.max_queue:
            if observe:
                monitor.CHECK_SHED.labels(reason="queue_full").inc()
            return ResourceExhaustedError(
                f"check queue full ({depth} >= {self.max_queue})")
        if self.brownout and self._brownout_active(depth):
            if observe:
                monitor.CHECK_SHED.labels(reason="brownout").inc()
            return ResourceExhaustedError(
                "brownout: live p99 over SLO target, shedding newest")
        return None

    def _brownout_active(self, depth: int) -> bool:
        """Brownout trips only when BOTH hold: the queue is past its
        soft threshold (half the cap, or half a max_batch when
        uncapped) AND the live p99 gauge is over the SLO target. The
        gauge refresh (a window sort) runs at most every 50ms, never
        per submit."""
        soft = (self.max_queue // 2) if self.max_queue is not None \
            else max(self.max_batch // 2, 1)
        if depth < soft:
            return False
        now = time.perf_counter()
        if now - self._p99_refreshed > 0.05:
            self._p99_refreshed = now
            monitor.refresh_latency_gauges()
        return monitor.CHECK_P99_MS.value() > monitor.CHECK_P99_TARGET_MS

    def submit(self, bag: Bag, trace: Any = None,
               deadline: float | None = None) -> Future:
        """`trace`: the caller's root span dict (API-layer rpc.check) —
        the batch span parents under it so queue-wait is attributed to
        a request, not a batch. None captures the submitting thread's
        current span (the sync fronts, which submit inside their root
        span's `with` block). `deadline`: absolute time.perf_counter()
        instant after which this request must not be dispatched —
        expired requests resolve DEADLINE_EXCEEDED before tensorize,
        and admission-control sheds resolve RESOURCE_EXHAUSTED; both
        surface on the returned future, never as a hang."""
        if self._closed:
            raise RuntimeError("batcher is closed")
        fut: Future = Future()
        err = self._admission_error(deadline)
        if err is not None:
            fut.set_exception(err)
            return fut
        fut._t_enq = time.perf_counter()   # queue-wait span tag
        fut._deadline = deadline
        if trace is None:
            try:
                from istio_tpu.utils import tracing
                tr = tracing.get_tracer()
                if tr.reporter is not None:
                    trace = tr._current()
            except Exception:
                trace = None   # tracing must never break submission
        fut._trace = trace
        self._queue.put((bag, fut))
        # TOCTOU vs the watchdog: the flusher may have died (and
        # drained the queue) between the admission check above and the
        # put — a future landing in a consumer-less queue would hang
        # its caller forever, the exact failure the watchdog exists to
        # prevent. InvalidStateError means the drain already got it
        # (and already counted the shed).
        if self._dead is not None:
            try:
                fut.set_exception(UnavailableError(
                    "check batcher flusher thread is dead"))
            except InvalidStateError:
                pass
            else:
                if self._observe_latency:
                    monitor.CHECK_SHED.labels(
                        reason="batcher_dead").inc()
        return fut

    def _loop_guard(self) -> None:
        """Flusher-thread watchdog: the loop must never die silently —
        an orphaned queue blocks every future submitter forever. On a
        fatal loop exception, mark the batcher dead (healthz +
        fail-fast submits) and resolve everything still queued."""
        try:
            self._loop()
        except BaseException as exc:   # noqa: BLE001 — watchdog belt
            self._dead = exc
            log.exception("check-batcher flusher thread died")
            err = UnavailableError(
                f"check batcher flusher died: "
                f"{type(exc).__name__}: {exc}")
            while True:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    return
                if item is None:
                    continue
                try:
                    item[1].set_exception(err)
                except InvalidStateError:
                    pass
                else:
                    # every client-visible rejection must show in the
                    # shed counters — an on-call diagnosing this exact
                    # incident reads them first
                    if self._observe_latency:
                        monitor.CHECK_SHED.labels(
                            reason="batcher_dead").inc()

    @staticmethod
    def _min_deadline(current: float | None, item) -> float | None:
        """Running-minimum fold over batch items' deadlines — O(1) per
        appended item (rescanning the batch per hold iteration was
        O(max_batch²) on the only flusher thread)."""
        d = getattr(item[1], "_deadline", None)
        if d is None:
            return current
        return d if current is None or d < current else current

    def _loop(self) -> None:
        """Collect batches under an OCCUPANCY-ADAPTIVE window: with
        fewer than `hold_at` trips in flight a batch sails after the
        fixed window (light-load latency = one trip), at or past that
        occupancy it keeps accumulating until a slot frees —
        dispatching a 1-row trip behind a busy transport wastes a trip
        slot the queued batch-mates then wait out (VERDICT r4 item 6:
        half of all saturation batches carried ≤2 rows while 1024
        clients were blocked). See __init__ for the hold_at default's
        measured rationale."""
        hold_at = min(self._pipeline, self._hold_at)
        depth = min(self._continuous_depth, self._pipeline)
        while True:
            item = self._queue.get()
            if item is None:
                self._drain_on_close()
                return
            batch = [item]
            dmin = self._min_deadline(None, item)
            if self.continuous:
                if self._collect_continuous(batch, dmin, depth):
                    self._flush(batch)
                    self._drain_on_close()
                    return
                self._flush(batch)
                continue
            deadline = time.perf_counter() + self.window_s
            while len(batch) < self.max_batch:
                busy = self._inflight_n >= hold_at
                timeout = deadline - time.perf_counter()
                if timeout <= 0:
                    if not busy:
                        break
                    # busy: hold, re-check occupancy — but NEVER hold a
                    # request past its deadline: flush while the
                    # earliest batch deadline still has a hold quantum
                    # of slack (flushing AT expiry would guarantee the
                    # row is shed in _run_one instead of served), and
                    # never sleep past that flush point
                    timeout = 0.002
                    if dmin is not None:
                        slack = dmin - time.perf_counter()
                        if slack <= 0.002:
                            break
                        timeout = min(timeout, slack - 0.002)
                try:
                    nxt = self._queue.get(timeout=timeout)
                except queue.Empty:
                    if busy and len(batch) < self.max_batch:
                        continue
                    break
                if nxt is None:
                    self._flush(batch)
                    self._drain_on_close()
                    return
                batch.append(nxt)
                dmin = self._min_deadline(dmin, nxt)
            self._flush(batch)

    def _collect_continuous(self, batch: list, dmin: float | None,
                            depth: int) -> bool:
        """Latency-lane collection: greedily absorb whatever is
        ALREADY queued, then dispatch the moment an in-flight slot
        under `depth` is free — never wait for fill or a window (a
        request never waits for a batch to fill). While every slot is
        busy, hold in fine quanta and keep absorbing arrivals, but
        never past the earliest row deadline. Returns True when the
        close sentinel arrived (the caller flushes, then drains)."""
        while len(batch) < self.max_batch:
            if self._inflight_n < depth:
                try:   # a step slot is free: take what's here and go
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    return False
            else:
                timeout = 0.0005
                if dmin is not None:
                    slack = dmin - time.perf_counter()
                    if slack <= 0.0005:
                        # dispatch now: _flush blocks on the pipeline
                        # semaphore at worst — holding longer would
                        # guarantee the row sheds in _run_one
                        return False
                    timeout = min(timeout, slack)
                try:
                    nxt = self._queue.get(timeout=timeout)
                except queue.Empty:
                    continue
            if nxt is None:
                return True
            batch.append(nxt)
            dmin = self._min_deadline(dmin, nxt)
        return False

    def _drain_on_close(self) -> None:
        """Requests that raced past close() must still resolve — flush
        whatever is left behind the sentinel instead of abandoning the
        futures (callers block forever otherwise)."""
        leftovers = []
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                leftovers.append(item)
        if leftovers:
            self._flush(leftovers)

    def _flush(self, batch: list[tuple[Bag, Future]]) -> None:
        self._inflight.acquire()
        with self._inflight_lock:
            self._inflight_n += 1
        try:
            self._pool.submit(self._run_one, batch)
        except BaseException as exc:
            # pool.submit can fail (shutdown race, thread-spawn
            # failure) — the in-hand futures must resolve before the
            # exception propagates to the watchdog, or their callers
            # block forever on a batch nobody owns
            with self._inflight_lock:
                self._inflight_n -= 1
            self._inflight.release()
            err = UnavailableError(
                f"check batch dispatch failed: "
                f"{type(exc).__name__}: {exc}")
            for _, fut in batch:
                try:
                    fut.set_exception(err)
                except InvalidStateError:
                    pass
                else:
                    if self._observe_latency:
                        monitor.CHECK_SHED.labels(
                            reason="batcher_dead").inc()
            raise

    def _shed_stale(self, batch: list[tuple[Bag, Future]]
                    ) -> list[tuple[Bag, Future]]:
        """Drop rows that must not reach tensorize: futures the caller
        already cancelled (an aio client disconnect — tensorizing and
        dispatching them is pure waste) and rows whose deadline expired
        in the queue (resolved DEADLINE_EXCEEDED; dispatching work the
        caller already timed out on only steals device time from live
        requests)."""
        now = time.perf_counter()
        keep: list[tuple[Bag, Future]] = []
        for bag, fut in batch:
            if fut.cancelled():
                if self._observe_latency:
                    monitor.CHECK_CANCELLED_SHED.inc()
                continue
            dl = getattr(fut, "_deadline", None)
            if dl is not None and now >= dl:
                if self._observe_latency:
                    monitor.CHECK_DEADLINE_EXPIRED.inc()
                try:
                    fut.set_exception(DeadlineExceededError(
                        "deadline expired in the check queue"))
                except InvalidStateError:
                    pass
                continue
            keep.append((bag, fut))
        return keep

    def _run_one(self, batch: list[tuple[Bag, Future]]) -> None:
        try:
            batch = self._shed_stale(batch)
            if not batch:
                return
            # flight-recorder tape (runtime/forensics.py): opened per
            # batch on this worker thread; the queue_wait observation
            # below and the dispatcher's monitor.stage sites feed it,
            # and the
            # completion note captures a slow exemplar only when the
            # batch's slowest request crossed the threshold. Check
            # path only — report batches carry their own stages.
            if self._observe_latency:
                from istio_tpu.runtime import forensics
                forensics.RECORDER.batch_begin()
            self._size_hist.observe(len(batch))
            bags = [bag for bag, _ in batch]
            padded = pad_to_bucket(bags, self.buckets) \
                if self._pad_batches else bags
            # the span's bucket field always reports the DEVICE shape
            # (even when a downstream re-padder owns the padding) so
            # size-vs-bucket keeps measuring pad overhead
            bucket_n = len(padded) if self._pad_batches \
                else bucket_size(len(bags), self.buckets)
            # queue-wait = oldest enqueue -> batch start (decomposable
            # served latency; pkg/tracing interceptor role)
            from istio_tpu.utils import tracing
            now = time.perf_counter()
            waits = [now - t for t in
                     (getattr(f, "_t_enq", None) for _, f in batch)
                     if t is not None]
            if self._observe_latency:
                monitor.observe_stage("queue_wait",
                                      max(waits, default=0.0))
            elif self._stage_observer is not None:
                self._stage_observer(max(waits, default=0.0))
            # parent under the OLDEST request's rpc root span — the
            # request whose queue-wait the batch's wait tag reports
            parent = next((t for t in
                           (getattr(f, "_trace", None)
                            for _, f in batch) if t is not None), None)
            span_ctx = tracing.get_tracer().span(
                "serve.batch", parent=parent, size=len(batch),
                bucket=bucket_n,
                queue_wait_ms=round(max(waits, default=0.0) * 1e3, 3))
            try:
                with span_ctx:
                    if self._run_takes_deadline:
                        # min over the batch's row deadlines: the fold
                        # must never hold ANY row past its own budget
                        dmin = None
                        for _, f in batch:
                            dmin = self._min_deadline(dmin, (None, f))
                        results = self.run_batch(padded, deadline=dmin)
                    else:
                        results = self.run_batch(padded)
            except Exception as exc:
                # failed batches are excluded from the stage
                # decomposition by design — this counter is their only
                # trace in /metrics
                if self._observe_latency:
                    monitor.CHECK_BATCH_FAILURES.inc()
                for _, fut in batch:
                    try:
                        fut.set_exception(exc)
                    except InvalidStateError:
                        pass                     # caller cancelled
                return
            if len(results) < len(batch):
                # zip() would silently truncate and hang the trailing
                # callers — route a contract violation through the belt
                raise RuntimeError(
                    f"run_batch returned {len(results)} results for a "
                    f"{len(batch)}-request batch")
            # a caller may cancel its future mid-batch (an aio client
            # disconnect) — even between a cancelled() check and the
            # set; one cancelled future must never abort result
            # distribution for its batch-mates
            for (_, fut), result in zip(batch, results):
                try:
                    fut.set_result(result)
                except InvalidStateError:
                    pass
            # per-request end-to-end (enqueue -> result delivered):
            # feeds the e2e histogram + sliding-window p99 tracker
            if self._observe_latency:
                done = time.perf_counter()
                e2e_max, slow_fut = 0.0, None
                for _, fut in batch:
                    t = getattr(fut, "_t_enq", None)
                    if t is not None:
                        e2e = done - t
                        monitor.observe_check_e2e(e2e)
                        if e2e > e2e_max:
                            e2e_max, slow_fut = e2e, fut
                # one exemplar per batch at most: batch-mates share
                # the stage timeline the tape recorded above
                from istio_tpu.runtime import forensics
                forensics.RECORDER.note_batch(
                    e2e_max, len(batch),
                    getattr(slow_fut, "_trace", None))
        except Exception as exc:
            # belt over the inner handler: NO failure in batch prep or
            # result distribution may abandon the futures — an
            # unresolved future hangs its caller forever (observed r4:
            # a NameError in the tracing-span line left every request
            # of the batch timing out)
            if self._observe_latency:
                monitor.CHECK_BATCH_FAILURES.inc()
            for _, fut in batch:
                try:
                    fut.set_exception(exc)
                except InvalidStateError:
                    pass
        finally:
            with self._inflight_lock:
                self._inflight_n -= 1
            self._inflight.release()

    def stats(self) -> dict:
        """Point-in-time queue/pipeline state for the introspect
        server's /debug/queues (reference: ControlZ's process state
        pages). `oldest_wait_ms` is the head-of-queue request's age —
        the wait the NEXT batch will report."""
        oldest_wait_ms = 0.0
        with self._queue.mutex:
            depth = len(self._queue.queue)
            head = self._queue.queue[0] if self._queue.queue else None
        if head is not None:
            t = getattr(head[1], "_t_enq", None)
            if t is not None:
                oldest_wait_ms = (time.perf_counter() - t) * 1e3
        healthy, health_err = self.healthy()
        return {
            "depth": depth,
            "oldest_wait_ms": round(oldest_wait_ms, 3),
            "in_flight": self._inflight_n,
            "pipeline": self._pipeline,
            "hold_at": self._hold_at,
            "window_s": self.window_s,
            "max_batch": self.max_batch,
            "buckets": list(self.buckets),
            "closed": self._closed,
            "draining": self._draining,
            "continuous": self.continuous,
            "continuous_depth": self._continuous_depth,
            "max_queue": self.max_queue,
            "brownout": self.brownout,
            "healthy": healthy,
            "health_error": health_err,
        }

    def quiesce(self) -> None:
        """Graceful-shutdown step 1: stop admission. Every submit from
        here on resolves a typed UNAVAILABLE immediately; queued and
        in-flight batches are unaffected (drain() waits them out)."""
        self._draining = True
        from istio_tpu.runtime import forensics
        forensics.record_event(
            "quiesce",
            lane="check" if self._observe_latency else "report")

    def drain(self, deadline: float | None = 5.0) -> bool:
        """Block until the queue is empty and no batch is in flight
        (bounded by `deadline` seconds; None = wait forever). Returns
        True when fully drained — False means the deadline expired
        with work still pending (close() then resolves the leftovers,
        never abandons them)."""
        end = None if deadline is None \
            else time.perf_counter() + deadline
        while True:
            if self._dead is not None:
                return False   # watchdog already resolved the queue
            with self._queue.mutex:
                empty = not self._queue.queue
            if empty and self._inflight_n == 0:
                return True
            if end is not None and time.perf_counter() >= end:
                return False
            time.sleep(0.005)

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._queue.put(None)
            self._thread.join(timeout=5)
            self._pool.shutdown(wait=True)
