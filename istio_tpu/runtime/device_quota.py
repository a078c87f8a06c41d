"""Device-backed quota pools for the serving path.

Reference flow (mixer/pkg/api/grpcServer.go:188-230): after a
successful precondition Check, the server walks the request's quotas
map and dispatches each to the one matching quota action. The host
path re-resolves rules per quota call — a full device round-trip per
request on this build. This module replaces that with:

  host dedup-replay cache  (memquota.go:259 buildWithDedup semantics)
        │ miss
  exact dims→bucket keymap (the host assigns each distinct instance
        │                   key its own counter row — no hash collisions
        │                   conflating cells)
  batched device scatter-add alloc (models/quota_alloc.py, one XLA
                                    step per batch window)

Rule matching reuses the CHECK step's activity bits: the fused plan
exposes which quota-bearing rules matched each request
(CheckResponse.active_quota_rules), so the quota loop never re-resolves.

Windowing (r4): ROLLING windows with host-adapter parity — counters
are per-(bucket, tick-slot) planes; each flush rolls the touched
buckets (reclaiming slots whose ticks left the window) before
allocating, exactly like adapters/memquota._Window (the reference's
rollingWindow.go quantized to _TICKS_PER_WINDOW slots per window).
Exact counters (duration 0) live in slot 0 of the same plane and match
the host `_Exact` cell; the parity tests pin both, plus dedup replay
and best-effort semantics, against MemQuotaHandler under an injected
clock.

State is per-replica and best-effort, like the reference. Pools are
REUSED across config generations when the (handler signature, quota
name) is unchanged — handlerTable.go's signature diffing applied to
counter state.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from istio_tpu.adapters.memquota import _TICKS_PER_WINDOW
from istio_tpu.adapters.memquota import _key as dims_key
from istio_tpu.adapters.sdk import QuotaArgs, QuotaResult
from istio_tpu.models.policy_engine import RESOURCE_EXHAUSTED
from istio_tpu.models.quota_alloc import make_rolling_alloc_step
from istio_tpu.runtime import monitor
from istio_tpu.utils.log import scope

log = scope("runtime.device_quota")

DEFAULT_BUCKETS = 131_072    # BASELINE config 4: 100k-key counter eval


def _outcome(granted: int, amount: int, duration: float) -> QuotaResult:
    """A consumed ask's result: a grant short of a non-zero ask is an
    error only at 0 (memquota)."""
    return QuotaResult(granted_amount=granted, valid_duration_s=duration,
                       status_code=0 if granted > 0 or amount == 0
                       else RESOURCE_EXHAUSTED)


def _one_plane(step, jit: bool):
    """`step` over a trip's eight host planes stacked in one int32
    [8, n] array (buckets, amounts, best effort, max, active, ticks,
    lasts, rolling): one host-to-device transfer a trip where there
    were eight. A transfer lets go of the interpreter lock, and under
    busy pumps the worker waits out a switch interval to take it back
    (a host timing under two pumps: the call's wall about halved)."""
    def run(counts, planes):
        b, a, be, mx, active, ticks, lasts, rolling = planes
        return step(counts, b, a, be != 0, mx, active != 0, ticks,
                    lasts, rolling != 0)
    return jax.jit(run, donate_argnums=(0,)) if jit else run


class DeviceQuotaPool:
    """Counters for every quota name of ONE memquota handler config.

    Bucket space is shared: each distinct (name, dimensions) instance
    key gets the next free row, so 100k live keys need ~100k rows
    regardless of how many quota names the handler defines."""

    def __init__(self, quotas: Mapping[str, Mapping[str, Any]],
                 n_buckets: int = DEFAULT_BUCKETS,
                 min_dedup_s: float = 1.0,
                 # a WIDE window: every flush is a device round-trip
                 # that contends with check batches for the transport
                 # (profiled: 0.5ms windows fragmented the device path
                 # into dozens of tiny trips and halved served
                 # throughput); +10ms on a quota grant is noise next
                 # to the trip itself
                 batch_window_s: float = 0.010,
                 max_batch: int = 512,
                 clock: Callable[[], float] = time.monotonic,
                 jit: bool = True):
        self.limits = {str(n): {"max": int(q.get("max_amount", 0)),
                                "duration": float(
                                    q.get("valid_duration_s", 0.0))}
                       for n, q in quotas.items()}
        self.n_buckets = n_buckets
        self.min_dedup_s = min_dedup_s
        self._clock = clock
        self._lock = threading.Lock()
        self._bucket_of: dict[str, int] = {}
        self._dedup: dict[str, tuple[int, float]] = {}
        # expiry order of the dedup entries, a FIFO a lifetime (max of
        # the quota's duration and min_dedup_s): (expiry, id, gate-off)
        # records in write order, popped from the front once expired
        # (_expire_dedup), so no ask walks the cache
        self._dedup_fifos: dict[float, collections.deque] = {}
        # rolling-window bookkeeping (host side): tick length per
        # bucket (0 = exact cell), the last tick each bucket rolled to
        # (absolute), and a per-bucket tick base so device ticks stay
        # small rebased int32s while HOST tick boundaries (floor of
        # absolute now / tick_len) match adapters/memquota._Window
        # exactly
        self.k_ticks = _TICKS_PER_WINDOW
        self._tick_len: np.ndarray = np.zeros(n_buckets, np.float64)
        self._last_tick: np.ndarray = np.zeros(n_buckets, np.int64)
        self._tick_base: np.ndarray = np.zeros(n_buckets, np.int64)
        self.counts = jnp.zeros((n_buckets, self.k_ticks), jnp.int32)
        # scan is the sequential parity oracle; the SERVING path only
        # ever selects fast/unit/seg (all parallel — VERDICT r4 item
        # 4: a hot key + amount=5 used to stall the transport for
        # ~177ms in the O(B) scan)
        (self._alloc_scan, self._alloc_fast, self._alloc_unit,
         self._alloc_seg) = \
            make_rolling_alloc_step(n_buckets, self.k_ticks, jit=jit)
        self._trips = {name: _one_plane(step, jit) for name, step in (
            ("fast", self._alloc_fast), ("unit", self._alloc_unit),
            ("seg", self._alloc_seg))}
        # pending batched allocations: [(bucket, amount, best_effort,
        # max, duration, dedup id, sink, key)] — the sink resolves the
        # ask under `key` (alloc_many)
        self._pending: list = []
        self._window_s = batch_window_s
        self._max_batch = max_batch
        self._small_batch = min(64, max_batch)
        self._wake = threading.Condition(self._lock)
        self._closed = False
        # counter-buffer ownership token: `counts` is mutated by the
        # worker's flush AND by in-step sessions (quota alloc riding
        # the check trip, see inline_begin). Sessions hold it only
        # from stage to DISPATCH (the successor buffer is swapped in
        # as a device future — trips chain on-device, so two pumps'
        # trips overlap on the transport while the data dependency
        # resolves in XLA). Lock order: ALWAYS _counts_lock then
        # self._lock (inline_begin and the worker's _flush both) —
        # taking self._lock first would deadlock against them.
        self._counts_lock = threading.Lock()
        # in-step commit ordering: bookkeeping (dedup-cache writes,
        # pending-dedup replays) must apply in DISPATCH order even
        # though pulls race — sessions take numbered turns
        self._seq_next = 0
        self._commit_cv = threading.Condition(threading.Lock())
        self._commit_turn = 0
        # dedup ids consumed by a dispatched-but-uncommitted session:
        # a same-id row staged meanwhile must NOT re-consume — it
        # resolves from the cache at its own (later) commit turn
        self._dedup_pending: dict[str, int] = {}
        # dedup ids whose consuming session committed GATE-OFF (rule
        # inactive → granted freely, nothing consumed, nothing in
        # _dedup — consumed outcomes only): id → expiry. A pending
        # replay that finds its id here replays grant-freely instead
        # of failing "quota trip failed" (ADVICE r5 parity gap).
        self._dedup_free: dict[str, float] = {}
        # last known-good counter handle (restore target when a
        # dispatched trip's pull fails)
        self._counts_good = self.counts
        # compile every program the serving path can hit (both pad
        # shapes × the serving alloc variants: fast/unit/seg)
        # BEFORE the worker starts — a first-quota-batch compile
        # mid-serve stalls every pending quota future behind it for
        # seconds (observed r4: 60s quota waits from variable-shape
        # compiles). Running here, pre-thread,
        # also keeps `counts` single-owner: only __init__ and the
        # worker ever touch it.
        self._prewarm()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="device-quota")
        self._thread.start()

    # -- public ---------------------------------------------------------

    def knows(self, name: str) -> bool:
        return name in self.limits

    def alloc(self, name: str, instance: Mapping[str, Any],
              args: QuotaArgs) -> "QuotaFuture":
        """Non-blocking; returns a future resolving to QuotaResult."""
        fut = QuotaFuture()
        for _key, result in self.alloc_many([(None, name, instance, args)],
                                            fut):
            fut.set(result)
        return fut

    def alloc_many(self, items: list, sink) -> list:
        """Submit a group of asks under ONE take of the pool's lock.
        `items`: [(key, quota name, instance, QuotaArgs)], the instance
        a built one or its memquota key already (runtime/quota_keys.py:
        the same string dims_key gives). → [(key,
        QuotaResult)] for the asks resolved here without a trip: an
        unknown name, a dedup replay (of a consumed or a gate-off
        outcome), a closed pool, an exhausted keyspace. Every other ask
        takes its place in the pending queue in `items`' order (so a
        flush's in-window replay of a repeated id stays exact) and is
        answered through `sink.resolve([(key, QuotaResult), ...])`,
        once a flush that answers any of them, from the worker thread
        with no pool lock held."""
        now = self._clock()
        early: list = []
        asks: list = []
        for key, name, instance, args in items:
            lim = self.limits.get(name)
            if lim is None:
                early.append((key, QuotaResult(
                    granted_amount=0, status_code=RESOURCE_EXHAUSTED,
                    status_message=f"unknown quota {name}")))
            else:
                asks.append((key, lim, instance if isinstance(
                    instance, str) else dims_key(instance), args))
        with self._lock:
            self._expire_dedup(now)
            was_empty = not self._pending
            for key, lim, dims, args in asks:
                did = args.dedup_id
                if did:
                    hit = self._dedup.get(did)
                    if hit is not None and hit[1] > now:
                        early.append((key, _outcome(
                            hit[0], args.quota_amount, lim["duration"])))
                        continue
                    free_exp = self._dedup_free.get(did)
                    if free_exp is not None and free_exp > now:
                        # first transmission committed GATE-OFF (granted
                        # freely, nothing consumed): dedup-id semantics
                        # replay that outcome on EVERY path — consuming
                        # fresh here would double-book the retransmission
                        early.append((key, QuotaResult(
                            granted_amount=args.quota_amount)))
                        continue
                if self._closed:   # post-swap drain raced the caller
                    early.append((key, QuotaResult(
                        granted_amount=0, status_code=14,  # UNAVAILABLE
                        status_message="quota pool closed by config "
                                       "swap")))
                    continue
                bucket = self._bucket_for(dims, lim, now)
                if bucket < 0:   # keyspace exhausted: fail closed
                    early.append((key, QuotaResult(
                        granted_amount=0, status_code=RESOURCE_EXHAUSTED,
                        status_message="quota keyspace exhausted")))
                    continue
                self._pending.append((bucket, int(args.quota_amount),
                                      bool(args.best_effort), lim["max"],
                                      lim["duration"], did, sink, key))
            # wake on the empty→non-empty edge (the worker idles in a
            # 100ms poll otherwise — a silent +100ms on every
            # low-rate quota RPC) and when a full batch is ready
            if self._pending and (
                    was_empty or len(self._pending) >= self._max_batch):
                self._wake.notify()
        return early

    def inline_begin(self, n: int, rows: list, now: float
                     ) -> "InlineQuotaSession | None":
        """Stage in-step quota rows for ONE check trip (the quota
        alloc rides the packed check program instead of its own
        serialized device trip — FusedPlan.packed_check_instep).

        `rows`: [(slot, name, instance, args)], slot < n indexing the
        check batch row (at most one quota per row — callers defer
        multi-quota requests to the classic pool path). Returns a
        session HOLDING the pool's counter token until commit/abort,
        or None when the pool is closed (callers fall back). Rows
        resolved without the trip — dedup replays, unknown quota
        names, keyspace exhaustion — land in session.early and their
        array rows stay inactive; in-batch duplicate dedup ids replay
        the first row's outcome at commit (the _flush first_of rule).
        """
        self._counts_lock.acquire()
        sess = InlineQuotaSession(self, n)
        try:
            with self._lock:
                if self._closed:
                    self._counts_lock.release()
                    return None
                sess.seq = self._seq_next
                self._seq_next += 1
                sess.prev_counts = self.counts
                self._expire_dedup(now)
                first_of: dict[str, int] = {}
                for slot, name, instance, args in rows:
                    lim = self.limits.get(name)
                    if lim is None:
                        sess.early[slot] = QuotaResult(
                            granted_amount=0,
                            status_code=RESOURCE_EXHAUSTED,
                            status_message=f"unknown quota {name}")
                        continue
                    did = args.dedup_id
                    if did:
                        hit = self._dedup.get(did)
                        if hit is not None and hit[1] > now:
                            sess.early[slot] = _outcome(
                                hit[0], args.quota_amount,
                                lim["duration"])
                            continue
                        free_exp = self._dedup_free.get(did)
                        if free_exp is not None and free_exp > now:
                            # gate-off outcome replay (see alloc)
                            sess.early[slot] = QuotaResult(
                                granted_amount=int(
                                    args.quota_amount))
                            continue
                        if did in first_of:
                            sess.replay_of[slot] = (first_of[did],
                                                    lim["duration"])
                            continue
                        if did in self._dedup_pending:
                            # consumed by a dispatched-but-uncommitted
                            # session: resolve from the cache at OUR
                            # (later) commit turn — never re-consume
                            sess.pending_replay[slot] = \
                                (did, lim["duration"],
                                 int(args.quota_amount))
                            continue
                    bucket = self._bucket_for(dims_key(instance),
                                              lim, now)
                    if bucket < 0:
                        sess.early[slot] = QuotaResult(
                            granted_amount=0,
                            status_code=RESOURCE_EXHAUSTED,
                            status_message="quota keyspace exhausted")
                        continue
                    if did:
                        first_of[did] = slot
                        self._dedup_pending[did] = sess.seq
                    sess.stage(slot, bucket, args, lim, did, now)
            sess.now = now
            return sess
        except BaseException:
            self._counts_lock.release()
            if sess.seq >= 0:   # consume the turn or later sessions wedge
                sess._take_turn()
                sess._end_turn()
            raise

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._wake.notify()
        self._thread.join(timeout=5)
        # the worker flushes pending work before exiting; anything
        # still queued (worker died) must not hang callers
        with self._lock:
            leftovers, self._pending = self._pending, []
        self._deliver([(sink, key, QuotaResult(
            granted_amount=0, status_code=14,
            status_message="quota pool closed"))
            for *_x, sink, key in leftovers])

    def audit_view(self) -> dict:
        """Sampled counter-plane reading for the mesh audit plane
        (runtime/audit.py quota_conservation). Copies the CURRENT
        counter handle reference and host bookkeeping under the locks
        (briefly, in the documented _counts_lock→_lock order), then
        pulls OUTSIDE both: counter arrays are functional — every trip
        swaps the pool onto a NEW handle rather than mutating this one
        — so a blocked pull only ever delays the auditor, never the
        serving path. Returns raw facts; the auditor judges. Cell
        invariants that hold regardless of tick staleness: every cell
        is >= 0, every cell is <= the pool's largest window max (each
        alloc caps in-window usage at max, so no single slot can ever
        accrue more), and cells beyond the allocated bucket range are
        exactly 0. The exact used<=max recount runs against the HOST
        memquota oracle (adapters/memquota._Window.used), which owns
        window gc — raw device row sums may legitimately include
        not-yet-reclaimed slots from expired ticks."""
        with self._counts_lock:
            with self._lock:
                handle = self.counts
                n_used = len(self._bucket_of)
        arr = np.asarray(handle)
        max_limit = max((l["max"] for l in self.limits.values()),
                        default=0)
        used = arr[:n_used] if n_used else arr[:0]
        beyond = arr[n_used:]
        return {
            "n_buckets": self.n_buckets,
            "n_used": n_used,
            "max_limit": int(max_limit),
            "negative_cells": int((arr < 0).sum()),
            "max_cell": int(used.max()) if used.size else 0,
            "over_cap_cells": int((used > max_limit).sum())
            if used.size else 0,
            "nonzero_beyond_keymap": int((beyond != 0).sum()),
        }

    # -- internals ------------------------------------------------------

    def _prewarm(self) -> None:
        # every program the SERVING path can hit; the scan oracle is
        # deliberately absent (never serving-selected, so its compile
        # would be pure startup cost). A host plane, as _trip passes
        # it: a device array is another signature, a retrace mid-serve
        for pn in {self._small_batch, self._max_batch}:
            for trip in self._trips.values():
                # all-inactive batch: grants nothing, counters unchanged
                _, self.counts = trip(self.counts,
                                      np.zeros((8, pn), np.int32))
        jax.block_until_ready(self.counts)

    def _bucket_for(self, key: str, lim: Mapping[str, Any],
                    now: float) -> int:
        b = self._bucket_of.get(key)
        if b is None:
            if len(self._bucket_of) >= self.n_buckets:
                return -1
            b = len(self._bucket_of)
            self._bucket_of[key] = b
            dur = lim["duration"]
            if dur > 0:
                tl = dur / self.k_ticks    # _Window.tick_len parity
                tick0 = int(now / tl)
                self._tick_len[b] = tl
                self._tick_base[b] = tick0
                self._last_tick[b] = tick0
        return b

    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._pending and not self._closed:
                    self._wake.wait(timeout=0.1)
                if self._closed and not self._pending:
                    return
                # batch-window timing is a TRANSPORT concern — always
                # wall clock. The injectable self._clock is quota
                # SEMANTICS (window ticks, dedup expiry); driving the
                # collect loop with it meant a frozen test clock never
                # expired the window and futures hung once arrivals
                # stopped short of a full batch
                deadline = time.monotonic() + self._window_s
                while (len(self._pending) < self._max_batch
                       and not self._closed):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._wake.wait(timeout=remaining)
                batch = self._pending[:self._max_batch]
                del self._pending[:len(batch)]
            if batch:
                try:
                    self._flush(batch)
                except Exception as exc:   # pragma: no cover
                    log.exception("quota flush failed")
                    self._deliver([(sink, key, QuotaResult(
                        granted_amount=0, status_code=13,
                        status_message=f"quota alloc failed: {exc}"))
                        for *_x, sink, key in batch])

    def _flush(self, batch: list) -> None:
        """One batch of pending asks: replays and grants are worked out
        under the locks, and every answer is handed to its sink after
        the last of them is let go (_deliver)."""
        t0 = time.perf_counter()
        now = self._clock()
        # mesh event timeline (runtime/forensics.py): a flush trip is
        # a control-plane event a concurrent request's tail can ride
        # behind; coalesced so a quota-heavy window is one ring entry
        from istio_tpu.runtime import forensics
        forensics.record_event("quota_flush", coalesce_s=0.25,
                               items=len(batch))
        # dedup WITHIN the window too: a sidecar retransmission can land
        # in the same batch as its original, before _flush has written
        # the dedup cache — memquota's mutex serializes those, replaying
        # the first outcome without consuming (buildWithDedup :259)
        first_of: dict[str, int] = {}
        replay_items: list[tuple[Any, int]] = []   # (item, kept index)
        deferred: list = []   # dedup id held by an uncommitted session
        kept: list = []
        done: list = []       # (sink, key, QuotaResult)
        with self._lock:
            for item in batch:
                dedup_id = item[5]
                if dedup_id:
                    # re-check the cache under the lock: a
                    # retransmission that raced the ORIGINAL's flush
                    # (alloc() checked before the cache was written)
                    # must replay, not re-consume
                    hit = self._dedup.get(dedup_id)
                    if hit is not None and hit[1] > now:
                        done.append((item[6], item[7], _outcome(
                            hit[0], item[1], item[4])))
                        continue
                    free_exp = self._dedup_free.get(dedup_id)
                    if free_exp is not None and free_exp > now:
                        # gate-off outcome: replay grant-freely (the
                        # deferred-past-a-gate-off-commit case lands
                        # here on its re-flush)
                        done.append((item[6], item[7], QuotaResult(
                            granted_amount=item[1])))
                        continue
                    if dedup_id in self._dedup_pending:
                        # consumed by a dispatched-but-uncommitted
                        # in-step session: memquota's mutex would
                        # serialize and REPLAY — defer this item past
                        # the session's commit (re-queued below; the
                        # next flush resolves it from the cache, or
                        # consumes fresh if the session aborted)
                        deferred.append(item)
                        continue
                    if dedup_id in first_of:
                        replay_items.append((item, first_of[dedup_id]))
                        continue
                    first_of[dedup_id] = len(kept)
                kept.append(item)
        if kept:
            granted = self._trip(kept, now)
            with self._lock:
                for (_, amount, _, _, duration, dedup_id, sink, key), g \
                        in zip(kept, granted):
                    if dedup_id:
                        self._remember(dedup_id, g, duration, now)
                    done.append((sink, key, _outcome(g, amount, duration)))
            for (_, amount, _, _, duration, _, sink, key), k \
                    in replay_items:
                done.append((sink, key,
                             _outcome(granted[k], amount, duration)))
        self._deliver(done)
        self._requeue_deferred(deferred)
        monitor.QUOTA_FLUSH_ROWS.inc(len(done))
        monitor.QUOTA_FLUSH_SECONDS.observe(time.perf_counter() - t0)

    def _trip(self, batch: list, now: float) -> list[int]:
        """Stage `batch` (pending asks, no replays among them), run the
        alloc on the device, pull → the granted amount of each ask."""
        n = len(batch)
        # pad to one of TWO fixed shapes: every distinct shape is its
        # own XLA compile (a second or more each), and a
        # mid-serve compile stalls every quota future behind it past
        # client deadlines (observed r4: variable pow-2 pads produced a
        # fresh compile per arrival-burst size and 60s quota waits)
        pn = self._small_batch if n <= self._small_batch \
            else self._max_batch
        planes = np.zeros((8, pn), np.int32)
        buckets, amounts, be, mx, active, ticks, lasts, rolling = planes
        # The tick/last staging and the roll application MUST happen
        # under _lock INSIDE the _counts_lock critical section, ordered
        # exactly like InlineQuotaSession.stage (ADVICE r5): _last_tick
        # is shared with in-step sessions, and a flush that read it
        # outside the locks could stage a stale `last` (the device
        # kernel then re-rolls slots holding fresh consumption — an
        # over-grant) or regress it after a session's optimistic
        # advance (under-grant). _counts_lock serializes this trip
        # against session dispatch; _lock orders the host bookkeeping.
        # The update is OPTIMISTIC like stage()'s: the dispatched
        # program rolls every active row's bucket unconditionally, so
        # host _last_tick and the device slots agree for whatever trip
        # chains next, on either path.
        with self._counts_lock:
            with self._lock:
                for i, (b_, a_, e_, m_, *_rest) in enumerate(batch):
                    buckets[i], amounts[i], be[i], mx[i] = \
                        b_, a_, e_, m_
                    active[i] = True
                    tl = self._tick_len[b_]
                    if tl > 0:
                        # absolute tick boundary = host adapter's
                        # _Window (floor(now / tick_len)); device gets
                        # REBASED int32s
                        abs_tick = int(now / tl)
                        base = int(self._tick_base[b_])
                        ticks[i] = abs_tick - base
                        lasts[i] = int(self._last_tick[b_]) - base
                        rolling[i] = True
                        self._last_tick[b_] = abs_tick
            # sequential-within-batch semantics only matter when a
            # bucket repeats — rare at 100k-key scale. Contended
            # batches where every amount is 1 (the dominant rate-limit
            # shape) take the parallel rank kernel; other contended
            # batches the segmented prefix-sum kernel (deterministic
            # ao-before-be amount-ascending intra-window order —
            # quota_alloc.step_seg). The O(B) scan is a test/bench
            # parity oracle only: NO serving-reachable input selects
            # it.
            if len(np.unique(buckets[:n])) < n:
                all_unit = bool((amounts[:n] == 1).all())   # hotpath: sync-ok (host numpy)
                trip = self._trips["unit" if all_unit else "seg"]
            else:
                trip = self._trips["fast"]
            granted, self.counts = trip(self.counts, planes)
            # the worker's designated pull — hotpath: sync-ok
            granted = np.asarray(granted)   # hotpath: sync-ok
        return granted[:n].tolist()

    def _requeue_deferred(self, deferred: list) -> None:
        """Items whose dedup id was held by a dispatched-but-
        uncommitted in-step session: re-queue for the next flush (the
        session commits in its dispatch-order turn — typically within
        one device trip — after which the cache replays the outcome,
        or a fresh consume runs if the session aborted). A closing
        pool resolves them immediately instead of spinning."""
        if not deferred:
            return
        with self._lock:
            if not self._closed:
                self._pending.extend(deferred)
                self._wake.notify()
                return
        self._deliver([(sink, key, QuotaResult(
            granted_amount=0, status_code=14,
            status_message="quota pool closed"))
            for *_x, sink, key in deferred])

    @staticmethod
    def _deliver(done: list) -> None:
        """Hand each sink its [(key, QuotaResult)] in one resolve(): a
        future's callbacks and a group's answers (the native front's
        response build and send) run here, with NO pool lock held, so an
        ask waiting at the pool's lock never waits them out."""
        by_sink: dict = {}
        for sink, key, result in done:
            by_sink.setdefault(sink, []).append((key, result))
        for sink, pairs in by_sink.items():
            try:
                sink.resolve(pairs)
            except Exception:   # a sink must not kill the worker
                log.exception("quota sink failed")

    def _remember(self, dedup_id: str, granted: int | None,
                  duration: float, now: float) -> None:
        """Cache a dedup id's outcome (under _lock): `granted` of a
        consumed ask in `_dedup`, or None for a gate-off one (granted
        freely, nothing consumed) in `_dedup_free`; its expiry joins
        its lifetime's FIFO."""
        life = max(duration, self.min_dedup_s)
        expiry = now + life
        if granted is None:
            self._dedup_free[dedup_id] = expiry
        else:
            self._dedup[dedup_id] = (granted, expiry)
        fifo = self._dedup_fifos.get(life)
        if fifo is None:
            fifo = self._dedup_fifos[life] = collections.deque()
        fifo.append((expiry, dedup_id, granted is None))

    def _expire_dedup(self, now: float) -> None:
        """Drop the expired dedup entries from the front of each
        lifetime's FIFO (under _lock): amortised O(1) an entry, no walk
        of the cache at any size. A record drops only the value it
        wrote: an id written again since keeps its newer entry. Writes
        come close to, not exactly in, expiry order (a flush and an
        in-step commit each stamp their own `now`): a record behind an
        unexpired one waits for it, and lookups test the expiry."""
        for fifo in self._dedup_fifos.values():
            while fifo and fifo[0][0] <= now:
                expiry, did, free = fifo.popleft()
                if free:
                    if self._dedup_free.get(did) == expiry:
                        del self._dedup_free[did]
                else:
                    hit = self._dedup.get(did)
                    if hit is not None and hit[1] == expiry:
                        del self._dedup[did]


class QuotaFuture:
    """Tiny thread-safe future. The sync gRPC front blocks in
    result(); the aio front registers a callback via add_done_callback
    and awaits — holding an executor thread per in-flight quota would
    serialize the event loop behind ~5 threads × a device RTT each
    (observed: served throughput collapsed 6× when it did)."""

    def __init__(self) -> None:
        self._ev = threading.Event()
        self._value: QuotaResult | None = None
        self._cbs: list = []
        self._lock = threading.Lock()

    def set(self, value: QuotaResult) -> None:
        with self._lock:
            self._value = value
            self._ev.set()
            cbs, self._cbs = self._cbs, []
        for cb in cbs:
            try:
                cb(value)
            except Exception:   # callbacks must not kill the worker
                log.exception("quota future callback failed")

    def resolve(self, pairs: list) -> None:
        """The pool's sink (DeviceQuotaPool.alloc_many): one ask."""
        (_key, value), = pairs
        self.set(value)

    def add_done_callback(self, cb) -> None:
        """cb(QuotaResult) — fires immediately if already resolved,
        else from the pool worker thread on set()."""
        with self._lock:
            if not self._ev.is_set():
                self._cbs.append(cb)
                return
            value = self._value
        cb(value)

    def result(self, timeout: float | None = 30.0) -> QuotaResult:
        if not self._ev.wait(timeout):
            raise TimeoutError("quota allocation timed out")
        assert self._value is not None
        return self._value

    def done(self) -> bool:
        return self._ev.is_set()


class InlineQuotaSession:
    """One check trip's staged in-step quota work (pipelined).

    Lifecycle: inline_begin (stage, token held) → dispatched(new)
    (pool.counts swaps to the trip's DEVICE FUTURE and the token
    releases — the next trip chains on-device, so trips overlap on
    the transport) → commit(granted, gate) in dispatch order (the
    commit turn serializes dedup-cache writes and pending replays).
    Tick bookkeeping is optimistic at stage time: the dispatched
    program rolls every staged row's bucket unconditionally (only the
    ALLOC is gated via zeroed amounts), so host _last_tick and device
    slots agree for chained trips. A trip that fails AFTER dispatch
    restores the last known-good counter handle; its optimistic tick
    advances then under-grant (never over-grant) for at most one
    window — the documented device-failure tradeoff.

    Result parity (memquota/dispatcher semantics): gate-off rows grant
    the requested amount freely WITHOUT consuming (dispatcher.quota's
    no-matching-rule tail); dedup ids cache only consumed outcomes."""

    def __init__(self, pool: DeviceQuotaPool, n: int) -> None:
        self.pool = pool
        self.n = n
        self.now = 0.0
        self.seq = -1
        self.prev_counts: Any = None
        self.new_counts: Any = None
        self.early: dict[int, QuotaResult] = {}
        self.replay_of: dict[int, tuple[int, float]] = {}
        # slot → (dedup id, duration, requested amount): same-id rows
        # racing a dispatched-but-uncommitted session
        self.pending_replay: dict[int, tuple[str, float, int]] = {}
        self._staged: dict[int, tuple] = {}   # slot → (amount, dur, did)
        self.buckets = np.zeros(n, np.int32)
        self.amounts = np.zeros(n, np.int32)
        self.be = np.zeros(n, bool)
        self.mx = np.zeros(n, np.int32)
        self.active = np.zeros(n, bool)
        self.ticks = np.zeros(n, np.int32)
        self.lasts = np.zeros(n, np.int32)
        self.rolling = np.zeros(n, bool)
        self._token_held = True
        self._done = False

    def stage(self, slot: int, bucket: int, args: QuotaArgs,
              lim: Mapping[str, Any], dedup_id: str,
              now: float) -> None:
        """Called under pool._lock (inline_begin)."""
        p = self.pool
        self.buckets[slot] = bucket
        self.amounts[slot] = int(args.quota_amount)
        self.be[slot] = bool(args.best_effort)
        self.mx[slot] = lim["max"]
        self.active[slot] = True
        tl = p._tick_len[bucket]
        if tl > 0:
            abs_tick = int(now / tl)
            base = int(p._tick_base[bucket])
            self.ticks[slot] = abs_tick - base
            self.lasts[slot] = int(p._last_tick[bucket]) - base
            self.rolling[slot] = True
            # OPTIMISTIC: the dispatched program rolls this bucket to
            # abs_tick regardless of the alloc gate — chained trips
            # must stage against the post-roll state
            p._last_tick[bucket] = abs_tick
        self._staged[slot] = (int(args.quota_amount), lim["duration"],
                              dedup_id)

    def dispatched(self, new_counts) -> None:
        """The program is in flight: swap the pool onto its output
        future and release the token — the next trip chains on it."""
        self.new_counts = new_counts
        self.pool.counts = new_counts
        self._token_held = False
        self.pool._counts_lock.release()

    def _take_turn(self) -> None:
        cv = self.pool._commit_cv
        with cv:
            while self.pool._commit_turn != self.seq:
                cv.wait(timeout=1.0)

    def _end_turn(self) -> None:
        cv = self.pool._commit_cv
        with cv:
            self.pool._commit_turn = self.seq + 1
            cv.notify_all()

    def commit(self, granted: np.ndarray, gate: np.ndarray
               ) -> dict[int, QuotaResult]:
        """granted/gate: the pulled per-row outputs. Returns
        {slot → QuotaResult} for staged/replay/pending rows (merge
        with .early for the full picture)."""
        p = self.pool
        out: dict[int, QuotaResult] = {}
        self._take_turn()
        try:
            with p._lock:
                p._counts_good = self.new_counts
                for slot, (amount, duration, did) in \
                        self._staged.items():
                    if did:
                        p._dedup_pending.pop(did, None)
                    if not gate[slot]:
                        # no active quota rule for this request: grant
                        # the requested amount freely, consuming
                        # nothing (dispatcher.quota tail). The outcome
                        # is recorded in _dedup_free (NOT the consumed-
                        # outcome cache) so a same-id row that raced
                        # this session into pending_replay resolves
                        # grant-freely too, like a serialized memquota
                        # would
                        if did:
                            p._remember(did, None, duration, self.now)
                        out[slot] = QuotaResult(granted_amount=amount)
                        continue
                    g = int(granted[slot])
                    if did:
                        p._remember(did, g, duration, self.now)
                    out[slot] = _outcome(g, amount, duration)
                for slot, (did, duration, amount) in \
                        self.pending_replay.items():
                    hit = p._dedup.get(did)
                    free_exp = p._dedup_free.get(did)
                    if hit is not None and hit[1] > self.now:
                        out[slot] = _outcome(hit[0], amount, duration)
                    elif free_exp is not None and free_exp > self.now:
                        # consuming session committed GATE-OFF: the
                        # serialized outcome is grant-freely (this
                        # row's own requested amount, nothing
                        # consumed) — never "quota trip failed"
                        out[slot] = QuotaResult(granted_amount=amount)
                    else:
                        # the consuming session aborted (device
                        # failure): no outcome to replay
                        out[slot] = QuotaResult(
                            granted_amount=0, status_code=14,
                            status_message="quota trip failed")
            for slot, (first, duration) in self.replay_of.items():
                prior = out.get(first, self.early.get(first))
                if prior is None:   # first row resolved early w/o entry
                    prior = QuotaResult(granted_amount=0,
                                        status_code=RESOURCE_EXHAUSTED)
                out[slot] = prior
            return out
        finally:
            self._done = True
            self._end_turn()

    def abort(self) -> None:
        """Trip failed. Pre-dispatch: release the token, nothing
        changed. Post-dispatch: take the commit turn, drop pending
        markers, and restore the last known-good counter handle unless
        a later trip already chained past this one."""
        if self._done:
            return
        self._done = True
        p = self.pool
        if self._token_held:
            self._token_held = False
            p._counts_lock.release()
            # the turn MUST still be consumed or every later session
            # wedges behind this seq
            self._take_turn()
            self._end_turn()
            return
        self._take_turn()
        try:
            with p._lock:
                for _slot, (_a, _d, did) in self._staged.items():
                    if did:
                        p._dedup_pending.pop(did, None)
            with p._counts_lock:
                if p.counts is self.new_counts:
                    p.counts = p._counts_good
        finally:
            self._end_turn()


class DeviceQuotaTable:
    """Pool lifecycle with signature reuse across config generations
    (handlerTable.go pattern): an unchanged (handler signature) keeps
    its pool — and therefore its counters, keymap and dedup cache —
    across snapshot swaps."""

    def __init__(self, n_buckets: int = DEFAULT_BUCKETS,
                 jit: bool = True):
        self.n_buckets = n_buckets
        self.jit = jit
        self._by_sig: dict[str, DeviceQuotaPool] = {}

    def rebuild(self, snapshot) -> tuple[dict[str, DeviceQuotaPool],
                                         list[DeviceQuotaPool]]:
        """→ (handler qname → pool, orphaned pools to close)."""
        out: dict[str, DeviceQuotaPool] = {}
        new_sigs: dict[str, DeviceQuotaPool] = {}
        for qname, hc in snapshot.handlers.items():
            if hc.adapter != "memquota":
                continue
            quotas = {str(q.get("name", "")): q
                      for q in hc.params.get("quotas", ())}
            if not quotas:
                continue
            sig = hc.signature
            pool = self._by_sig.get(sig) or new_sigs.get(sig)
            if pool is None:
                pool = DeviceQuotaPool(
                    quotas, n_buckets=self.n_buckets,
                    min_dedup_s=float(hc.params.get(
                        "min_deduplication_duration_s", 1.0)),
                    jit=self.jit)
            new_sigs[sig] = pool
            out[qname] = pool
        orphans = [p for sig, p in self._by_sig.items()
                   if sig not in new_sigs]
        self._by_sig = new_sigs
        return out, orphans

    def close(self) -> None:
        for p in self._by_sig.values():
            p.close()
        self._by_sig = {}
