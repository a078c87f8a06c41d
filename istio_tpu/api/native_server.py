"""Native Mixer front-end — the C++ HTTP/2 wire + python engine pumps.

The data-plane component SURVEY §2.9 implication (a) owes: unary
istio.mixer.v1.Mixer/Check|Report terminated in C++
(native/httpd.cpp — connections, HTTP/2 framing, HPACK, gRPC framing,
envelope split, adaptive batch formation, response framing), with
python doing only per-BATCH engine work through the same fused path
the grpc front uses. Reference anchor: mixer/pkg/api/grpcServer.go:118
(Check), :262 (Report) — same request semantics (precondition check +
per-quota loop with dedup ids), different transport economics: the
python-grpc front pays ~0.4 ms of interpreter per RPC; this front pays
it once per batch.

Pump threads block in h2srv_take (ctypes releases the GIL, so the C++
wire keeps running), run the batch through
RuntimeServer.check_batch_preprocessed / report, resolve quotas via
the device pools, and hand serialized CheckResponse bytes back for
C++ to frame. A taken batch stays one buffer from the C++ queue to the
C++ tensorizer: take_impl writes a fixed-width row index in front of
the rows' bytes, the pump reads it with one np.frombuffer over its own
buffer (api/take.TakenRows: no copy of the take, no object a row), the
tensorizer gets the payloads' spans, and a row becomes a LazyWireBag
only where something on the host asks for it (a host action, a quota,
a row the host decides, a response whose bytes depend on its bag;
every row under an APA) — mixer_front_bags_materialised_total counts
them. Response serialization is memoized per verdict signature
(uniform traffic → a handful of distinct responses per snapshot), and
a batch that comes with its verdict classes (ClassedResponses) is
serialised and framed once a class, not once a row.
"""
from __future__ import annotations

import ctypes
import functools
import logging
import struct
import threading

import numpy as np

from istio_tpu.adapters.sdk import QuotaArgs
from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.grpc_server import MixerGrpcServer
from istio_tpu.api.take import CHECK, REPORT, TakenRows
from istio_tpu.native.build import ensure_httpd_built
from istio_tpu.runtime import forensics, monitor
from istio_tpu.runtime.server import RuntimeServer

log = logging.getLogger("istio_tpu.api.native")

_TAKE_TIMEOUT_MS = 200
_COUNTER_NAMES = ("requests_decoded", "responses_sent",
                  "batches_formed", "batch_rows", "in_flight",
                  "conns_opened", "conns_closed", "protocol_errors",
                  "bytes_in", "bytes_out")


class _RowRequest:
    """The slice of RawCheckRequest the quota loop reads."""

    __slots__ = ("deduplication_id", "quotas")

    def __init__(self, dedup: str, quotas: dict):
        self.deduplication_id = dedup
        self.quotas = quotas


# Rows of a batch that share their response's bytes are framed as one
# numpy record array from this many on; fewer take the per-row
# framing. A record array costs 4-5 us whatever its length, a row of
# struct.pack and its two appends ~0.6 us (timed on a CPU host, 90-byte
# responses: 8 rows 4.8 against 5.3 us, 64 rows 10 against 35).
_FRAME_CLASS_MIN_ROWS = 8


@functools.lru_cache(maxsize=256)
def _frame_dtype(length: int) -> np.dtype:
    """One completion of the blob h2srv_complete takes (tag, status,
    length, bytes), packed, for a response of `length` bytes."""
    return np.dtype([("tag", "<u8"), ("status", "<i4"), ("len", "<u4"),
                     ("raw", "u1", (length,))])


class _Completions(list):
    """A batch's completions on their way to h2srv_complete: the list
    holds (tag, status, bytes), one a row, framed at the send; rows
    that share an OK response's bytes are framed here, a class at a
    time, and counted beside it."""

    def __init__(self):
        super().__init__()
        self.framed: list[bytes] = []
        self.framed_tags: list[np.ndarray] = []
        self.n_framed = 0

    def frame(self, tags: np.ndarray, raw: bytes) -> None:
        rec = np.empty(len(tags), _frame_dtype(len(raw)))
        rec["tag"] = tags
        rec["status"] = 0
        rec["len"] = len(raw)
        rec["raw"] = np.frombuffer(raw, np.uint8)
        self.framed.append(rec.tobytes())
        self.framed_tags.append(tags)
        self.n_framed += len(tags)


# must mirror Server::kLatBuckets in httpd.cpp: the wire latency
# histogram's log-bucket count (bucket i covers ≤ 1µs·2^(i/8))
_LAT_BUCKETS = 192
# the order of Server::gaps in httpd.cpp: {count, sum_ns} a kind
_GAP_KINDS = ("starved", "silent", "io")


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(ensure_httpd_built())
    lib.h2srv_start.restype = ctypes.c_void_p
    lib.h2srv_start.argtypes = [ctypes.c_int32] * 3 + \
        [ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
         ctypes.c_int32]
    lib.h2srv_port.restype = ctypes.c_int32
    lib.h2srv_port.argtypes = [ctypes.c_void_p]
    lib.h2srv_latency.restype = None
    lib.h2srv_latency.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_int64)]
    lib.h2srv_take.restype = ctypes.c_int64
    lib.h2srv_take.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                               ctypes.c_char_p, ctypes.c_int64]
    lib.h2srv_complete.restype = None
    lib.h2srv_complete.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int64]
    lib.h2srv_counters.restype = None
    lib.h2srv_counters.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_int64),
                                   ctypes.POINTER(ctypes.c_int64)]
    lib.h2srv_queue_wait.restype = None
    lib.h2srv_queue_wait.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_int64)]
    lib.h2srv_gaps.restype = None
    lib.h2srv_gaps.argtypes = [ctypes.c_void_p,
                               ctypes.POINTER(ctypes.c_int64)]
    lib.h2srv_stop.restype = None
    lib.h2srv_stop.argtypes = [ctypes.c_void_p]
    lib.h2srv_quiesce.restype = None
    lib.h2srv_quiesce.argtypes = [ctypes.c_void_p]
    return lib


class _PumpStopped(Exception):
    """h2srv_take answered shutdown (or stop() set the flag)."""


class NativeMixerServer(MixerGrpcServer):
    """C++-wire Mixer server over a RuntimeServer core.

    Inherits the response/quota assembly from MixerGrpcServer (the
    single home of PreconditionResult/quota-loop semantics); replaces
    the grpcio transport entirely.
    """

    def __init__(self, runtime: RuntimeServer, port: int = 0,
                 max_batch: int = 1024, min_fill: int = 256,
                 window_us: int = 2000, pumps: int = 2,
                 continuous: bool = False, tls=None,
                 mtls_mode: str = "strict"):
        # deliberately NOT calling super().__init__ — no grpc.server
        # `continuous`: the C++ take policy never holds for min_fill/
        # window — an idle pump launches the next device step the
        # moment anything is queued (in-flight depth bounded by
        # `pumps`); the latency lane vs the occupancy-fill default
        self.runtime = runtime
        # `tls` (secure.mtls.ServingCerts): start a TLS-terminating
        # lane (secure/tlslane.py) in front of the C++ pump —
        # `secure_port` is what clients dial; the plaintext `port`
        # stays loopback-reachable so the pump's wire accounting and
        # every parity gate see byte-for-byte the plaintext stream.
        # Strict mode requires + verifies the client cert at the lane
        # handshake (connection-level authn; per-request identity→bag
        # lives on the gRPC fronts — see the tlslane module docstring).
        self._tls_lane = None
        self._tls_mode = mtls_mode
        self._tls_certs = tls
        self._ref_cache: dict = {}
        self._ref_cache_lock = threading.Lock()
        self._resp_memo: dict = {}
        self._lib = _load_lib()
        self.continuous = bool(continuous)
        self._h = self._lib.h2srv_start(port, max_batch, min_fill,
                                        window_us, pumps, 0,
                                        1 if continuous else 0)
        if not self._h:
            raise RuntimeError("h2srv_start failed (port in use?)")
        self.port = self._lib.h2srv_port(self._h)
        self._stop_flag = threading.Event()
        self._final_counters: dict | None = None
        self._final_latency: dict | None = None
        self._final_queue_wait = {"sum_ns": 0, "rows": 0}
        self._final_gaps = {kind: {"count": 0, "sum_ns": 0}
                            for kind in _GAP_KINDS}
        self._watched = False
        # serializes h2srv_complete against stop(): deferred quota
        # completions fire from pool-worker threads and must never
        # race the server teardown into a freed handle
        self._comp_lock = threading.Lock()
        self._pumps = [
            threading.Thread(target=self._pump_loop, daemon=True,
                             name=f"mixer-native-pump-{i}")
            for i in range(pumps)]

    # -- lifecycle --

    def start(self) -> int:
        # whatever was built since the runtime's constructor (a
        # blocking prewarm, this front) outlives every request
        monitor.settle_heap("start")
        monitor.pump_watch_start(self.gaps)
        self._watched = True
        for t in self._pumps:
            t.start()
        if self._tls_certs is not None:
            from istio_tpu.secure.tlslane import TlsTerminatingLane
            self._tls_lane = TlsTerminatingLane(
                self._tls_certs, self.port, mode=self._tls_mode)
            self.secure_port = self._tls_lane.start()
            log.info("native mixer server on port %d (tls lane :%d)",
                     self.port, self.secure_port)
        else:
            log.info("native mixer server on port %d", self.port)
        return self.port

    def tls_lane_stats(self) -> dict:
        """Connection/handshake accounting of the TLS terminating
        lane ({} when serving plaintext or already stopped)."""
        lane = self._tls_lane
        if lane is None:
            return {}
        with lane._lock:
            return dict(lane.stats)

    def stop(self, grace: float = 1.0) -> None:
        """Ordered graceful stop (the native leg of the lifecycle
        plane): quiesce intake → drain in-flight rows → join pumps →
        tear down the wire. Every submitted row resolves — to its real
        verdict during the drain window, or to a typed UNAVAILABLE
        rejection past it — never a silent drop."""
        if self._h is None:
            return
        import time as _time

        # 0. the TLS lane stops accepting first (quiesce ordering: the
        #    outermost intake closes before the pump's)
        if self._tls_lane is not None:
            self._tls_lane.stop()
            self._tls_lane = None
        # 1. stop intake: new wire requests answer UNAVAILABLE in C++;
        #    already-queued rows dispatch to the pumps immediately
        #    (no min_fill hold during a drain)
        self._lib.h2srv_quiesce(self._h)
        # 2. drain: wait for queued + dispatched + deferred-quota rows
        #    to complete (in_flight counts enqueue → completion-write)
        deadline = _time.monotonic() + grace
        while _time.monotonic() < deadline:
            if self.counters().get("in_flight", 0) <= 0:
                break
            _time.sleep(0.01)
        # 3. pumps must be out of h2srv_take before the handle is torn
        #    down
        self._stop_flag.set()
        for t in self._pumps:
            t.join(timeout=grace + 30)
        self._final_counters = self.counters()
        self._final_latency = self.latency_raw()
        self._final_queue_wait = self.queue_wait()
        self._final_gaps = self.gaps()
        if self._watched:
            self._watched = False
            monitor.pump_watch_stop(self.gaps)
        if any(t.is_alive() for t in self._pumps):
            # a pump is wedged mid-batch (device stall): freeing the
            # handle under it would turn a stall into a segfault —
            # leak the C++ server instead (it stays valid for the
            # straggler's h2srv_take/complete calls, and h2srv_stop's
            # own abi-call guard would leak it anyway)
            log.error("native server handle leaked: pump stuck "
                      "past %.0fs grace", grace + 30)
            return
        # 4. teardown: rows the drain deadline abandoned get typed
        #    rejections framed + flushed by the IO thread's shutdown
        #    drain; double-stop is a C++-side no-op
        with self._comp_lock:
            self._lib.h2srv_stop(self._h)
            self._h = None

    def counters(self) -> dict:
        with self._comp_lock:   # h2srv_complete's teardown guard too
            if self._h is None:   # post-stop: last snapshot, no NULL
                return dict(self._final_counters or {})
            c = (ctypes.c_int64 * 10)()
            hist = (ctypes.c_int64 * 16)()
            self._lib.h2srv_counters(self._h, c, hist)
        out = dict(zip(_COUNTER_NAMES, [int(v) for v in c]))
        out["batch_size_hist"] = {1 << b: int(hist[b])
                                  for b in range(16) if hist[b]}
        self._publish_counters(out)
        return out

    def queue_wait(self) -> dict:
        """How long rows waited in the C++ queue for a free pump:
        {"sum_ns", "rows"}, cumulative since start — summed at the
        moment h2srv_take hands rows over (take time − the row's
        enqueue stamp). Delta two reads for a window's mean."""
        with self._comp_lock:
            if self._h is None:
                return dict(self._final_queue_wait)
            out = (ctypes.c_int64 * 2)()
            self._lib.h2srv_queue_wait(self._h, out)
        return {"sum_ns": int(out[0]), "rows": int(out[1])}

    def gaps(self) -> dict:
        """Gaps of 0.2 s or more that the C++ front saw, with no
        python involved: {"starved" | "silent" | "io": {"count",
        "sum_ns"}}, cumulative. starved: rows waited in the queue
        and no pump took them (measured at the handover that ended
        it); silent: the server had answered everything and the client
        sent nothing (measured at the enqueue that ended it: the
        client's stall, not the server's); io: the IO thread itself
        did not run between two polls. The pump watch
        (monitor.pump_watch_start) reads it once a tick."""
        with self._comp_lock:
            if self._h is None:
                return self._final_gaps
            out = (ctypes.c_int64 * 6)()
            self._lib.h2srv_gaps(self._h, out)
        return {kind: {"count": int(out[2 * i]),
                       "sum_ns": int(out[2 * i + 1])}
                for i, kind in enumerate(_GAP_KINDS)}

    # -- wire latency (the measured wire-to-verdict plane) --

    def latency_raw(self) -> dict:
        """Cumulative wire-to-verdict histogram straight off the C++
        ABI: {"buckets": [n]*192, "min_ns", "max_ns"}. Bucket i counts
        requests whose frame-decode → response-frame-write latency was
        ≤ 1µs·2^(i/8). Use as the `since` baseline for per-window
        quantiles via latency_snapshot(since=...)."""
        with self._comp_lock:
            if self._h is None:
                return dict(self._final_latency or {
                    "buckets": [0] * _LAT_BUCKETS,
                    "min_ns": 0, "max_ns": 0})
            buckets = (ctypes.c_int64 * _LAT_BUCKETS)()
            mm = (ctypes.c_int64 * 2)()
            self._lib.h2srv_latency(self._h, buckets, mm)
        return {"buckets": [int(v) for v in buckets],
                "min_ns": int(mm[0]), "max_ns": int(mm[1])}

    @staticmethod
    def _quantiles(buckets: list, qs=(0.50, 0.95, 0.99)) -> dict:
        """Quantiles (ms) from the log-bucket counts, geometric-mean
        interpolated within the landing bucket (bucket ratio 2^(1/8)
        → ≤ ±4.5% quantile error by construction)."""
        total = sum(buckets)
        out = {"n": total}
        for q in qs:
            key = "p" + f"{q * 100:g}".replace(".", "")
            if not total:
                out[key] = 0.0
                continue
            target = q * total
            acc = 0
            idx = len(buckets) - 1
            for i, n in enumerate(buckets):
                acc += n
                if acc >= target:
                    idx = i
                    break
            # bucket i spans (2^((i-1)/8), 2^(i/8)] µs → report the
            # geometric midpoint, in ms
            hi = 2.0 ** (idx / 8.0)
            lo = hi / (2.0 ** 0.125) if idx else hi / 2.0
            out[key] = round((lo * hi) ** 0.5 / 1000.0, 4)
        return out

    def latency_snapshot(self, since: dict | None = None) -> dict:
        """Wire-to-verdict latency quantiles — cumulative, or the
        DELTA vs a latency_raw() baseline (per-window reads).
        The measurement is taken entirely in C++ (frame decode →
        response frame write), so it is the one number that holds the
        whole of a request's stay: the wait in the C++ queue for a
        free pump (queue_wait()), then the pump's cycle from the take
        on (spans wire_decode → the check stages → serialize → send,
        monitor.latency_snapshot()["spans"]), then the IO thread's
        framing. Bucketed: quantiles are within ±4.5 %."""
        raw = self.latency_raw()
        buckets = raw["buckets"]
        if since is not None:
            buckets = [a - b for a, b in
                       zip(buckets, since.get("buckets", []))]
            if len(buckets) != _LAT_BUCKETS:
                buckets = raw["buckets"]
        snap = self._quantiles(buckets)
        # min/max scoped to the SAME window as the quantiles: the
        # geometric bounds of the extreme non-empty delta buckets
        # (bucket-resolution, ±9%). The exact lifetime extremes ride
        # under explicit *_lifetime names — mixing scopes silently
        # made a warmup-era outlier look like a window straggler.
        nz = [i for i, v in enumerate(buckets) if v > 0]
        if nz:
            lo_hi = 2.0 ** (nz[0] / 8.0) / 1000.0
            snap["min_ms"] = round(
                (lo_hi / (2.0 ** 0.125) if nz[0] else lo_hi / 2.0),
                4)
            snap["max_ms"] = round(2.0 ** (nz[-1] / 8.0) / 1000.0, 4)
        else:
            snap["min_ms"] = snap["max_ms"] = 0.0
        snap["min_ms_lifetime"] = round(raw["min_ns"] / 1e6, 4)
        snap["max_ms_lifetime"] = round(raw["max_ns"] / 1e6, 4)
        snap["raw"] = raw      # pass-through: the next window's base
        self._publish_latency(snap)
        return snap

    _LAT_GAUGES: dict = {}

    def _publish_latency(self, snap: dict) -> None:
        """Mirror the wire quantiles into the shared registry
        (mixer_native_wire_p{50,95,99}_ms + count) so /metrics carries
        the measured wire-to-verdict numbers."""
        from istio_tpu.utils import metrics as hostmetrics

        with NativeMixerServer._NATIVE_GAUGES_LOCK:
            g = NativeMixerServer._LAT_GAUGES
            if not g:
                for k, name, desc in (
                        ("p50", "mixer_native_wire_p50_ms",
                         "wire-to-verdict p50 ms"),
                        ("p95", "mixer_native_wire_p95_ms",
                         "wire-to-verdict p95 ms"),
                        ("p99", "mixer_native_wire_p99_ms",
                         "wire-to-verdict p99 ms"),
                        ("n", "mixer_native_wire_latency_count",
                         "wire-to-verdict observations")):
                    g[k] = hostmetrics.default_registry.gauge(
                        name, f"native front {desc}")
        for k in ("p50", "p95", "p99", "n"):
            if k in snap:
                g[k].set(float(snap[k]))

    # gauges (not counters): the C++ side owns the monotonic totals,
    # we mirror absolute snapshots — lazily created so merely importing
    # this module never registers native metrics. The lock serializes
    # first-use registration: an introspect scrape thread and a bench
    # thread racing the init would double-register the families (a
    # malformed exposition forever) or KeyError on a half-built dict.
    _NATIVE_GAUGES: dict = {}
    _NATIVE_GAUGES_LOCK = threading.Lock()

    def _publish_counters(self, snap: dict) -> None:
        """Mirror the C++ wire counters into the shared homegrown
        registry so /metrics covers the native front end (previously
        these lived only in this ad-hoc dict — invisible to scrapes).
        Called on every counters() read; the introspect server reads
        counters() before each exposition."""
        from istio_tpu.utils import metrics as hostmetrics

        with NativeMixerServer._NATIVE_GAUGES_LOCK:
            gauges = NativeMixerServer._NATIVE_GAUGES
            if not gauges:
                for name in _COUNTER_NAMES:
                    gauges[name] = hostmetrics.default_registry.gauge(
                        f"mixer_native_{name}",
                        f"native front-end wire counter {name}")
                gauges["batch_size_hist"] = \
                    hostmetrics.default_registry.gauge(
                        "mixer_native_batch_rows_bucketed",
                        "native front-end batch counts by power-of-two "
                        "size bucket (label: bucket; per-bucket point "
                        "values, NOT a cumulative histogram ladder)")
        for name in _COUNTER_NAMES:
            gauges[name].set(float(snap.get(name, 0)))
        # label is `bucket`, not `le`: these are per-bucket point
        # counts — `le` is reserved for cumulative histogram series
        # and would silently break histogram_quantile()
        for bucket, n in snap.get("batch_size_hist", {}).items():
            gauges["batch_size_hist"].set(float(n), bucket=str(bucket))

    # -- pump --

    def _pump_loop(self) -> None:
        """One pump. A cycle (span `pump_cycle`) runs from the first
        h2srv_take after the previous batch's completions went out to
        the return of this batch's _send_completions; inside it the
        top-level spans take_wait, wire_decode, the check stages
        (monitor.CHECK_STAGES), serialize and send tile the wall, so
        what no span covers is one subtraction (the benchmark's
        pump_unaccounted_ms_per_batch)."""
        take = [ctypes.create_string_buffer(1 << 23)]
        monitor.pump_enter(self._pumps.index(threading.current_thread()))
        try:
            while True:
                with monitor.span("pump_cycle"):
                    with monitor.span("take_wait"):
                        self._take(take)
                    try:
                        self._run_batch(take[0])
                    except Exception:
                        log.exception("native pump batch failed")
        except _PumpStopped:
            return
        finally:
            monitor.pump_leave()

    def _take(self, take: list) -> int:
        """Block in h2srv_take until it hands this pump a batch: empty
        time-outs and buffer growth are part of the wait. `take` holds
        the pump's one buffer, replaced here when a batch outgrows it.
        Returns the bytes taken into take[0]. Raises _PumpStopped at
        shutdown, which closes the open spans unobserved."""
        while not self._stop_flag.is_set():
            n = self._lib.h2srv_take(self._h, _TAKE_TIMEOUT_MS,
                                     take[0], len(take[0]))
            if n == -1:
                break
            if n > 0:
                return n
            if n < 0:          # buffer too small: grow and retry
                take[0] = ctypes.create_string_buffer(-int(n) * 2)
        raise _PumpStopped

    _read_take = staticmethod(TakenRows.read)

    @staticmethod
    def _unanswered(tags: np.ndarray, completions: _Completions,
                    deferred: set) -> list[int]:
        """The tags of a take that no completion names and no deferred
        quota row holds: exact, by tag and not by count (a fault may
        answer one row twice and another never)."""
        done = [np.fromiter((c[0] for c in completions), np.uint64,
                            len(completions)),
                np.fromiter(deferred, np.uint64, len(deferred)),
                *completions.framed_tags]
        return tags[~np.isin(tags, np.concatenate(done))].tolist()

    def _run_batch(self, buf) -> None:
        """One taken batch: the blob h2srv_take wrote into the pump's
        buffer `buf` (api/take.py: a row index in front of the rows'
        bytes), read where it lies. The pump does not take again
        before this batch's completions are sent, so the buffer is
        stable for the batch's life; what outlives it (the bag of a
        deferred quota row, of a tapped row) owns a copy of its
        bytes. Checks and reports are split by the index's `kind`
        column; a row becomes a python object only where something on
        the host asks for it."""
        taken = None
        completions = _Completions()
        deferred: set[int] = set()
        try:
            with monitor.span("wire_decode") as decode:
                taken = self._read_take(buf)
                checks = taken.of_kind(CHECK)
                # the batch itself when the snapshot has no APA; with
                # one, every row's bag goes through it
                bags = self.runtime.preprocess_batch(checks)
            if len(checks):
                # flight-recorder pre-mark: the wire→bag decode wall
                # joins the next batch tape on this pump thread
                # (httpd.cpp's t_decode_ns covers the C++ side; this
                # is the python envelope's share)
                forensics.RECORDER.note_wire_decode(decode.seconds)
            self._run_batch_inner(taken, checks, bags, completions,
                                  deferred)
        except Exception:
            # belt: NO failure may abandon a row — an unanswered tag
            # hangs its client until deadline AND leaks the C++
            # in_flight count (one bad request must not poison its
            # batch-mates' connections)
            log.exception("native pump batch failed")
        with monitor.span("send"):
            if taken is not None:
                completions.extend(
                    (tag, 13, b"internal: batch processing failed")
                    for tag in self._unanswered(taken.tags, completions,
                                                deferred))
            self._send_completions(completions)

    def _run_batch_inner(self, taken: TakenRows, checks: TakenRows,
                         bags, completions: list,
                         deferred: set) -> None:
        from istio_tpu.utils import tracing

        def first_parent(rows: TakenRows):
            # first row whose header PARSES (a malformed header in an
            # earlier row must not suppress a valid one behind it);
            # only rows that sent one are looked at
            return next(
                (p for p in map(tracing.parent_from_traceparent,
                                rows.traceparents())
                 if p is not None), None)

        reports = taken.of_kind(REPORT)

        if len(checks):
            # ROOT span at wire decode (API-layer root, same role as
            # the grpc fronts' rpc.check): downstream engine spans on
            # this pump thread parent under it via the thread-local
            # stack, so the batch's queue/tensorize/device time is
            # attributed to the RPC group that paid it. The batch
            # parents under the FIRST row's W3C traceparent (wire
            # header, decoded in C++) when one was sent — the same
            # oldest-request attribution rule the batcher uses.
            span_ctx = tracing.get_tracer().span(
                "rpc.check", parent=first_parent(checks),
                transport="native", batch=len(checks))
            with span_ctx as span:
                self._run_checks(checks, bags, completions, deferred,
                                 span=span)

        if len(reports):
            # rpc.report root at the wire (same role as rpc.check
            # above): parents under the first report row's W3C
            # traceparent when one was sent
            with tracing.get_tracer().span(
                    "rpc.report", parent=first_parent(reports),
                    transport="native", rpcs=len(reports)) as span:
                self._run_reports(reports, completions, span=span)

    def _run_reports(self, reports: TakenRows, completions: list,
                     span: dict | None = None) -> None:
        """ACK-AFTER-ENQUEUE report serving (the ingestion plane's
        native leg): each RPC's records are decoded, admitted into the
        bounded cross-RPC record coalescer, and the RPC is answered
        the moment its records are ACCEPTED — the pump thread never
        waits out a device trip, so Report rows sharing a take batch
        with Check rows add only decode+enqueue time in front of them.

        Admission overflow answers a typed RESOURCE_EXHAUSTED (and a
        draining coalescer UNAVAILABLE) instead of buffering without
        bound behind an already-acked wire; admitted records are
        conservation-accounted by submit_report (every one ends
        exported or typed-rejected — never silently dropped)."""
        from istio_tpu.runtime.resilience import CheckRejected

        import time as _time

        n_records = 0
        first_bad = 0
        for row, tag in enumerate(reports.tags.tolist()):
            monitor.REPORT_REQUESTS.inc()
            try:
                t0 = _time.perf_counter()
                req = pb.ReportRequest.FromString(reports.payload(row))
                bags = self._decode_report(req)
                monitor.observe_report_stage(
                    "wire_decode", _time.perf_counter() - t0)
            except Exception as exc:
                completions.append(
                    (tag, 13, f"report decode failed: {exc}".encode()))
                first_bad = first_bad or 13
                continue
            n_records += len(bags)
            try:
                futs = self.runtime.submit_report(bags)
            except CheckRejected as exc:   # inline path's typed shed
                completions.append((tag, exc.grpc_code,
                                    str(exc).encode()))
                first_bad = first_bad or exc.grpc_code
                continue
            except Exception as exc:
                completions.append(
                    (tag, 13, f"report failed: {exc}".encode()))
                first_bad = first_bad or 13
                continue
            # ack-after-enqueue: only ALREADY-REJECTED futures (typed
            # admission sheds resolve synchronously inside submit)
            # turn the ack into an error — everything admitted will
            # export or typed-reject on its own, counted either way
            err = None
            for f in futs:
                if f.done():
                    try:
                        err = f.exception()
                    except BaseException as cancel:
                        # a cancelled admission future did NOT export
                        # its record (the ledger counted it rejected)
                        # — the ack must say so, never OK
                        err = cancel
                    if err is not None:
                        break
            if err is not None:
                code = getattr(err, "grpc_code", 13)
                completions.append((tag, code, str(err).encode()))
                first_bad = first_bad or code
            else:
                completions.append((tag, 0, b""))
                monitor.REPORT_RESPONSES.inc()
        if span is not None:
            span["tags"]["records"] = n_records
            span["tags"]["status"] = "ok" if first_bad == 0 \
                else str(first_bad)

    def _run_checks(self, checks: TakenRows, bags, completions: list,
                    deferred: set, span: dict | None = None) -> None:
        """`bags`: the rows of `checks` as the dispatcher takes them
        (`checks` itself, or its preprocessed bags under an APA)."""
        monitor.CHECK_REQUESTS.inc(len(checks))
        # the C++ wire carries no per-RPC deadline — apply the
        # server-side default (--default-check-deadline-ms) from the
        # moment the pump took the batch: under saturation, chunks
        # this batch can't reach in time answer DEADLINE_EXCEEDED
        # pre-tensorize instead of queueing dead device work
        deadline = self._deadline_from(None)
        # in-step quota (ServerArgs.quota_in_step): eligible
        # single-quota rows allocate IN the check trip — no
        # pool-flush trip serialized behind it, no defer
        # machinery. Ineligible rows (multi-quota, unknown name,
        # target-less snapshot) keep the classic defer path.
        target = self.runtime.instep_quota_target()
        qspecs = None
        if target is not None:
            _, by_name = target
            qspecs = [None] * len(checks)
            for row in checks.asking():
                quotas = checks.quotas(row)
                if len(quotas) == 1:
                    (qname, (amount, be)), = quotas.items()
                    if qname in by_name:
                        dedup = checks.dedup_id(row)
                        qspecs[row] = (qname, QuotaArgs(
                            quota_amount=amount, best_effort=be,
                            dedup_id=dedup + ":" + qname
                            if dedup else ""))
            if not any(qspecs):
                qspecs = None
        from istio_tpu.runtime.resilience import CheckRejected
        try:
            if qspecs is not None:
                results, inres = self._check_bags_quota_instep(
                    bags, qspecs, target, deadline=deadline)
            else:
                results = self._check_bags_chunked(bags,
                                                   deadline=deadline)
                inres = {}
        except CheckRejected as exc:
            # typed serving rejection (fail-closed UNAVAILABLE, shed):
            # answer every row with the honest status code instead of
            # letting the belt degrade it to a blanket INTERNAL
            msg = str(exc).encode()
            completions.extend((tag, exc.grpc_code, msg)
                               for tag in checks.tags.tolist())
            if span is not None:
                span["tags"]["status"] = str(exc.grpc_code)
            return
        finally:
            # a dispatch that ended in a typed rejection (or expired
            # every chunk) ran no batch_begin — drop the decode
            # pre-mark so a stale wall never inflates the NEXT
            # batch's wire_decode stage (no-op when a chunk consumed
            # it normally)
            forensics.RECORDER.clear_premarks()
        # span `serialize`: everything between the dispatcher's return
        # and the completions' send — the per-row response build /
        # SerializeToString loop, quota deferral included
        with monitor.span("serialize"):
            self._serialize_rows(checks, bags, results, inres,
                                 completions, deferred, span)

    def _memo_response(self, result) -> tuple[bytes | None, bool]:
        """→ (the serialized response of a quota-less `result`, whether
        the memo held it). Memoised ONLY where the bytes do not depend
        on the bag, which is therefore not asked for: presence must
        COVER the referenced set (incomplete presence makes
        _referenced_proto fall back to per-bag lookups —
        grpc_server._referenced_proto applies the same gate); (None,
        False) where it does not."""
        presence = result.referenced_presence
        if presence is None or \
                len(presence) != len(result.referenced):
            return None, False
        key = (result.status_code, result.status_message,
               result.valid_duration_s,
               result.valid_use_count, result.referenced,
               frozenset(presence.items()))
        raw = self._resp_memo.get(key)
        if raw is not None:
            return raw, True
        raw = self._check_response(
            None, None, result, quotas=[]).SerializeToString()
        if len(self._resp_memo) > 8192:
            self._resp_memo.clear()
        self._resp_memo[key] = raw
        return raw, False

    def _frame_classes(self, checks: TakenRows, results,
                       completions: _Completions) -> list[int]:
        """Serialise once a verdict class (ClassedResponses) and frame
        the rows that share bytes together: one record array a wire
        class of _FRAME_CLASS_MIN_ROWS rows or more, the per-row tuple
        below it. → the rows left to the per-row code: those that ask
        for a quota, and those whose bytes depend on their bag."""
        class_of = results.class_of
        asking = checks.asking()
        here = range(len(results.classes))
        if asking:
            # a class all of whose rows ask for a quota is not looked
            # up: every response built here answers a row here (the
            # count below stays exact, and never negative)
            quiet = np.ones(len(checks), bool)
            quiet[asking] = False
            here = np.unique(class_of[quiet]).tolist()
        raws: list[bytes] = []
        wire_ids: dict[bytes, int] = {}
        wire_of_class = np.full(len(results.classes), -1, np.intp)
        built = 0
        for c in here:
            raw, held = self._memo_response(results.classes[c])
            if raw is None:
                continue
            built += not held
            wire = wire_ids.get(raw)
            if wire is None:
                wire = wire_ids[raw] = len(raws)
                raws.append(raw)
            wire_of_class[c] = wire
        wire_of = wire_of_class[class_of]
        if asking:
            wire_of[asking] = -1
        order = np.argsort(wire_of, kind="stable")
        counts = np.bincount(wire_of + 1, minlength=len(raws) + 1)
        tags = checks.tags
        left = at = int(counts[0])
        for raw, rows in zip(raws, counts[1:].tolist()):
            mine = tags[order[at:at + rows]]
            at += rows
            if rows >= _FRAME_CLASS_MIN_ROWS:
                completions.frame(mine, raw)
            else:
                completions.extend((tag, 0, raw)
                                   for tag in mine.tolist())
        # every row answered here is a response; _check_response
        # counted the ones it built
        monitor.CHECK_RESPONSES.inc(len(checks) - left - built)
        return order[:left].tolist()

    def _serialize_rows(self, checks: TakenRows, bags, results: list,
                        inres: dict, completions: _Completions,
                        deferred: set, span: dict | None) -> None:
        """The per-row code: rows no verdict class answered. A row's
        bag (`bags[row]`) is asked for only by a quota row and by a
        response whose bytes depend on it."""
        # `status` tag (batch-level: ok or the first non-OK code) so
        # /debug/traces can filter failing check spans on this front
        if span is not None:
            first_bad = next((r.status_code for r in results
                              if r.status_code), 0)
            span["tags"]["status"] = "ok" if first_bad == 0 \
                else str(first_bad)
        rows = range(len(checks))
        if len(getattr(results, "class_of", ())) == len(checks) \
                and len(results.classes) < len(checks) and not inres:
            rows = self._frame_classes(checks, results, completions)
        memo_hits = 0
        tags = checks.tags
        asking = set(checks.asking())
        for row in rows:
            result, tag = results[row], int(tags[row])
            quotas = checks.quotas(row) if row in asking else {}
            try:
                if row in inres:
                    # quota already allocated in the check trip;
                    # attach it only on success (a denied row's
                    # entry is grant-freely noise the gate never
                    # consumed for — the fronts omit quotas on
                    # denial, grpcServer.go:188)
                    qpair = []
                    if result.status_code == 0:
                        (qname, _), = quotas.items()
                        qpair = [(qname, inres[row])]
                    raw = self._check_response(
                        None, bags[row], result,
                        quotas=qpair).SerializeToString()
                    completions.append((tag, 0, raw))
                    continue
                if quotas and result.status_code == 0:
                    # quota rows complete via pool-future
                    # callbacks: a batch's non-quota rows must NOT
                    # wait out the quota flush window + device
                    # trip (that added ~2 serialized trips to
                    # EVERY row's latency)
                    req = _RowRequest(checks.dedup_id(row), {
                        name: pb.CheckRequest.QuotaParams(
                            amount=amount, best_effort=be)
                        for name, (amount, be) in quotas.items()})
                    bag = bags[row]
                    self._defer_quota_row(
                        tag, bag, result,
                        self._submit_quotas(req, bag, result))
                    deferred.add(tag)
                    continue
            except Exception as exc:   # row-isolated (quota path)
                monitor.DISPATCH_ERRORS.inc()
                completions.append(
                    (tag, 13, f"quota submit: {exc}".encode()))
                continue
            raw, held = self._memo_response(result)
            if raw is None:
                raw = self._check_response(
                    None, bags[row], result,
                    quotas=[]).SerializeToString()
            memo_hits += held
            completions.append((tag, 0, raw))
        if memo_hits:   # memoized rows skip _check_response
            monitor.CHECK_RESPONSES.inc(memo_hits)

    def _send_completions(self, completions: list) -> None:
        """A _Completions brings the completions it framed already
        beside its tuples; their order in the blob is free, the tags
        name them."""
        framed = getattr(completions, "framed", ())
        n_framed = getattr(completions, "n_framed", 0)
        if not completions and not n_framed:
            return
        out = [struct.pack("<I", len(completions) + n_framed), *framed]
        for tag, status, raw in completions:
            out.append(struct.pack("<QiI", tag, status, len(raw)))
            out.append(raw)
        comp = b"".join(out)
        with self._comp_lock:
            if self._h is None:    # torn down under a deferred row
                return
            self._lib.h2srv_complete(self._h, comp, len(comp))

    def _defer_quota_row(self, tag: int, bag, result,
                         subs: list) -> None:
        """Complete one quota-carrying row when its pool futures
        resolve. All quotas were submitted already (they share a flush
        window); the LAST future to land builds + sends the response
        from the pool-worker thread — no pump thread blocks."""
        futures = [qr for _, qr in subs
                   if hasattr(qr, "add_done_callback")]
        remaining = [len(futures)]
        lock = threading.Lock()

        def finish() -> None:
            try:
                raw = self._check_response(
                    None, bag, result,
                    quotas=subs).SerializeToString()
                self._send_completions([(tag, 0, raw)])
            except Exception:
                log.exception("deferred quota completion failed")
                self._send_completions(
                    [(tag, 13, b"quota completion failed")])

        if not futures:
            finish()
            return

        def on_done(_value) -> None:
            with lock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            finish()

        for fut in futures:
            fut.add_done_callback(on_done)
