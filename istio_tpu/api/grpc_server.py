"""Mixer gRPC server — istio.mixer.v1.Mixer over grpcio.

Reference: mixer/pkg/api/grpcServer.go. Check (:118): decode
CompressedAttributes → Preprocess → precondition check → per-quota
loop (:188-230); Report (:262): per-record delta decode → Preprocess →
report. Service wiring uses generic method handlers (no grpcio-tools
in this image); serialization is the generated mixer_pb2.

The precondition path rides the RuntimeServer's batcher, so concurrent
Check RPCs from many sidecar connections coalesce into device steps.
"""
from __future__ import annotations

import datetime
import logging
import threading
import time
from concurrent import futures
from typing import Any

import grpc

from istio_tpu.runtime import resilience
from istio_tpu.runtime.resilience import (CheckRejected,
                                          InvalidArgumentError,
                                          UnauthenticatedError)
from istio_tpu.secure.mtls import (MTLS_OFF, MTLS_STRICT, ServingCerts,
                                   peer_identity_from_auth_context,
                                   validate_mode)

from istio_tpu.adapters.sdk import QuotaArgs
from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.wire import (LazyWireBag, RawBatchCheckRequest,
                                RawCheckRequest, WireError,
                                encode_batch_check_response,
                                referenced_to_proto, update_dict_from_proto)
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST
from istio_tpu.runtime import monitor
from istio_tpu.runtime.server import RuntimeServer

log = logging.getLogger("istio_tpu.api")

_CLAMP_DURATION_S = 3600.0

# typed serving rejections (runtime/resilience.py) → wire status codes:
# overload and degradation must surface as DEADLINE_EXCEEDED /
# RESOURCE_EXHAUSTED / UNAVAILABLE, never a generic INTERNAL
_REJECT_CODES = {
    resilience.INVALID_ARGUMENT: grpc.StatusCode.INVALID_ARGUMENT,
    resilience.DEADLINE_EXCEEDED: grpc.StatusCode.DEADLINE_EXCEEDED,
    resilience.RESOURCE_EXHAUSTED: grpc.StatusCode.RESOURCE_EXHAUSTED,
    resilience.UNAVAILABLE: grpc.StatusCode.UNAVAILABLE,
    resilience.UNAUTHENTICATED: grpc.StatusCode.UNAUTHENTICATED,
}


def _real_rows(responses: list, n: int) -> list:
    """A padded chunk's responses without its padding rows' (the
    fused path answers the real rows alone, and keeps its classes)."""
    return responses if len(responses) == n else responses[:n]


def _joined(parts: list) -> list:
    """A batch served in chunks, as one list. One chunk, the usual
    case, stays the list it is, with its verdict classes
    (ClassedResponses); more make a plain list, which a front answers
    a row at a time."""
    if len(parts) == 1:
        return parts[0]
    return [r for part in parts for r in part]


def _reject_status(exc: CheckRejected) -> "grpc.StatusCode":
    return _REJECT_CODES.get(exc.grpc_code, grpc.StatusCode.UNKNOWN)


class MixerGrpcServer:
    """Serves Check/Report for a RuntimeServer core."""

    def __init__(self, runtime: RuntimeServer, address: str = "127.0.0.1:0",
                 max_workers: int = 16,
                 tls: ServingCerts | None = None,
                 mtls_mode: str = MTLS_OFF):
        self.runtime = runtime
        self._tls = tls
        self.mtls_mode = validate_mode(mtls_mode)
        if self.mtls_mode != MTLS_OFF and tls is None:
            raise ValueError(
                f"mtls={self.mtls_mode} needs serving certs (tls=)")
        # ReferencedAttributes protos memoized per (referenced,
        # presence) signature — the fused dispatcher shares those
        # objects across requests with identical device bitmaps, so
        # uniform traffic builds the proto once instead of per RPC
        self._ref_cache: dict = {}
        self._ref_cache_lock = threading.Lock()
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers,
                                       thread_name_prefix="mixer-grpc"))
        handlers = {
            # Check splits the request at the top level instead of fully
            # parsing it: the attributes submessage stays raw bytes for
            # the C++ tensorizer (api/wire.py RawCheckRequest)
            "Check": grpc.unary_unary_rpc_method_handler(
                self._check,
                request_deserializer=RawCheckRequest,
                response_serializer=pb.CheckResponse.SerializeToString),
            "Report": grpc.unary_unary_rpc_method_handler(
                self._report,
                request_deserializer=pb.ReportRequest.FromString,
                response_serializer=pb.ReportResponse.SerializeToString),
            # shim protocol (mixer.proto BatchCheck): raw in, raw out —
            # per-item protos are built once and hand-framed
            "BatchCheck": grpc.unary_unary_rpc_method_handler(
                self._batch_check,
                request_deserializer=RawBatchCheckRequest,
                response_serializer=lambda b: b),
        }
        self._server.add_generic_rpc_handlers((
            grpc.method_handlers_generic_handler("istio.mixer.v1.Mixer",
                                                 handlers),))
        if tls is not None:
            # strict: the handshake REQUIRES + verifies the client
            # cert (grpcio has no request-but-optional mode); _admit
            # then rejects verified-but-identity-less certs typed.
            # The credentials are rotation-aware (cert-config fetcher
            # rides ServingCerts.generation) — see secure/mtls.py.
            self.port = self._server.add_secure_port(
                address, tls.grpc_server_credentials(
                    require_client_auth=self.mtls_mode == MTLS_STRICT))
        else:
            self.port = self._server.add_insecure_port(address)

    # -- lifecycle --

    def start(self) -> int:
        # whatever was built since the runtime's constructor outlives
        # every request: out of the collector's way (settle_heap)
        monitor.settle_heap("start")
        self._server.start()
        log.info("mixer grpc server on port %d", self.port)
        return self.port

    def stop(self, grace: float = 1.0) -> None:
        self._server.stop(grace).wait()

    # -- admission (secure plane) --

    def _admit(self, context) -> str | None:
        """Peer-identity admission for every RPC on this front.

        Returns the verified SPIFFE identity (first spiffe:// URI SAN
        of the TLS-verified client cert) or None for an anonymous
        peer. In strict mode the handshake already required + verified
        a client cert; a peer whose VERIFIED cert carries no SPIFFE
        identity is refused here with a typed UNAUTHENTICATED
        (runtime/resilience.UnauthenticatedError) — an honest wire
        status the meshlint typed-rejection pass and the client's
        error handling both see, never a silent anonymous admit."""
        identity = None
        if self._tls is not None and context is not None:
            try:
                auth = context.auth_context()
            except Exception:
                auth = None
            identity = peer_identity_from_auth_context(auth)
        if identity is not None:
            monitor.IDENTITY_AUTHENTICATED.inc()
            return identity
        if self.mtls_mode == MTLS_STRICT:
            monitor.IDENTITY_UNAUTHENTICATED.inc()
            raise UnauthenticatedError(
                "mTLS strict: no verified client certificate identity")
        return None

    @staticmethod
    def _identity_attrs(identity: str | None) -> dict | None:
        """Admission attributes the verified identity contributes:
        `source.user` (the SPIFFE principal RBAC/authz predicates
        evaluate — on-device, via the re-encoded wire) and
        `connection.mtls`. None for anonymous peers (permissive/off):
        client-supplied attributes pass through untouched."""
        if identity is None:
            return None
        return {"source.user": identity, "connection.mtls": True}

    # -- RPCs --

    def _deadline_from(self, context) -> float | None:
        """Absolute perf_counter deadline for one Check: the client's
        RPC deadline when it sent one, else the server-side default
        (ServerArgs.default_check_deadline_ms; the native front's
        --default-check-deadline-ms knob), else None."""
        remaining = None
        if context is not None:
            try:
                remaining = context.time_remaining()
            except Exception:   # a front without deadline support
                remaining = None
        # grpcio reports a deadline-LESS client as a huge
        # time_remaining (years), not None — treating that as a real
        # deadline both defeats the server-side default below and
        # overflows bounded waits downstream (the executor fold's
        # Event.wait). Anything past a day is "no client deadline".
        if remaining is not None and remaining < 86_400.0:
            return time.perf_counter() + max(remaining, 0.0)
        d_ms = getattr(self.runtime.args, "default_check_deadline_ms",
                       0.0)
        if d_ms:
            return time.perf_counter() + d_ms / 1e3
        return None

    @staticmethod
    def _traceparent_from(context):
        """Incoming W3C traceparent (grpc metadata) → parent-span dict
        for the rpc.check root, so exemplar/server trace ids are
        join-able with the client's trace; None (self-generated ids,
        the previous behavior) when absent or malformed."""
        from istio_tpu.utils import tracing
        if context is None:
            return None
        try:
            md = context.invocation_metadata()
        except Exception:
            return None
        for item in md or ():
            key, value = item[0], item[1]
            if key == "traceparent":
                return tracing.parent_from_traceparent(value)
        return None

    @staticmethod
    def _tag_status(span, code) -> None:
        """`status` tag on a check span — "ok" or the google.rpc /
        grpc code — so /debug/traces?status=failed can filter."""
        if span is not None:
            span["tags"]["status"] = "ok" if code in (0, "0") \
                else str(code)

    def _check(self, request: RawCheckRequest,
               context) -> "pb.CheckResponse":
        # ROOT span at RPC decode (pkg/tracing's interceptor role):
        # the batcher's serve.batch span parents under it (submit
        # captures this thread's current span), so queue-wait is
        # attributed to a REQUEST, not anonymously to a batch. The
        # client's traceparent (when sent) becomes the root's parent.
        from istio_tpu.utils import tracing
        with tracing.get_tracer().span(
                "rpc.check",
                parent=self._traceparent_from(context)) as root:
            try:
                identity = self._admit(context)
                bag = self._check_bag(request, identity=identity)
                deadline = self._deadline_from(context)
                result = self.runtime.check_preprocessed(
                    bag, deadline=deadline)
                self._tag_status(root, result.status_code)
                return self._check_response(request, bag, result,
                                            deadline=deadline,
                                            identity=identity)
            except CheckRejected as exc:
                # abort() raises — the typed rejection becomes the
                # RPC's status instead of an INTERNAL stack trace
                self._tag_status(root, exc.grpc_code)
                context.abort(_reject_status(exc), str(exc))

    def _batch_check(self, request: RawBatchCheckRequest,
                     context) -> bytes:
        """One RPC, many independent Check bags (the data-plane shim's
        amortized front; mixer.proto BatchCheck). Per-item semantics =
        unary Check without quotas/dedup. The batch is padded to the
        server's prewarmed bucket shapes so arbitrary client batch
        sizes never re-trace."""
        try:
            return self._batch_check_body(
                request, self._deadline_from(context),
                parent=self._traceparent_from(context),
                identity=self._admit(context))
        except CheckRejected as exc:
            context.abort(_reject_status(exc), str(exc))

    def _batch_check_body(self, request: RawBatchCheckRequest,
                          deadline: float | None,
                          parent: dict | None = None,
                          identity: str | None = None) -> bytes:
        """Span + dispatch, shared by the sync front (which aborts
        inline) and the aio front (whose abort must be awaited on the
        loop, not called from the executor thread)."""
        from istio_tpu.utils import tracing
        with tracing.get_tracer().span(
                "rpc.batch_check", parent=parent,
                items=len(request.attributes_raw)) as span:
            try:
                return self._batch_check_traced(
                    request, deadline=deadline, span=span,
                    identity=identity)
            except CheckRejected as exc:
                # tag BEFORE the span closes: a rejected batch must
                # show in /debug/traces?status=failed (the unary
                # fronts tag in their own handlers)
                self._tag_status(span, exc.grpc_code)
                raise

    def _batch_check_traced(self, request: RawBatchCheckRequest,
                            deadline: float | None = None,
                            span: dict | None = None,
                            identity: str | None = None) -> bytes:
        gwc = request.global_word_count
        native = gwc in (0, len(GLOBAL_WORD_LIST))
        attrs = self._identity_attrs(identity)

        def _bag(raw):
            bag = LazyWireBag(raw, gwc or None, native_ok=native)
            if attrs is not None:
                # the connection's verified identity covers every item
                # in the batch (one peer, many bags)
                try:
                    bag = bag.with_attributes(attrs)
                except WireError as exc:
                    raise InvalidArgumentError(
                        f"malformed check attributes: {exc}") from exc
            return bag

        bags = [self.runtime.preprocess(_bag(raw))
                for raw in request.attributes_raw]
        if not bags:
            return b""
        monitor.CHECK_REQUESTS.inc(len(bags))
        results = self._check_bags_chunked(bags, deadline=deadline)
        first_bad = next((r.status_code for r in results
                          if r.status_code), 0)
        self._tag_status(span, first_bad)
        blobs = [
            self._check_response(None, bag, result, quotas=[],
                                 identity=identity).SerializeToString()
            for bag, result in zip(bags, results)]
        return encode_batch_check_response(blobs)

    @staticmethod
    def _expired_response():
        """CheckResponse for a request whose deadline expired before
        its chunk dispatched: the precondition status carries
        DEADLINE_EXCEEDED and zero TTLs (nothing was evaluated, so
        nothing may be cached)."""
        from istio_tpu.runtime.dispatcher import CheckResponse
        from istio_tpu.runtime.resilience import DEADLINE_EXCEEDED
        return CheckResponse(status_code=DEADLINE_EXCEEDED,
                             status_message="deadline expired before "
                                            "dispatch",
                             valid_duration_s=0.0, valid_use_count=0)

    def _check_bags_chunked(self, bags: list,
                            deadline: float | None = None) -> list:
        """Preprocessed bags → results, in largest-bucket CHUNKS padded
        to the prewarmed bucket shapes — an arbitrary over-bucket shape
        would force a fresh device compile per distinct size (client-
        controlled stalls). Single home of the rule: the BatchCheck
        front and the native front-end pump both ride it. `deadline`:
        chunks reached after it expire pre-tensorize — every remaining
        row answers DEADLINE_EXCEEDED instead of queueing device work
        the caller already abandoned."""
        from istio_tpu.runtime.batcher import pad_to_bucket

        buckets = self.runtime.batcher.buckets
        parts: list = []
        for lo in range(0, len(bags), buckets[-1]):
            chunk = bags[lo:lo + buckets[-1]]
            if deadline is not None and \
                    time.perf_counter() >= deadline:
                monitor.CHECK_DEADLINE_EXPIRED.inc(len(chunk))
                parts.append([self._expired_response()
                              for _ in range(len(chunk))])
                continue
            padded = pad_to_bucket(chunk, buckets)
            parts.append(_real_rows(
                self.runtime.check_batch_preprocessed(padded),
                len(chunk)))
        return _joined(parts)

    def _check_bags_quota_instep(self, bags: list, qspecs: list,
                                 target, deadline: float | None = None
                                 ) -> tuple[list, dict]:
        """_check_bags_chunked with each chunk's quota rows allocated
        IN its check trip (ServerArgs.quota_in_step; the pool-flush
        trip disappears — FusedPlan.packed_check_instep). qspecs[i] is
        (name, QuotaArgs) or None; `target` from
        RuntimeServer.instep_quota_target(). Returns (results,
        {global row → QuotaResult}); rows whose check was denied keep
        their entry but callers must NOT attach it (the device gate
        consumed nothing for them — grpcServer.go:188). `deadline`:
        chunks reached after it expire pre-tensorize like the
        non-quota chunked path — their quota rows allocate NOTHING
        (nothing was evaluated, nothing may be consumed)."""
        from istio_tpu.runtime.batcher import pad_to_bucket

        buckets = self.runtime.batcher.buckets
        parts: list = []
        qres: dict[int, Any] = {}
        cap = buckets[-1]
        for lo in range(0, len(bags), cap):
            chunk = bags[lo:lo + cap]
            if deadline is not None and \
                    time.perf_counter() >= deadline:
                monitor.CHECK_DEADLINE_EXPIRED.inc(len(chunk))
                parts.append([self._expired_response()
                              for _ in range(len(chunk))])
                continue
            padded = pad_to_bucket(chunk, buckets)
            qrows = [(i, qspecs[lo + i][0], qspecs[lo + i][1])
                     for i in range(len(chunk))
                     if qspecs[lo + i] is not None]
            resps, rq = self.runtime.check_batch_quota_instep(
                padded, qrows, target)
            parts.append(_real_rows(resps, len(chunk)))
            for i, qr in rq.items():
                qres[lo + i] = qr
        return _joined(parts), qres

    def _check_bag(self, request: RawCheckRequest,
                   identity: str | None = None):
        monitor.CHECK_REQUESTS.inc()
        gwc = request.global_word_count
        # a non-default dictionary prefix forces the python wire path —
        # the C++ decoder assumes the full global list
        bag = LazyWireBag(request.attributes_raw, gwc or None,
                          native_ok=gwc in (0, len(GLOBAL_WORD_LIST)))
        attrs = self._identity_attrs(identity)
        if attrs is not None:
            # fold the VERIFIED peer identity into the wire itself
            # (re-encode) so device tensorization — and therefore the
            # compiled RBAC predicates — see source.user exactly as
            # the SnapshotOracle does
            try:
                bag = bag.with_attributes(attrs)
            except WireError as exc:
                raise InvalidArgumentError(
                    f"malformed check attributes: {exc}") from exc
        # preprocess ONCE; precondition check and quota loop share the
        # bag (a no-op returning the wire bag when no APA is configured)
        return self.runtime.preprocess(bag)

    def _check_response(self, request: RawCheckRequest, bag,
                        result, quotas: list | None = None,
                        deadline: float | None = None,
                        identity: str | None = None
                        ) -> "pb.CheckResponse":
        resp = pb.CheckResponse()
        resp.precondition.status.code = result.status_code
        if result.status_message:
            resp.precondition.status.message = result.status_message
        ttl_s = min(result.valid_duration_s, _CLAMP_DURATION_S)
        uses = min(result.valid_use_count, 2**31 - 1)
        if identity is not None and self.runtime.grants is not None:
            # identity axis of the grant plane (runtime/grants.py):
            # a peer whose identity just rotated must not ride a
            # stale cached verdict — min() like every TTL source
            ittl, iuses = self.runtime.grants.identity_grant(identity)
            ttl_s = min(ttl_s, ittl)
            uses = min(uses, iuses)
        resp.precondition.valid_duration.FromTimedelta(
            datetime.timedelta(seconds=ttl_s))
        resp.precondition.valid_use_count = uses
        resp.precondition.referenced_attributes.CopyFrom(
            self._referenced_proto(result, bag))

        # quota loop (grpcServer.go:188-230): only on successful check.
        # Fused path: device quota pools + the check step's activity
        # bits (no re-resolve); pending futures are collected first so
        # multiple quotas in one request share a device batch.
        if result.status_code == 0:
            if quotas is None:
                quotas = self._submit_quotas(request, bag, result,
                                             deadline=deadline)
            for name, qr in quotas:
                if hasattr(qr, "result"):   # QuotaFuture (sync front)
                    qr = qr.result()
                out = resp.quotas[name]
                out.granted_amount = qr.granted_amount
                out.valid_duration.FromTimedelta(datetime.timedelta(
                    seconds=min(qr.valid_duration_s, _CLAMP_DURATION_S)))
        monitor.CHECK_RESPONSES.inc()
        return resp

    @staticmethod
    def _quota_args(request: RawCheckRequest, name: str,
                    params) -> QuotaArgs:
        return QuotaArgs(quota_amount=params.amount,
                         best_effort=params.best_effort,
                         dedup_id=request.deduplication_id + ":" + name
                         if request.deduplication_id else "")

    def _submit_quotas(self, request: RawCheckRequest, bag,
                       result, deadline: float | None = None) -> list:
        """→ [(name, QuotaResult | QuotaFuture)] — non-blocking on the
        fused path (pool futures); the dispatcher fallback (generic
        path / non-device quota handler) resolves inline, its host
        adapter call bounded by the RPC deadline (executor plane)."""
        pending = []
        for name, params in request.quotas.items():
            args = self._quota_args(request, name, params)
            qr = self.runtime.quota_fused(bag, name, args, result)
            if qr is None:   # generic path / non-device handler
                qr = self.runtime.quota(bag, name, args,
                                        preprocessed=True,
                                        deadline=deadline)
            pending.append((name, qr))
        return pending

    def _referenced_proto(self, result, bag) -> "pb.ReferencedAttributes":
        presence = result.referenced_presence
        if presence is None or len(presence) != len(result.referenced):
            # presence incomplete → the proto depends on this bag
            return referenced_to_proto(result.referenced, bag, presence)
        key = (result.referenced,
               frozenset(presence.items()) if presence else frozenset())
        with self._ref_cache_lock:
            cached = self._ref_cache.get(key)
        if cached is None:
            cached = referenced_to_proto(result.referenced, bag, presence)
            with self._ref_cache_lock:
                if len(self._ref_cache) > 4096:
                    self._ref_cache.clear()
                self._ref_cache[key] = cached
        return cached

    def _decode_report(self, request: "pb.ReportRequest") -> list:
        bags = []
        current: dict[str, Any] = {}
        default_words = list(request.default_words)
        for record in request.attributes:
            # delta decode (grpcServer.go:262-353)
            update_dict_from_proto(current, record,
                                   request.global_word_count or None,
                                   default_words)
            bags.append(bag_from_mapping(dict(current)))
        return bags

    def _report(self, request: "pb.ReportRequest",
                context) -> "pb.ReportResponse":
        # ROOT span at RPC decode (the report analog of rpc.check):
        # the coalescer's serve.batch span parents under it via the
        # thread-local stack; the client's W3C traceparent (metadata)
        # becomes the root's parent when sent
        from istio_tpu.utils import tracing
        monitor.REPORT_REQUESTS.inc()
        with tracing.get_tracer().span(
                "rpc.report", parent=self._traceparent_from(context),
                records=len(request.attributes)) as root:
            try:
                # strict mTLS covers the telemetry path too — an
                # anonymous peer must not inject report records
                self._admit(context)
            except CheckRejected as exc:
                self._tag_status(root, exc.grpc_code)
                context.abort(_reject_status(exc), str(exc))
            t0 = time.perf_counter()
            bags = self._decode_report(request)
            monitor.observe_report_stage("wire_decode",
                                         time.perf_counter() - t0)
            try:
                if bags:
                    self.runtime.report(bags)
            except CheckRejected as exc:
                # typed admission rejection (bounded report queue,
                # draining): the honest wire code, never INTERNAL
                self._tag_status(root, exc.grpc_code)
                context.abort(_reject_status(exc), str(exc))
            self._tag_status(root, 0)
        monitor.REPORT_RESPONSES.inc()
        return pb.ReportResponse()


class MixerAioGrpcServer(MixerGrpcServer):
    """Asyncio variant of the Mixer front-end.

    The sync server parks one thread-pool thread in `future.result()`
    for every in-flight Check — with the batcher's round-trip at
    ~100ms+ behind a remote device transport, throughput caps at
    workers / round-trip and the thread count itself melts the GIL.
    Here handlers `await` the batcher future on one event loop, so
    thousands of checks can be in flight from a single thread (the
    role grpcServer.go gets for free from goroutines)."""

    def __init__(self, runtime: RuntimeServer,
                 address: str = "127.0.0.1:0",
                 tls: ServingCerts | None = None,
                 mtls_mode: str = MTLS_OFF):
        # note: deliberately NOT calling super().__init__ — the sync
        # grpc.server and its thread pool are replaced by an aio
        # server owned by a loop thread
        self.runtime = runtime
        self._tls = tls
        self.mtls_mode = validate_mode(mtls_mode)
        if self.mtls_mode != MTLS_OFF and tls is None:
            raise ValueError(
                f"mtls={self.mtls_mode} needs serving certs (tls=)")
        self._ref_cache = {}
        self._ref_cache_lock = threading.Lock()
        self._address = address
        self._loop = None
        self._server = None
        self._ready = threading.Event()
        self._stopped = threading.Event()
        self.port = 0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="mixer-aio-grpc")

    async def _abatch_check(self, request: RawBatchCheckRequest,
                            context) -> bytes:
        import asyncio
        deadline = self._deadline_from(context)
        try:
            identity = self._admit(context)
            # tensorize + device step block — off the loop
            return await asyncio.get_running_loop().run_in_executor(
                None, self._batch_check_body, request, deadline,
                self._traceparent_from(context), identity)
        except CheckRejected as exc:
            # aio abort is a coroutine and must run ON the loop — the
            # sync _batch_check's inline abort would no-op here
            await context.abort(_reject_status(exc), str(exc))

    async def _acheck(self, request: RawCheckRequest,
                      context) -> "pb.CheckResponse":
        import asyncio

        from istio_tpu.utils import tracing
        loop = asyncio.get_running_loop()
        # ROOT span at RPC decode, DETACHED (start_span): a `with`
        # span held across an await would leak onto interleaved tasks
        # via the thread-local stack. The batcher parents its batch
        # span under this dict (submit trace=).
        tr = tracing.get_tracer()
        root = tr.start_span("rpc.check",
                             parent=self._traceparent_from(context))
        try:
            return await self._acheck_traced(
                request, loop, root,
                deadline=self._deadline_from(context),
                identity=self._admit(context))
        except CheckRejected as exc:
            self._tag_status(root, exc.grpc_code)
            await context.abort(_reject_status(exc), str(exc))
        finally:
            tr.finish_span(root)

    async def _acheck_traced(self, request: RawCheckRequest, loop,
                             root,
                             deadline: float | None = None,
                             identity: str | None = None
                             ) -> "pb.CheckResponse":
        import asyncio
        d = self.runtime.controller.dispatcher
        if d.has_apa:
            # preprocess runs an APA device round-trip — off the loop
            bag = await loop.run_in_executor(None, self._check_bag,
                                             request, identity)
        else:
            # identity preprocess: the executor hop would cost more
            # than the work
            bag = self._check_bag(request, identity)
        # shield: a client cancel must cancel THIS handler only, never
        # the shared batcher future (a cancelled batch-mate would
        # otherwise poison result distribution for the whole batch)
        result = await asyncio.shield(asyncio.wrap_future(
            self.runtime.submit_check_preprocessed(
                bag, trace=root, deadline=deadline)))
        self._tag_status(root, result.status_code)
        if request.quotas and result.status_code == 0:
            # fused-path quota futures bridge to the loop via
            # callbacks — an in-flight quota holds NO thread (an
            # executor thread per pending device batch serialized the
            # whole server behind ~5 threads × an RTT)
            # submit EVERY quota first so they share a device batch
            # window, then await — a per-quota await would serialize k
            # quotas into k windows
            pending = []
            for name, params in request.quotas.items():
                args = self._quota_args(request, name, params)
                qr = self.runtime.quota_fused(bag, name, args, result)
                if qr is None:
                    # dispatcher fallback re-resolves (device RTT) —
                    # off the loop; host adapter call bounded by the
                    # RPC deadline (executor plane)
                    qr = loop.run_in_executor(
                        None, self.runtime.quota, bag, name, args,
                        True, deadline)
                elif hasattr(qr, "add_done_callback"):
                    af = loop.create_future()

                    def _resolve(v, af=af):
                        # a client cancel mid-quota marks af done —
                        # setting a result then raises InvalidStateError
                        # inside a loop callback (observed r4)
                        if not af.done():
                            af.set_result(v)
                    qr.add_done_callback(
                        lambda v, _r=_resolve: loop.call_soon_threadsafe(
                            _r, v))
                    qr = af
                pending.append((name, qr))
            quotas = []
            for name, qr in pending:
                if asyncio.isfuture(qr):
                    qr = await qr
                quotas.append((name, qr))
            return self._check_response(request, bag, result,
                                        quotas=quotas,
                                        identity=identity)
        return self._check_response(request, bag, result,
                                    identity=identity)

    async def _areport(self, request: "pb.ReportRequest",
                       context) -> "pb.ReportResponse":
        import asyncio

        from istio_tpu.utils import tracing
        loop = asyncio.get_running_loop()
        monitor.REPORT_REQUESTS.inc()
        # rpc.report root: built inline (not via the thread-local
        # `with` — handler awaits hop threads); wire_decode is timed
        # in the executor wrapper so the stage covers the real work
        root = tracing.get_tracer().span(
            "rpc.report", parent=self._traceparent_from(context),
            records=len(request.attributes), transport="grpc-aio")

        def _decode():
            t0 = time.perf_counter()
            bags = self._decode_report(request)
            monitor.observe_report_stage("wire_decode",
                                         time.perf_counter() - t0)
            return bags

        with root as span:
            try:
                # strict mTLS covers the telemetry path too
                self._admit(context)
            except CheckRejected as exc:
                self._tag_status(span, exc.grpc_code)
                await context.abort(_reject_status(exc), str(exc))
            # decode + preprocess are synchronous host work — off the
            # loop; the WAIT for the coalesced batches holds no thread
            # (futures bridge back via wrap_future, like _acheck), so
            # in-flight Reports are bounded by the batcher, not a pool
            bags = await loop.run_in_executor(None, _decode)
            if bags:
                futs = await loop.run_in_executor(
                    None, self.runtime.submit_report, bags)
                if futs:
                    # shield: a client cancel must never poison shared
                    # batch-mates; gather-with-exceptions retrieves
                    # every future before the first error re-raises
                    results = await asyncio.shield(asyncio.gather(
                        *[asyncio.wrap_future(f) for f in futs],
                        return_exceptions=True))
                    first = next((r for r in results
                                  if isinstance(r, BaseException)),
                                 None)
                    if first is not None:
                        if isinstance(first, CheckRejected):
                            # typed shed (bounded report queue,
                            # draining) → honest wire status; aio
                            # abort is a coroutine and must run ON
                            # the loop
                            self._tag_status(span, first.grpc_code)
                            await context.abort(_reject_status(first),
                                                str(first))
                        # programming errors (non-CheckRejected) ride
                        # grpc's catch-all to UNKNOWN on purpose — a
                        # typed wrapper here would mislabel bugs as
                        # load sheds
                        raise first   # meshlint: raise-ok bug-surface
            self._tag_status(span, 0)
        monitor.REPORT_RESPONSES.inc()
        return pb.ReportResponse()

    def _run(self) -> None:
        import asyncio

        from grpc import aio

        async def serve():
            # dedicated executor for the blocking offloads. Check and
            # Report decode are short (their batch waits bridge back
            # via wrap_future, holding no thread), but _abatch_check
            # and the non-fused quota fallback still park a thread
            # across a full device trip — size for a burst of those
            # so unary decode never queues behind a device step
            # (asyncio's default is only ~cpu+4 on a small box)
            from concurrent.futures import ThreadPoolExecutor
            asyncio.get_running_loop().set_default_executor(
                ThreadPoolExecutor(max_workers=32,
                                   thread_name_prefix="mixer-aio-exec"))
            server = aio.server()
            handlers = {
                "Check": grpc.unary_unary_rpc_method_handler(
                    self._acheck,
                    request_deserializer=RawCheckRequest,
                    response_serializer=pb.CheckResponse.SerializeToString),
                "Report": grpc.unary_unary_rpc_method_handler(
                    self._areport,
                    request_deserializer=pb.ReportRequest.FromString,
                    response_serializer=pb.ReportResponse.SerializeToString),
                "BatchCheck": grpc.unary_unary_rpc_method_handler(
                    self._abatch_check,
                    request_deserializer=RawBatchCheckRequest,
                    response_serializer=lambda b: b),
            }
            server.add_generic_rpc_handlers((
                grpc.method_handlers_generic_handler(
                    "istio.mixer.v1.Mixer", handlers),))
            if self._tls is not None:
                # same posture as the sync front: strict requires the
                # client cert at handshake, _admit types the
                # identity-less-cert rejection
                self.port = server.add_secure_port(
                    self._address, self._tls.grpc_server_credentials(
                        require_client_auth=self.mtls_mode
                        == MTLS_STRICT))
            else:
                self.port = server.add_insecure_port(self._address)
            await server.start()
            self._server = server
            self._ready.set()
            await server.wait_for_termination()

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(serve())
        finally:
            self._loop.close()
            self._stopped.set()

    def start(self) -> int:
        monitor.settle_heap("start")
        self._thread.start()
        if not self._ready.wait(timeout=30):
            raise RuntimeError("aio grpc server failed to start")
        log.info("mixer aio grpc server on port %d", self.port)
        return self.port

    def stop(self, grace: float = 1.0) -> None:
        import asyncio
        if self._loop is None or self._server is None:
            return
        asyncio.run_coroutine_threadsafe(
            self._server.stop(grace), self._loop)
        self._stopped.wait(timeout=grace + 10)
