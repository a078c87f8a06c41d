"""Overload resilience for the Check() serving path.

The BASELINE tail SLO (<1ms p99 at 10k rules) only means something if
it survives the bad day: an unbounded batcher queue turns overload
into unbounded queue_wait, a request with no deadline is work the
caller stopped wanting long ago, and a single device-step exception
used to fail every batch-mate with a raw INTERNAL. The pieces here are
the standard overload-control toolkit ("The Tail at Scale", CACM 2013;
DAGOR, SOSP'18; Istio's Mixer client fail-open semantics):

  * typed rejections (CheckRejected) that the API fronts map onto real
    gRPC status codes — DEADLINE_EXCEEDED / RESOURCE_EXHAUSTED /
    UNAVAILABLE instead of INTERNAL for every failure shape;
  * a device CIRCUIT BREAKER (closed → open → half-open) in front of
    the fused device step: transient failures retry once with jittered
    backoff, consecutive failures trip the breaker and whole batches
    route to the CPU SnapshotOracle path (compiler/ruleset.py) — the
    same per-rule oracles the compiler tests conformance against, so
    degraded answers are CORRECT answers, just slower;
  * a fail policy for when even the oracle path is down: fail-open
    answers OK (Mixer client `policyCheckFailOpen`), fail-closed
    answers UNAVAILABLE;
  * ChaosHooks — the fault-injection seam the chaos suite and
    scripts/chaos_smoke.py drive (injected device-step exceptions,
    added device latency, oracle failures). The hooks sit at the real
    device boundary (FusedPlan.packed_check / Dispatcher._resolve), so
    an injected failure exercises exactly the production unwind path.

Admission control (queue cap, brownout, deadline expiry) lives in
runtime/batcher.py; this module owns what happens once a batch reaches
the device. Counters for every shed/expired/fallback decision are in
runtime/monitor.py and exported through /metrics and the introspect
server's /debug/resilience.
"""
from __future__ import annotations

import dataclasses
import logging
import random
import threading
import time
from typing import Any, Callable, Sequence

log = logging.getLogger("istio_tpu.runtime.resilience")

# gRPC status codes the serving path rejects with (google.rpc.Code)
INVALID_ARGUMENT = 3
DEADLINE_EXCEEDED = 4
RESOURCE_EXHAUSTED = 8
UNAVAILABLE = 14
UNAUTHENTICATED = 16


class CheckRejected(RuntimeError):
    """A request the serving path refused to answer — carries the gRPC
    status code the API fronts must surface (INTERNAL is reserved for
    genuine bugs; overload and degradation get honest codes)."""
    grpc_code = 2   # UNKNOWN; subclasses override


class InvalidArgumentError(CheckRejected):
    """The request's wire attributes could not be decoded/re-encoded
    (malformed bag at the identity-injection boundary): the caller
    sent garbage, not the server — typed so the wire says so."""
    grpc_code = INVALID_ARGUMENT


class DeadlineExceededError(CheckRejected):
    grpc_code = DEADLINE_EXCEEDED


class ResourceExhaustedError(CheckRejected):
    grpc_code = RESOURCE_EXHAUSTED


class UnavailableError(CheckRejected):
    grpc_code = UNAVAILABLE


class UnauthenticatedError(CheckRejected):
    """Strict-mTLS admission refused a request that presented no
    verified peer identity (secure/mtls.py). Typed so the wire shows
    UNAUTHENTICATED — never an opaque TLS alert or INTERNAL — and the
    meshlint typed-rejection pass can audit the boundary."""
    grpc_code = UNAUTHENTICATED


@dataclasses.dataclass
class ResilienceConfig:
    """Knobs for the ResilientChecker (ServerArgs mirrors these; the
    mixs CLI exposes them as --check-fail-policy / --breaker-*)."""
    # "open": when device AND oracle paths are down, answer OK (the
    # Mixer client's fail-open posture — policy must not take the mesh
    # down with it). "closed": answer UNAVAILABLE.
    fail_policy: str = "closed"
    # consecutive failed batches (after the in-batch retry) that trip
    # the breaker
    breaker_failures: int = 3
    # how long the breaker stays open before a half-open probe
    breaker_reset_s: float = 5.0
    # retry a failed device step once, with jittered backoff, before
    # counting it as a breaker failure
    retry: bool = True
    retry_backoff_s: float = 0.005
    retry_jitter_s: float = 0.010


class CircuitBreaker:
    """closed → open (N consecutive failures) → half-open (one probe
    after reset_s) → closed on probe success / open on probe failure.

    Thread-safe: batches run concurrently on the batcher's worker pool,
    and state transitions must be decided under one lock (two probes in
    flight would double-count a flapping device)."""

    CLOSED, HALF_OPEN, OPEN = "closed", "half_open", "open"

    def __init__(self, failures: int = 3, reset_s: float = 5.0,
                 publish: bool = True, name: str = "device"):
        self.failure_threshold = max(int(failures), 1)
        self.reset_s = reset_s
        # forensics identity: which breaker transitioned ("device",
        # "handler:<qualified name>", "bank:<shard>") — the mesh
        # event timeline records transitions by this name
        self.name = name
        # False for NON-device breakers (the adapter executor's
        # per-handler lanes): they must not clobber the device
        # breaker's mixer_check_breaker_state gauge — their state
        # surfaces via their owner's snapshot (/debug/executor)
        self._publish_gauge = publish
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self._publish()

    def _publish(self) -> None:
        if not self._publish_gauge:
            return
        from istio_tpu.runtime import monitor
        monitor.BREAKER_STATE.set(
            {self.CLOSED: 0, self.HALF_OPEN: 1, self.OPEN: 2}[self._state])

    def _transition(self, to: str) -> None:
        if to == self._state:
            return
        log.warning("circuit breaker %s: %s -> %s", self.name,
                    self._state, to)
        # mesh event timeline (runtime/forensics.py): a breaker flip
        # is exactly the control-plane event a slow-request exemplar
        # needs next to it. record_event never raises and the ring
        # lock is a leaf, so holding self._lock here is safe.
        from istio_tpu.runtime import forensics
        forensics.record_event("breaker", name=self.name,
                               frm=self._state, to=to)
        self._state = to
        if self._publish_gauge:
            from istio_tpu.runtime import monitor
            monitor.BREAKER_TRANSITIONS.labels(to=to).inc()
        self._publish()

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow_device(self) -> bool:
        """May this batch try the device? OPEN past the reset window
        admits exactly ONE half-open probe; everyone else falls back
        until the probe verdict lands."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN and \
                    time.monotonic() - self._opened_at >= self.reset_s:
                self._transition(self.HALF_OPEN)
            if self._state == self.HALF_OPEN and not self._probe_inflight:
                self._probe_inflight = True
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            self._probe_inflight = False
            if self._state != self.CLOSED:
                self._transition(self.CLOSED)

    def record_failure(self) -> None:
        with self._lock:
            self._consecutive += 1
            if self._state == self.HALF_OPEN:
                # the probe failed: back to open, restart the window
                self._probe_inflight = False
                self._opened_at = time.monotonic()
                self._transition(self.OPEN)
            elif self._state == self.CLOSED and \
                    self._consecutive >= self.failure_threshold:
                self._opened_at = time.monotonic()
                self._transition(self.OPEN)

    def release_probe(self) -> None:
        """A batch that got a device slot ended with NO verdict (a
        typed rejection or a non-Exception unwind rode out of the
        device call). The probe slot must be returned or a half-open
        breaker wedges with probe_inflight forever and never tries the
        device again."""
        with self._lock:
            self._probe_inflight = False

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "state": self._state,
                "consecutive_failures": self._consecutive,
                "failure_threshold": self.failure_threshold,
                "reset_s": self.reset_s,
                "probe_inflight": self._probe_inflight,
            }
            if self._state == self.OPEN:
                out["open_for_s"] = round(
                    time.monotonic() - self._opened_at, 3)
            return out


class ChaosHooks:
    """Fault-injection seams for the chaos suite. All fields default to
    inert; production code pays one attribute read per batch. The
    device seam fires at the REAL device boundary (packed_check /
    the generic resolve step) so injected failures exercise the same
    unwind the hardware would."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()
        # injection observer (the audit plane's explainability scorer
        # registers expected-signature records here). Assigned AFTER
        # reset() and never touched by it: the scorer's registration
        # must survive the chaos suite's per-scenario resets. Called
        # OUTSIDE self._lock at each injection-commit point; must
        # never raise. Zero cost while chaos is unarmed.
        self.on_inject: Callable[..., None] | None = None

    def _notify(self, kind: str, **detail) -> None:
        cb = self.on_inject
        if cb is None:
            return
        try:
            cb(kind, **detail)
        except Exception:
            pass

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            # fail the next N device steps (a huge N = hard outage)
            self.device_failures = 0
            # exception factory for injected device failures
            self.device_exception: Callable[[], BaseException] | None = None
            # sleep added to every device step (queue-saturation lever:
            # a slow device backs the batcher queue up to its cap)
            self.device_latency_s = 0.0
            # fail the next N oracle-fallback batches (drives the
            # fail-open/fail-closed policy paths)
            self.oracle_failures = 0
            self.injected_device = 0
            self.injected_oracle = 0
            # -- adapter-boundary seams (the executor plane's chaos
            #    levers, keyed by qualified handler name) -------------
            # sleep added to every call on this handler's lane
            self.adapter_latency_s: dict[str, float] = {}
            # fail the next N calls on this handler's lane
            self.adapter_failures: dict[str, int] = {}
            # wedge: calls on this handler BLOCK until the event sets
            # (unwedge_adapter / reset releases them) — the bulkhead
            # and overrun paths' primary lever
            wedged = getattr(self, "_adapter_wedged", None)
            if wedged:
                for ev in wedged.values():
                    ev.set()   # release stuck workers before dropping
            self._adapter_wedged: dict[str, threading.Event] = {}
            self.injected_adapter = 0
            # -- quota-backend seams (memquota host lane, keyed by
            #    instance name) — the soak's "quota-backend stall" -----
            # sleep added to every handle_quota on this instance
            self.quota_latency_s: dict[str, float] = {}
            # fail the next N handle_quota calls on this instance
            self.quota_failures: dict[str, int] = {}
            self.injected_quota = 0
            # -- discovery-plane seam: sleep inserted at the top of
            #    DiscoveryService.publish (inside the publish lock, so
            #    the delay is a REAL push-pipeline stall) --------------
            self.discovery_push_delay_s = 0.0
            self.injected_discovery = 0
            # replay provenance: the seeded smokes stamp their --seed
            # here after reset() so /debug/resilience names the seed
            # any injected-fault run is replayable from
            self.seed: int | None = None

    def wedge_adapter(self, handler: str) -> None:
        """Every subsequent call on `handler`'s lane blocks until
        unwedge_adapter(handler) or reset()."""
        with self._lock:
            self._adapter_wedged.setdefault(handler, threading.Event())
        # chaos arms are control-plane events too: the forensics
        # smoke attributes a slow exemplar to the wedge that caused it
        from istio_tpu.runtime import forensics
        forensics.record_event("chaos_wedge", handler=handler)
        self._notify("wedge", handler=handler)

    def unwedge_adapter(self, handler: str) -> None:
        with self._lock:
            ev = self._adapter_wedged.pop(handler, None)
        if ev is not None:
            ev.set()
            from istio_tpu.runtime import forensics
            forensics.record_event("chaos_unwedge", handler=handler)

    def adapter_call(self, handler: str) -> None:
        """Called by the executor's lane worker immediately before a
        real adapter call — the adapter-boundary seam (latency, wedge,
        injected errors per handler). Inert fields cost two dict
        lookups per call."""
        ev = self._adapter_wedged.get(handler)
        if ev is not None:
            ev.wait()
        lat = self.adapter_latency_s.get(handler, 0.0)
        if lat:
            time.sleep(lat)
        if self.adapter_failures.get(handler, 0) <= 0:
            return
        with self._lock:
            n = self.adapter_failures.get(handler, 0)
            if n <= 0:
                return
            self.adapter_failures[handler] = n - 1
            self.injected_adapter += 1
        self._notify("adapter", handler=handler)
        raise RuntimeError(
            f"chaos: injected adapter failure ({handler})")

    def quota_call(self, name: str) -> None:
        """Called by MemQuotaHandler.handle_quota immediately before
        the real cell allocation — the quota-backend seam (stall
        latency + injected backend failures per instance name). Inert
        fields cost two dict lookups per quota. Latency-only arms do
        not notify the ledger (the device_latency_s precedent): a
        stall is not a fault, just tail pressure."""
        lat = self.quota_latency_s.get(name, 0.0)
        if lat:
            time.sleep(lat)
        if self.quota_failures.get(name, 0) <= 0:
            return
        with self._lock:
            n = self.quota_failures.get(name, 0)
            if n <= 0:
                return
            self.quota_failures[name] = n - 1
            self.injected_quota += 1
        self._notify("quota", handler=name)
        raise RuntimeError(
            f"chaos: injected quota-backend failure ({name})")

    def discovery_publish(self) -> None:
        """Called at the top of DiscoveryService.publish, inside the
        publish lock — an armed delay stalls the whole push pipeline
        (watchers stay parked on the old generation). Each delayed
        publish registers with the ledger; the expected evidence is
        the generation still advancing (the delayed push completed)."""
        lat = self.discovery_push_delay_s
        if not lat:
            return
        time.sleep(lat)
        with self._lock:
            self.injected_discovery += 1
        self._notify("discovery")

    def device_step(self) -> None:
        """Called immediately before a real check device step."""
        lat = self.device_latency_s
        if lat:
            time.sleep(lat)
        if self.device_failures <= 0:
            return
        with self._lock:
            if self.device_failures <= 0:
                return
            self.device_failures -= 1
            self.injected_device += 1
        self._notify("device")
        exc = self.device_exception
        raise exc() if exc is not None else \
            RuntimeError("chaos: injected device-step failure")

    def oracle_step(self) -> None:
        """Called before an oracle-fallback batch executes."""
        if self.oracle_failures <= 0:
            return
        with self._lock:
            if self.oracle_failures <= 0:
                return
            self.oracle_failures -= 1
            self.injected_oracle += 1
        self._notify("oracle")
        raise RuntimeError("chaos: injected oracle failure")

    def snapshot(self) -> dict:
        return {
            "device_failures_pending": self.device_failures,
            "oracle_failures_pending": self.oracle_failures,
            "device_latency_s": self.device_latency_s,
            "injected_device": self.injected_device,
            "injected_oracle": self.injected_oracle,
            "adapter_wedged": sorted(self._adapter_wedged),
            "adapter_latency_s": dict(self.adapter_latency_s),
            "adapter_failures_pending": dict(self.adapter_failures),
            "injected_adapter": self.injected_adapter,
            "quota_latency_s": dict(self.quota_latency_s),
            "quota_failures_pending": dict(self.quota_failures),
            "injected_quota": self.injected_quota,
            "discovery_push_delay_s": self.discovery_push_delay_s,
            "injected_discovery": self.injected_discovery,
            "seed": self.seed,
        }


# process-wide chaos seam: tests/scripts arm it, serving code probes it
CHAOS = ChaosHooks()


def _takes_deadline(fn: Callable) -> bool:
    """Does `fn` accept a `deadline` keyword? Decided once at wiring
    time (never per batch); unintrospectable callables answer False
    and are called (bags)-shaped."""
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    if "deadline" in params:
        return True
    return any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in params.values())


class ResilientChecker:
    """Wraps the dispatcher's device check with retry, the circuit
    breaker, the CPU oracle fallback and the fail policy. This is
    RuntimeServer._run_check_batch's implementation — every serving
    entry (batcher, BatchCheck chunks, the native pump, check_many)
    rides it."""

    def __init__(self, device: Callable[[Sequence[Any]], Sequence[Any]],
                 oracle: Callable[[Sequence[Any]], Sequence[Any]],
                 config: ResilienceConfig | None = None,
                 chaos: ChaosHooks | None = None,
                 name: str = "device"):
        self.device = device
        self.oracle = oracle
        # deadline propagation (the adapter-executor plane): callables
        # that accept it get the batch's min remaining deadline so
        # host actions inherit the request budget; plain (bags)-shaped
        # callables (tests, legacy hooks) keep working
        self._device_takes_deadline = _takes_deadline(device)
        self._oracle_takes_deadline = _takes_deadline(oracle)
        self.config = config or ResilienceConfig()
        self.chaos = chaos if chaos is not None else CHAOS
        self.breaker = CircuitBreaker(self.config.breaker_failures,
                                      self.config.breaker_reset_s,
                                      name=name)

    def _n_real(self, bags: Sequence[Any]) -> int:
        from istio_tpu.runtime.batcher import trim_pads
        return len(trim_pads(bags))

    def _device_call(self, bags: Sequence[Any],
                     deadline: float | None) -> Sequence[Any]:
        if self._device_takes_deadline:
            return self.device(bags, deadline=deadline)
        return self.device(bags)

    def run_batch(self, bags: Sequence[Any],
                  deadline: float | None = None) -> Sequence[Any]:
        from istio_tpu.runtime import forensics, monitor

        if not self.breaker.allow_device():
            return self._fallback(bags, "breaker_open",
                                  deadline=deadline)
        # every exit below must leave the breaker with a verdict
        # (success/failure) — or release the probe slot: an unwound
        # half-open probe with no verdict would wedge the breaker in
        # half_open and never try the device again
        recorded = False
        try:
            try:
                out = self._device_call(bags, deadline)
            except CheckRejected:
                raise           # typed rejections are answers, not faults
            except Exception as exc:
                first = exc
                if self.config.retry:
                    # one jittered retry absorbs transient device
                    # faults (a failed transfer, a preempted step)
                    # without involving the breaker
                    time.sleep(self.config.retry_backoff_s +  # hotpath: sync-ok failure-path backoff only
                               random.random() *
                               self.config.retry_jitter_s)
                    monitor.CHECK_DEVICE_RETRIES.inc()
                    try:
                        out = self._device_call(bags, deadline)
                    except CheckRejected:
                        raise
                    except Exception as exc2:
                        first = exc2
                    else:
                        # absorbed: say what it was (a pump.stall
                        # event's retry delta has this text behind it)
                        log.warning("device check batch failed once "
                                    "(%s: %s); the retry succeeded",
                                    type(first).__name__, first)
                        forensics.record_event(
                            "device.retry",
                            error=f"{type(first).__name__}: {first}")
                        self.breaker.record_success()
                        recorded = True
                        return out
                self.breaker.record_failure()
                recorded = True
                log.warning("device check batch failed (%s: %s); "
                            "serving via the CPU oracle path",
                            type(first).__name__, first)
                return self._fallback(bags, "device_error",
                                      deadline=deadline)
            self.breaker.record_success()
            recorded = True
            return out
        finally:
            if not recorded:
                self.breaker.release_probe()

    def _fallback(self, bags: Sequence[Any], reason: str,
                  deadline: float | None = None) -> Sequence[Any]:
        from istio_tpu.runtime import monitor

        n = self._n_real(bags)
        try:
            self.chaos.oracle_step()
            # the degraded path keeps the request's deadline when the
            # oracle callable takes one (check_host_oracle does) — a
            # wedged adapter must stay bounded even while the device
            # breaker routes batches host-side
            out = self.oracle(bags, deadline=deadline) \
                if self._oracle_takes_deadline else self.oracle(bags)
        except Exception as exc:
            if self.config.fail_policy == "open":
                # Mixer-client fail-open: policy outage must not take
                # the data plane down — answer OK, but with a 1s/1-use
                # TTL so sidecars re-check promptly instead of caching
                # the blanket allow for a normal success's 5s/10k uses
                # (the policy-bypass window must close with the outage)
                from istio_tpu.runtime.dispatcher import CheckResponse
                monitor.CHECK_FALLBACK.labels(reason="fail_open").inc(n)
                log.error("oracle fallback failed (%s: %s); policy is "
                          "fail-open, answering OK",
                          type(exc).__name__, exc)
                return [CheckResponse(valid_duration_s=1.0,
                                      valid_use_count=1)
                        for _ in range(n)]
            raise UnavailableError(
                f"device and oracle check paths both failed "
                f"({type(exc).__name__}: {exc})") from exc
        monitor.CHECK_FALLBACK.labels(reason=reason).inc(n)
        return out

    def snapshot(self) -> dict:
        """/debug/resilience payload fragment."""
        return {
            "breaker": self.breaker.snapshot(),
            "fail_policy": self.config.fail_policy,
            "retry": self.config.retry,
            "chaos": self.chaos.snapshot(),
        }
