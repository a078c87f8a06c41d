"""Overload-resilience suite: deadlines, admission control, the device
circuit breaker with oracle fallback, fail-open/closed policy, the
batcher watchdog and the submit()-vs-close() race.

Most tests drive a raw CheckBatcher with a stub run_batch (no device
anywhere — the admission/deadline machinery is pure host logic); the
breaker/fallback integration tests share one small RuntimeServer and
inject faults through the ChaosHooks seam (runtime/resilience.py), so
they exercise the production unwind path end to end.
"""
import threading
import time
from concurrent.futures import Future

import pytest

from istio_tpu.runtime import monitor
from istio_tpu.runtime.batcher import CheckBatcher, PadBag
from istio_tpu.runtime.resilience import (CHAOS, CircuitBreaker,
                                          DeadlineExceededError,
                                          ResilienceConfig,
                                          ResilientChecker,
                                          ResourceExhaustedError,
                                          UnavailableError)


@pytest.fixture(autouse=True)
def _clean_chaos():
    CHAOS.reset()
    yield
    CHAOS.reset()
    monitor.reset_latency_window()


# ---------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------

def test_breaker_trips_after_threshold_and_recovers():
    b = CircuitBreaker(failures=3, reset_s=0.05)
    assert b.state == "closed"
    for _ in range(2):
        b.record_failure()
    assert b.state == "closed" and b.allow_device()
    b.record_failure()
    assert b.state == "open"
    assert not b.allow_device()          # open, reset window not over
    time.sleep(0.06)
    assert b.allow_device()              # the single half-open probe
    assert b.state == "half_open"
    assert not b.allow_device()          # probe in flight: no second
    b.record_success()
    assert b.state == "closed" and b.allow_device()


def test_breaker_probe_failure_reopens():
    b = CircuitBreaker(failures=1, reset_s=0.05)
    b.record_failure()
    assert b.state == "open"
    time.sleep(0.06)
    assert b.allow_device()
    b.record_failure()                   # probe failed
    assert b.state == "open"
    assert not b.allow_device()          # fresh reset window


# ---------------------------------------------------------------------
# ResilientChecker (stub device/oracle — no jax anywhere)
# ---------------------------------------------------------------------

def _fast_config(**kw):
    kw.setdefault("retry_backoff_s", 0.001)
    kw.setdefault("retry_jitter_s", 0.001)
    return ResilienceConfig(**kw)


def test_retry_absorbs_transient_device_fault():
    calls = {"n": 0}

    def device(bags):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return ["dev"] * len(bags)

    rc = ResilientChecker(device, lambda bags: ["oracle"] * len(bags),
                          config=_fast_config())
    before = int(monitor.CHECK_DEVICE_RETRIES._value.get())
    assert rc.run_batch(["a", "b"]) == ["dev", "dev"]
    assert calls["n"] == 2
    assert rc.breaker.state == "closed"
    assert int(monitor.CHECK_DEVICE_RETRIES._value.get()) == before + 1


def test_an_absorbed_device_fault_is_logged_and_recorded():
    """The retry that succeeds says what it absorbed: one device.retry
    event and one log record with the first exception's type."""
    import logging

    from istio_tpu.runtime import forensics

    records = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = records.append
    logger = logging.getLogger("istio_tpu.runtime.resilience")

    def device(bags):
        CHAOS.device_step()
        return ["dev"] * len(bags)

    rc = ResilientChecker(device, lambda bags: ["oracle"] * len(bags),
                          config=_fast_config())
    CHAOS.device_failures = 1
    seen = len(forensics.EVENTS.snapshot("device.retry", limit=0))
    logger.addHandler(handler)
    try:
        assert rc.run_batch(["a", "b"]) == ["dev", "dev"]
        assert rc.run_batch(["c"]) == ["dev"]       # a clean batch: silent
    finally:
        logger.removeHandler(handler)
    events = forensics.EVENTS.snapshot("device.retry", limit=0)[seen:]
    assert [e["detail"]["error"] for e in events] == [
        "RuntimeError: chaos: injected device-step failure"]
    (record,) = records
    assert "RuntimeError" in record.getMessage()
    assert "retry succeeded" in record.getMessage()
    assert rc.breaker.state == "closed"


def test_double_failure_falls_back_to_oracle_and_counts():
    from istio_tpu.runtime.batcher import trim_pads

    def device(bags):
        raise RuntimeError("down")

    def oracle(bags):
        # the real check_host_oracle answers per REAL row (pads
        # trimmed, like the fused path)
        return ["oracle"] * len(trim_pads(list(bags)))

    rc = ResilientChecker(device, oracle,
                          config=_fast_config(breaker_failures=2))
    fb0 = monitor.resilience_counters()["fallback"]
    assert rc.run_batch(["a", "b", PadBag()]) == ["oracle", "oracle"]
    fb = monitor.resilience_counters()["fallback"]
    # pad rows carry no caller: the per-request counter must not
    # count them
    assert fb["device_error"] - fb0["device_error"] == 2
    assert rc.breaker.state == "closed"   # 1 failure < threshold 2
    rc.run_batch(["c"])
    assert rc.breaker.state == "open"
    # breaker open: device never called, straight to oracle
    assert rc.run_batch(["d"]) == ["oracle"]
    fb2 = monitor.resilience_counters()["fallback"]
    assert fb2["breaker_open"] - fb0["breaker_open"] == 1


def test_half_open_probe_released_on_typed_rejection():
    """A typed rejection riding out of the device call during the
    half-open probe must release the probe slot — otherwise the
    breaker wedges in half_open with probe_inflight set and never
    tries the device again."""
    calls = {"n": 0}

    def device(bags):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("down")
        if calls["n"] == 2:
            raise UnavailableError("typed rejection mid-probe")
        return ["dev"] * len(bags)

    rc = ResilientChecker(device, lambda bags: ["oracle"] * len(bags),
                          config=_fast_config(breaker_failures=1,
                                              breaker_reset_s=0.05,
                                              retry=False))
    assert rc.run_batch(["a"]) == ["oracle"]     # failure -> open
    assert rc.breaker.state == "open"
    time.sleep(0.06)
    with pytest.raises(UnavailableError):
        rc.run_batch(["b"])                      # probe raises typed
    assert rc.breaker.state == "half_open"
    # the slot was released: the next batch gets a fresh probe and
    # closes the breaker
    assert rc.run_batch(["c"]) == ["dev"]
    assert rc.breaker.state == "closed"


def test_fail_open_short_ttls():
    """Fail-open allows must not be cached like a healthy success —
    1s/1-use TTLs close the policy-bypass window with the outage."""
    def broken(bags):
        raise RuntimeError("down")

    rc = ResilientChecker(broken, broken,
                          config=_fast_config(fail_policy="open"))
    out = rc.run_batch(["a"])
    assert out[0].status_code == 0
    assert out[0].valid_duration_s <= 1.0
    assert out[0].valid_use_count == 1


def test_fail_closed_raises_unavailable():
    def broken(bags):
        raise RuntimeError("down")

    rc = ResilientChecker(broken, broken,
                          config=_fast_config(fail_policy="closed"))
    with pytest.raises(UnavailableError):
        rc.run_batch(["a"])


def test_fail_open_answers_ok():
    def broken(bags):
        raise RuntimeError("down")

    rc = ResilientChecker(broken, broken,
                          config=_fast_config(fail_policy="open"))
    out = rc.run_batch(["a", "b", PadBag()])
    assert len(out) == 2                 # per REAL row, pads trimmed
    assert all(r.status_code == 0 for r in out)


# ---------------------------------------------------------------------
# batcher admission control + deadlines
# ---------------------------------------------------------------------

def _blocked_batcher(release: threading.Event, max_batch: int = 1,
                     **kw):
    """pipeline=1 + a run_batch that blocks: the first batch occupies
    the worker, the second wedges the flusher in _flush's semaphore,
    and everything after queues — deterministic depth for the
    admission tests."""
    seen: list = []

    def run_batch(bags):
        seen.append(list(bags))
        release.wait(timeout=30)
        return [("ok", i) for i in range(len(bags))]

    b = CheckBatcher(run_batch, window_s=0.0005, max_batch=max_batch,
                     pipeline=1, buckets=(max_batch,),
                     pad_batches=False, **kw)
    return b, seen


def test_queue_cap_sheds_resource_exhausted():
    release = threading.Event()
    b, _ = _blocked_batcher(release, max_queue=2)
    try:
        shed0 = monitor.resilience_counters()["shed"]["queue_full"]
        futs = [b.submit(f"bag{i}") for i in range(8)]
        shed = [f for f in futs
                if f.done() and isinstance(f.exception(),
                                           ResourceExhaustedError)]
        assert shed, "no submit shed despite a full queue"
        assert b.stats()["depth"] <= 2
        release.set()
        for f in futs:
            if f not in shed:
                assert f.result(timeout=10)[0] == "ok"
        c = monitor.resilience_counters()
        assert c["shed"]["queue_full"] - shed0 == len(shed)
    finally:
        release.set()
        b.close()


def test_brownout_sheds_newest_when_p99_over_target():
    release = threading.Event()
    b, _ = _blocked_batcher(release, max_queue=4, brownout=True)
    try:
        # an SLO-breaching live window (p99 >> 1ms target)
        for _ in range(64):
            monitor.observe_check_e2e(0.100)
        shed0 = monitor.resilience_counters()["shed"]["brownout"]
        futs = [b.submit(f"bag{i}") for i in range(8)]
        brown = [f for f in futs
                 if f.done() and isinstance(f.exception(),
                                            ResourceExhaustedError)
                 and "brownout" in str(f.exception())]
        assert brown, "brownout shed nothing despite p99 over target"
        assert monitor.resilience_counters()["shed"]["brownout"] \
            - shed0 == len(brown)
        release.set()
        for f in futs:
            if f not in brown:
                f.result(timeout=10)
    finally:
        release.set()
        b.close()


def test_deadline_expired_at_submit_rejects():
    b = CheckBatcher(lambda bags: [1] * len(bags), window_s=0.0005)
    try:
        exp0 = monitor.resilience_counters()["expired_total"]
        fut = b.submit("bag", deadline=time.perf_counter() - 0.1)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=5)
        assert monitor.resilience_counters()["expired_total"] == \
            exp0 + 1
    finally:
        b.close()


def test_deadline_expired_in_queue_shed_before_run_batch():
    """A row whose deadline passes while it waits behind an in-flight
    batch must resolve DEADLINE_EXCEEDED and never reach run_batch
    (the pre-tensorize shed)."""
    release = threading.Event()
    b, seen = _blocked_batcher(release, max_batch=4)
    try:
        f1 = b.submit("first")           # trip 1 occupies the worker
        time.sleep(0.02)
        f2 = b.submit("stale", deadline=time.perf_counter() + 0.01)
        time.sleep(0.05)                 # expire behind trip 1
        release.set()
        with pytest.raises(DeadlineExceededError):
            f2.result(timeout=10)
        assert f1.result(timeout=10)[0] == "ok"
        assert all("stale" not in batch for batch in seen)
    finally:
        release.set()
        b.close()


def test_occupancy_hold_never_outlasts_deadline():
    """hold_at=1 + an in-flight trip puts the loop in its busy-hold
    accumulation; a held request must flush while its deadline still
    has a hold quantum of slack (so it can be SERVED — flushing at
    expiry would guarantee a shed), never wait out the trip."""
    release = threading.Event()
    seen: list = []

    def run_batch(bags):
        seen.append(list(bags))
        if len(seen) == 1:
            release.wait(timeout=30)
        return ["ok"] * len(bags)

    b = CheckBatcher(run_batch, window_s=0.0005, max_batch=64,
                     pipeline=2, buckets=(64,), pad_batches=False,
                     hold_at=1)
    try:
        f1 = b.submit("first")
        time.sleep(0.02)                 # trip 1 in flight -> busy
        t0 = time.perf_counter()
        deadline = time.perf_counter() + 0.05
        f2 = b.submit("held", deadline=deadline)
        # resolves around its own deadline (served via worker 2, or
        # shed if dispatch lost the race) — never after the 30s trip
        try:
            assert f2.result(timeout=10) == "ok"
            # served: the batch flushed BEFORE expiry
            assert any("held" in batch for batch in seen)
        except DeadlineExceededError:
            pass
        waited = time.perf_counter() - t0
        assert waited < 2.0, f"held {waited:.3f}s past its deadline"
        release.set()
        assert f1.result(timeout=10) == "ok"
    finally:
        release.set()
        b.close()


def test_cancelled_future_shed_at_batch_build():
    """An aio client disconnect cancels its future; the row must be
    dropped before padding/tensorize, and its batch-mates must still
    resolve."""
    gate = threading.Event()
    seen: list = []

    def run_batch(bags):
        seen.append(list(bags))
        return ["ok"] * len(bags)

    b = CheckBatcher(run_batch, window_s=0.2, max_batch=8,
                     buckets=(8,), pad_batches=False)
    try:
        c0 = int(monitor.CHECK_CANCELLED_SHED._value.get())
        f1 = b.submit("keep1")
        f2 = b.submit("gone")
        f3 = b.submit("keep2")
        assert f2.cancel()               # pending future: cancellable
        assert f1.result(timeout=10) == "ok"
        assert f3.result(timeout=10) == "ok"
        assert seen and all("gone" not in batch for batch in seen)
        assert int(monitor.CHECK_CANCELLED_SHED._value.get()) == c0 + 1
        gate.set()
    finally:
        gate.set()
        b.close()


def test_batch_failure_counter_and_typed_error():
    def run_batch(bags):
        raise RuntimeError("device exploded")

    b = CheckBatcher(run_batch, window_s=0.0005)
    try:
        n0 = int(monitor.CHECK_BATCH_FAILURES._value.get())
        fut = b.submit("bag")
        with pytest.raises(RuntimeError, match="device exploded"):
            fut.result(timeout=10)
        assert int(monitor.CHECK_BATCH_FAILURES._value.get()) == n0 + 1
    finally:
        b.close()


def test_report_batcher_does_not_pollute_check_counters():
    """The report coalescer reuses CheckBatcher with
    observe_latency=False — its failures/sheds must stay out of the
    CHECK resilience counters."""
    def run_batch(bags):
        raise RuntimeError("boom")

    b = CheckBatcher(run_batch, window_s=0.0005,
                     size_hist=monitor.REPORT_BATCH_SIZE,
                     observe_latency=False, max_queue=1)
    try:
        n0 = int(monitor.CHECK_BATCH_FAILURES._value.get())
        shed0 = monitor.resilience_counters()["shed_total"]
        fut = b.submit("bag")
        with pytest.raises(RuntimeError):
            fut.result(timeout=10)
        assert int(monitor.CHECK_BATCH_FAILURES._value.get()) == n0
        assert monitor.resilience_counters()["shed_total"] == shed0
    finally:
        b.close()


# ---------------------------------------------------------------------
# flusher-thread watchdog
# ---------------------------------------------------------------------

def test_watchdog_dead_flusher_fails_fast():
    b = CheckBatcher(lambda bags: ["ok"] * len(bags),
                     window_s=0.0005, max_batch=4, buckets=(4,),
                     pad_batches=False)
    try:
        assert b.submit("warm").result(timeout=10) == "ok"
        # kill the flusher: the next dispatch explodes inside _flush
        b._pool.submit = None
        f2 = b.submit("bag2")            # flusher dies flushing this
        deadline = time.time() + 10
        while b._dead is None and time.time() < deadline:
            time.sleep(0.005)
        assert b._dead is not None, "watchdog never marked the death"
        ok, err = b.healthy()
        assert not ok and "died" in err
        # the batch in the flusher's hands was resolved, not orphaned
        with pytest.raises(UnavailableError):
            f2.result(timeout=10)
        # new submits fail fast instead of queueing forever
        shed0 = monitor.resilience_counters()["shed"]["batcher_dead"]
        f3 = b.submit("bag3")
        with pytest.raises(UnavailableError):
            f3.result(timeout=10)
        assert monitor.resilience_counters()["shed"]["batcher_dead"] \
            == shed0 + 1
        assert "healthy" in b.stats() and not b.stats()["healthy"]
    finally:
        b._pool.submit = type(b._pool).submit.__get__(b._pool)
        b._closed = True                 # close() would join a dead
        b._pool.shutdown(wait=False)     # thread; tear down manually


def test_healthz_reports_dead_flusher(tmp_path):
    """/healthz must go 503 when the check flusher dies — the
    introspect server consults batcher.healthy() (satellite 1)."""
    import json
    import urllib.request
    from types import SimpleNamespace

    from istio_tpu.introspect import IntrospectServer

    b = CheckBatcher(lambda bags: [1] * len(bags), window_s=0.0005)
    runtime = SimpleNamespace(
        batcher=b, _report_batcher=None,
        controller=SimpleNamespace(dispatcher=SimpleNamespace(
            snapshot=SimpleNamespace(revision=7))))
    intro = IntrospectServer(runtime=runtime, trace_capacity=0)
    try:
        port = intro.start()

        def healthz():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=10) as r:
                    return r.status, json.load(r)
            except urllib.error.HTTPError as e:
                return e.code, json.load(e)

        code, body = healthz()
        assert code == 200 and body["status"] == "ok"
        b._dead = RuntimeError("flusher crashed")
        code, body = healthz()
        assert code == 503
        assert "flusher" in body["error"]
    finally:
        intro.close()
        b._dead = None
        b.close()


# ---------------------------------------------------------------------
# submit()-vs-close() race (satellite 4)
# ---------------------------------------------------------------------

def test_requests_racing_past_close_resolve_via_drain():
    """A request that lands in the queue behind the close() sentinel
    must still resolve (the _drain_on_close contract)."""
    seen: list = []

    def run_batch(bags):
        seen.append(list(bags))
        return [f"ok:{bag}" for bag in bags]

    b = CheckBatcher(run_batch, window_s=10.0, max_batch=8,
                     buckets=(8,), pad_batches=False)
    fa = b.submit("early")               # loop is collecting [early]
    time.sleep(0.02)
    # simulate the race: the sentinel enters the queue, then a request
    # that beat the _closed flag lands BEHIND it
    fb: Future = Future()
    fb._t_enq = time.perf_counter()
    b._closed = True
    b._queue.put(None)
    b._queue.put(("racer", fb))
    b._thread.join(timeout=10)
    assert not b._thread.is_alive()
    assert fa.result(timeout=5) == "ok:early"
    assert fb.result(timeout=5) == "ok:racer"
    assert any("racer" in batch for batch in seen)
    b._pool.shutdown(wait=True)


def test_drain_on_close_failing_batch_resolves_with_exception():
    """Even when the DRAIN batch itself fails, the raced-past-close
    futures must resolve (with the exception), never hang."""
    def run_batch(bags):
        if "poison" in bags:
            raise RuntimeError("drain batch failed")
        return [f"ok:{bag}" for bag in bags]

    b = CheckBatcher(run_batch, window_s=10.0, max_batch=8,
                     buckets=(8,), pad_batches=False)
    fa = b.submit("early")
    time.sleep(0.02)
    fb: Future = Future()
    fb._t_enq = time.perf_counter()
    b._closed = True
    b._queue.put(None)
    b._queue.put(("poison", fb))
    b._thread.join(timeout=10)
    assert fa.result(timeout=5) == "ok:early"
    with pytest.raises(RuntimeError, match="drain batch failed"):
        fb.result(timeout=5)
    b._pool.shutdown(wait=True)


# ---------------------------------------------------------------------
# end-to-end: RuntimeServer + ChaosHooks (shared small server)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_server():
    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.testing import workloads

    store = workloads.make_store(12)
    srv = RuntimeServer(store, ServerArgs(
        batch_window_s=0.0005, max_batch=8, buckets=(8,),
        breaker_failures=2, breaker_reset_s=0.2,
        default_manifest=workloads.MESH_MANIFEST))
    plan = srv.controller.dispatcher.fused
    if plan is not None:
        plan.prewarm((8,))
    yield srv
    CHAOS.reset()
    srv.close()


def test_breaker_fallback_parity_end_to_end(small_server):
    from istio_tpu.testing import workloads

    bags = workloads.make_bags(10)
    clean = [small_server.check(b).status_code for b in bags]
    CHAOS.device_failures = 10**9
    try:
        degraded = [small_server.check(b).status_code for b in bags]
    finally:
        CHAOS.reset()
    assert degraded == clean
    assert small_server.resilience.breaker.state == "open"
    # recovery via the half-open probe once the fault clears
    time.sleep(0.25)
    assert small_server.check(bags[0]).status_code == clean[0]
    assert small_server.resilience.breaker.state == "closed"


def test_fail_policy_end_to_end(small_server):
    from istio_tpu.testing import workloads

    bag = workloads.make_bags(1)[0]
    CHAOS.device_failures = 10**9
    CHAOS.oracle_failures = 10**9
    cfg = small_server.resilience.config
    old_policy = cfg.fail_policy
    try:
        cfg.fail_policy = "closed"
        with pytest.raises(UnavailableError):
            small_server.check(bag)
        cfg.fail_policy = "open"
        assert small_server.check(bag).status_code == 0
    finally:
        cfg.fail_policy = old_policy
        CHAOS.reset()
        small_server.resilience.breaker.record_success()


def test_chunked_front_rejects_expired_pre_tensorize(small_server):
    """The BatchCheck/native chunked entry answers DEADLINE_EXCEEDED
    for chunks its deadline can't reach — without tensorizing them."""
    from istio_tpu.api.grpc_server import MixerGrpcServer
    from istio_tpu.testing import workloads

    g = MixerGrpcServer(small_server)    # never started: direct call
    bags = workloads.make_bags(6)
    tz0 = monitor.CHECK_STAGE_SECONDS.count(stage="tensorize")
    exp0 = monitor.resilience_counters()["expired_total"]
    out = g._check_bags_chunked(list(bags),
                                deadline=time.perf_counter() - 1.0)
    assert len(out) == len(bags)
    assert all(r.status_code == 4 for r in out)
    assert all(r.valid_use_count == 0 for r in out)
    assert monitor.CHECK_STAGE_SECONDS.count(stage="tensorize") == tz0
    assert monitor.resilience_counters()["expired_total"] - exp0 == \
        len(bags)
    # a live deadline serves normally
    out = g._check_bags_chunked(list(bags),
                                deadline=time.perf_counter() + 30.0)
    assert [r.status_code for r in out] == \
        [small_server.check(b).status_code for b in bags]
