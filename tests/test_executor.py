"""Adapter-executor plane (runtime/executor.py): bulkheads, deadline
bounds, per-handler breakers, maintenance lane, typed-rejection
conservation — ISSUE 12's wedged-adapter chaos suite."""
from __future__ import annotations

import threading
import time

import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.runtime import RuntimeServer, ServerArgs
from istio_tpu.runtime import monitor
from istio_tpu.runtime.resilience import CHAOS
from istio_tpu.testing import workloads

UNAVAILABLE = 14

CI = "cilist.istio-system"
PROV = "provlist.istio-system"

# A wedged adapter blocks until CHAOS.reset() in the test's `finally`,
# so a call that returns at all was released by its deadline and not
# by the wedge. The ceiling over the deadline only has to tell that
# from a hang, so it leaves room for a scheduler that six busy xdist
# workers share.
SCHED_SLACK_S = 5.0


@pytest.fixture(autouse=True)
def _chaos_clean():
    CHAOS.reset()
    yield
    CHAOS.reset()


def _server(store, **kw):
    args = dict(batch_window_s=0.0005, max_batch=16, buckets=(8, 16),
                default_manifest=workloads.MESH_MANIFEST)
    args.update(kw)
    return RuntimeServer(store, ServerArgs(**args))


def _overlay_bag(i: int, n_services: int = 30) -> object:
    """A bag matching make_store(host_overlay_every=5) rule `i`
    (i % 5 == 2 rules carry a host list action; k = (i//5) % 3 picks
    cilist / provlist / dynpat)."""
    return bag_from_mapping({
        "destination.service":
            f"svc{i % n_services}.ns{i % 23}.svc.cluster.local",
        "source.namespace": "ns2",
        "request.method": "GET",
        # k==7 rules gate on request.path.startsWith("/api/v{i%3}/")
        "request.path": f"/api/v{i % 3}/items",
    })


def _counters_delta(before: dict, key: str = "outcomes") -> dict:
    after = monitor.host_action_counters()
    return {k: after[key][k] - before[key].get(k, 0)
            for k in after[key]}


def test_wedged_adapter_bulkhead_and_recovery():
    """THE chaos scenario: one handler wedged under load — other
    adapters' throughput unaffected (bulkhead), affected rules resolve
    via the fail policy within the deadline, the lane breaker opens,
    then half-open-probes closed on recovery, and the typed-rejection
    conservation stays EXACT."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store, host_breaker_failures=2,
                  host_breaker_reset_s=0.3)
    try:
        base = monitor.host_action_counters()
        ci_bag = _overlay_bag(2)      # k=0 → cilist
        prov_bag = _overlay_bag(7)    # k=1 → provlist
        # clean baseline verdicts
        clean_ci = srv.check(ci_bag).status_code
        clean_prov = srv.check(prov_bag).status_code

        CHAOS.wedge_adapter(CI)
        deadline_s = 0.4
        # wedged-handler requests: answered WITHIN the deadline with
        # the fail-closed verdict, never held by the wedged backend
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            r = srv.check(ci_bag,
                          deadline=time.perf_counter() + deadline_s)
            walls.append(time.perf_counter() - t0)
            assert r.status_code == UNAVAILABLE
        assert max(walls) < deadline_s + 0.35, walls
        # bulkhead: the OTHER handler's lane is untouched — fast, and
        # verdicts unchanged
        t0 = time.perf_counter()
        assert srv.check(prov_bag).status_code == clean_prov
        assert time.perf_counter() - t0 < deadline_s
        # breaker: 2 overruns tripped the cilist lane open; further
        # actions short-circuit (breaker_open) without queueing
        lane = srv.executor.lane(CI)
        assert lane.breaker.state == "open"
        r = srv.check(ci_bag,
                      deadline=time.perf_counter() + deadline_s)
        assert r.status_code == UNAVAILABLE
        d = _counters_delta(base)
        assert d["overrun"] >= 2
        assert d["breaker_open"] >= 1

        # recovery: unwedge, wait out the reset window — the next
        # action is the half-open probe, closes the breaker, and the
        # verdict returns to the clean baseline
        CHAOS.unwedge_adapter(CI)
        time.sleep(0.35)
        assert srv.check(ci_bag).status_code == clean_ci
        assert lane.breaker.state == "closed"
    finally:
        CHAOS.reset()
        srv.close()
    # EXACT conservation: every submitted action resolved with exactly
    # one outcome (late completions counted separately, never twice)
    hc = monitor.host_action_counters()
    assert hc["exact"], hc
    assert hc["submitted"] - base["submitted"] == \
        sum(_counters_delta(base).values())


def test_bulkhead_overflow_sheds_typed_with_deadline():
    """A wedged lane's queue fills to its cap; further submits shed
    typed (outcome=shed → fail policy) instantly, never block, and
    the batch folds in roughly one action-timeout window."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store, executor_queue_cap=1, executor_workers=1,
                  host_breaker_failures=100,
                  host_action_timeout_ms=300.0)
    try:
        base = monitor.host_action_counters()
        CHAOS.wedge_adapter(CI)
        ci_bags = [_overlay_bag(2) for _ in range(8)]
        t0 = time.perf_counter()
        out = srv.check_many(ci_bags)
        wall = time.perf_counter() - t0
        assert all(r.status_code == UNAVAILABLE for r in out)
        # 8 actions: 1 running + 1 queued wait out the 300ms action
        # timeout, 6 shed instantly at the cap (the shed count below
        # is what tells one timeout window from eight)
        assert wall < 0.3 + SCHED_SLACK_S, wall
        d = _counters_delta(base)
        assert d["shed"] >= 5, d
        assert d["shed"] + d["overrun"] + d["expired"] == 8, d
    finally:
        CHAOS.reset()
        srv.close()
    assert monitor.host_action_counters()["exact"]


def test_adapter_errors_keep_safedispatch_parity_and_trip_breaker():
    """Injected adapter exceptions: one retry, then the action's own
    INTERNAL verdict (safeDispatch parity — oracle-identical), and
    consecutive failures trip the lane breaker."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store, host_breaker_failures=3,
                  host_breaker_reset_s=60.0)
    try:
        base = monitor.host_action_counters()
        bag = _overlay_bag(2)
        clean = srv.check(bag).status_code
        CHAOS.adapter_failures[CI] = 10 ** 6   # every attempt fails
        sts = [srv.check(bag).status_code for _ in range(3)]
        # INTERNAL (13): the adapter-panic shape, not the fail policy
        assert sts == [13, 13, 13], sts
        assert srv.executor.lane(CI).breaker.state == "open"
        # open breaker → fail policy (closed → UNAVAILABLE)
        assert srv.check(bag).status_code == UNAVAILABLE
        d = _counters_delta(base)
        assert d["error"] == 3 and d["breaker_open"] == 1, d
        # retries happened (one per failed action)
        hc = monitor.host_action_counters()
        assert hc["retries"] - base["retries"] == 3
        CHAOS.reset()
        srv.executor.lane(CI).breaker.record_success()  # force close
        assert srv.check(bag).status_code == clean
    finally:
        CHAOS.reset()
        srv.close()


def test_fail_open_policy_answers_ok_with_short_ttl():
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store, host_fail_policy="open",
                  host_action_timeout_ms=100.0)
    try:
        CHAOS.wedge_adapter(CI)
        r = srv.check(_overlay_bag(2))
        assert r.status_code == 0
        # the policy-bypass window must close with the outage
        assert r.valid_duration_s <= 1.0
        assert r.valid_use_count == 1
    finally:
        CHAOS.reset()
        srv.close()


def test_deadline_inherited_from_request_bounds_host_actions():
    """Deadline propagation end to end: the batcher's min-deadline
    reaches the executor fold, so a wedged adapter can never hold a
    request past its own budget."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store)
    try:
        CHAOS.wedge_adapter(CI)
        t0 = time.perf_counter()
        r = srv.check(_overlay_bag(2),
                      deadline=time.perf_counter() + 0.25)
        wall = time.perf_counter() - t0
        assert r.status_code == UNAVAILABLE
        assert wall < 0.25 + SCHED_SLACK_S, wall
    finally:
        CHAOS.reset()
        srv.close()


def test_ns_invisible_fallback_pairs_skipped():
    """Satellite regression: _overlay_active must not host_eval a
    (bag, rule) pair whose namespace can never see the rule — a slow
    fallback predicate is only paid by traffic that could match it,
    and error accounting stays oracle-identical (visible-only)."""
    from istio_tpu.runtime.store import MemStore

    s = MemStore()
    s.set(("handler", "nsa", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "nsa", "nothing"), {
        "template": "checknothing", "params": {}})
    # dynamic map key → host-fallback predicate, namespaced to nsa
    s.set(("rule", "nsa", "dynkey"), {
        "match": 'request.headers[request.method] == "yes"',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    srv = _server(s)
    try:
        d = srv.controller.dispatcher
        rs = d.snapshot.ruleset
        assert rs.host_fallback, "dynkey must be host-fallback"
        calls = []
        real = rs.host_eval

        def spy(ridx, bag):
            calls.append(ridx)
            return real(ridx, bag)

        rs.host_eval = spy
        try:
            vis = bag_from_mapping({
                "destination.service": "x.nsa.svc.cluster.local",
                "request.method": "GET",
                "request.headers": {"GET": "yes"}})
            invis = bag_from_mapping({
                "destination.service": "x.nsb.svc.cluster.local",
                "request.method": "GET",
                "request.headers": {"GET": "yes"}})
            out = d.check([vis, invis, invis])
            # only the VISIBLE row paid a host_eval
            assert len(calls) == 1, calls
            # verdicts oracle-identical
            oracle = d.check_host_oracle([vis, invis, invis])
            assert [r.status_code for r in out] == \
                [r.status_code for r in oracle] == [7, 0, 0]
            # invisible errored pairs: no RESOLVE_ERRORS movement
            calls.clear()
            err0 = monitor.RESOLVE_ERRORS._value.get()
            bad = bag_from_mapping({
                "destination.service": "x.nsb.svc.cluster.local"})
            d.check([bad])   # would error in dynkey — but invisible
            assert calls == []
            assert monitor.RESOLVE_ERRORS._value.get() == err0
        finally:
            rs.host_eval = real
    finally:
        srv.close()


def test_list_provider_refresh_failure_keeps_last_good(tmp_path):
    """Satellite: a failing file:// provider keeps serving the last
    good list, the refresh counter pair moves, and the failure is
    visible in refresh stats."""
    from istio_tpu.adapters.list_adapter import ListHandler

    p = tmp_path / "allow.txt"
    p.write_text("ns0\nns2\n")
    h = ListHandler({"provider_url": f"file://{p}",
                     "refresh_interval_s": 60.0}, env=None)
    assert h.handle_check("listentry", {"value": "ns2"}).ok
    t0 = int(monitor.LIST_REFRESH_TOTAL._value.get())
    f0 = int(monitor.LIST_REFRESH_FAILURES._value.get())

    from istio_tpu.runtime.executor import (AdapterExecutor,
                                            ExecutorConfig)
    ex = AdapterExecutor(ExecutorConfig())
    try:
        ex.register_refreshables({"lh.ns": h})
        p.unlink()   # provider now fails
        assert ex.refresh_now("lh.ns")
        # last good list keeps serving
        assert h.handle_check("listentry", {"value": "ns2"}).ok
        assert not h.handle_check("listentry", {"value": "ns1"}).ok
        assert int(monitor.LIST_REFRESH_TOTAL._value.get()) == t0 + 1
        assert int(monitor.LIST_REFRESH_FAILURES._value.get()) == \
            f0 + 1
        st = h.refresh_stats()
        assert st["refresh_failures"] == 1
        assert st["last_refresh_error"]
        snap = ex.snapshot()
        m = snap["maintenance"]["lh.ns"]
        assert m["refresh_failures"] == 1 and m["refresh_total"] == 1
        # provider restored → next refresh picks up the new list
        p.write_text("ns1\n")
        assert ex.refresh_now("lh.ns")
        assert h.handle_check("listentry", {"value": "ns1"}).ok
        assert h.refresh_stats()["last_refresh_error"] is None
    finally:
        ex.close()


def test_maintenance_scheduler_drives_periodic_refresh():
    from istio_tpu.runtime.executor import (AdapterExecutor,
                                            ExecutorConfig)

    pulls = []

    class H:
        refresh_interval_s = 0.05
        _provider = staticmethod(lambda: [])

        def refresh(self):
            pulls.append(time.monotonic())

    ex = AdapterExecutor(ExecutorConfig(maintenance_tick_s=0.01))
    try:
        ex.register_refreshables({"h.ns": H()})
        deadline = time.monotonic() + 3.0
        while len(pulls) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(pulls) >= 2, "scheduler never fired"
    finally:
        ex.close()


def test_opa_scenario_oracle_parity_and_verdicts():
    """The rego/OPA engine as a first-class overlay scenario: real
    allow AND deny verdicts, exactly matching the generic host oracle
    path (the executor changes where adapter work runs, never what it
    answers)."""
    store = workloads.make_opa_store(42)
    srv = _server(store)
    try:
        bags = [bag_from_mapping(x)
                for x in workloads.make_opa_requests(24, 42)]
        d = srv.controller.dispatcher
        fused = d.check(bags)
        oracle = d.check_host_oracle(bags)
        sts = [r.status_code for r in fused]
        assert [r.status_code for r in oracle] == sts
        assert 7 in sts and 0 in sts, sts   # both verdicts exercised
        hc = monitor.host_action_counters()
        assert hc["by_handler"]["opah.istio-system"]["outcomes"][
            "ok"] >= len(bags) // 2
    finally:
        srv.close()


def test_shared_quota_dedup_across_replicas():
    """memquota over one shared QuotaBackend behind two server
    replicas, allocations through the executor's mq lane: a dedup_id
    retried on the OTHER replica replays the original grant, and the
    global window is conserved under concurrency."""
    from istio_tpu.adapters.memquota import QuotaBackend
    from istio_tpu.adapters.sdk import QuotaArgs

    backend = QuotaBackend()
    a = _server(workloads.make_shared_quota_store(backend,
                                                  max_amount=32))
    b = _server(workloads.make_shared_quota_store(backend,
                                                  max_amount=32))
    try:
        bag = bag_from_mapping({
            "source.user": "u1",
            "destination.service": "x.ns0.svc.cluster.local"})
        r1 = a.quota(bag, "rq.istio-system",
                     QuotaArgs(quota_amount=5, dedup_id="d-1"))
        r2 = b.quota(bag, "rq.istio-system",
                     QuotaArgs(quota_amount=5, dedup_id="d-1"))
        assert (r1.granted_amount, r2.granted_amount) == (5, 5)
        assert backend.dedup["d-1"][0] == 5   # ONE real allocation

        # concurrent best-effort allocs across both replicas: total
        # real grants never exceed the shared window (32 - 5 = 27)
        granted = []
        lock = threading.Lock()

        def worker(srv, n):
            for i in range(n):
                r = srv.quota(bag, "rq.istio-system",
                              QuotaArgs(quota_amount=3,
                                        best_effort=True))
                with lock:
                    granted.append(r.granted_amount)

        ts = [threading.Thread(target=worker, args=(s, 10))
              for s in (a, b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sum(granted) == 27, granted
        hc = monitor.host_action_counters()
        assert hc["by_handler"]["mq.istio-system"]["outcomes"]["ok"] \
            >= 22
    finally:
        a.close()
        b.close()


def test_inline_path_parity_when_executor_disabled():
    """host_executor=False restores the pre-executor inline loop —
    verdict-identical on the same traffic (the behavioral oracle)."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv_ex = _server(store)
    srv_in = _server(workloads.make_store(60, host_overlay_every=5),
                     host_executor=False)
    try:
        assert srv_in.executor is None
        assert srv_in.controller.dispatcher.executor is None
        bags = [_overlay_bag(i) for i in (2, 7, 12, 22, 32, 42)]
        out_ex = [r.status_code for r in
                  srv_ex.controller.dispatcher.check(bags)]
        out_in = [r.status_code for r in
                  srv_in.controller.dispatcher.check(bags)]
        assert out_ex == out_in
    finally:
        srv_ex.close()
        srv_in.close()


def test_abandon_keeps_conservation_exact_without_breaker_blame():
    """A fold unwinding past submitted actions (exception between
    submit and claim) must account every action exactly once — and
    must NOT charge the adapter's breaker for the fold's failure."""
    from istio_tpu.runtime.executor import (AdapterExecutor,
                                            ExecutorConfig)

    ex = AdapterExecutor(ExecutorConfig())
    try:
        base = monitor.host_action_counters()
        # the wedge is LANE-wide, so the completing action must live
        # on its own lane
        CHAOS.wedge_adapter("h.ns")
        running = ex.submit("h.ns", lambda: "never",
                            lambda p, r: None)
        done = ex.submit("ok.ns", lambda: "fast", lambda p, r: None)
        claimed = ex.resolve(done)   # normally claimed by the fold
        assert claimed == "fast"
        # the fold dies here: abandon both (claimed one is a no-op)
        ex.abandon(done)
        ex.abandon(running)
        hc = monitor.host_action_counters()
        assert hc["exact"], hc
        d = {k: hc["outcomes"][k] - base["outcomes"][k]
             for k in hc["outcomes"]}
        assert d == {"ok": 1, "error": 0, "shed": 0, "expired": 1,
                     "overrun": 0, "breaker_open": 0}, d
        # the adapter is not blamed for the fold's exception
        assert ex.lane("h.ns").breaker.state == "closed"
    finally:
        CHAOS.reset()
        ex.close()


def test_quota_adapter_call_bounded_by_server_default_deadline():
    """RuntimeServer.quota inherits the server default deadline when
    the caller passes none — a wedged shared-quota backend cannot
    hold a front thread unbounded."""
    from istio_tpu.adapters.sdk import QuotaArgs

    srv = _server(workloads.make_shared_quota_store(max_amount=8),
                  default_check_deadline_ms=250.0)
    try:
        bag = bag_from_mapping({
            "source.user": "u1",
            "destination.service": "x.ns0.svc.cluster.local"})
        CHAOS.wedge_adapter("mq.istio-system")
        t0 = time.perf_counter()
        r = srv.quota(bag, "rq.istio-system",
                      QuotaArgs(quota_amount=2))
        wall = time.perf_counter() - t0
        assert wall < 0.25 + SCHED_SLACK_S, wall
        # fail-closed: granted nothing, typed UNAVAILABLE
        assert (r.granted_amount, r.status_code) == (0, UNAVAILABLE)
    finally:
        CHAOS.reset()
        srv.close()


def test_executor_survives_config_swap_with_breaker_state():
    """Lanes (and their breakers) persist across config republishes —
    a wedged handler stays short-circuited through a swap instead of
    re-paying the failure budget in-band."""
    store = workloads.make_store(60, host_overlay_every=5)
    srv = _server(store, host_breaker_failures=1,
                  host_breaker_reset_s=60.0,
                  host_action_timeout_ms=100.0)
    try:
        CHAOS.wedge_adapter(CI)
        srv.check(_overlay_bag(2))   # overrun → breaker opens
        assert srv.executor.lane(CI).breaker.state == "open"
        # republish (quiet edit + explicit rebuild)
        store.set(("rule", "ns1", "rule1"), {
            "match": 'destination.service == "zz.ns1.svc.cluster.local"',
            "actions": [{"handler": "denyall.istio-system",
                         "instances": []}]})
        srv.controller.rebuild()
        assert srv.controller.dispatcher.executor is srv.executor
        assert srv.executor.lane(CI).breaker.state == "open"
        r = srv.check(_overlay_bag(2))
        assert r.status_code == UNAVAILABLE   # still short-circuited
    finally:
        CHAOS.reset()
        srv.close()
