"""Unit tests for the mesh audit plane (istio_tpu/runtime/audit.py):
AuditCheck verdict semantics, the time-AND-count stuck detector, the
test-only counter seams, the injection ledger's coalescing /
matching / expiry, the grant watermark, the device-pool audit view,
the discovery scope-pair derivation and the fused /debug/slo
scorecard. The heavier end-to-end path (real fronts, chaos, HTTP)
lives in scripts/audit_smoke.py."""
from __future__ import annotations

import time

import pytest

from istio_tpu.runtime import forensics, monitor
from istio_tpu.runtime.audit import (AuditCheck, AuditPlane,
                                     InjectionLedger, SEAMS)
from istio_tpu.testing import workloads


@pytest.fixture(autouse=True)
def _clean_seams():
    SEAMS.reset()
    yield
    SEAMS.reset()


@pytest.fixture(scope="module")
def srv():
    from istio_tpu.runtime import RuntimeServer, ServerArgs

    s = RuntimeServer(workloads.make_store(8), ServerArgs(
        batch_window_s=0.0005, max_batch=8, buckets=(4, 8),
        check_grants=True,            # grant_coherence enabled leg
        default_manifest=workloads.MESH_MANIFEST))
    yield s
    s.close()


def test_audit_check_as_dict_shape():
    chk = AuditCheck("report_conservation", evidence={"x": 1},
                     note="n")
    d = chk.as_dict()
    assert d["name"] == "report_conservation"
    assert d["status"] == "ok" and d["evidence"] == {"x": 1}
    assert set(d) == {"name", "status", "evidence", "generation",
                      "wall", "note"}


def test_negative_residue_violates_immediately():
    """A negative ledger (more exported than accepted) is an
    impossible state — no stuck window applies."""
    aud = AuditPlane(None)
    SEAMS.report_accepted_skew = -(
        monitor.report_conservation()["accepted"] + 3)
    chk = aud._report_conservation()
    assert chk.status == "violated"
    assert chk.evidence["in_flight"] < 0


def test_stuck_promotion_needs_count_and_time():
    """A frozen residue must be BOTH stuck_after evaluations old and
    stuck_floor_s seconds old before it is promoted to violated —
    back-to-back manual evaluations or one slow in-deadline request
    must read degraded, not violated."""
    aud = AuditPlane(None, stuck_after=3, stuck_floor_s=0.4)
    SEAMS.report_accepted_skew = 5
    # count satisfied quickly, time floor not yet
    for _ in range(4):
        chk = aud._report_conservation()
    assert chk.status == "degraded", chk.as_dict()
    assert chk.evidence["stuck_evaluations"] >= 3
    time.sleep(0.45)
    chk = aud._report_conservation()
    assert chk.status == "violated"
    assert chk.evidence["frozen_s"] >= 0.4
    # clearing the skew clears the stuck state
    SEAMS.reset()
    chk = aud._report_conservation()
    assert chk.status == "ok"


def test_check_accounting_typed_residue_is_ok():
    """A steady decode/response residue covered by typed rejections
    is the rejected-RPC shape, not a leak."""
    aud = AuditPlane(None, stuck_after=2, stuck_floor_s=0.05)
    rc = monitor.resilience_counters()
    typed = (rc["shed_total"] + rc["expired_total"]
             + rc["cancelled_shed_total"])
    SEAMS.check_decoded_skew = typed + 1 \
        - monitor.serving_counters()["in_flight"]
    aud._check_accounting()
    time.sleep(0.1)
    chk = aud._check_accounting()
    assert chk.status == "violated"     # 1 beyond the typed cover
    SEAMS.check_decoded_skew -= 1
    aud._check_accounting()
    time.sleep(0.1)
    chk = aud._check_accounting()
    assert chk.status == "ok"
    if typed:   # residue == typed → the covered-rejection shape
        assert "typed rejections" in chk.note


def test_injection_ledger_coalesces_and_matches_by_event():
    led = InjectionLedger(coalesce_s=5.0)
    led.note("device")
    led.note("device")                  # coalesces into one record
    forensics.record_event("breaker", name="device")
    out = led.evaluate(window_s=30.0)
    assert out["matched"] == 2 and out["unexplained"] == 0
    assert out["rate"] == 1.0
    recs = [r for r in out["records"] if r["kind"] == "device"]
    assert len(recs) == 1 and recs[0]["n"] == 2
    assert recs[0]["matched_by"] == "event:breaker device"


def test_injection_ledger_matches_device_fault_absorbed_by_retry():
    """One injected device failure whose retry succeeds moves no
    fallback counter and no breaker: the retry counter is its only
    evidence. Without it the record stays pending for the whole
    explain window (the soak's explainability gate under load)."""
    from istio_tpu.runtime.resilience import (CHAOS, ResilienceConfig,
                                              ResilientChecker)

    forensics.EVENTS.reset()    # no neighbour's breaker event in reach
    led = InjectionLedger()
    prev, CHAOS.on_inject = CHAOS.on_inject, led.note
    try:
        def device(bags):
            CHAOS.device_step()
            return list(bags)

        chk = ResilientChecker(
            device, lambda bags: ["oracle"] * len(bags),
            ResilienceConfig(retry_backoff_s=0.0, retry_jitter_s=0.0))
        CHAOS.device_failures = 1
        fb0 = monitor.resilience_counters()["fallback_total"]
        assert chk.run_batch(["a", "b"]) == ["a", "b"]
        assert monitor.resilience_counters()["fallback_total"] == fb0
    finally:
        CHAOS.on_inject = prev
        CHAOS.reset()
    out = led.evaluate(window_s=30.0)
    assert out["pending"] == 0 and out["matched"] == 1, out
    assert out["records"][-1]["matched_by"] == \
        "counter:device_retries_total"


def test_injection_ledger_expires_unmatched():
    led = InjectionLedger()
    led.note("oracle")                  # nothing will explain it
    time.sleep(0.05)
    out = led.evaluate(window_s=0.01)
    assert out["unexplained"] == 1 and out["matched"] == 0
    assert out["rate"] == 0.0
    # a fresh ledger is vacuously explainable again
    led.reset()
    assert led.evaluate(window_s=1.0)["rate"] == 1.0


def test_grant_watermark_and_coherence(srv):
    aud = srv.audit
    wm = srv.grants.watermark()
    assert set(wm) == {"generation", "revocations", "grants_issued",
                      "issued_at_generation"}
    assert wm["issued_at_generation"] <= wm["generation"]
    chk = aud._grant_coherence()
    assert chk.status == "ok" and chk.evidence["enabled"]
    # the seam pushes issued_at beyond the watermark: a grant
    # apparently minted from a generation that never existed
    SEAMS.grant_issue_skew = wm["generation"] + 10
    chk = aud._grant_coherence()
    assert chk.status == "violated"
    assert "watermark" in chk.note


def test_plane_agreement_seam_detects_divergence(srv):
    aud = srv.audit
    chk = aud._plane_agreement()
    assert chk.status == "ok", chk.as_dict()
    SEAMS.plane_pairs_extra = [
        ("seam-pair", 'source.service == "a"',
         'source.service == "b"')]
    chk = aud._plane_agreement()
    assert chk.status == "violated"
    assert any(f["code"] == "plane-divergence"
               for f in chk.evidence["findings"])
    # clearing the seam re-proves agreement (fresh digest, no memo)
    SEAMS.reset()
    chk = aud._plane_agreement()
    assert chk.status == "ok"


def test_routing_disabled_on_monolithic(srv):
    chk = srv.audit._routing_conservation()
    assert chk.status == "ok"
    assert chk.evidence == {"enabled": False}


def test_device_pool_audit_view(srv):
    pools = getattr(srv.controller, "device_quotas", {})
    if not pools:
        pytest.skip("workload carries no device quota pool")
    view = next(iter(pools.values())).audit_view()
    assert view["negative_cells"] == 0
    assert view["over_cap_cells"] == 0
    assert view["nonzero_beyond_keymap"] == 0
    assert view["n_used"] <= view["n_buckets"]


def test_discovery_scope_pairs_agree():
    from istio_tpu.pilot.discovery import DiscoveryService

    registry, store, nodes, meta = workloads.make_discovery_world(
        n_services=12, n_namespaces=3, replicas=2, source_ns=2,
        seed=3)
    ds = DiscoveryService(registry, store)
    try:
        pairs = ds._snapshot.scope_audit_pairs()
        assert pairs
        for _name, served, compiled in pairs:
            assert served == compiled
    finally:
        ds.stop()


def test_slo_scorecard_verdict_fusion():
    from istio_tpu.runtime import slo

    assert slo._worst(["ok", "no_data"]) == "ok"
    assert slo._worst(["ok", "miss"]) == "miss"
    assert slo._worst(["no_data"]) == "no_data"
    card = slo.scorecard(monitor, forensics)
    assert set(card["planes"]) == {"check_wire", "report_export",
                                   "discovery_push", "quota_flush",
                                   "audit"}
    assert card["planes"]["audit"]["verdict"] == "no_data"
    # an unhealthy audit snapshot forces a miss
    card = slo.scorecard(monitor, forensics, audit={
        "healthy": False, "explainability": {"rate": 1.0},
        "checks": [{"name": "report_conservation",
                    "status": "violated"}]})
    assert card["planes"]["audit"]["verdict"] == "miss"
    assert card["overall"] == "miss"
    assert card["planes"]["audit"]["violated"] == \
        ["report_conservation"]


def test_audit_plane_snapshot_and_evaluate(srv):
    snap = srv.audit.evaluate()
    assert snap["enabled"] and snap["evaluations"] >= 1
    assert [c["name"] for c in snap["checks"]] == list(
        monitor.AUDIT_INVARIANTS)
    assert snap["healthy"] is True
    assert 0.0 <= snap["explainability"]["rate"] <= 1.0
    # sizes no ServerArgs field sets: the rings' and the auditor's own
    assert srv.audit.quota_every == 8
    assert forensics.RECORDER.snapshot()["capacity"] == 256
    assert forensics.EVENTS._buf.maxlen == 512
