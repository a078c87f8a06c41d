"""Satellite of the whole-mesh soak: the client-side conservation
identity in isolation. With the server up the whole time (no restart
window), the per-sidecar outcome ledgers must sum EXACTLY to the
server-side mixer_* front accounting — across an adapter-wedge window
AND a mixer config swap — on both the gRPC and the native front:

    wire_checks                       == requests_decoded delta
    ok + denied (wire-answered)       == responses_sent delta
    shed + expired + unavailable + err == decoded - responded
"""
import time

import pytest

from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.audit import INJECTIONS, SEAMS
from istio_tpu.runtime.resilience import CHAOS
from istio_tpu.testing import workloads

WEDGED = "cilist.istio-system"


@pytest.fixture
def mesh():
    CHAOS.reset()
    INJECTIONS.reset()
    SEAMS.reset()
    store = workloads.make_store(24, host_overlay_every=5, seed=3)
    srv = RuntimeServer(store, ServerArgs(
        batch_window_s=0.0005, max_batch=16, buckets=(8, 16),
        default_check_deadline_ms=600.0,
        host_breaker_failures=2, host_breaker_reset_s=0.4,
        default_manifest=workloads.MESH_MANIFEST))
    plan = srv.controller.dispatcher.fused
    if plan is not None:
        plan.prewarm((8, 16))
    try:
        yield store, srv
    finally:
        srv.close()
        CHAOS.reset()
        INJECTIONS.reset()
        SEAMS.reset()


def _drain(base):
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and \
            monitor.report_conservation(since=base)["in_flight"]:
        time.sleep(0.02)


def _run_fleet(front_start, front_stop, store, n_sidecars=2,
               seed=11):
    from istio_tpu.soak.fleet import FleetSimulator

    base_serving = monitor.serving_counters()
    base_report = monitor.report_conservation()
    port = front_start()
    reqs = workloads.make_request_dicts(16, seed=seed)
    fleet = FleetSimulator(lambda: f"127.0.0.1:{port}", reqs,
                           n_sidecars=n_sidecars, seed=seed,
                           pace_s=0.001, report_every=9,
                           enable_check_cache=False)
    try:
        fleet.start()
        time.sleep(0.4)
        # wedge window: typed rejections, not lost requests
        CHAOS.wedge_adapter(WEDGED)
        time.sleep(0.5)
        CHAOS.unwedge_adapter(WEDGED)
        # mixer config swap mid-run: the rebuilt snapshot must not
        # double- or drop-count in-flight fronts
        key = ("rule", "istio-system", "report-all")
        store.set(key, dict(store.get(key)))
        time.sleep(0.6)
        # a loaded machine (the suite's six workers) serves these
        # 1.5 s at a third of the pace: the identities below are the
        # subject, so the fleet runs on until it has what they need
        busy = time.monotonic() + 20
        while fleet.totals()["checks"] <= 120 and time.monotonic() < busy:
            time.sleep(0.05)
    finally:
        totals = fleet.stop()
        front_stop()
    _drain(base_report)

    sc = monitor.serving_counters()
    decoded = sc["requests_decoded"] - base_serving["requests_decoded"]
    responded = sc["responses_sent"] - base_serving["responses_sent"]
    oc = totals["outcomes"]
    assert totals["checks"] > 100, "fleet barely ran"
    assert totals["cache_hits"] == 0
    assert oc["misrouted"] == 0
    assert totals["wire_checks"] == decoded, (totals, decoded)
    assert oc["ok"] + oc["denied"] == responded, (oc, responded)
    assert (oc["shed"] + oc["expired"] + oc["unavailable"]
            + oc["error"]) == decoded - responded, (
        oc, decoded, responded)
    return totals


def test_conservation_grpc_front(mesh):
    store, srv = mesh
    from istio_tpu.api.grpc_server import MixerGrpcServer
    g = MixerGrpcServer(runtime=srv)
    _run_fleet(g.start, g.stop, store)


def test_conservation_native_front(mesh):
    store, srv = mesh
    from istio_tpu.api.native_server import NativeMixerServer
    native = NativeMixerServer(srv, min_fill=8, window_us=500)
    _run_fleet(native.start, native.stop, store)
