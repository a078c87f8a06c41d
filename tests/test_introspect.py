"""Introspect server end-to-end: scrape the admin HTTP surface
in-process while a served check_many burst runs, and assert the
Check() latency decomposition holds together — all six stage
histograms populated, stage sums bounded by end-to-end, live p99
gauge in agreement with a client-side measurement of the same run.

Reference anchors: ControlZ introspection + Mixer's :9093
self-monitoring port (mixer/pkg/server/monitoring.go).
"""
import json
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from istio_tpu.introspect import IntrospectServer
from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.monitor import CHECK_STAGES
from istio_tpu.testing import workloads
from istio_tpu.utils import tracing
from tests.test_metrics_exposition import _parse, lint_histograms


@pytest.fixture(scope="module")
def served():
    store = workloads.make_store(24)
    srv = RuntimeServer(store, ServerArgs(
        batch_window_s=0.0005, max_batch=64, buckets=(16, 64),
        default_manifest=workloads.MESH_MANIFEST))
    plan = srv.controller.dispatcher.fused
    assert plan is not None
    plan.prewarm((16, 64))
    intro = IntrospectServer(runtime=srv)
    intro.start()
    try:
        yield srv, intro
    finally:
        intro.close()
        srv.close()
        tracing.shutdown()    # drop the ring-installed global tracer


def _get(intro: IntrospectServer, path: str):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{intro.port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def _get_json(intro: IntrospectServer, path: str):
    status, _, body = _get(intro, path)
    return status, json.loads(body)


def test_scrape_during_check_many_burst(served):
    srv, intro = served
    monitor.reset_latency_window()
    bags = workloads.make_bags(32)
    for _ in range(4):
        results = srv.check_many(bags)
        assert len(results) == 32

    status, ctype, body = _get(intro, "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    text = body.decode()

    # ONE merged exposition: prometheus_client families (runtime
    # counters) AND homegrown families (stage decomposition) in the
    # same blob
    assert "mixer_runtime_resolve_count" in text
    assert "mixer_runtime_config_generation" in text
    assert "mixer_check_stage_seconds_bucket" in text

    samples = _parse(text)
    # all six stage histograms populated by the served burst
    stage_counts = {lb["stage"]: v
                    for lb, v in samples["mixer_check_stage_seconds_count"]
                    if "stage" in lb}
    for stage in CHECK_STAGES:
        assert stage_counts.get(stage, 0) > 0, \
            f"stage {stage!r} not populated: {stage_counts}"

    # monotone: per-batch stage work can never exceed the per-request
    # end-to-end mass it decomposes (each batch carries >= 1 request)
    stage_sums = {lb["stage"]: v
                  for lb, v in samples["mixer_check_stage_seconds_sum"]
                  if "stage" in lb}
    e2e_sum = dict((tuple(lb.items()), v) for lb, v in
                   samples["mixer_check_e2e_seconds_sum"])[()]
    assert sum(stage_sums.values()) <= e2e_sum + 1e-6, \
        f"stage sums {stage_sums} exceed e2e {e2e_sum}"

    # live percentile gauges present and live
    p99 = dict((tuple(lb.items()), v) for lb, v in
               samples["mixer_check_p99_ms"])[()]
    assert p99 > 0.0
    assert "check_p99_under_target" in samples

    # the whole merged blob passes the exposition lint
    lint_histograms(text, expect={"mixer_check_stage_seconds",
                                  "mixer_check_e2e_seconds"})


def test_live_p99_agrees_with_measured(served):
    """The acceptance cross-check, in-process: drive concurrent checks
    through the batcher, measure latency at the caller, and compare
    against the live p99 gauge over the same window."""
    srv, _ = served
    bags = workloads.make_bags(64)
    # warm the batcher path before the measured window
    with ThreadPoolExecutor(max_workers=16) as pool:
        list(pool.map(srv.check, bags[:16]))
    monitor.reset_latency_window()
    lat = []

    def one(bag):
        t0 = time.perf_counter()
        srv.check(bag)
        return time.perf_counter() - t0

    with ThreadPoolExecutor(max_workers=16) as pool:
        lat = list(pool.map(one, bags))
    live = monitor.refresh_latency_gauges()
    assert live["n_window"] >= len(bags)
    measured_p99_ms = float(np.percentile(lat, 99) * 1e3)
    live_p99_ms = live["p99_ms"]
    assert live_p99_ms > 0
    # caller-side wall time >= server-side e2e (enqueue->delivery),
    # and the two p99s must track: generous bound for CI scheduling
    # jitter (bench asserts the tight 20% on real runs)
    assert abs(live_p99_ms - measured_p99_ms) <= \
        0.5 * max(measured_p99_ms, 1.0), \
        f"live p99 {live_p99_ms}ms vs measured {measured_p99_ms}ms"
    # SLO gauge reflects the refreshed window
    assert live["under_target"] == (
        live_p99_ms <= monitor.CHECK_P99_TARGET_MS)


def test_healthz_readyz_config(served):
    srv, intro = served
    status, payload = _get_json(intro, "/healthz")
    assert status == 200
    assert payload["status"] == "ok"
    assert payload["config_generation"] >= 1

    status, payload = _get_json(intro, "/readyz")
    assert status == 200
    assert payload["status"] == "ready"
    assert payload["n_rules"] > 0

    status, payload = _get_json(intro, "/debug/config")
    assert status == 200
    assert payload["fused"] is True
    assert payload["n_rules"] >= 24
    assert payload["buckets"] == [16, 64]
    assert payload["generation"] >= 1


def test_debug_queues_and_cache(served):
    srv, intro = served
    srv.check_many(workloads.make_bags(8))
    status, payload = _get_json(intro, "/debug/queues")
    assert status == 200
    check = payload["check"]
    assert check["depth"] >= 0
    assert check["pipeline"] >= 1
    assert check["buckets"] == [16, 64]
    assert not check["closed"]
    assert "report" in payload            # report coalescer visible too
    stages = payload["latency"]["stages"]
    assert "device_step" in stages and stages["device_step"]["count"] > 0

    status, payload = _get_json(intro, "/debug/cache")
    assert status == 200
    # both prewarmed bucket shapes live in the check program's jit
    # cache, and no packer was launched behind it
    compile_stats = payload["compile"]
    if compile_stats.get("step_entries") is not None:
        assert compile_stats["step_entries"] >= 2
    assert "packer_entries" not in compile_stats
    assert payload.get("interner_values", 1) > 0


def test_debug_traces_and_root_span_parenting(served):
    """API-layer root span satellite: a serve.batch span must share
    its trace with (and parent under) the rpc.check root opened at
    RPC decode, so queue-wait is attributed to a request."""
    srv, intro = served
    tr = tracing.get_tracer()
    assert tr.reporter is not None    # the introspect ring installed it
    with tr.span("rpc.check") as root:
        srv.check(workloads.make_bags(1)[0])
    status, payload = _get_json(intro, "/debug/traces")
    assert status == 200
    spans = payload["spans"]
    batch_spans = [s for s in spans if s["name"] == "serve.batch"
                   and s.get("traceId") == root["traceId"]]
    assert batch_spans, f"no serve.batch under the rpc.check root in " \
                        f"{[s['name'] for s in spans]}"
    assert batch_spans[-1]["parentId"] == root["id"]


def test_debug_rulestats_view(served):
    """/debug/rulestats: drains on demand and serves top-K hot rules
    with per-namespace deny rates, never-hit bookkeeping (with the
    analyzer cross-check flag present) and decision exemplars whose
    trace ids join /debug/traces."""
    srv, intro = served
    # crafted deny traffic: rule 0 (deny action) of make_store(24),
    # through the batcher so exemplars sample the serve.batch span
    from istio_tpu.attribute.bag import bag_from_mapping
    for _ in range(4):
        srv.check(bag_from_mapping({
            "destination.service": "svc0.ns0.svc.cluster.local",
            "source.namespace": "ns9"}))
    status, payload = _get_json(intro, "/debug/rulestats?k=50")
    assert status == 200
    assert payload["drains"] >= 1
    assert payload["rules_tracked"] == 26    # 24 mesh + quota + report
    top = {t["rule"]: t for t in payload["top"]}
    entry = top.get("ns0/rule0")
    assert entry is not None, sorted(top)
    assert entry["hits"] >= 4 and entry["denies"] >= 4
    assert entry["deny_rate_by_namespace"].get("ns0") == 1.0
    assert entry["exemplars"] and entry["exemplars"][0]["trace_id"]
    # never-hit entries carry the analyzer cross-check flag
    assert payload["never_hit"], "some rules never fire in this mix"
    assert all("analyzer_shadowed" in e for e in payload["never_hit"])
    hot = {t["rule"] for t in payload["top"]}
    assert hot.isdisjoint({e["rule"] for e in payload["never_hit"]})
    # the counter families surface on the merged /metrics exposition
    _, _, body = _get(intro, "/metrics")
    text = body.decode()
    assert "mixer_rule_check_hits_total" in text
    assert "mixer_rulestats_drains_total" in text


def test_debug_traces_status_filter(served):
    """?status=failed keeps only spans whose status tag is set and not
    ok — the failure-filter satellite over the check spans' new status
    tags."""
    _, intro = served
    tr = tracing.get_tracer()
    with tr.span("rpc.check") as s_ok:
        s_ok["tags"]["status"] = "ok"
    with tr.span("rpc.check") as s_bad:
        s_bad["tags"]["status"] = "7"
    status, payload = _get_json(intro, "/debug/traces?status=failed")
    assert status == 200
    statuses = {(s["tags"] or {}).get("status")
                for s in payload["spans"]}
    assert "7" in statuses and "ok" not in statuses
    status, payload = _get_json(intro, "/debug/traces?status=7")
    assert {(s["tags"] or {}).get("status")
            for s in payload["spans"]} == {"7"}


def test_close_without_start_does_not_hang():
    """shutdown() blocks on serve_forever()'s event — close() on a
    never-started server (a pre-start failure's cleanup path, e.g. the
    smoke script's finally block) must return, not deadlock."""
    prev = tracing.get_tracer()
    intro = IntrospectServer()
    intro.close()                      # would hang before the guard
    assert tracing.get_tracer() is prev    # ring restored too


def test_ring_enable_disable_restores_tracer():
    """enable_ring/disable_ring must unwind cleanly: a closed
    introspect server leaves no span construction on the hot path and
    create/close cycles never stack dead rings."""
    prev = tracing.get_tracer()
    ring = tracing.enable_ring(8)
    installed = tracing.get_tracer()
    assert installed is not prev and installed.reporter is not None
    with installed.span("probe"):
        pass
    assert ring.snapshot()[-1]["name"] == "probe"
    tracing.disable_ring(ring)
    assert tracing.get_tracer() is prev
    # non-LIFO close order: disabling the earlier ring leaves the
    # later owner's stack alone; disabling the later one then unwinds
    # PAST the already-closed earlier ring back to the base tracer
    r1 = tracing.enable_ring(8)
    r2 = tracing.enable_ring(8)
    tracing.disable_ring(r1)            # r2 still owns the stack
    assert tracing.get_tracer()._ring is r2
    with tracing.get_tracer().span("while-r1-closed"):
        pass
    assert not r1.snapshot()            # closed ring records nothing
    assert r2.snapshot()[-1]["name"] == "while-r1-closed"
    tracing.disable_ring(r2)
    assert tracing.get_tracer() is prev


def test_unknown_path_404(served):
    _, intro = served
    try:
        _get(intro, "/nope")
        raise AssertionError("expected HTTP 404")
    except urllib.error.HTTPError as exc:
        assert exc.code == 404
        assert b"/metrics" in exc.read()
