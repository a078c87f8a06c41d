"""Rule-level telemetry (runtime/rulestats.py): on-device per-rule
accumulators drained to exact counts, host-fallback patching, padding
hygiene, decision exemplars, and config-swap continuity.

The exactness bar (ISSUE 4): drained per-rule hit/deny/error counts
must EQUAL an independent oracle recount of the served traffic —
telemetry is a measurement, not an estimate. The recount helper lives
in scripts/rulestats_smoke.py (shared with the CI gate) and walks the
compiler's SnapshotOracle + the snapshot's fused action metadata.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs
from istio_tpu.testing import workloads
from istio_tpu.utils import tracing


def _smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "rulestats_smoke.py")
    spec = importlib.util.spec_from_file_location(
        "rulestats_smoke_helpers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _server(store, **kw):
    args = dict(batch_window_s=0.0005, max_batch=32, buckets=(8, 32),
                rulestats_drain_s=0.0,   # manual drains: deterministic
                default_manifest=workloads.MESH_MANIFEST)
    args.update(kw)
    return RuntimeServer(store, ServerArgs(**args))


def _names(snapshot):
    return [f"{r.namespace}/{r.name}" if r.namespace else r.name
            for r in snapshot.rules]


@pytest.mark.parametrize("seed", [3, 11])
def test_drained_counts_match_oracle_recount(seed):
    """Property test over testing/corpus-style seeded workloads: serve
    a mix of random + crafted (deny-triggering) traffic, drain, and
    compare every rule's hit/deny/error counts to the oracle recount
    EXACTLY — including rules that never fired."""
    mod = _smoke()
    srv = _server(workloads.make_store(20, seed=seed))
    try:
        dicts = mod.make_traffic(20, 24, seed)
        bags = [bag_from_mapping(d) for d in dicts]
        srv.check_many(bags)
        srv.rulestats.drain()
        got = srv.rulestats.counts()
        snap = srv.controller.dispatcher.snapshot
        plan = srv.controller.dispatcher.fused
        hits, denies, errors = mod.oracle_recount(snap, plan, bags)
        assert hits, "traffic must exercise rules"
        assert denies, "traffic must trigger denies"
        for ridx, name in enumerate(_names(snap)):
            g = got.get(name, {"hits": 0, "denies": 0, "errors": 0})
            assert (g["hits"], g["denies"], g["errors"]) == \
                (hits.get(ridx, 0), denies.get(ridx, 0),
                 errors.get(ridx, 0)), f"rule {name}"
    finally:
        srv.close()


def test_padding_rows_never_counted():
    """Bucket padding (PadBags) must be invisible to the counters: the
    same requests served padded-to-bucket and unpadded drain to
    identical per-rule counts."""
    from istio_tpu.runtime.batcher import pad_to_bucket

    mod = _smoke()
    dicts = mod.make_traffic(12, 6, 5)
    srv = _server(workloads.make_store(12, seed=5))
    try:
        bags = [bag_from_mapping(d) for d in dicts]
        # padded entry: 18 real rows pad to the 32 bucket
        srv.check_batch_preprocessed(pad_to_bucket(bags, (8, 32)))
        srv.rulestats.drain()
        padded = srv.rulestats.counts()
        srv.rulestats.reset()
        srv.check_many(bags)
        srv.rulestats.drain()
        plain = srv.rulestats.counts()
        nz = {k: v for k, v in padded.items()
              if v["hits"] or v["denies"] or v["errors"]}
        assert nz, "traffic must hit rules"
        for name, c in padded.items():
            p = plain.get(name, {"hits": 0, "denies": 0, "errors": 0})
            assert (c["hits"], c["denies"], c["errors"]) == \
                (p["hits"], p["denies"], p["errors"]), name
    finally:
        srv.close()


def test_host_fallback_rule_hits_and_errors_counted():
    """Rules whose predicate falls back to the host oracle are
    invisible to the device accumulators; their hits/errors must
    arrive via the dispatcher's overlay patch — and still match the
    oracle recount exactly."""
    mod = _smoke()
    s = MemStore()
    s.set(("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    # dynamic map key → no device lowering → host-fallback predicate
    s.set(("rule", "istio-system", "dynkey"), {
        "match": 'request.headers[request.method] == "yes"',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    s.set(("rule", "istio-system", "plain"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    srv = _server(s)
    try:
        plan = srv.controller.dispatcher.fused
        rs = srv.controller.dispatcher.snapshot.ruleset
        assert rs.host_fallback, "dynkey must be host-fallback"
        bags = [
            bag_from_mapping({"request.method": "GET",
                              "request.headers": {"GET": "yes"},
                              "request.path": "/x"}),   # dynkey hit
            bag_from_mapping({"request.method": "GET",
                              "request.headers": {"GET": "no"},
                              "request.path": "/admin/z"}),  # plain
            bag_from_mapping({"request.path": "/y"}),   # dynkey errs
        ]
        srv.check_many(bags)
        srv.rulestats.drain()
        got = srv.rulestats.counts()
        snap = srv.controller.dispatcher.snapshot
        hits, denies, errors = mod.oracle_recount(snap, plan, bags)
        for ridx, name in enumerate(_names(snap)):
            g = got.get(name, {"hits": 0, "denies": 0, "errors": 0})
            assert (g["hits"], g["denies"], g["errors"]) == \
                (hits.get(ridx, 0), denies.get(ridx, 0),
                 errors.get(ridx, 0)), f"rule {name}"
        fb_name = _names(snap)[sorted(rs.host_fallback)[0]]
        assert got[fb_name]["hits"] == 1
        assert got[fb_name]["errors"] >= 1
    finally:
        srv.close()


def test_exemplars_record_denied_requests_with_trace_ids():
    """Denied rows reservoir-sample into per-rule exemplars carrying
    the decoded attribute bag and the active span's trace id — the
    one-click join from /debug/rulestats to /debug/traces."""
    mem = tracing.MemoryReporter()
    tracing._global = tracing.Tracer(reporter=mem)
    try:
        s = MemStore()
        s.set(("handler", "istio-system", "deny"), {
            "adapter": "denier", "params": {"status_code": 7}})
        s.set(("instance", "istio-system", "nothing"), {
            "template": "checknothing", "params": {}})
        s.set(("rule", "istio-system", "blockadmin"), {
            "match": 'request.path.startsWith("/admin")',
            "actions": [{"handler": "deny", "instances": ["nothing"]}]})
        srv = _server(s)
        try:
            for i in range(10):
                srv.check(bag_from_mapping(
                    {"request.path": f"/admin/{i}"}))
            srv.rulestats.drain()
            snap = srv.rulestats.snapshot(top_k=5)
            top = {t["rule"]: t for t in snap["top"]}
            entry = top["istio-system/blockadmin"]
            assert entry["denies"] == 10
            exs = entry["exemplars"]
            assert exs, "denied traffic must leave exemplars"
            assert len(exs) <= 4, "reservoir must cap at K"
            for ex in exs:
                assert ex["status"] == 7
                assert any("/admin/" in v
                           for v in ex["attributes"].values())
                assert ex["trace_id"], "exemplar must link a trace"
            # the trace id is a real recorded span's trace
            trace_ids = {s_["traceId"] for s_ in mem.spans}
            assert exs[0]["trace_id"] in trace_ids
        finally:
            srv.close()
    finally:
        tracing._global = tracing.NOOP_TRACER


def test_counts_survive_config_swap():
    """attach() drains the outgoing plan before rebinding, so a config
    swap never drops in-flight counts; name-keyed cumulative totals
    carry across revisions."""
    s = MemStore()
    s.set(("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "r0"), {
        "match": 'request.path.startsWith("/a")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    srv = _server(s)
    try:
        rev0 = srv.rulestats.revision
        srv.check(bag_from_mapping({"request.path": "/a/1"}))
        # swap WITHOUT draining first: the publish hook must flush the
        # old plan's device accumulators before rebinding
        s.set(("rule", "istio-system", "r1"), {
            "match": 'request.path.startsWith("/b")',
            "actions": [{"handler": "deny", "instances": ["nothing"]}]})
        srv.controller.rebuild()
        assert srv.rulestats.revision != rev0
        got = srv.rulestats.counts()
        assert got["istio-system/r0"]["hits"] == 1
        assert got["istio-system/r0"]["denies"] == 1
        # traffic on the NEW snapshot keeps accumulating by name
        srv.check(bag_from_mapping({"request.path": "/a/2"}))
        srv.rulestats.drain()
        assert srv.rulestats.counts()["istio-system/r0"]["hits"] == 2
    finally:
        srv.close()


def test_generation_tags_advance_per_drain():
    srv = _server(workloads.make_store(6, seed=1))
    try:
        plan = srv.controller.dispatcher.fused
        g0 = plan.telemetry.generation
        srv.rulestats.drain()
        srv.rulestats.drain()
        assert plan.telemetry.generation == g0 + 2
        assert srv.rulestats.drains >= 2
    finally:
        srv.close()


def test_telemetry_disabled_serves_without_accumulators():
    srv = _server(workloads.make_store(6, seed=1),
                  rule_telemetry=False)
    try:
        assert srv.controller.dispatcher.fused.telemetry is None
        r = srv.check(bag_from_mapping({"request.path": "/x"}))
        assert r is not None
        assert srv.rulestats.drain() is None
        snap = srv.rulestats.snapshot()
        assert snap["top"] == []
    finally:
        srv.close()


def test_never_hit_shadow_crosscheck_ambiguity_guard():
    """snapshot(shadowed=...) matches the analyzer's BARE rule names
    against qualified never-hit names — but only when the bare name is
    unique in the snapshot, so a same-named rule in another namespace
    is never marked provably dead."""
    from istio_tpu.runtime import rulestats
    from istio_tpu.utils.metrics import Registry

    agg = rulestats.RuleStatsAggregator(
        metrics=rulestats.register_families(Registry()))

    class _Rule:
        def __init__(self, name, ns):
            self.name, self.namespace = name, ns

    class _Snap:
        rules = [_Rule("allow", "ns-a"), _Rule("allow", "ns-b"),
                 _Rule("dead", "ns-a")]
        revision = 1

        class ruleset:
            ns_ids = {"": 0}

    class _Dispatcher:
        snapshot = _Snap()
        fused = None

    agg.attach(_Dispatcher())
    view = agg.snapshot(shadowed={"allow", "dead"})
    flags = {e["rule"]: e["analyzer_shadowed"]
             for e in view["never_hit"]}
    assert flags["ns-a/dead"] is True          # unique bare name
    assert flags["ns-a/allow"] is False        # ambiguous: two rules
    assert flags["ns-b/allow"] is False


def test_two_pumps_and_a_drain_lose_and_double_nothing():
    """FusedPlan.packed_check threads the accumulator handles through
    its one device program under RuleTelemetry's lock (chain). Two
    threads x 50 batches with drains falling between them: the drains
    sum to 100 x one batch's counts, exactly."""
    import sys
    import threading

    from istio_tpu.runtime.batcher import pad_to_bucket
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan

    mod = _smoke()
    plan = build_fused_plan(SnapshotBuilder(
        default_manifest=workloads.MESH_MANIFEST).build(
            workloads.make_store(20, seed=3)))
    tele = plan.telemetry
    dicts = mod.make_traffic(20, 4, 3)
    n_real, rounds, pumps = len(dicts), 50, 2
    bags = pad_to_bucket([bag_from_mapping(d) for d in dicts], (32,))
    batch = plan.engine.tensorizer.tensorize(bags)
    rs = plan.engine.ruleset
    ns = np.zeros(32, np.int32)
    ns[:n_real] = [rs.namespace_id(d.get("source.namespace", ""))
                   for d in dicts]
    plan.packed_check(batch, ns, n_real=n_real)      # compiles
    one = tele.drain()
    assert one["hit"].sum() > 0 and one["deny"].sum() > 0

    start = threading.Barrier(pumps + 1)
    failed: list = []

    def pump():
        try:
            start.wait(timeout=30)
            for _ in range(rounds):
                plan.packed_check(batch, ns, n_real=n_real)
        except Exception as exc:      # read back below
            failed.append(exc)

    threads = [threading.Thread(target=pump) for _ in range(pumps)]
    total = {k: np.zeros_like(one[k]) for k in ("hit", "deny", "err")}
    generations = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        start.wait(timeout=30)
        while any(t.is_alive() for t in threads):
            d = tele.drain()          # falls between two batches
            generations.append(d["generation"])
            for k in total:
                total[k] += d[k]
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not failed and not any(t.is_alive() for t in threads)
    last = tele.drain()
    for k in total:
        np.testing.assert_array_equal(total[k] + last[k],
                                      pumps * rounds * one[k])
    assert len(generations) >= 1
    assert generations == sorted(set(generations))
