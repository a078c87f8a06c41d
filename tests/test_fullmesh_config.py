"""The `fullmesh5k` deployment (benchmark/configs/fullmesh.py, BASELINE
config 5) at its smoke sizes, through the served entry: per-service SAN
whitelists and ServiceRoles in ONE snapshot, verdicts from two sections
of the step, several referenced/presence signature classes a batch —
and the counters that say which section decided. Then a quota at the
wire: every 4th Check of `mixer10k` carries one.

The configuration's files are the benchmark's; they are loaded by path
as benchmark/run.py loads them.
"""
import collections
import importlib.util
import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.wire import (LazyWireBag, bag_to_compressed,
                                referenced_to_proto)
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.batcher import pad_to_bucket

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
ROWS, BUCKET = 1024, 256
BATCHES = range(ROWS // BUCKET)
SEED = 2147484443           # the driver's seeds pass 2**31
QUOTA_EVERY = 4


def _load(name: str):
    """(smoke sizes, generator module) of one benchmark configuration."""
    sizes = json.loads((CONFIGS / f"{name}.json").read_text())
    sizes.update(sizes["smoke"])
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{sizes['module']}",
        CONFIGS / f"{sizes['module']}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return sizes, module


def _server(sizes: dict, config) -> RuntimeServer:
    return RuntimeServer(config.make_store(sizes), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]},
        buckets=tuple(sizes["buckets"]), max_batch=sizes["max_batch"],
        initial_prewarm=False))


@pytest.fixture(scope="module")
def served():
    """ROWS seeded requests through the pump's own entry, a padded
    bucket at a time, with everything the cases below compare."""
    sizes, config = _load("fullmesh5k")
    requests = config.make_requests(sizes, ROWS, SEED)
    srv = _server(sizes, config)
    try:
        plan = srv.controller.dispatcher.fused
        assert plan is not None and plan.native is not None
        assert len(plan.list_rules) == sizes["services"]
        assert len(plan.rbac_rules) == 1 and not plan.host_actions
        before = monitor.check_decided_counters()
        fallbacks = monitor.resilience_counters()["fallback_total"]
        wire_bags = [srv.preprocess(LazyWireBag(
            bag_to_compressed(d).SerializeToString())) for d in requests]
        got = []
        for lo in range(0, ROWS, BUCKET):
            padded = pad_to_bucket(wire_bags[lo:lo + BUCKET], (BUCKET,))
            got += srv.check_batch_preprocessed(padded)[:BUCKET]
        after = monitor.check_decided_counters()
        bags = [bag_from_mapping(d) for d in requests]
        oracle = srv.controller.dispatcher.check_host_oracle(bags)
        fallbacks = monitor.resilience_counters()["fallback_total"] \
            - fallbacks
    finally:
        srv.close()
    expected = [config.reference(sizes)(d) for d in requests]
    return {"config": config, "requests": requests, "bags": bags,
            "got": got, "oracle": oracle, "expected": expected,
            "before": before, "after": after, "fallbacks": fallbacks}


# make_requests' four classes and the status each is stated to get
CLASS_STATUS = {"conformant": 0, "wrong_san": 5, "no_role": 7,
                "plain_text": 7}


def _request_class(request: dict) -> str:
    """Which class make_requests drew this request from, read off the
    request itself."""
    if not request["connection.mtls"]:
        return "plain_text"
    if request["request.method"] == "DELETE":
        return "no_role"
    service_ns = request["destination.service"].split(".")[1]
    user_ns = request["source.user"].split("/")[4]
    return "conformant" if user_ns == service_ns else "wrong_san"


def _classes_in(responses) -> int:
    """fold builds one (referenced, presence) object per signature
    class and shares it among the class's rows."""
    return len({id(r.referenced) for r in responses})


@pytest.mark.parametrize("kind", list(CLASS_STATUS))
def test_each_traffic_class_gets_its_stated_status(served, kind):
    assert tuple(CLASS_STATUS) == served["config"].CLASSES
    rows = [i for i, d in enumerate(served["requests"])
            if _request_class(d) == kind]
    share = len(rows) / ROWS
    assert abs(share - (0.7 if kind == "conformant" else 0.1)) < 0.04
    want = CLASS_STATUS[kind]
    for i in rows:
        assert served["got"][i].status_code == want, served["requests"][i]
        assert served["expected"][i] == want
        assert served["oracle"][i].status_code == want


def test_served_entry_is_the_reference_is_the_host_oracle(served):
    got = [int(r.status_code) for r in served["got"]]
    assert got == served["expected"]
    assert got == [int(r.status_code) for r in served["oracle"]]
    assert set(got) == {0, 5, 7}
    assert not served["fallbacks"]


def test_where_list_and_rbac_both_reject_the_list_rule_answers(served):
    # a wrong SAN has no role either: rule order gives NOT_FOUND, and
    # the message names the service's own rule
    rows = [i for i, d in enumerate(served["requests"])
            if _request_class(d) == "wrong_san"]
    assert rows
    for i in rows:
        service = served["requests"][i]["destination.service"]
        rule = "san" + service.split(".")[0][3:]
        assert served["got"][i].status_message == \
            f"rejected by list check (rule {rule}.{service.split('.')[1]})"


@pytest.mark.parametrize("batch", BATCHES)
def test_referenced_attributes_row_by_row_over_several_classes(
        served, batch):
    rows = range(batch * BUCKET, (batch + 1) * BUCKET)
    assert _classes_in(served["got"][i] for i in rows) >= 2
    wire = set()
    for i in rows:
        r, o, bag = served["got"][i], served["oracle"][i], served["bags"][i]
        assert r.referenced == o.referenced, i
        mine = referenced_to_proto(r.referenced, bag, r.referenced_presence)
        assert mine == referenced_to_proto(o.referenced, bag, None), i
        wire.add(mine.SerializeToString())
    assert len(wire) >= 2   # plain-text callers carry no source.user


@pytest.mark.parametrize("by", monitor.CHECK_DECIDED_BY)
def test_decided_counter_splits_as_the_reference_says(served, by):
    rows = {k: served["after"]["decided"][k] - served["before"]["decided"][k]
            for k in monitor.CHECK_DECIDED_BY}
    assert sum(rows.values()) == ROWS
    status = collections.Counter(served["expected"])
    want = {"ok": status[0], "list": status[5], "rbac": status[7],
            "deny": 0, "host": 0}
    assert rows[by] == want[by]


def test_signature_classes_counter_rises_by_the_classes_seen(served):
    seen = sum(_classes_in(served["got"][b * BUCKET:(b + 1) * BUCKET])
               for b in BATCHES)
    assert seen >= 2 * len(BATCHES)
    assert served["after"]["signature_classes_total"] \
        - served["before"]["signature_classes_total"] == seen


def test_decided_families_expose_zero_series_before_any_batch():
    import prometheus_client

    text = prometheus_client.generate_latest(monitor.REGISTRY).decode()
    for by in monitor.CHECK_DECIDED_BY:
        assert f'mixer_check_decided_total{{by="{by}"}}' in text
    assert "mixer_fold_signature_classes_total" in text


def test_host_overlay_rows_the_device_left_ok_count_as_host():
    # a rule whose one action stays on the host (the noop adapter
    # never fuses) beside a fused denier
    from istio_tpu.runtime import MemStore

    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("handler", "istio-system", "noop"), {
        "adapter": "noop", "params": {}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "r0-admin"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    s.set(("rule", "istio-system", "r1-host"), {
        "match": 'request.method == "POST"',
        "actions": [{"handler": "noop", "instances": ["nothing"]}]})
    srv = RuntimeServer(s, ServerArgs(batch_window_s=0.001, fused=True))
    try:
        d = srv.controller.dispatcher
        assert list(d.fused.host_rule_idx) == [1]
        cases = [{"request.path": "/a", "request.method": "GET"},    # ok
                 {"request.path": "/a", "request.method": "POST"},   # host
                 {"request.path": "/admin", "request.method": "POST"},
                 {"request.path": "/admin", "request.method": "GET"}]
        before = monitor.check_decided_counters()["decided"]
        d._check_fused([srv.preprocess(bag_from_mapping(c))
                        for c in cases])
        after = monitor.check_decided_counters()["decided"]
    finally:
        srv.close()
    assert {k: after[k] - before[k] for k in after} == {
        "ok": 1, "host": 1, "deny": 2, "list": 0, "rbac": 0}


def test_quota_on_every_fourth_check_is_granted_exactly_one():
    """mixer10k at smoke size through the native front, as a mix with
    `quota_every: 4` would drive it (benchmark/run.py write_payloads:
    `rq`, amount 1, best effort, request i when i % 4 == 0). No cell
    drives quota yet: the harness's `correct` compares no grant, so
    this test is what holds one (PERF.md section 7)."""
    sizes, config = _load("mixer10k")
    assert sizes["quota_name"] == "rq"
    requests = config.make_requests(sizes, 256, SEED)
    srv = _server(sizes, config)
    native = NativeMixerServer(srv, max_batch=sizes["max_batch"])
    client = MixerClient(f"127.0.0.1:{native.start()}",
                         enable_check_cache=False)
    try:
        def one(i: int):
            quotas = {sizes["quota_name"]: 1} \
                if i % QUOTA_EVERY == 0 else None
            return client.check(requests[i], quotas=quotas)

        with ThreadPoolExecutor(max_workers=16) as pool:
            replies = list(pool.map(one, range(len(requests))))
    finally:
        client.close()
        native.stop()
        srv.close()
    expected = config.reference(sizes)
    granted = 0
    for i, (request, reply) in enumerate(zip(requests, replies)):
        status = int(reply.precondition.status.code)
        assert status == expected(request), i
        if i % QUOTA_EVERY == 0 and status == 0:
            assert set(reply.quotas) == {"rq"}, i
            assert reply.quotas["rq"].granted_amount == 1, i
            granted += 1
        else:   # no quota asked, or the precondition denied it
            assert not reply.quotas, i
    assert granted >= 48
