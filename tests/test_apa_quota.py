"""A quota instance keyed by what the step's APA binds (runtime/apa.py
ApaStep.bind_rows, RuntimeServer.quota_bags): Bookinfo's rate limit
(benchmark/configs/ratelimit.py) on a small seeded cluster. The APA
stays in the Check step; through the native front (the test is the
pump, as in test_quota_group.py) each OK quota row's instance is built
on its bag with every binding applied from the pod rows the stamp
found, and its grants equal the configuration's plain reference under
a limit small enough that a wrong key shows; the bound bag is the host
APA's on every row; the rows are counted; a host reader of a binding
still sends the APA to the host; a quota that reads no binding keeps
its wire bag."""
import importlib.util
import json
import struct
import threading
import time
import types
from pathlib import Path

import pytest

from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.apa import GeneratedBag
from istio_tpu.runtime.batcher import pad_to_bucket
from istio_tpu.testing.take_blob import encode_take

ROOT = Path(__file__).resolve().parent.parent
CONFIGS = ROOT / "benchmark" / "configs"
BUCKET = 64
LIMIT = 2          # a key's third ask is refused: a wrong key shows
QUOTA = "requestcount"
BOUND = {f"{side}.{attr}" for side in ("source", "destination")
         for attr in ("ip", "labels", "namespace", "service",
                      "serviceAccount")}


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", CONFIGS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


RATELIMIT = _load("ratelimit")
SIZES = json.loads((CONFIGS / "ratelimit150k.json").read_text())
SIZES.update(SIZES["smoke"], rules=40, services=12, nodes=12,
             request_services=12, max_batch=BUCKET, buckets=[BUCKET])


def _limited(store, limit: int = LIMIT):
    store.set(("handler", "istio-system", "handler"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": f"{QUOTA}.istio-system",
                               "max_amount": limit}]}})
    return store


def _server(store) -> RuntimeServer:
    return RuntimeServer(store, ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in SIZES["manifest"]},
        buckets=(BUCKET,), max_batch=BUCKET, initial_prewarm=False,
        rule_telemetry=False))


def _wire(d: dict) -> bytes:
    return bag_to_compressed(d).SerializeToString()


class Front:
    """A RuntimeServer behind a native front whose pump is the test and
    whose completions land in `answers` {tag: (status, bytes)}."""

    def __init__(self, srv: RuntimeServer):
        self.srv = srv
        self.native = NativeMixerServer(srv, max_batch=BUCKET)
        self.native._pumps = []         # never started: nothing to join
        self.lib = self.native._lib
        self.native._lib = types.SimpleNamespace(
            h2srv_complete=self._complete)
        self.answers: dict[int, tuple[int, bytes]] = {}
        self._cv = threading.Condition()
        self._tags = iter(range(1, 1 << 40))

    def _complete(self, _handle, blob: bytes, n: int) -> None:
        (count,) = struct.unpack_from("<I", blob, 0)
        off = 4
        with self._cv:
            for _ in range(count):
                tag, status, length = struct.unpack_from("<QiI", blob, off)
                self.answers[tag] = (status,
                                     blob[off + 16:off + 16 + length])
                off += 16 + length
            self._cv.notify_all()

    def ask(self, requests: list[dict], dedup: str) -> list:
        """One batch, each row asking QUOTA for 1, best effort → each
        row's grant, None where the reply carries no entry."""
        tags = [next(self._tags) for _ in requests]
        self.native._run_batch(encode_take([
            (tag, 0, _wire(d), 0, f"{dedup}-{i}", {QUOTA: (1, True)}, "")
            for i, (tag, d) in enumerate(zip(tags, requests))]))
        with self._cv:
            assert self._cv.wait_for(
                lambda: all(t in self.answers for t in tags), 30)
        out = []
        for tag in tags:
            status, raw = self.answers[tag]
            assert status == 0
            quotas = pb.CheckResponse.FromString(raw).quotas
            out.append(quotas[QUOTA].granted_amount if QUOTA in quotas
                       else None)
        return out

    def close(self) -> None:
        self.native._lib = self.lib
        self.native.stop(grace=0.2)
        self.srv.close()


def _source_kind(request: dict, pods: dict) -> str:
    uid = request.get("source.uid", "").removeprefix(
        RATELIMIT.base.UID_PREFIX)
    if uid in pods:
        return "uid_and_ip"
    return "gone" if uid else "ip_only"


@pytest.fixture(scope="module")
def rig():
    pods = RATELIMIT.base.make_pods(SIZES)
    front = Front(_server(_limited(RATELIMIT.make_store(SIZES))))
    reference = RATELIMIT.quota_reference(SIZES)
    reference.max_amount = LIMIT
    try:
        yield types.SimpleNamespace(front=front, pods=pods,
                                    reference=reference,
                                    requests=RATELIMIT.make_requests(
                                        SIZES, 4096, 4200000042))
    finally:
        front.close()


def test_the_rate_limit_leaves_the_apa_in_the_step(rig):
    plan = rig.front.srv.controller.dispatcher.fused
    assert plan.apa is not None
    bound = {b.attr for b in plan.apa.quota_bindings if b.key}
    assert bound == BOUND
    # the quota reads a binding no rule reads: the step does not write
    # it, the quota's bag has it all the same
    lay = rig.front.srv.controller.dispatcher.snapshot.ruleset.layout
    assert lay.map_slots["destination.labels"] not in \
        {w[1] for w in plan.apa.map_writes}


@pytest.mark.parametrize("reader", [
    # a host action: a list the step cannot fuse, on a binding the
    # rules read
    ("handler", {"adapter": "list", "params": {
        "overrides": ["ns1"], "entry_type": "CASE_INSENSITIVE_STRINGS"}},
     {"match": "", "actions": [{"handler": "hostlist",
                                "instances": ["srcns.istio-system"]}]}),
    # a host-fallback predicate: a map indexed by a request's value
    (None, None, {"match": 'source.labels[request.method] == "x"',
                  "actions": [{"handler": "denyall.istio-system",
                               "instances": ["nothing.istio-system"]}]}),
], ids=["host_action", "host_fallback"])
def test_a_host_reader_of_a_binding_still_sends_the_apa_to_the_host(
        reader):
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan

    kind, handler, rule = reader
    store = RATELIMIT.make_store(SIZES)
    if kind:
        store.set(("handler", "istio-system", "hostlist"), handler)
    store.set(("rule", "istio-system", "host-reader"), rule)
    snap = SnapshotBuilder({k: GLOBAL_MANIFEST[k]
                            for k in SIZES["manifest"]}).build(store)
    plan = build_fused_plan(snap)
    if kind:
        assert plan.host_actions and not snap.ruleset.host_fallback
    else:
        assert snap.ruleset.host_fallback
    assert plan.apa is None
    # and without the host reader the same snapshot keeps it
    store.delete(("rule", "istio-system", "host-reader"))
    assert build_fused_plan(SnapshotBuilder(
        {k: GLOBAL_MANIFEST[k] for k in SIZES["manifest"]}).build(
            store)).apa is not None


@pytest.mark.parametrize("kind", [
    "uid_and_ip", "ip_only", "gone", "no_destination_pod"])
def test_grants_through_the_native_front_equal_the_plain_reference(rig,
                                                                   kind):
    """A batch of one source kind (or with its destination pod gone),
    keys colliding under a limit of LIMIT: the grants are the plain
    reference's, the OK quota rows counted on the quota path and none
    on the host's."""
    if kind == "no_destination_pod":
        requests = [dict(r, **{"destination.uid":
                               f"{RATELIMIT.base.UID_PREFIX}gone-{i}.ns0"})
                    for i, r in enumerate(rig.requests[:BUCKET])]
    else:
        requests = [r for r in rig.requests
                    if _source_kind(r, rig.pods) == kind][:BUCKET]
    assert len(requests) >= 40
    key_of = rig.reference.key_of
    keys = {key_of(r) for r in requests}
    if kind in ("gone", "no_destination_pod"):
        assert any("unknown" in key for key in keys)
    if kind == "no_destination_pod":
        assert {key[2:] for key in keys} == {("unknown", "unknown")}
    else:
        assert len(keys) > 4
    before = monitor.apa_counters()["rows"]
    got = rig.front.ask(requests, f"grants-{kind}")
    want = [rig.reference.grant(r, QUOTA, 1, True, f"grants-{kind}-{i}")
            for i, r in enumerate(requests)]
    assert got == want
    assert {0, 1} <= set(want)           # some key refused its third ask
    after = monitor.apa_counters()["rows"]
    assert after["quota"] - before["quota"] == \
        sum(g is not None for g in want)
    assert after["host"] == before["host"]


def test_a_key_the_apa_binds_is_built_a_row(rig):
    """The quota reads what the step's APA binds: the plan keys it by
    the pod rows the stamp found (source and destination) and no
    column, and every OK quota row takes its key from the memo or
    builds it once (`path=memo|built`, none `row`), its grant the plain
    reference's."""
    keys = rig.front.srv.controller.dispatcher.fused.quota_keys
    assert set(keys.at) == {f"{QUOTA}.istio-system"}
    assert keys.at[f"{QUOTA}.istio-system"] == ([], 0)
    assert keys.sides[f"{QUOTA}.istio-system"] == [0, 1]
    requests = rig.requests[2 * BUCKET:3 * BUCKET]
    before = monitor.quota_key_counters()
    got = rig.front.ask(requests, "keys")
    want = [rig.reference.grant(r, QUOTA, 1, True, f"keys-{i}")
            for i, r in enumerate(requests)]
    assert got == want
    after = monitor.quota_key_counters()
    moved = {path: after[path] - before[path] for path in after}
    assert moved["row"] == 0 and moved["built"] > 0
    assert moved["memo"] + moved["built"] == sum(g is not None
                                                 for g in want)


@pytest.fixture
def handed(monkeypatch):
    """{tag: the key the front handed a pool} for every ask of a native
    quota group."""
    from istio_tpu.adapters.memquota import _key as dims_key
    from istio_tpu.api.native_server import _QuotaGroup
    from istio_tpu.runtime.device_quota import DeviceQuotaPool

    got: dict[int, str] = {}
    alloc_many = DeviceQuotaPool.alloc_many

    def spy(pool, items, sink):
        if isinstance(sink, _QuotaGroup):
            for (at, _q), _name, instance, _args in items:
                got[sink.tags[at]] = instance if isinstance(
                    instance, str) else dims_key(instance)
        return alloc_many(pool, items, sink)

    monkeypatch.setattr(DeviceQuotaPool, "alloc_many", spy)
    return got


def _host_keys(front: Front, requests: list[dict]) -> list[str]:
    """Each request's quota key as the host builds it: the instance on
    the bag the host APA binds (Dispatcher._with_apa)."""
    from istio_tpu.adapters.memquota import _key as dims_key

    d = front.srv.controller.dispatcher
    instance = d.snapshot.instances[f"{QUOTA}.istio-system"]
    return [dims_key(instance.build(bag)) for bag in
            d._with_apa([LazyWireBag(_wire(r)) for r in requests])]


def _granted(rig, requests: list[dict], dedup: str) -> list:
    """One batch through the rig's front, each grant the plain
    reference's (which counts it too, so later cases stay exact)."""
    got = rig.front.ask(requests, dedup)
    assert got == [rig.reference.grant(r, QUOTA, 1, True, f"{dedup}-{i}")
                   for i, r in enumerate(requests)]
    return got


def _asked(front: Front, requests: list[dict], dedup: str,
           handed: dict, rig=None) -> tuple[dict, list]:
    """One batch through the front (the rig's, its grants checked,
    where `rig` is given) → (the keys counters' delta, the key handed a
    pool a row: None where the row asked no pool)."""
    start = next(front._tags)
    before = monitor.quota_key_counters()
    if rig is None:
        front.ask(requests, dedup)
    else:
        _granted(rig, requests, dedup)
    after = monitor.quota_key_counters()
    tags = range(start + 1, start + 1 + len(requests))
    return ({path: after[path] - before[path] for path in after},
            [handed.get(tag) for tag in tags])


@pytest.mark.parametrize("kind", [
    "uid_and_ip", "ip_only", "gone", "no_destination_pod"])
def test_the_memo_key_is_the_bound_bags_key_on_every_row(rig, handed,
                                                         kind):
    """Every row's key, built on a miss and found on the second pass,
    equals the instance built on the bag the host APA binds for it."""
    if kind == "no_destination_pod":
        requests = [dict(r, **{"destination.uid":
                               f"{RATELIMIT.base.UID_PREFIX}nod-{i}.ns2"})
                    for i, r in enumerate(rig.requests[BUCKET:2 * BUCKET])]
    else:
        requests = [r for r in rig.requests[BUCKET:]
                    if _source_kind(r, rig.pods) == kind][:BUCKET]
    want = _host_keys(rig.front, requests)
    ok = rig.reference.expected_status
    for n, dedup in enumerate(("memo-a", "memo-b")):
        moved, got = _asked(rig.front, requests, f"{dedup}-{kind}",
                            handed, rig)
        for row, r in enumerate(requests):
            assert got[row] == (want[row] if ok(r) == 0 else None), row
        asks = sum(ok(r) == 0 for r in requests)
        assert moved["row"] == 0 and \
            moved["memo"] + moved["built"] == asks
        if n:
            assert moved == {"memo": asks, "built": 0, "row": 0}


def _front_with(**params) -> Front:
    """A front on the rig's store, the APA instance's params updated."""
    store = _limited(RATELIMIT.make_store(SIZES))
    key = ("instance", "istio-system", "attributes")
    inst = store.get(key)
    store.set(key, dict(inst, params=dict(inst["params"], **params)))
    return Front(_server(store))


def test_a_default_that_reads_the_request_takes_the_row_path(handed):
    """destination.labels (no rule reads it) defaulted to the request's
    headers (a default that evaluates on an empty bag too): what the
    APA binds there is no function of the pod rows, so the plan keys no
    instance and every ask takes the per-row path, its key the host
    APA's."""
    bindings = dict(RATELIMIT.base.BINDINGS, **{
        "destination.labels": "$out.destination_labels | "
                              "request.headers | emptyStringMap()"})
    front = _front_with(attribute_bindings=bindings)
    try:
        plan = front.srv.controller.dispatcher.fused
        assert plan.apa is not None and plan.apa.quota_bindings
        assert plan.quota_keys is None
        requests = RATELIMIT.make_requests(SIZES, BUCKET, 4200000044)
        moved, got = _asked(front, requests, "dflt", handed)
        asks = [k for k in got if k is not None]
        assert moved == {"memo": 0, "built": 0, "row": len(asks)} and asks
        assert got == [k if g is not None else None for k, g in
                       zip(_host_keys(front, requests), got)]
    finally:
        front.close()


def test_a_row_whose_pods_bind_nothing_takes_the_row_path(handed):
    """source.uid without a default: a request that sends none builds
    no APA instance (PodRows.ok false), so nothing is bound and its
    row takes the per-row path; the others are keyed by their pods."""
    front = _front_with(source_uid="source.uid")
    try:
        apa = front.srv.controller.dispatcher.fused.apa
        assert apa.ok_cols
        requests = RATELIMIT.make_requests(SIZES, 4 * BUCKET, 4200000045)
        requests = [r for r in requests if "source.uid" not in r][:16] + \
            [r for r in requests if "source.uid" in r][:BUCKET - 16]
        moved, got = _asked(front, requests, "nouid", handed)
        no_uid = sum(k is not None for k in got[:16])
        assert no_uid and moved["row"] == no_uid
        assert moved["memo"] + moved["built"] == \
            sum(k is not None for k in got[16:])
        assert [k for k in got if k is not None] == [
            k for k, g in zip(_host_keys(front, requests), got)
            if g is not None]
    finally:
        front.close()


def test_a_batch_without_pod_rows_binds_on_the_host(rig, monkeypatch):
    """A batch served without its pod rows (in chunks, or by the host
    oracle: a plain list of responses) binds every OK quota row by the
    host APA, as before, its grants the plain reference's."""
    native = rig.front.native
    chunked = native._check_bags_chunked
    monkeypatch.setattr(native, "_check_bags_chunked",
                        lambda bags, deadline=None: list(
                            chunked(bags, deadline=deadline)))
    requests = rig.requests[3 * BUCKET:4 * BUCKET]
    before = monitor.apa_counters()["rows"]
    keys = monitor.quota_key_counters()
    asks = sum(g is not None for g in _granted(rig, requests, "plain"))
    after = monitor.apa_counters()["rows"]
    assert (after["host"] - before["host"],
            after["quota"] - before["quota"]) == (asks, 0)
    assert monitor.quota_key_counters()["row"] - keys["row"] == asks


def test_a_memo_row_makes_no_bag(rig, monkeypatch):
    """Once a batch's keys are in the memo, its OK quota rows are
    answered with no bag made, none bound, and span `quota.bind`
    entered once for the batch; every row counted on the quota path."""
    from istio_tpu.runtime.apa import ApaStep

    requests = rig.requests[4 * BUCKET:5 * BUCKET]
    _granted(rig, requests, "nobag-warm")
    bound = []
    bind_rows = ApaStep.bind_rows
    monkeypatch.setattr(ApaStep, "bind_rows", lambda self, bags, which,
                        *a: bound.extend(which) or bind_rows(
                            self, bags, which, *a))
    bags = monitor.front_bag_counters()["materialised"]
    rows = monitor.apa_counters()["rows"]["quota"]
    token = monitor.stage_baseline()
    before = monitor.quota_key_counters()
    asks = sum(g is not None for g in _granted(rig, requests, "nobag"))
    assert monitor.quota_key_counters()["memo"] - before["memo"] == asks
    assert monitor.front_bag_counters()["materialised"] == bags
    assert bound == []
    assert monitor.apa_counters()["rows"]["quota"] - rows == asks
    spans = monitor.latency_snapshot(since=token)["spans"]
    assert spans["quota.bind"]["count"] == 1


def test_a_publish_keys_by_the_new_plans_pods(handed):
    """A publish that relabels every pod's version: the new plan's memo
    starts empty and keys by the new pod table, never by the old
    plan's memo."""
    from istio_tpu.runtime.store import Event

    front = Front(_server(_limited(RATELIMIT.make_store(SIZES))))
    try:
        requests = RATELIMIT.make_requests(SIZES, BUCKET, 4200000046)
        _asked(front, requests, "pub-a", handed)
        old = front.srv.controller.dispatcher.fused.quota_keys
        assert old.memo and not any("v9" in k for k in old.memo.values())
        pods = {uid: dict(pod, labels=dict(pod["labels"], version="v9"))
                for uid, pod in RATELIMIT.base.make_pods(SIZES).items()}
        front.srv.controller.store.apply_events([Event(
            ("handler", "istio-system", "kubernetesenv"),
            {"adapter": "kubernetesenv", "params": {"pods": pods}})],
            notify=False)
        plan = front.srv.controller.rebuild().fused
        deadline = time.monotonic() + 120
        while plan._warm_pending:
            assert time.monotonic() < deadline, "the new plan never warmed"
            time.sleep(0.05)
        new = plan.quota_keys
        assert new is not old and not new.memo
        moved, got = _asked(front, requests, "pub-b", handed)
        assert moved["row"] == 0 and moved["built"] > 0
        asks = [k for k in got if k is not None]
        assert asks == [k for k, g in zip(_host_keys(front, requests), got)
                        if g is not None]
        assert any("v9" in k for k in asks)
        assert all("v9" in k for k in new.memo.values()
                   if "unknown" not in k)
    finally:
        front.close()


def test_the_bound_bag_is_the_host_apas_on_every_row(rig):
    """The bag bind_rows makes from the step's pod rows against
    Dispatcher._generate's (the adapter asked, the outputs bound), on
    every row of a batch of every source kind and a gone destination:
    every binding's value, and the quota instance built on each."""
    srv = rig.front.srv
    d = srv.controller.dispatcher
    requests = rig.requests[:BUCKET - 8] + [
        dict(r, **{"destination.uid": f"{RATELIMIT.base.UID_PREFIX}x.ns1"})
        for r in rig.requests[BUCKET:BUCKET + 8]]
    assert {_source_kind(r, rig.pods) for r in requests} == \
        {"uid_and_ip", "ip_only", "gone"}
    bags = [LazyWireBag(_wire(r)) for r in requests]
    results = srv.check_batch_preprocessed(pad_to_bucket(bags, (BUCKET,)))
    bound = results.apa_pods.bind(bags, range(len(bags)))
    host = d._with_apa(bags)
    instance = d.snapshot.instances[f"{QUOTA}.istio-system"]
    for row, (mine, theirs) in enumerate(zip(bound, host)):
        assert isinstance(mine, GeneratedBag)
        for attr in sorted(BOUND):
            assert mine.get(attr) == theirs.get(attr), (row, attr)
        built = instance.build(mine)
        assert built == instance.build(theirs)
        assert tuple(built["dimensions"][k] for k in RATELIMIT.DIMENSIONS) \
            == rig.reference.key_of(requests[row])
    # a batch served without its pod rows (in chunks, or by the host
    # oracle: a plain list) binds on the host, to the same bags
    rows = [0, 9, len(bags) - 1]
    before = monitor.apa_counters()["rows"]
    for mine, theirs in zip(srv.quota_bags(bags, list(results), rows),
                            (bound[r] for r in rows)):
        assert {a: mine.get(a) for a in BOUND} == \
            {a: theirs.get(a) for a in BOUND}
    after = monitor.apa_counters()["rows"]
    assert (after["host"] - before["host"], after["quota"]) == \
        (len(rows), before["quota"])


def test_the_grpc_fronts_bind_a_request_on_the_host(rig):
    """A request at a time (no stamped pod rows): quota_bag binds it by
    the host APA, counted there."""
    srv = rig.front.srv
    request = rig.requests[0]
    before = monitor.apa_counters()["rows"]["host"]
    bag = srv.quota_bag(LazyWireBag(_wire(request)))
    assert monitor.apa_counters()["rows"]["host"] == before + 1
    key = srv.controller.dispatcher.snapshot.instances[
        f"{QUOTA}.istio-system"].build(bag)["dimensions"]
    assert tuple(key[k] for k in RATELIMIT.DIMENSIONS) == \
        rig.reference.key_of(request)


def test_a_quota_that_reads_no_binding_keeps_its_wire_bag():
    """kubeenv's own store: mixer.py's `rq`, keyed by source.user, which
    the sidecar sends. No binder, no pod rows carried, nothing counted;
    the grants a plain counter a user gives."""
    kubeenv = RATELIMIT.base
    store = kubeenv.make_store(SIZES)
    store.set(("handler", "istio-system", "mq"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": "rq.istio-system",
                               "max_amount": LIMIT}]}})
    front = Front(_server(store))
    try:
        d = front.srv.controller.dispatcher
        assert d.apa_in_step and d.fused.apa.quota_bindings == ()
        requests = RATELIMIT.make_requests(SIZES, BUCKET, 4200000043)
        bags = [LazyWireBag(_wire(r)) for r in requests]
        results = front.srv.check_batch_preprocessed(
            pad_to_bucket(bags, (BUCKET,)))
        assert results.apa_pods is None
        assert front.srv.quota_bags(bags, results, [0, 1]) is None
        assert front.srv.quota_bag(bags[0]) is bags[0]
        before = monitor.apa_counters()["rows"]
        tags = [next(front._tags) for _ in requests]
        front.native._run_batch(encode_take([
            (tag, 0, _wire(r), 0, f"rq-{i}", {"rq": (1, True)}, "")
            for i, (tag, r) in enumerate(zip(tags, requests))]))
        with front._cv:
            assert front._cv.wait_for(
                lambda: all(t in front.answers for t in tags), 30)
        status = kubeenv.reference(SIZES)
        used: dict = {}
        for tag, r in zip(tags, requests):
            quotas = pb.CheckResponse.FromString(front.answers[tag][1]).quotas
            if status(r):
                assert "rq" not in quotas
                continue
            user = r.get("source.user", "anon")
            want = int(used.get(user, 0) < LIMIT)
            used[user] = used.get(user, 0) + want
            assert quotas["rq"].granted_amount == want
        assert monitor.apa_counters()["rows"] == dict(
            before, step=monitor.apa_counters()["rows"]["step"])
        # the step's APA binds no column the key reads: the plan keys
        # rq by the batch's ids
        assert set(d.fused.quota_keys.at) == {"rq.istio-system"}
    finally:
        front.close()
