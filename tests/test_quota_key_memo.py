"""A quota row's memquota key from the batch's interned ids
(runtime/quota_keys.py): the plan's memo, (instance, the row's ids) →
key, built on the row's bag the first time a key is seen and looked up
with no bag after.

Through the native front (the test is the pump, as in
test_quota_group.py) on CPU with real DeviceQuotaPools, each case
against the per-row path a second server keeps (`quota_fused` a quota,
in row order, the response built by `_check_response`): the bytes of
every row equal, and the key of every ask the front hands a pool equal
to `dims_key(instance.build(bag))` on the snapshot that served it.
Limits are small, so a wrong key shows in the grants too."""
import time

import pytest

from istio_tpu.adapters.memquota import _key as dims_key
from istio_tpu.api.native_server import _QuotaGroup
from istio_tpu.api.take import TakenRows
from istio_tpu.api.wire import LazyWireBag
from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import build_layout
from istio_tpu.expr.parser import parse
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.batcher import pad_to_bucket
from istio_tpu.runtime.device_quota import DeviceQuotaPool
from istio_tpu.runtime.quota_keys import _ABSENT, _reads
from istio_tpu.runtime.store import Event
from istio_tpu.testing.take_blob import encode_take
from test_quota_group import BUCKETS, Front, _per_row, _wire

RQ = 'source.user | "anon"'


def _store(rq: str = RQ) -> MemStore:
    """A namespace whitelist the step decides; a denier on users that start with
    `deny-` (a byte predicate: source.user has a byte slot, so a long
    user takes the wide plane); quota rq (`rq`, limit 3) on /metered
    paths, and rq2, whose build raises where source.user is absent, on
    /strict paths."""
    s = MemStore()
    s.set(("handler", "istio-system", "wl"), {
        "adapter": "list", "params": {"overrides": ["ns1", "ns2"]}})
    s.set(("instance", "istio-system", "ns"), {
        "template": "listentry", "params": {"value": "source.namespace"}})
    s.set(("rule", "istio-system", "r"), {
        "match": "", "actions": [{"handler": "wl", "instances": ["ns"]}]})
    s.set(("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "d"), {
        "match": 'source.user.startsWith("deny-")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    for handler, quota, dims, path in (("mq", "rq", rq, "/metered"),
                                       ("mq2", "rq2", "source.user",
                                        "/strict")):
        s.set(("handler", "istio-system", handler), {
            "adapter": "memquota",
            "params": {"quotas": [{"name": f"{quota}.istio-system",
                                   "max_amount": 3}]}})
        s.set(("instance", "istio-system", quota), {
            "template": "quota", "params": {"dimensions": {"user": dims}}})
        s.set(("rule", "istio-system", f"q{quota}"), {
            "match": f'request.path.startsWith("{path}")',
            "actions": [{"handler": handler, "instances": [quota]}]})
    return s


def _server() -> RuntimeServer:
    return RuntimeServer(_store(), ServerArgs(
        buckets=BUCKETS, max_batch=BUCKETS[-1], initial_prewarm=False))


@pytest.fixture(scope="module")
def rig():
    front, ref = Front(_server()), _server()
    try:
        yield front, ref
    finally:
        front.close()
        ref.close()


@pytest.fixture
def handed(monkeypatch):
    """{tag: the key the front handed a pool} for every ask of a
    native quota group (the per-row reference's asks come as futures)."""
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


def _req(user=None, ns: str = "ns1", path: str = "/metered/a") -> dict:
    d = {"destination.service": "q.b.svc", "source.namespace": ns,
         "request.path": path}
    if user is not None:
        d["source.user"] = user
    return d


_TAGS = iter(range(50_000, 1 << 40))
RQ1 = {"rq": (1, True)}


def _case(name: str) -> list[list[tuple]]:
    """Batches of (request, {quota: (amount, best effort)}, dedup id);
    users named by the case, so no two cases share a counter cell."""
    u = f"{name}-u"
    return {
        "absent": [[(_req(), RQ1, ""), (_req(), RQ1, "")]],
        "empty": [[(_req(""), RQ1, ""), (_req(""), {"rq": (2, True)}, "")]],
        "long": [[(_req(u + "x" * 300), RQ1, ""),
                  (_req(u + "x" * 300), RQ1, ""),
                  (_req(u + "x" * 299), RQ1, "")]],
        "new-intern": [[(_req(u), RQ1, ""), (_req(u), RQ1, "")],
                       [(_req(u), RQ1, ""), (_req(u + "2"), RQ1, "")]],
        "wrong-type": [[(_req(7), RQ1, ""), (_req(u), RQ1, ""),
                        (_req(7), RQ1, ""), (_req(True), RQ1, ""),
                        (_req(True), RQ1, "")]],
        "one-key-two-asks": [[(_req(u), {"rq": (2, True)}, "a"),
                              (_req(u), {"rq": (1, False)}, "b"),
                              (_req(u), {"rq": (1, False)}, "c")]],
        "replayed-dedup": [[(_req(u), {"rq": (2, True)}, "r"),
                            (_req(u), {"rq": (2, True)}, "r"),
                            (_req(u), RQ1, "")]],
        "denied": [[(_req(u, ns="ns9"), RQ1, ""),
                    (_req("deny-" + u), RQ1, ""), (_req(u), RQ1, "")]],
        "build-raises": [[(_req(path="/strict"), {"rq2": (1, True)}, ""),
                          (_req(path="/strict"), {"rq2": (1, True)}, ""),
                          (_req(u, path="/strict"), {"rq2": (1, True)},
                           "")]],
    }[name]


def _pump(front, ref, batch: list[tuple], handed: dict) -> dict:
    """One batch through the front and the per-row reference: every
    row's bytes equal, and every ask's key the instance's built on the
    row's bag by the snapshot in force. → the keys counters' delta."""
    tags = [next(_TAGS) for _ in batch]
    want = _per_row(ref, front.native, batch)
    before = monitor.quota_key_counters()
    front.pump([(tag, 0, _wire(d), 0, dedup, asks, "")
                for tag, (d, asks, dedup) in zip(tags, batch)])
    front.wait(tags)
    for tag, raw in zip(tags, want):
        assert front.answers[tag] == (0, raw), tag
    snap = front.srv.controller.dispatcher.snapshot
    for tag, (d, asks, _) in zip(tags, batch):
        if tag in handed:
            (name,) = asks
            built = snap.instances[f"{name}.istio-system"].build(
                LazyWireBag(_wire(d)))
            assert handed[tag] == dims_key(built), d
    after = monitor.quota_key_counters()
    return {path: after[path] - before[path] for path in after}


@pytest.mark.parametrize("name", [
    "absent", "empty", "long", "new-intern", "wrong-type",
    "one-key-two-asks", "replayed-dedup", "denied", "build-raises"])
def test_the_memo_keys_as_the_row_one_at_a_time(rig, handed, name):
    """Bytes, grants and keys the per-row path's, batch after batch;
    the rows whose ids decide their key take it from the memo."""
    front, ref = rig
    moved = {"memo": 0, "built": 0, "row": 0}
    for batch in _case(name):
        for path, n in _pump(front, ref, batch, handed).items():
            moved[path] += n
    if name == "build-raises":
        # the two errors are answered by the per-row path, never
        # memoised; the user's key is
        assert moved == {"memo": 0, "built": 1, "row": 2}
        assert ("rq2.istio-system", _ABSENT) not in _keys(front).memo
    elif name == "wrong-type":
        # an int is a runtime value, a bool an interned id: neither
        # is a string
        assert moved["row"] == 4 and moved["memo"] + moved["built"] == 1
    elif name == "denied":       # a denied row asks nothing
        assert sum(moved.values()) == 1 and moved["row"] == 0
    else:
        asks = sum(len(b) for b in _case(name))
        assert moved["row"] == 0 and \
            moved["memo"] + moved["built"] == asks
        if name in ("new-intern", "long", "one-key-two-asks",
                    "replayed-dedup"):
            assert moved["memo"] >= 1


def _keys(front):
    return front.srv.controller.dispatcher.fused.quota_keys


def _publish(srv: RuntimeServer, dims: str) -> None:
    """Quota rq's dimension as `dims`, published by the test itself (a
    store edit no watcher hears of, then one rebuild), and served by
    the step once the new plan's shapes are warm (not by the oracle
    that bridges the warm)."""
    srv.controller.store.apply_events([Event(
        ("instance", "istio-system", "rq"),
        {"template": "quota", "params": {"dimensions": {"user": dims}}})],
        notify=False)
    plan = srv.controller.rebuild().fused
    deadline = time.monotonic() + 120
    while plan._warm_pending:
        assert time.monotonic() < deadline, "the new plan never warmed"
        time.sleep(0.05)


def test_a_publish_keys_by_the_new_snapshot(rig, handed):
    """A publish that changes the instance's expression: the new
    plan's memo starts empty and keys by the new expression, never by
    the old plan's memo; then the original config comes back."""
    front, ref = rig
    batch = [(_req("pub-u"), RQ1, ""), (_req("pub-u"), RQ1, "")]
    _pump(front, ref, batch, handed)
    old = _keys(front)
    assert any("pub-u" in key for key in old.memo.values())
    try:
        for srv in (front.srv, ref):
            _publish(srv, 'source.user.startsWith("pub")')
        new = _keys(front)
        assert new is not old and not new.memo
        moved = _pump(front, ref, batch, handed)
        assert moved == {"memo": 1, "built": 1, "row": 0}
        assert set(new.memo.values()) == {
            "rq.istio-system|[('user', True)]"}
    finally:
        for srv in (front.srv, ref):
            _publish(srv, RQ)


def test_a_memo_row_makes_no_bag(rig, handed):
    """Once its key is in the memo, an OK quota row whose response
    bytes do not read its bag is answered with no bag made."""
    front, ref = rig
    batch = [(_req(f"nobag-{i % 3}"), RQ1, f"nb{i}") for i in range(12)]
    _pump(front, ref, batch[:3], handed)      # the three keys, built
    bags = monitor.FRONT_BAGS_MATERIALISED._value.get()
    tags = [next(_TAGS) for _ in batch]
    before = monitor.quota_key_counters()
    front.pump([(tag, 0, _wire(d), 0, dedup, asks, "")
                for tag, (d, asks, dedup) in zip(tags, batch)])
    front.wait(tags)
    assert monitor.FRONT_BAGS_MATERIALISED._value.get() == bags
    assert monitor.quota_key_counters()["memo"] - before["memo"] == 12


def test_a_batch_without_a_quota_row_gathers_no_key_columns(rig):
    front, _ = rig
    srv = front.srv
    for asks, gathered in (({}, False), (RQ1, True)):
        taken = TakenRows.read(encode_take([
            (next(_TAGS), 0, _wire(_req("cols-u")), 0, "", asks, "")
            for _ in range(3)]))
        out = srv.check_batch_preprocessed(pad_to_bucket(taken, BUCKETS))
        assert (out.quota_keys is not None) == gathered


LAYOUT = build_layout({"source.user": ValueType.STRING,
                       "request.size": ValueType.INT64,
                       "source.ip": ValueType.IP_ADDRESS,
                       "source.labels": ValueType.STRING_MAP},
                      derived_keys=[("source.labels", "app")])


@pytest.mark.parametrize("expression, keyed", [
    (RQ, True),
    ('source.labels["app"] | "none"', True),
    ('source.user.startsWith(source.labels["app"] | "")', True),
    ('source.labels["version"] | "none"', False),   # no derived column
    ("request.size | 0", False),                    # not a string
    ('source.ip == ip("10.0.0.1")', False),         # not a string
    ("source.user | source.unknown", False),        # no column
])
def test_an_instance_is_keyed_by_ids_only_where_they_decide_it(
        expression, keyed):
    assert _reads(parse(expression), LAYOUT, []) is keyed
