"""One response a verdict class (Dispatcher._fold_respond, stage
`respond`, and the native front's `serialize` / `send`): a batch's rows
that are equal in everything a response is built from share one
CheckResponse, one serialisation and one frame. Held here against the
per-row builder on the four benchmark deployments at their smoke sizes
and on a store with host-overlay rules, whose rows must keep a response
of their own.

The configurations' files are the benchmark's; they are loaded by path
as benchmark/run.py loads them.
"""
import dataclasses
import importlib.util
import json
import struct
import types
from pathlib import Path

import numpy as np
import pytest

from istio_tpu.api.grpc_server import _joined
from istio_tpu.api.native_server import (_FRAME_CLASS_MIN_ROWS,
                                         NativeMixerServer, _Completions)
from istio_tpu.api.take import TakenRows
from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.runtime import RuntimeServer, ServerArgs, dispatcher, monitor
from istio_tpu.runtime.batcher import pad_to_bucket
from istio_tpu.runtime.dispatcher import (CheckResponse, ClassedResponses,
                                          Dispatcher)
from istio_tpu.runtime.fused import class_int_rows
from istio_tpu.sharding.router import ShardRouter
from istio_tpu.testing import workloads
from istio_tpu.testing.take_blob import encode_take

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"
ROWS = 256                  # one bucket of every smoke configuration
SEED = 2147484557           # the driver's seeds pass 2**31
DEPLOYMENTS = ("mixer10k", "rbac1k", "fullmesh5k", "routematch10k",
               "overlay")
OVERLAY_RULES, OVERLAY_SERVICES = 200, 100


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


def _overlay_requests(n: int) -> list[dict]:
    """Requests of make_store(OVERLAY_RULES, host_overlay_every=5):
    two in three aimed at a rule that carries a host list action (rule
    i with i % 5 == 2), with source namespaces on both sides of the
    lists; the rest the generator's own mix."""
    out = workloads.make_request_dicts(n, seed=SEED % 1000)
    for j in range(n):
        if j % 3 == 2:
            continue
        i = 5 * (j % (OVERLAY_RULES // 5)) + 2
        out[j] = {
            "destination.service":
                f"svc{i % OVERLAY_SERVICES}.ns{i % 23}.svc.cluster.local",
            "source.namespace": f"ns{j % 5}",
            "request.method": "GET",
            "request.path": f"/api/v{i % 3}/items",
        }
    return out


def _build(name: str):
    """→ (server, requests) of one deployment."""
    if name == "overlay":
        srv = RuntimeServer(
            workloads.make_store(OVERLAY_RULES, OVERLAY_SERVICES,
                                 host_overlay_every=5),
            ServerArgs(default_manifest=workloads.MESH_MANIFEST,
                       buckets=(ROWS,), max_batch=ROWS,
                       initial_prewarm=False))
        return srv, _overlay_requests(ROWS)
    sizes, config = _load(name)
    srv = RuntimeServer(config.make_store(sizes), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]},
        buckets=tuple(sizes["buckets"]), max_batch=sizes["max_batch"],
        initial_prewarm=False))
    return srv, config.make_requests(sizes, ROWS, SEED)


class _Blob:
    """Stands where the C++ library stands at h2srv_complete, and
    keeps the completion blobs a front hands it, parsed."""

    def __init__(self):
        self.rows: dict[int, tuple[int, bytes]] = {}
        self.count = 0

    def h2srv_complete(self, _handle, blob: bytes, n: int) -> None:
        assert n == len(blob)
        (count,) = struct.unpack_from("<I", blob, 0)
        off = 4
        for _ in range(count):
            tag, status, length = struct.unpack_from("<QiI", blob, off)
            off += 16
            assert tag not in self.rows
            self.rows[tag] = (status, blob[off:off + length])
            off += length
        assert off == len(blob)
        self.count += count


@pytest.fixture(scope="module", params=DEPLOYMENTS)
def served(request):
    """One bucket of seeded requests through the pump's own entry
    twice, once a response a verdict class and once a response a row
    (the same builder, every row a class of one), and the first's
    rows through the native front's serialize + send."""
    srv, requests = _build(request.param)
    native = NativeMixerServer(srv, max_batch=ROWS)
    native.start()
    seen = {}
    overlay_active = Dispatcher._overlay_active

    def spy(self, *args, **kwargs):
        seen["active_sub"], seen["col_pos"] = out = \
            overlay_active(self, *args, **kwargs)
        return out

    patch = pytest.MonkeyPatch()
    try:
        bags = [srv.preprocess(LazyWireBag(
            bag_to_compressed(d).SerializeToString())) for d in requests]
        padded = pad_to_bucket(bags, (ROWS,))
        srv.check_batch_preprocessed(padded)          # warm the shape
        patch.setattr(Dispatcher, "_overlay_active", spy)
        before = (monitor.respond_class_counters(),
                  monitor.CHECK_RESPONSES._value.get())
        classed = srv.check_batch_preprocessed(padded)
        counted = monitor.respond_class_counters()
        plan = srv.controller.dispatcher.fused
        host_rows = seen["active_sub"][:, [
            seen["col_pos"][int(r)] for r in plan.host_rule_idx]
        ].any(axis=1)
        patch.setattr(dispatcher, "RESPOND_CLASS_MIN_ROWS", ROWS + 1)
        by_row = srv.check_batch_preprocessed(padded)
        # the front's serialize + send over the classed batch, the
        # library's entry replaced by a parser of its blob
        blob = _Blob()
        patch.setattr(native, "_lib", blob)
        # the process's ledger stays balanced: the rows serialised
        # here, and again for `want`, as requests a front decoded
        monitor.CHECK_REQUESTS.inc(2 * ROWS)
        checks = TakenRows.read(encode_take(
            [(1000 + row, 0, b"", 0, "", {}, "") for row in range(ROWS)]))
        completions = _Completions()
        native._serialize_rows(checks, bags, classed, {}, completions,
                               set(), None)
        framed = completions.n_framed
        native._send_completions(completions)
        responses = monitor.CHECK_RESPONSES._value.get() - before[1]
        want = [native._check_response(None, bag, result, quotas=[])
                .SerializeToString()
                for bag, result in zip(bags, by_row)]
    finally:
        patch.undo()
        native.stop()
        srv.close()
    return types.SimpleNamespace(
        name=request.param, classed=classed, by_row=by_row,
        host_rows=host_rows, blob=blob, framed=framed, want=want,
        responses=responses, before=before[0], counted=counted)


def test_every_row_gets_what_the_row_builder_gives(served):
    """(a) field for field, and the classes are what they claim."""
    assert isinstance(served.classed, ClassedResponses)
    assert len(served.classed) == len(served.by_row) == ROWS
    for row, (got, want) in enumerate(zip(served.classed, served.by_row)):
        for field in dataclasses.fields(CheckResponse):
            assert getattr(got, field.name) == \
                getattr(want, field.name), (row, field.name)
    classes, class_of = served.classed.classes, served.classed.class_of
    assert all(classes[c] is r for c, r in zip(class_of, served.classed))
    assert len({id(r) for r in served.classed}) == len(classes)
    # a row a class is what the row path is
    assert len(served.by_row.classes) == ROWS
    assert len({r.status_code for r in served.by_row}) > 1


def test_rows_share_an_object_only_with_their_equals(served):
    """Two rows of one class are equal in every field of the per-row
    response; the deployments differ in how many classes that gives."""
    rows_of = {}
    for row, c in enumerate(served.classed.class_of):
        rows_of.setdefault(int(c), []).append(row)
    for rows in rows_of.values():
        assert all(served.by_row[r] == served.by_row[rows[0]]
                   for r in rows)
    if served.name != "overlay":
        assert len(rows_of) < ROWS // 2


def test_the_front_frames_each_row_its_own_bytes(served):
    """(b) the blob holds every tag once, with the bytes the per-row
    result serialises to; big classes went as record arrays."""
    assert served.blob.count == ROWS == len(served.blob.rows)
    for row, want in enumerate(served.want):
        assert served.blob.rows[1000 + row] == (0, want), row
    sizes = np.bincount(np.unique(
        served.want, return_inverse=True)[1])
    assert served.framed == sizes[sizes >= _FRAME_CLASS_MIN_ROWS].sum()
    if served.name != "overlay":
        assert served.framed > ROWS // 2


def test_a_row_under_a_host_action_keeps_an_object_of_its_own(served):
    """(c) told from the planes: the row's active host-action bits."""
    size = np.bincount(served.classed.class_of)[served.classed.class_of]
    if served.name == "overlay":
        assert 16 < served.host_rows.sum() < ROWS
    else:
        assert not served.host_rows.any()
    assert (size[served.host_rows] == 1).all()
    assert (size[~served.host_rows] > 1).any()


def test_the_counters_advance_by_the_batch(served):
    """(e) classes and rows of the one classed batch; CHECK_RESPONSES
    by exactly the rows the front answered."""
    classes = len(served.classed.classes)
    size = np.bincount(served.classed.class_of)[served.classed.class_of]
    assert served.counted["classes_total"] \
        - served.before["classes_total"] == classes
    assert {path: served.counted["rows"][path]
            - served.before["rows"][path] for path in ("classed", "row")} \
        == {"classed": int((size > 1).sum()),
            "row": int((size == 1).sum())}
    assert served.responses == ROWS


def test_the_router_leaves_a_shared_response_untouched():
    """(d) sharding/router folds a bank's local deny_rule to the
    global index on a copy, one a class."""
    denied = CheckResponse(status_code=7, deny_rule=1)
    allowed = CheckResponse()
    from_bank = ClassedResponses([denied, allowed, denied])

    class Bank:
        dispatcher = types.SimpleNamespace(buckets=())
        local_to_global = np.asarray([5, 9])

        @staticmethod
        def check(bags, deadline=None):
            return from_bank

    router = ShardRouter({0: Bank()},
                         types.SimpleNamespace(shard_of=lambda ns: 0),
                         "destination.service")
    out = router.check([workloads.make_bags(1)[0]] * 3)
    assert [r.deny_rule for r in out] == [9, -1, 9]
    assert denied.deny_rule == 1 and out[0] is not denied
    assert out[0] is out[2] and out[1] is allowed


@pytest.mark.parametrize("sizes, joined", [
    ((), 0), ((3,), 3), ((3, 2), 5), ((2, 0, 4), 6)])
def test_chunks_join_as_one_list(sizes, joined):
    """A batch served in chunks (_check_bags_chunked): one chunk stays
    the list it is, classes and all; more make a plain list, which a
    front answers a row at a time."""
    parts = []
    for n in sizes:
        part = ClassedResponses(CheckResponse(valid_use_count=n)
                                for _ in range(n))
        part.classes, part.class_of = list(part), np.arange(n)
        parts.append(part)
    out = _joined(parts)
    assert [r.valid_use_count for r in out] == \
        [n for n in sizes for _ in range(n)] and len(out) == joined
    if len(sizes) == 1:
        assert out is parts[0]
    else:
        assert type(out) is list


@pytest.fixture(scope="module")
def quota_front():
    """mixer10k's smoke store behind a native front whose library is
    a parser of its blobs: one bucket of rows, a response a class and
    a response a row."""
    srv, requests = _build("mixer10k")
    native = NativeMixerServer(srv, max_batch=ROWS)
    native.start()
    patch = pytest.MonkeyPatch()
    try:
        bags = [srv.preprocess(LazyWireBag(
            bag_to_compressed(d).SerializeToString())) for d in requests]
        padded = pad_to_bucket(bags, (ROWS,))
        classed = srv.check_batch_preprocessed(padded)
        patch.setattr(dispatcher, "RESPOND_CLASS_MIN_ROWS", ROWS + 1)
        by_row = srv.check_batch_preprocessed(padded)
        patch.undo()
        yield types.SimpleNamespace(srv=srv, native=native, bags=bags,
                                    classed=classed, by_row=by_row)
    finally:
        patch.undo()
        native.stop()
        srv.close()


@pytest.mark.parametrize("asks, memo", [
    ("all", "cold"), ("all", "warm"), ("two-in-three", "cold"),
    ("two-in-three", "warm"), ("ok-rows", "cold"), ("one", "cold")])
def test_rows_that_ask_a_quota_keep_their_path(quota_front, asks, memo):
    """A classed batch in which rows ask for a quota (the default
    quota path: nothing allocated in the step): an OK row that asks is
    answered by the quota path with its quota in the bytes, every
    other row with the per-row result's exact bytes, and
    CHECK_RESPONSES advances by exactly the rows, on a cold memo
    (every class a miss) as on a warm one."""
    q, patch = quota_front, pytest.MonkeyPatch()
    native, by_row = q.native, q.by_row
    ok = [r.status_code == 0 for r in by_row]
    assert 48 <= sum(ok) < ROWS
    asking = {"all": [True] * ROWS,
              "two-in-three": [row % 3 != 0 for row in range(ROWS)],
              "ok-rows": ok,
              "one": [row == ok.index(True) for row in range(ROWS)]}[asks]
    checks = TakenRows.read(encode_take(
        [(1000 + row, 0, b"", 0, "", {"q": (1, True)} if asking[row]
          else {}, "") for row in range(ROWS)]))
    subs_of = {}
    answer = native._answer_quota_rows

    def spy(group, rows, completions):
        subs_of.update((group.tags[i], group.quotas(i)) for i in rows)
        answer(group, rows, completions)

    blob = _Blob()
    try:
        patch.setattr(native, "_lib", blob)
        patch.setattr(native, "_answer_quota_rows", spy)
        native._resp_memo.clear()
        native._quota_memo.clear()
        completions, deferred = _Completions(), set()
        if memo == "warm":
            native._serialize_rows(
                TakenRows.read(encode_take(
                    [(0, 0, b"", 0, "", {}, "")] * ROWS)), q.bags,
                q.classed, {}, _Completions(), set(), None)
            monitor.CHECK_REQUESTS.inc(ROWS)
        monitor.CHECK_REQUESTS.inc(2 * ROWS)
        before = monitor.CHECK_RESPONSES._value.get()
        native._serialize_rows(checks, q.bags, q.classed, {},
                               completions, deferred, None)
        native._send_completions(completions)
        assert monitor.CHECK_RESPONSES._value.get() - before == ROWS
        want = [native._check_response(
            None, bag, result, quotas=subs_of.get(1000 + row, []))
            .SerializeToString()
            for row, (bag, result) in enumerate(zip(q.bags, by_row))]
    finally:
        patch.undo()
    took_quota_path = [asking[row] and ok[row] for row in range(ROWS)]
    # the store has no rule for quota `q`: every ask resolves without
    # a trip (granted freely), so the group answers its rows with the
    # batch and none is left to a flush
    assert set(subs_of) == \
        {1000 + row for row in range(ROWS) if took_quota_path[row]}
    assert not deferred
    assert blob.count == ROWS == len(blob.rows)
    for row in range(ROWS):
        assert blob.rows[1000 + row] == (0, want[row]), row
    assert all(want[row] != native._check_response(
        None, q.bags[row], by_row[row], quotas=[]).SerializeToString()
        for row in range(ROWS) if took_quota_path[row])
    monitor.CHECK_REQUESTS.inc(sum(took_quota_path))


def test_the_belt_answers_a_row_left_out_beside_one_answered_twice(
        quota_front):
    """A fault that answers one tag twice and another never balances
    the count of completions; the belt names tags, so the row left out
    still gets its INTERNAL and its client does not hang."""
    native, patch = quota_front.native, pytest.MonkeyPatch()
    taken = TakenRows.read(encode_take(
        [(tag, 1, b"", 0, "", {}, "") for tag in range(2000, 2012)]))
    sent = []

    def inner(taken, checks, bags, completions, deferred):
        completions.frame(np.arange(2000, 2008, dtype=np.uint64), b"ok")
        completions.extend((tag, 0, b"ok") for tag in (2008, 2009, 2009))
        deferred.add(2010)
        raise RuntimeError("a fault after the rows were answered")

    def complete(_handle, blob: bytes, n: int) -> None:
        (count,) = struct.unpack_from("<I", blob, 0)
        off = 4
        for _ in range(count):
            tag, status, length = struct.unpack_from("<QiI", blob, off)
            sent.append((tag, status, blob[off + 16:off + 16 + length]))
            off += 16 + length
        assert off == n == len(blob)

    try:
        patch.setattr(native, "_read_take", lambda buf: taken)
        patch.setattr(native, "_run_batch_inner", inner)
        patch.setattr(native, "_lib",
                      types.SimpleNamespace(h2srv_complete=complete))
        native._run_batch(b"")
    finally:
        patch.undo()
    assert len(sent) == 12          # 11 answers and the belt's one
    assert sorted(tag for tag, _, _ in sent) == \
        [*range(2000, 2010), 2009, 2011]
    assert [(status, raw) for tag, status, raw in sent if tag == 2011] \
        == [(13, b"internal: batch processing failed")]


@pytest.mark.parametrize("seed", range(4))
def test_class_int_rows_is_exact(seed):
    """The mixed-radix key names exactly the distinct rows: narrow
    columns by offset, wide ones by rank, constant ones not at all,
    and past 2**62 the key is ranked again."""
    rng = np.random.default_rng(seed)
    n = 350
    wide = rng.integers(-2**62, 2**62, 2000)
    columns = [rng.integers(0, 3, n), np.full(n, 7),
               rng.choice(wide[:5], n),
               rng.integers(-4, 4, n).astype(np.int32)]
    columns += [rng.choice(wide, n) for _ in range(seed * 3)]
    # every row twice, shuffled
    order = rng.permutation(2 * n)
    columns = [np.concatenate([c, c])[order] for c in columns]
    first, inverse = class_int_rows(columns)
    rows = np.stack([c.astype(np.int64) for c in columns], axis=1)
    assert len(first) == len(np.unique(rows, axis=0)) <= n
    assert (rows[first][inverse] == rows).all()
    one = class_int_rows([np.full(5, 3), np.zeros(5, np.int32)])
    assert one[0].tolist() == [0] and one[1].tolist() == [0] * 5
