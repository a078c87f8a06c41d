"""A batch's quota rows go through the device quota pools as one group
(api/native_server._QuotaGroup, runtime/device_quota.DeviceQuotaPool.
alloc_many): one take of a pool's lock a batch, every answer handed on
after the pool's locks are let go, one completion a flush, and the
dedup cache expired from the front of its FIFOs.

Through the native front (the test is the pump; h2srv_complete is a
parser of the completions), each case against the per-row semantics
the gRPC fronts keep: `RuntimeServer.quota_fused` a quota, in row
order, on a second server over the same store, the response built by
`_check_response`, so the bytes are compared whole: grants and
statuses, a dedup id twice in a batch and across batches, a denied row,
a row whose quota rule is inactive, a row asking two quotas, an unknown
quota. At the pool: no answer handed on under its lock, no ask waiting
out an answer, the FIFO's expiry."""
import dataclasses
import struct
import sys
import threading
import types

import numpy as np
import pytest

from istio_tpu.adapters.sdk import QuotaArgs, QuotaResult
from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.native_server import (NativeMixerServer, _Completions,
                                         _QuotaGroup)
from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.batcher import pad_to_bucket
from istio_tpu.runtime.device_quota import DeviceQuotaPool
from istio_tpu.testing.take_blob import encode_take

OK, NOT_FOUND, RESOURCE_EXHAUSTED = 0, 5, 8
BUCKETS = (64,)


def _store() -> MemStore:
    """A list rule that denies an unlisted namespace, and a metered rule
    with two memquota handlers (a pool each), a cell a user. No rule
    reads a map key, so every verdict's bytes are the class's."""
    s = MemStore()
    s.set(("handler", "istio-system", "wl"), {
        "adapter": "list", "params": {"overrides": ["ns1", "ns2"]}})
    s.set(("instance", "istio-system", "ns"), {
        "template": "listentry",
        "params": {"value": 'source.namespace | "none"'}})
    s.set(("rule", "istio-system", "r"), {
        "match": "", "actions": [{"handler": "wl", "instances": ["ns"]}]})
    for handler, quota, limit in (("mq", "rq", 3), ("mq2", "rq2", 2)):
        s.set(("handler", "istio-system", handler), {
            "adapter": "memquota",
            "params": {"quotas": [{"name": f"{quota}.istio-system",
                                   "max_amount": limit}]}})
        s.set(("instance", "istio-system", quota), {
            "template": "quota",
            "params": {"dimensions": {"user": 'source.user | "anon"'}}})
    s.set(("rule", "istio-system", "qr"), {
        "match": 'request.path.startsWith("/metered")',
        "actions": [{"handler": "mq", "instances": ["rq"]},
                    {"handler": "mq2", "instances": ["rq2"]}]})
    return s


def _server() -> RuntimeServer:
    return RuntimeServer(_store(), ServerArgs(
        buckets=BUCKETS, max_batch=BUCKETS[-1], initial_prewarm=False))


def _wire(d: dict) -> bytes:
    return bag_to_compressed(d).SerializeToString()


class Front:
    """A RuntimeServer behind a native front whose pump is the test and
    whose completions land in `answers` {tag: (status, bytes)}."""

    def __init__(self, srv: RuntimeServer):
        self.srv = srv
        self.native = NativeMixerServer(srv, max_batch=BUCKETS[-1])
        self.native._pumps = []         # never started: nothing to join
        self.lib = self.native._lib
        self.native._lib = types.SimpleNamespace(
            h2srv_complete=self._complete)
        self.answers: dict[int, tuple[int, bytes]] = {}
        self._cv = threading.Condition()

    def _complete(self, _handle, blob: bytes, n: int) -> None:
        (count,) = struct.unpack_from("<I", blob, 0)
        off = 4
        with self._cv:
            for _ in range(count):
                tag, status, length = struct.unpack_from("<QiI", blob, off)
                assert tag not in self.answers
                self.answers[tag] = (status,
                                     blob[off + 16:off + 16 + length])
                off += 16 + length
            assert off == n == len(blob)
            self._cv.notify_all()

    def pump(self, items: list[tuple]) -> None:
        self.native._run_batch(encode_take(items))

    def wait(self, tags, timeout: float = 30.0) -> None:
        tags = list(tags)
        with self._cv:
            assert self._cv.wait_for(
                lambda: all(t in self.answers for t in tags), timeout), \
                [t for t in tags if t not in self.answers]

    def close(self) -> None:
        self.native._lib = self.lib
        self.native.stop(grace=0.2)
        self.srv.close()


@pytest.fixture(scope="module")
def rig():
    front, ref = Front(_server()), _server()
    try:
        yield types.SimpleNamespace(front=front, ref=ref)
    finally:
        front.close()
        ref.close()


def _per_row(ref: RuntimeServer, native, batch: list[tuple]) -> list[bytes]:
    """The bytes a row at a time: every quota submitted through
    quota_fused in row order (so they share the pool's window, as the
    gRPC fronts' asks do), then each response built with its quotas."""
    bags = [LazyWireBag(_wire(d)) for d, _, _ in batch]
    results = ref.check_batch_preprocessed(
        pad_to_bucket(bags, BUCKETS))[:len(bags)]
    subs = []
    for (_, asks, dedup), bag, result in zip(batch, bags, results):
        subs.append([])
        if result.status_code != 0:
            continue
        for name, (amount, be) in asks.items():
            args = QuotaArgs(quota_amount=amount, best_effort=be,
                             dedup_id=f"{dedup}:{name}" if dedup else "")
            qr = ref.quota_fused(bag, name, args, result)
            if qr is None:
                qr = ref.quota(bag, name, args, preprocessed=True)
            subs[-1].append((name, qr))
    # the ledger's balance: a response built here answers no request
    # the front decoded (the mesh audit holds decoded >= answered)
    monitor.CHECK_REQUESTS.inc(len(batch))
    return [native._check_response(None, bag, result, quotas=[
        (name, qr.result() if hasattr(qr, "result") else qr)
        for name, qr in quotas]).SerializeToString()
        for bag, result, quotas in zip(bags, results, subs)]


def _req(user: str, ns: str = "ns1", path: str = "/metered/a") -> dict:
    return {"destination.service": "q.b.svc", "source.user": user,
            "source.namespace": ns, "request.path": path}


_TAGS = iter(range(10_000, 1 << 40))


def _case(name: str) -> list[list[tuple]]:
    """Batches of (request, {quota: (amount, best effort)}, dedup id),
    sent one after the other; users named by the case, so no two cases
    share a counter cell."""
    u = lambda who: f"{name}-{who}"          # noqa: E731
    rq = {"rq": (1, True)}
    return {
        "grants": [[(_req(u("alice")), rq, "") for _ in range(5)]
                   + [(_req(u("bob")), {"rq": (2, False)}, "")] * 2],
        "dedup-in-a-batch": [
            [(_req(u("a")), rq, "x"), (_req(u("a")), rq, "x"),
             (_req(u("a")), rq, "")],
            [(_req(u("a")), rq, ""), (_req(u("a")), rq, "")]],
        "dedup-across-batches": [
            [(_req(u("a")), {"rq": (2, True)}, "y")],
            [(_req(u("a")), {"rq": (2, True)}, "y")],
            [(_req(u("a")), rq, ""), (_req(u("a")), rq, "")]],
        "denied": [[(_req(u("a"), ns="ns7"), {"rq": (3, True)}, ""),
                    (_req(u("a")), {"rq": (3, False)}, "")]],
        "rule-inactive": [[(_req(u("a"), path="/open"), {"rq": (5, True)},
                            ""), (_req(u("a")), {"rq": (3, False)}, "")]],
        "two-quotas": [[(_req(u("a")), {"rq": (1, True), "rq2": (2, True)},
                         "z")] * 2 + [(_req(u("a")), {"rq2": (1, True)},
                                       "")]],
        "unknown-quota": [[(_req(u("a")), {"nope": (4, True)}, ""),
                           (_req(u("a")), {"nope": (1, True),
                                           "rq": (1, True)}, "")]],
    }[name]


@pytest.mark.parametrize("name", [
    "grants", "dedup-in-a-batch", "dedup-across-batches", "denied",
    "rule-inactive", "two-quotas", "unknown-quota"])
def test_a_group_answers_as_the_rows_one_at_a_time(rig, name):
    """Every row's bytes equal the per-row path's, batch after batch;
    every OK quota row is answered by the class path."""
    front = rig.front
    codes, grants = set(), set()
    for batch in _case(name):
        tags = [next(_TAGS) for _ in batch]
        want = _per_row(rig.ref, front.native, batch)
        before = monitor.quota_counters()["answered"]
        front.pump([(tag, 0, _wire(d), 0, dedup, asks, "")
                    for tag, (d, asks, dedup) in zip(tags, batch)])
        front.wait(tags)
        for tag, raw in zip(tags, want):
            assert front.answers[tag] == (0, raw), tag
            resp = pb.CheckResponse.FromString(raw)
            codes.add(resp.precondition.status.code)
            grants.update(q.granted_amount for q in resp.quotas.values())
        answered = monitor.quota_counters()["answered"]
        ok_asking = sum(pb.CheckResponse.FromString(raw).precondition
                        .status.code == OK for raw in want)
        assert answered["class"] - before["class"] == ok_asking
        assert answered["row"] == before["row"]
    if name == "grants":      # 3 of 5 granted, one all-or-nothing refused
        assert grants == {0, 1, 2}
    if name == "denied":
        assert codes == {OK, NOT_FOUND}


def test_a_row_asking_two_quotas_is_answered_after_both(rig):
    """The quotas of one row in two pools: the row waits for the second
    pool's flush; a row that asked the first pool alone does not."""
    front = rig.front
    pools = set(front.srv.controller.device_quotas.values())
    first = next(p for p in pools if p.knows("rq.istio-system"))
    second = next(p for p in pools if p.knows("rq2.istio-system"))
    flushed = threading.Event()
    flush = first._flush

    def spy(batch):
        flush(batch)
        flushed.set()

    both, alone = next(_TAGS), next(_TAGS)
    batch = [(_req("pair-a"), {"rq": (1, True), "rq2": (1, True)}, ""),
             (_req("pair-b"), {"rq": (1, True)}, "")]
    first._flush = spy
    second._counts_lock.acquire()       # its flush waits at the trip
    try:
        front.pump([(tag, 0, _wire(d), 0, dedup, asks, "")
                    for tag, (d, asks, dedup) in zip((both, alone), batch)])
        assert flushed.wait(30)
        front.wait([alone])
        assert both not in front.answers
    finally:
        second._counts_lock.release()
        del first._flush
    front.wait([both])
    quotas = pb.CheckResponse.FromString(front.answers[both][1]).quotas
    assert {k: v.granted_amount for k, v in quotas.items()} == \
        {"rq": 1, "rq2": 1}


def test_a_row_whose_bytes_read_its_bag_is_built_alone(rig):
    """Incomplete presence: the row's bytes depend on its bag, so it is
    built from it and counted `row`; its batch-mates share a class."""
    front, srv = rig.front, rig.front.srv
    requests = [_req(f"alone-{i}") for i in range(3)]
    bags = [LazyWireBag(_wire(d)) for d in requests]
    results = srv.check_batch_preprocessed(
        pad_to_bucket(bags, BUCKETS))[:3]
    assert all(front.native._verdict_key(r) is not None for r in results)
    results[0] = dataclasses.replace(results[0], referenced_presence=None)
    group, granted = _QuotaGroup(front.native), QuotaResult(granted_amount=1)
    for i, (bag, result) in enumerate(zip(bags, results)):
        group.add(900 + i, bag, result, ["rq"])
    group.resolve([((i, 0), granted) for i in range(3)])
    before = monitor.quota_counters()["answered"]
    completions, deferred = _Completions(), set()
    group.seal(completions, deferred)
    after = monitor.quota_counters()["answered"]
    assert (after["row"] - before["row"],
            after["class"] - before["class"]) == (1, 2)
    assert not deferred and len(completions) == 3
    monitor.CHECK_REQUESTS.inc(6)       # the ledger's balance (above)
    for tag, status, raw in completions:
        i = tag - 900
        assert (status, raw) == (0, front.native._check_response(
            None, bags[i], results[i],
            quotas=[("rq", granted)]).SerializeToString())


# -- at the pool -------------------------------------------------------

class _Clock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _pool(clock=None, max_amount: int = 1 << 20) -> DeviceQuotaPool:
    return DeviceQuotaPool(
        {"rq": {"name": "rq", "max_amount": max_amount,
                "valid_duration_s": 0.0}},
        n_buckets=64, clock=clock or _Clock(), batch_window_s=0.0005,
        max_batch=64)


def _inst(dims: dict) -> dict:
    return {"name": "rq", "dimensions": dims}


class _Sink:
    """A group as the pool sees one: what each resolve() hands it, and
    whether a pool lock was held meanwhile."""

    def __init__(self, pool: DeviceQuotaPool, n: int, hold=None):
        self.pool, self.n, self.hold = pool, n, hold
        self.got: dict = {}
        self.locked: list[bool] = []
        self.entered, self.done = threading.Event(), threading.Event()
        self._lock = threading.Lock()

    def resolve(self, pairs: list) -> None:
        self.locked.append(self.pool._lock.locked()
                           or self.pool._counts_lock.locked())
        self.entered.set()
        if self.hold is not None:
            assert self.hold.wait(30)
        with self._lock:
            for key, result in pairs:
                assert key not in self.got
                self.got[key] = result
            if len(self.got) == self.n:
                self.done.set()


@pytest.mark.parametrize("sink", ["future", "group"])
def test_no_answer_is_handed_on_under_the_pools_lock(sink):
    pool = _pool()
    asks = [(i, "rq", _inst({"u": f"u{i % 3}"}),
             QuotaArgs(quota_amount=1, dedup_id=f"d{i % 7}"))
            for i in range(20)]
    seen: list[bool] = []
    try:
        with pool._counts_lock:         # every flush waits at its trip
            if sink == "future":
                futs = [pool.alloc(name, inst, args)
                        for _, name, inst, args in asks]
                for fut in futs:
                    fut.add_done_callback(
                        lambda _v: seen.append(pool._lock.locked()))
            else:
                group = _Sink(pool, len(asks))
                assert pool.alloc_many(asks, group) == []
        if sink == "future":
            assert all(f.result(30).granted_amount == 1 for f in futs)
            assert seen == [False] * len(asks)
        else:
            assert group.done.wait(30)
            assert group.locked and not any(group.locked)
            assert sorted(group.got) == list(range(len(asks)))
    finally:
        pool.close()


@pytest.mark.parametrize("sink", ["future", "group"])
def test_an_ask_does_not_wait_out_an_answer(sink):
    """While the worker is inside an answer (a callback, a group's build
    and send), an ask from another thread still gets into the pool."""
    pool, release = _pool(), threading.Event()
    entered, asker = threading.Event(), None
    try:
        if sink == "future":
            with pool._counts_lock:
                fut = pool.alloc("rq", _inst({"u": "a"}),
                                 QuotaArgs(quota_amount=1))
                fut.add_done_callback(
                    lambda _v: (entered.set(), release.wait(30)))
        else:
            group = _Sink(pool, 1, hold=release)
            entered = group.entered
            assert not pool.alloc_many(
                [(0, "rq", _inst({"u": "a"}), QuotaArgs(quota_amount=1))],
                group)
        assert entered.wait(30)
        returned = threading.Event()
        asker = threading.Thread(target=lambda: (pool.alloc(
            "rq", _inst({"u": "b"}), QuotaArgs(quota_amount=1)),
            returned.set()), daemon=True)
        asker.start()
        assert returned.wait(5), "an ask waited out a flush's answer"
    finally:
        release.set()
        pool.close()
        if asker is not None:
            asker.join(10)
    assert not asker.is_alive()


def test_groups_racing_on_one_pool_consume_each_id_once():
    """Sixteen threads, each submitting groups whose asks share dedup ids
    across threads (an id belongs to one key), with a short switch
    interval: every ask is answered once, and each key's counter moved
    by exactly its distinct ids."""
    limit, keys, ids = 10_000, 4, 64
    pool, interval = _pool(max_amount=limit), sys.getswitchinterval()
    sinks: list[_Sink] = []
    errors: list = []
    picked: set[int] = set()

    def submit(seed: int) -> None:
        try:
            rng = np.random.default_rng(seed)
            for _ in range(10):
                picks = rng.integers(ids, size=12).tolist()
                picked.update(picks)
                sink = _Sink(pool, len(picks))
                sinks.append(sink)
                early = pool.alloc_many(
                    [(j, "rq", _inst({"k": f"k{d % keys}"}),
                      QuotaArgs(quota_amount=1, dedup_id=f"id{d}"))
                     for j, d in enumerate(picks)], sink)
                sink.resolve(early)
        except Exception as exc:        # surfaced in the main thread
            errors.append(exc)

    try:
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=submit, args=(s,))
                   for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors and not any(t.is_alive() for t in threads)
        assert all(s.done.wait(30) for s in sinks)
        assert all(r.granted_amount == 1 for s in sinks
                   for r in s.got.values())
        for k in range(keys):
            left = pool.alloc("rq", _inst({"k": f"k{k}"}), QuotaArgs(
                quota_amount=limit, best_effort=True)).result(30)
            assert limit - left.granted_amount == \
                sum(d % keys == k for d in picked), k
    finally:
        sys.setswitchinterval(interval)
        pool.close()


def _fifo_len(pool: DeviceQuotaPool) -> int:
    return sum(len(f) for f in pool._dedup_fifos.values())


def test_the_dedup_cache_expires_in_order():
    clock = _Clock()
    pool = _pool(clock)             # min_dedup_s 1: an entry lives 1 s
    try:
        def ask(did: str = "") -> None:
            pool.alloc("rq", _inst({}), QuotaArgs(
                quota_amount=1, dedup_id=did)).result(30)

        for at, did in ((0.0, "a"), (0.5, "b"), (1.2, "c")):
            clock.t = 1000.0 + at
            ask(did)
        for at, alive in ((1.3, ["b", "c"]), (1.6, ["c"]), (2.3, [])):
            clock.t = 1000.0 + at
            ask()
            assert sorted(pool._dedup) == alive
            assert _fifo_len(pool) == len(alive)
    finally:
        pool.close()


class _Walked(dict):
    """A dict that counts the walks over it."""

    def __init__(self):
        super().__init__()
        self.walks = 0

    def __iter__(self):
        self.walks += 1
        return super().__iter__()

    def items(self):
        self.walks += 1
        return super().items()

    def keys(self):
        self.walks += 1
        return super().keys()

    def values(self):
        self.walks += 1
        return super().values()


@pytest.mark.parametrize("expired", [False, True])
def test_an_ask_walks_no_dedup_cache(expired):
    """50 000 entries, half consumed, half gate-off: an ask walks
    neither table, live or expired; what expired is dropped from the
    FIFOs' fronts."""
    clock = _Clock()
    pool = _pool(clock)
    try:
        pool._dedup, pool._dedup_free = _Walked(), _Walked()
        with pool._lock:
            for i in range(50_000):
                pool._remember(f"id{i}", 1 if i % 2 else None, 0.0,
                               clock())
        assert _fifo_len(pool) == 50_000
        if expired:
            clock.t += 1.5
        pool.alloc("rq", _inst({}), QuotaArgs(
            quota_amount=1, dedup_id="fresh")).result(30)
        assert pool._dedup.walks == pool._dedup_free.walks == 0
        live = 0 if expired else 25_000
        assert (len(pool._dedup), len(pool._dedup_free),
                _fifo_len(pool)) == (live + 1, live, 2 * live + 1)
    finally:
        pool.close()


@pytest.mark.parametrize("gate", ["consumed", "gate-off"])
def test_a_rewritten_id_keeps_its_newer_entry(gate):
    """An id written twice: the first write's FIFO record expires first
    and must not drop the second's value."""
    clock = _Clock()
    pool = _pool(clock)
    granted = (lambda g: g) if gate == "consumed" else (lambda g: None)
    try:
        with pool._lock:
            pool._remember("x", granted(2), 0.0, 1000.0)
            pool._remember("x", granted(3), 0.0, 1000.6)
        clock.t = 1001.2                # the first record has expired
        got = pool.alloc("rq", _inst({}), QuotaArgs(
            quota_amount=1, dedup_id="x")).result(30)
        if gate == "consumed":
            assert pool._dedup["x"] == (3, 1000.6 + 1.0)
            assert got.granted_amount == 3      # replayed, not consumed
        else:
            assert pool._dedup_free["x"] == 1000.6 + 1.0
            assert "x" not in pool._dedup
            assert got.granted_amount == 1      # granted freely
        assert _fifo_len(pool) == 1
    finally:
        pool.close()


def test_in_step_commits_expire_too():
    clock = _Clock()
    pool = _pool(clock)
    try:
        rows = [(0, "rq", _inst({"u": "a"}),
                 QuotaArgs(quota_amount=1, dedup_id="on")),
                (1, "rq", _inst({"u": "b"}),
                 QuotaArgs(quota_amount=1, dedup_id="off"))]
        sess = pool.inline_begin(2, rows, clock())
        sess.dispatched(sess.prev_counts)
        sess.commit(np.ones(2, np.int32), np.array([True, False]))
        assert "on" in pool._dedup and "off" in pool._dedup_free
        assert _fifo_len(pool) == 2
        clock.t += 1.5
        pool.alloc("rq", _inst({}), QuotaArgs(quota_amount=1)).result(30)
        assert "on" not in pool._dedup and "off" not in pool._dedup_free
        assert _fifo_len(pool) == 0
    finally:
        pool.close()
