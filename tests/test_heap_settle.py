"""The long-lived heap lives in the collector's permanent generation
for as long as a RuntimeServer serves (monitor.settle_heap): frozen at
the constructor's end, at a front's start, after a publish and after
any long full collection; reclaimed at init and publish; unfrozen at
the last close.
Nothing here reads a clock: what a collection walks is told by what it
frees.
"""
import gc
import sys
import threading
import weakref

import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.store import Event


class _Node:
    pass


def _cycle():
    """A pair that only a collection can free, and a weakref to it."""
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    return a, weakref.ref(a)


@pytest.fixture
def own_heap(monkeypatch):
    """The permanent generation is the process's: each test starts as a
    process with no server, whatever this worker's earlier tests left
    open, and leaves the heap unfrozen."""
    monkeypatch.setattr(monitor, "_GC_USERS", 0)
    gc.unfreeze()
    yield
    gc.unfreeze()


def _settles() -> dict:
    return monitor.gc_pause_snapshot()["settles"]


def test_a_settle_hides_the_heap_and_a_reclaim_returns_its_garbage(
        own_heap):
    monitor.install_gc_hook()
    try:
        pair, alive = _cycle()
        monitor.settle_heap("init")
        assert gc.get_freeze_count() > 0
        assert monitor.gc_pause_snapshot()["frozen"] > 0
        del pair
        gc.collect()
        assert alive() is not None        # frozen: no collection walks it
        monitor.settle_heap("publish", reclaim=True)
        assert alive() is None
        assert gc.get_freeze_count() > 0
        # what is allocated after a settle is collected as ever
        pair, alive = _cycle()
        del pair
        gc.collect()
        assert alive() is None
    finally:
        monitor.remove_gc_hook()
    assert gc.get_freeze_count() == 0
    assert monitor.gc_pause_snapshot()["frozen"] == 0


@pytest.mark.parametrize("reclaim", [False, True])
def test_a_settle_without_a_server_is_a_no_op(own_heap, reclaim):
    before = _settles()
    pair, alive = _cycle()
    monitor.settle_heap("start", reclaim=reclaim)
    assert gc.get_freeze_count() == 0
    assert _settles() == before
    del pair
    gc.collect()
    assert alive() is None


@pytest.mark.parametrize("reclaim", [False, True])
def test_the_heap_stays_frozen_until_the_last_user_leaves(own_heap,
                                                          reclaim):
    monitor.install_gc_hook()
    monitor.settle_heap("init", reclaim=reclaim)
    monitor.install_gc_hook()
    monitor.settle_heap("init", reclaim=reclaim)
    monitor.remove_gc_hook()
    assert gc.get_freeze_count() > 0
    monitor.remove_gc_hook()
    assert gc.get_freeze_count() == 0
    monitor.remove_gc_hook()              # one too many: nothing moves
    monitor.settle_heap("init")
    assert gc.get_freeze_count() == 0


@pytest.mark.parametrize("pause_s, resettles", [(0.0, 1),
                                                (float("inf"), 0)])
def test_a_long_full_collection_freezes_what_survived_it(
        own_heap, monkeypatch, pause_s, resettles):
    # no clock is read here: every collection is as long as 0.0 and
    # none as long as inf
    monkeypatch.setattr(monitor, "RESETTLE_PAUSE_S", pause_s)
    monitor.install_gc_hook()
    try:
        monitor.settle_heap("init")
        before = _settles()["gc"]
        late, alive = _cycle()            # built after every named site
        gc.collect(1)                     # a young collection: no hook
        assert _settles()["gc"] == before
        gc.collect()
        assert _settles()["gc"] == before + resettles
        del late
        gc.collect()
        assert (alive() is not None) == bool(resettles)
        # the reclaim's own walk ends in the hook too, and settles once
        monitor.settle_heap("publish", reclaim=True)
        assert alive() is None
        assert _settles()["publish"] >= 1
    finally:
        monitor.remove_gc_hook()
    assert gc.get_freeze_count() == 0


@pytest.mark.parametrize("generation", [0, 1])
def test_a_young_collection_moves_the_young_histogram_alone(own_heap,
                                                            generation):
    monitor.install_gc_hook()
    try:
        before = monitor.gc_pause_snapshot()
        gc.collect(generation)
        moved = monitor.gc_pause_snapshot(since=before)
        assert moved["young"]["count"] >= 1     # others may run beside
        assert moved["young"]["sum_s"] > 0.0
        assert moved["count"] == 0 and moved["sum_s"] == 0.0
        assert monitor.GC_PAUSE_SECONDS.state()[2] == before["count"]
        gc.collect()
        both = monitor.gc_pause_snapshot(since=before)
        assert both["count"] == 1
        # the keys gc_pause_share and gc_frozen_objects read are there
        assert {"count", "sum_s", "frozen", "settles"} <= set(both)
    finally:
        monitor.remove_gc_hook()


def test_settles_from_many_threads_lose_no_garbage_and_do_not_deadlock(
        own_heap, monkeypatch):
    # the hook settles on whichever thread's allocation set a full
    # collection off while another thread reclaims: every collection
    # is long enough here, and threads switch every few bytecodes
    monkeypatch.setattr(monitor, "RESETTLE_PAUSE_S", 0.0)
    interval = sys.getswitchinterval()
    refs: list = []
    errors: list = []

    def churn():
        try:
            for i in range(400):
                pair, alive = _cycle()
                refs.append(alive)
                if i % 50 == 0:
                    gc.collect()          # the hook's own settle
                if i % 97 == 0:
                    monitor.settle_heap("publish", reclaim=True)
        except Exception as exc:          # surfaced below
            errors.append(exc)

    monitor.install_gc_hook()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn, daemon=True)
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        assert _settles()["gc"] > 0
        monitor.settle_heap("publish", reclaim=True)
        # whatever a settle caught alive has been returned since
        assert len(refs) == 8 * 400 and not any(r() for r in refs)
    finally:
        sys.setswitchinterval(interval)
        monitor.remove_gc_hook()
    assert gc.get_freeze_count() == 0


def _quietly(store, key, value) -> None:
    """A store edit no watcher hears of: the tests publish themselves
    (controller.rebuild()), so that each publish is one they count."""
    store.apply_events([Event(key, value)], notify=False)


def _store() -> MemStore:
    s = MemStore()
    _quietly(s, ("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    _quietly(s, ("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    _quietly(s, ("rule", "istio-system", "r0"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    return s


def _server(store=None) -> RuntimeServer:
    return RuntimeServer(store or _store(), ServerArgs(
        batch_window_s=0.001, max_batch=64, buckets=(64,),
        initial_prewarm=False))


@pytest.mark.parametrize("closes_first", ["older", "newer"])
def test_two_servers_share_one_frozen_heap(own_heap, closes_first):
    older = _server()
    newer = _server()
    first, last = (older, newer) if closes_first == "older" \
        else (newer, older)
    try:
        assert gc.get_freeze_count() > 0
        first.close()
        assert gc.get_freeze_count() > 0
    finally:
        first.close()
        last.close()
    assert gc.get_freeze_count() == 0
    last.close()                          # idempotent: still unfrozen
    assert gc.get_freeze_count() == 0


PATHS = ["/admin/x", "/", "/admin", "/administrator", "/adm", "/late/x"]


def _agrees_with_the_oracle(srv) -> list[int]:
    bags = [bag_from_mapping({"request.path": p}) for p in PATHS]
    got = srv.check_many(bags)
    want = srv.controller.dispatcher.check_host_oracle(
        [srv.preprocess(b) for b in bags])
    assert [r.status_code for r in got] == [o.status_code for o in want]
    assert [r.referenced for r in got] == [o.referenced for o in want]
    return [r.status_code for r in got]


def _deny_late(store) -> None:
    _quietly(store, ("rule", "istio-system", "r1"), {
        "match": 'request.path.startsWith("/late")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})


def test_a_served_process_freezes_at_init_start_and_publish(own_heap):
    before = _settles()
    store = _store()
    srv = _server(store)
    native = None
    try:
        assert _settles()["init"] == before["init"] + 1
        assert monitor.gc_pause_snapshot()["frozen"] > 0
        # garbage caught by a settle stays until a publish reclaims it
        pair, alive = _cycle()
        native = NativeMixerServer(srv, max_batch=64, min_fill=8,
                                   window_us=500)
        client = MixerClient(f"127.0.0.1:{native.start()}",
                             enable_check_cache=False)
        try:
            assert _settles()["start"] == before["start"] + 1
            assert _settles()["publish"] == before["publish"]
            del pair
            gc.collect()
            assert alive() is not None
            assert client.check(
                {"request.path": "/admin/x"}).precondition.status.code == 7
            assert client.check(
                {"request.path": "/late/x"}).precondition.status.code == 0
            _deny_late(store)
            srv.controller.rebuild()
            assert _settles()["publish"] == before["publish"] + 1
            assert alive() is None
            assert gc.get_freeze_count() > 0
            assert monitor.gc_pause_snapshot()["frozen"] > 0
            assert client.check(
                {"request.path": "/late/x"}).precondition.status.code == 7
        finally:
            client.close()
    finally:
        if native is not None:
            native.stop()
        srv.close()
    assert gc.get_freeze_count() == 0
    assert monitor.gc_pause_snapshot()["frozen"] == 0


def test_verdicts_are_the_oracles_across_settles_and_a_publish(own_heap):
    store = _store()
    srv = _server(store)
    try:
        first = _agrees_with_the_oracle(srv)
        assert first == [7, 0, 7, 7, 0, 0]
        monitor.settle_heap("start")
        assert _agrees_with_the_oracle(srv) == first
        monitor.settle_heap("publish", reclaim=True)
        assert _agrees_with_the_oracle(srv) == first
        _deny_late(store)
        srv.controller.rebuild()
        assert _agrees_with_the_oracle(srv) == [7, 0, 7, 7, 0, 7]
        # a collection of everything young changes no answer either
        gc.collect()
        assert _agrees_with_the_oracle(srv) == [7, 0, 7, 7, 0, 7]
    finally:
        srv.close()
    assert gc.get_freeze_count() == 0
