"""Systematic concurrency harness.

The reference leans on Go's race detector in CI (SURVEY §5); the
equivalent discipline here is targeted interleaving stress: hammer
every shared structure from many threads while mutating the state it
guards, and assert invariants — every caller gets a correct answer,
no exception escapes, nothing deadlocks, resources drain on close.
"""
import threading
import time

import numpy as np
import pytest

from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs

OK, NOT_FOUND, PERMISSION_DENIED = 0, 5, 7


def _store(n_extra=0):
    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": PERMISSION_DENIED}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "denyadmin"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    for i in range(n_extra):
        s.set(("rule", "istio-system", f"r{i}"), {
            "match": f'request.path.startsWith("/x{i}/")',
            "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    return s


def test_checks_race_config_swaps():
    """Checks from many threads while the config churns: every caller
    must get a verdict consistent with SOME published snapshot (the
    deny rule is never removed, so /admin must always deny)."""
    store = _store()
    srv = RuntimeServer(store, ServerArgs(batch_window_s=0.001,
                                          max_batch=32, buckets=(32,)))
    failures: list = []
    stop = threading.Event()

    def checker(tid):
        i = 0
        while not stop.is_set():
            r = srv.check(bag_from_mapping(
                {"request.path": f"/admin/{tid}/{i}"}))
            if r.status_code != PERMISSION_DENIED:
                failures.append(("admin-not-denied", r.status_code))
            r2 = srv.check(bag_from_mapping(
                {"request.path": f"/ok/{tid}/{i}"}))
            if r2.status_code not in (OK, PERMISSION_DENIED):
                # /ok may hit a transient /x{i}/ rule only if the path
                # matched — it can't, so OK is the only legal verdict
                failures.append(("ok-bad-status", r2.status_code))
            i += 1

    def swapper():
        gen = 0
        while not stop.is_set():
            store.set(("rule", "istio-system", "churn"), {
                "match": f'request.path.startsWith("/churn{gen}/")',
                "actions": [{"handler": "denyall",
                             "instances": ["nothing"]}]})
            gen += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=checker, args=(t,), daemon=True)
               for t in range(6)] + \
              [threading.Thread(target=swapper, daemon=True)]
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "thread wedged"
    srv.close()
    assert not failures, failures[:5]


def test_close_races_inflight_checks():
    """close() while requests are in flight: every submitted future
    must resolve (result or error) — callers must never hang."""
    for _ in range(3):
        store = _store()
        srv = RuntimeServer(store, ServerArgs(batch_window_s=0.005,
                                              max_batch=64, buckets=(64,)))
        resolved = []
        errors = []

        def caller(i):
            try:
                srv.check(bag_from_mapping({"request.path": f"/p/{i}"}))
                resolved.append(i)
            except Exception:
                errors.append(i)

        threads = [threading.Thread(target=caller, args=(i,), daemon=True)
                   for i in range(24)]
        for t in threads:
            t.start()
        time.sleep(0.002)
        srv.close()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive(), "caller hung across close()"
        assert len(resolved) + len(errors) == 24


def test_quota_exactness_under_concurrency():
    """memquota must never over-grant across concurrent callers."""
    from istio_tpu.adapters.registry import adapter_registry, load_inventory
    from istio_tpu.adapters.sdk import Env, QuotaArgs
    load_inventory()
    info = adapter_registry.get("memquota")
    builder = info.builder({"quotas": [{"name": "q", "max_amount": 50,
                                        "valid_duration_s": 60.0}]},
                           Env("test"))
    assert not builder.validate()
    h = builder.build()
    granted = []
    barrier = threading.Barrier(8)

    def taker():
        barrier.wait()
        got = 0
        for _ in range(25):
            r = h.handle_quota("quota", {"name": "q", "dimensions": {}},
                               QuotaArgs(quota_amount=1,
                                         best_effort=False))
            got += r.granted_amount
        granted.append(got)

    threads = [threading.Thread(target=taker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    h.close()
    assert sum(granted) == 50, f"granted {sum(granted)} of 50"


def test_device_quota_pool_exactness_under_concurrency():
    """The device-backed pool (runtime/device_quota.py) must never
    over-grant across concurrent callers hammering one cell — batched
    scatter-add allocation included. Mirrors the host memquota
    invariant above."""
    from istio_tpu.adapters.sdk import QuotaArgs
    from istio_tpu.runtime.device_quota import DeviceQuotaPool

    pool = DeviceQuotaPool({"q": {"name": "q", "max_amount": 50}},
                           n_buckets=32, batch_window_s=0.001,
                           max_batch=64)
    try:
        granted = []
        barrier = threading.Barrier(8)

        def taker():
            barrier.wait()
            got = 0
            futs = [pool.alloc("q", {"name": "q", "dimensions": {}},
                               QuotaArgs(quota_amount=1,
                                         best_effort=False))
                    for _ in range(25)]
            for f in futs:
                got += f.result(timeout=30).granted_amount
            granted.append(got)

        threads = [threading.Thread(target=taker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert sum(granted) == 50, f"granted {sum(granted)} of 50"
    finally:
        pool.close()


def test_device_quota_pool_close_races_allocs():
    """close() during a storm: every future resolves (grant or
    UNAVAILABLE), none hangs."""
    from istio_tpu.adapters.sdk import QuotaArgs
    from istio_tpu.runtime.device_quota import DeviceQuotaPool

    pool = DeviceQuotaPool({"q": {"name": "q", "max_amount": 1 << 20}},
                           n_buckets=64, batch_window_s=0.001,
                           max_batch=32)
    futs = []
    stop = threading.Event()

    def feeder():
        i = 0
        while not stop.is_set():
            futs.append(pool.alloc(
                "q", {"name": "q", "dimensions": {"k": str(i % 16)}},
                QuotaArgs(quota_amount=1)))
            i += 1

    t = threading.Thread(target=feeder)
    t.start()
    time.sleep(0.2)
    pool.close()
    stop.set()
    t.join(timeout=10)
    for f in futs:
        r = f.result(timeout=10)   # resolves — never hangs
        assert r.status_code in (0, 14)


def test_batch_check_races_config_swaps():
    """BatchCheck RPCs (the shim protocol) from several threads while
    the config churns: every per-item verdict must be consistent with
    SOME published snapshot, like the unary race above."""
    import pytest
    pytest.importorskip("grpc")
    from istio_tpu.api import MixerClient
    from istio_tpu.api.grpc_server import MixerGrpcServer

    store = _store()
    srv = RuntimeServer(store, ServerArgs(batch_window_s=0.001,
                                          max_batch=32, buckets=(32,)))
    g = MixerGrpcServer(srv)
    port = g.start()
    failures: list = []
    stop = threading.Event()

    def checker(tid):
        client = MixerClient(f"127.0.0.1:{port}",
                             enable_check_cache=False)
        i = 0
        try:
            while not stop.is_set():
                resps = client.batch_check(
                    [{"request.path": f"/admin/{tid}/{i}/{j}"}
                     for j in range(5)] +
                    [{"request.path": f"/ok/{tid}/{i}/{j}"}
                     for j in range(5)])
                codes = [r.precondition.status.code for r in resps]
                if codes[:5] != [PERMISSION_DENIED] * 5:
                    failures.append(("admin-not-denied", codes[:5]))
                if any(c not in (OK, PERMISSION_DENIED)
                       for c in codes[5:]):
                    failures.append(("ok-bad-status", codes[5:]))
                i += 1
        finally:
            client.close()

    def swapper():
        gen = 0
        while not stop.is_set():
            store.set(("rule", "istio-system", "churn"), {
                "match": f'request.path.startsWith("/churn{gen}/")',
                "actions": [{"handler": "denyall",
                             "instances": ["nothing"]}]})
            gen += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=checker, args=(t,), daemon=True)
               for t in range(4)] + \
              [threading.Thread(target=swapper, daemon=True)]
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(timeout=15)
            assert not t.is_alive(), "thread wedged"
        assert not failures, failures[:5]
    finally:
        stop.set()
        g.stop()
        srv.close()


def test_rolling_pool_never_overgrants_across_window_rolls():
    """Concurrent unit allocs against a live ROLLING window while the
    clock advances: the safety invariant is that within any window,
    total granted never exceeds max_amount + (reclaimed slots). With
    the clock frozen per phase, each phase must grant exactly the
    reclaimed budget."""
    from istio_tpu.adapters.sdk import QuotaArgs
    from istio_tpu.runtime.device_quota import DeviceQuotaPool

    class Clock:
        def __init__(self):
            self.t = 50.0

        def __call__(self):
            return self.t

    clock = Clock()
    pool = DeviceQuotaPool(
        {"q": {"name": "q", "max_amount": 40,
               "valid_duration_s": 10.0}},
        n_buckets=8, batch_window_s=0.001, max_batch=64, clock=clock)
    try:
        def storm(n_threads=6, per_thread=20):
            granted = []
            barrier = threading.Barrier(n_threads)

            def taker():
                barrier.wait()
                futs = [pool.alloc("q", {"name": "q", "dimensions": {}},
                                   QuotaArgs(quota_amount=1,
                                             best_effort=True))
                        for _ in range(per_thread)]
                granted.append(sum(
                    f.result(timeout=30).granted_amount for f in futs))

            ts = [threading.Thread(target=taker)
                  for _ in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
                assert not t.is_alive(), "taker wedged"
            return sum(granted)

        assert storm() == 40          # window fills exactly once
        assert storm() == 0           # same ticks: nothing reclaimed
        clock.t += 5.0                # half the window rolls out...
        assert storm() == 0           # ...but all 40 were consumed at
        #                               the same tick — still live
        clock.t += 6.0                # now the consuming tick expired
        assert storm() == 40
    finally:
        pool.close()


def test_batcher_never_abandons_futures_on_prep_failure(monkeypatch):
    """A failure in batch PREP (outside the run_batch call — e.g. the
    tracing span construction) must resolve every future with the
    exception, never leave callers hanging (r4: a NameError in the
    span line hung every request of its batch)."""
    import pytest

    from istio_tpu.runtime.batcher import CheckBatcher
    from istio_tpu.utils import tracing

    b = CheckBatcher(lambda bags: [1] * len(bags), window_s=0.001,
                     max_batch=4)

    def boom():
        raise RuntimeError("span construction failed")

    monkeypatch.setattr(tracing, "get_tracer", boom)
    try:
        fut = b.submit(bag_from_mapping({"request.path": "/x"}))
        with pytest.raises(RuntimeError, match="span construction"):
            fut.result(timeout=15)
    finally:
        monkeypatch.undo()
        b.close()


def test_batcher_holds_batches_while_transport_busy():
    """Occupancy-adaptive window (VERDICT r4 item 6): while a device
    trip is in flight, arriving requests accumulate instead of
    dispatching tiny trips behind a busy serialized transport; an idle
    transport still dispatches after the fixed window (light-load
    latency stays one trip)."""
    import time as _time

    from istio_tpu.runtime.batcher import CheckBatcher, PadBag

    sizes = []
    lock = threading.Lock()

    def run_batch(bags):
        with lock:   # count REAL rows (the batcher pads to buckets)
            sizes.append(sum(1 for x in bags
                             if not isinstance(x, PadBag)))
        _time.sleep(0.12)          # a slow device trip
        return ["ok"] * len(bags)

    b = CheckBatcher(run_batch, window_s=0.002, max_batch=64,
                     pipeline=1, buckets=(64,))
    try:
        futs = [b.submit(object())]
        _time.sleep(0.02)          # first trip departs near-empty
        # 30 requests arrive while that trip is in flight: they must
        # coalesce into few fat batches, not 30 tiny trips
        for _ in range(30):
            futs.append(b.submit(object()))
            _time.sleep(0.002)
        for f in futs:
            assert f.result(timeout=30) == "ok"
    finally:
        b.close()
    assert sizes[0] <= 2, sizes
    # the 30 busy-period arrivals ride at most a handful of batches
    assert len(sizes) <= 6, sizes
    assert max(sizes) >= 10, sizes


def test_store_watch_delivery_under_write_storm():
    """Concurrent writers + a watcher: the watcher must observe a
    coherent final state once writes quiesce (no lost updates)."""
    store = _store()
    seen = []
    store.watch(lambda events: seen.extend(events))

    def writer(tid):
        for i in range(30):
            store.set(("rule", "ns", f"w{tid}-{i}"), {
                "match": "", "actions": []})

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    deadline = time.time() + 5
    while time.time() < deadline:
        written = {(e.key[1], e.key[2]) for e in seen
                   if e.key[1] == "ns"}
        if len(written) == 120:
            break
        time.sleep(0.02)
    assert len([k for k in store.list("rule") if k[1] == "ns"]) == 120
    # the watcher must have OBSERVED every write, not just the store
    assert len({(e.key[1], e.key[2]) for e in seen
                if e.key[1] == "ns"}) == 120


def test_kube_informer_churn_consistency():
    """Pod informer index vs cluster state after concurrent add/delete
    churn: indexes must converge exactly to the surviving pods."""
    from istio_tpu.adapters.kubernetesenv import InformerPodSource
    from istio_tpu.kube.fake import FakeKubeCluster

    cluster = FakeKubeCluster()
    src = InformerPodSource(cluster)

    def churner(tid):
        for i in range(40):
            name = f"pod-{tid}-{i}"
            cluster.apply({"kind": "Pod",
                           "metadata": {"name": name, "namespace": "d"},
                           "status": {"podIP": f"10.{tid}.0.{i}"}})
            if i % 3 == 0:
                cluster.delete("Pod", "d", name)

    threads = [threading.Thread(target=churner, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    expected = {f"{p['metadata']['name']}.d"
                for p in cluster.list("Pod")}
    assert set(src._pods) == expected
    src.close()


def test_cancelled_future_never_poisons_batch():
    """An aio client disconnect cancels its batcher future mid-batch;
    batch-mates must still resolve (set_result on a cancelled future
    raises InvalidStateError and previously aborted distribution)."""
    from istio_tpu.runtime.batcher import CheckBatcher

    release = threading.Event()

    def run_batch(bags):
        release.wait(5)
        return ["ok"] * len(bags)

    b = CheckBatcher(run_batch, window_s=0.2, max_batch=8, buckets=(8,))
    try:
        futs = [b.submit(object()) for _ in range(4)]
        futs[1].cancel()
        release.set()
        for i, f in enumerate(futs):
            if i == 1:
                assert f.cancelled()
            else:
                assert f.result(timeout=10) == "ok"
    finally:
        b.close()


def test_wire_dedup_replay_across_clients_and_windows():
    """VERDICT r4 item 10: CONCURRENT gRPC clients firing the SAME
    deduplication_id must replay ONE grant, never double-consume —
    ids landing in one device batch window, ids racing the original's
    flush, and ids re-sent after the window flushed all take the
    replay path (memquota.go:259 buildWithDedup semantics, proven at
    the real wire against the device quota pool)."""
    pytest.importorskip("grpc")
    from concurrent.futures import ThreadPoolExecutor

    from istio_tpu.api.client import MixerClient
    from istio_tpu.api.grpc_server import MixerGrpcServer

    s = MemStore()
    s.set(("handler", "istio-system", "mq"), {
        "adapter": "memquota",
        "params": {"quotas": [{"name": "rq.istio-system",
                               "max_amount": 10}]}})   # exact counter
    s.set(("instance", "istio-system", "rq"), {
        "template": "quota",
        "params": {"dimensions": {"user": 'source.user | "anon"'}}})
    s.set(("rule", "istio-system", "quota-all"), {
        "match": "",
        "actions": [{"handler": "mq", "instances": ["rq"]}]})
    srv = RuntimeServer(s, ServerArgs(batch_window_s=0.001,
                                      max_batch=32, buckets=(32,)))
    g = MixerGrpcServer(srv)
    port = g.start()
    values = {"source.user": "alice", "request.path": "/ok"}
    try:
        assert srv.controller.dispatcher.fused is not None

        def one(dedup_id):
            # own channel per call: real concurrent client sockets
            cli = MixerClient(f"127.0.0.1:{port}",
                              enable_check_cache=False)
            try:
                resp = cli.check(values, quotas={"rq": 5},
                                 dedup_id=dedup_id)
                assert resp.precondition.status.code == OK
                return resp.quotas["rq"].granted_amount
            finally:
                cli.close()

        # wave 1: 8 clients, one dedup id, one batch window — exactly
        # ONE 5-unit consumption, every caller sees the grant replayed
        with ThreadPoolExecutor(max_workers=8) as pool:
            wave1 = list(pool.map(one, ["X"] * 8))
        assert wave1 == [5] * 8

        # wave 2 (after the window flushed): the SAME id replays from
        # the dedup cache without consuming
        time.sleep(0.2)
        with ThreadPoolExecutor(max_workers=4) as pool:
            wave2 = list(pool.map(one, ["X"] * 4))
        assert wave2 == [5] * 4

        # the proof of single consumption: 5 of 10 remain for a FRESH
        # id; after that the counter is exhausted
        assert one("Y") == 5
        assert one("Z") == 0
    finally:
        g.stop()
        srv.close()
