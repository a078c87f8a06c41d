"""The `routelong10k` deployment (benchmark/configs/routelong.py:
routematch10k's route table under real header sizes) through the served
entry, and the length split it forced: rows with a subject at the
128-byte cap are served on the wide byte plane by `step_wide`, rows
past that plane too are decided by the host, and every response comes
back in its row's place. At a table small enough for a CPU to scan
2 048-byte subjects (the store's generator is routematch.py's, which
tests/test_routematch_config.py holds at the smoke and candidate
scales); the at-the-cap case of `rbac1k` and `mixer10k` besides.

The configurations' files are the benchmark's, loaded by path as
benchmark/run.py loads them.
"""
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.compiler.layout import WIDE_STR_LEN
from istio_tpu.runtime import monitor
from istio_tpu.runtime.batcher import pad_to_bucket

from test_routematch_config import CONFIGS, SEED, _load, _server

SMALL = {"rules": 60, "services": 6, "buckets": [16, 64], "max_batch": 64}
ROWS, BUCKET, WIRE = 192, 64, 48
NARROW = 128                  # layout.DEFAULT_MAX_STR_LEN: the narrow cap
LENGTHS = (127, 128, 129, 512, 513,
           WIDE_STR_LEN - 1, WIDE_STR_LEN, WIDE_STR_LEN + 1)
BOUNDARIES = (NARROW, 512, WIDE_STR_LEN)


def _block(sizes, config, family: int) -> int:
    """The first denying block of the family."""
    return next(r for r in range(sizes["rules"])
                if config.family_of(sizes, r) == family and r % 3 == 0)


def _request(sizes, config, r: int, path: str, **headers) -> dict:
    return {"destination.service":
            config.host_of(sizes, r % sizes["services"]),
            "source.namespace": "ns1", "request.method": "GET",
            "request.path": path,
            "request.headers": {":authority": "x", **headers}}


def _line(r: int, length: int, hit: bool) -> str:
    """A request line of exactly `length` bytes that block r (family
    3) full-matches, or misses by one byte behind `/r<r>`."""
    head = f"/v{r % 3}/t/acme-7/r{r}" + ("" if hit else "x") + "/"
    return head + "a" * (length - len(head))


def _cookie(group: int, boundary: int, where: str) -> str:
    """`user=group<n>` wholly before the boundary byte, or wholly
    behind it, in a cookie 200 bytes longer than the boundary."""
    user, fill = f"user=group{group}", "sid=" + "b" * (boundary + 180)
    return f"{user};{fill}" if where == "before" else f"{fill};{user}"


def boundary_cases(sizes, config) -> list[tuple[str, dict, int]]:
    """(name, request, status the snapshot gives)."""
    uri, cookie = _block(sizes, config, 3), _block(sizes, config, 2)
    cases = []
    for length in LENGTHS:
        for hit in (True, False):
            cases.append((
                f"path-{length}-{'hit' if hit else 'miss'}",
                _request(sizes, config, uri, _line(uri, length, hit),
                         **{"x-version": f"v{uri % 5}"}),
                config.DENIED if hit else 0))
    for boundary in BOUNDARIES:
        for where in ("before", "after"):
            cases.append((
                f"cookie-{boundary}-user-{where}",
                _request(sizes, config, cookie, "/static/assets/1",
                         cookie=_cookie(cookie, boundary, where)),
                config.DENIED))
        cases.append((
            f"cookie-{boundary}-miss",
            _request(sizes, config, cookie, "/static/assets/1",
                     cookie=_cookie(cookie + sizes["rules"], boundary,
                                    "after")), 0))
    return cases


SIZES, CONFIG = _load("routelong10k", **SMALL)
CASES = boundary_cases(SIZES, CONFIG)


def _wire_bags(srv, requests):
    return [srv.preprocess(LazyWireBag(
        bag_to_compressed(d).SerializeToString())) for d in requests]


def _pump(srv, requests, bucket: int) -> list[int]:
    """Status codes through the pump's own entry, a padded bucket at a
    time."""
    bags, got = _wire_bags(srv, requests), []
    for lo in range(0, len(bags), bucket):
        chunk = bags[lo:lo + bucket]
        got += srv.check_batch_preprocessed(
            pad_to_bucket(chunk, (bucket,)))[:len(chunk)]
    return [int(r.status_code) for r in got]


@pytest.fixture(scope="module")
def served():
    """ROWS seeded requests and the boundary cases through the pump's
    own entry, the first WIRE of the seeded ones and every case
    through the socket too."""
    requests = CONFIG.make_requests(SIZES, ROWS, SEED) \
        + [d for _, d, _ in CASES]
    srv = _server(SIZES, CONFIG)
    native = NativeMixerServer(srv, max_batch=SIZES["max_batch"])
    client = MixerClient(f"127.0.0.1:{native.start()}",
                         enable_check_cache=False)
    try:
        plan = srv.controller.dispatcher.fused
        assert plan is not None and plan.native is not None
        split0 = monitor.length_split_counters()
        programs0 = monitor.device_program_counters()["check"]
        got = _pump(srv, requests, BUCKET)
        split = monitor.length_split_counters()
        programs = monitor.device_program_counters()["check"] - programs0
        on_wire = requests[:WIRE] + requests[ROWS:]
        with ThreadPoolExecutor(max_workers=16) as pool:
            replies = list(pool.map(client.check, on_wire))
        oracle = srv.controller.dispatcher.check_host_oracle(
            [bag_from_mapping(d) for d in requests])
        yield {
            "srv": srv, "plan": plan, "requests": requests, "got": got,
            "wire": dict(zip(
                list(range(WIRE)) + list(range(ROWS, len(requests))),
                [int(r.precondition.status.code) for r in replies])),
            "oracle": [int(r.status_code) for r in oracle],
            "expected": [CONFIG.reference(SIZES)(d) for d in requests],
            "rows_by_width": {
                w: n - split0["rows_by_width"].get(w, 0)
                for w, n in split["rows_by_width"].items()},
            "undecided": sum(split["undecided"].values())
            - sum(split0["undecided"].values()),
            "programs": programs}
    finally:
        client.close()
        native.stop()
        srv.close()


def test_the_store_is_routematch10ks_byte_for_byte():
    sizes, routematch = _load("routematch10k", **SMALL)
    assert CONFIG.make_store is CONFIG.base.make_store     # not a copy
    assert CONFIG.make_store(SIZES).list() == \
        routematch.make_store(sizes).list()


def test_system_reference_and_oracle_agree_row_for_row(served):
    n = ROWS
    assert served["got"][:n] == served["expected"][:n] == \
        served["oracle"][:n]
    assert len(set(served["expected"][:n])) > 1
    assert [served["wire"][i] for i in range(WIRE)] == \
        served["expected"][:WIRE]


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[name for name, _, _ in CASES])
def test_boundary_length_is_answered_as_the_snapshot_does(served, case):
    name, request, status = CASES[case]
    row = ROWS + case
    assert served["requests"][row] is request
    assert served["expected"][row] == status, name
    assert served["got"][row] == served["wire"][row] == \
        served["oracle"][row] == status, name


def test_rows_past_the_cap_rode_the_wide_plane_and_the_host_decided_few(
        served):
    requests = served["requests"]
    longest = [max(len(d["request.path"]),
                   len(d["request.headers"].get("cookie", "")))
               for d in requests]
    by_width = served["rows_by_width"]
    assert by_width.get(str(WIDE_STR_LEN), 0) == \
        sum(n >= NARROW for n in longest)
    assert sum(by_width.values()) == len(requests)
    # the host decides a row only where a subject fills the wide plane
    # under a rule that reads it: never more rows than reach it
    reach = sum(n >= WIDE_STR_LEN for n in longest)
    assert 0 < served["undecided"] <= reach
    # a batch of the seeded traffic: its short rows' program and one
    # launch of the wide program (64 rows a launch at this size)
    assert served["programs"] > len(requests) // BUCKET


@pytest.mark.parametrize("mix", ["all-short", "all-long", "mixed"])
def test_a_batchs_responses_come_back_in_the_rows_order(served, mix):
    """One batch whose rows alternate between a denying block's line
    and a miss, short, long or both: the statuses come back in the
    rows' own order, whichever program served each."""
    uri = _block(SIZES, CONFIG, 3)
    lengths = {"all-short": [40, 60], "all-long": [300, 1500],
               "mixed": [40, 1500, 60, 300, 2100]}[mix]
    requests, want = [], []
    for i in range(48):
        hit = i % 3 != 1
        requests.append(_request(
            SIZES, CONFIG, uri, _line(uri, lengths[i % len(lengths)], hit),
            **{"x-version": f"v{uri % 5}"}))
        want.append(CONFIG.DENIED if hit else 0)
    programs0 = monitor.device_program_counters()["check"]
    split0 = monitor.length_split_counters()["rows_by_width"]
    got = _pump(served["srv"], requests, BUCKET)
    assert got == want
    programs = monitor.device_program_counters()["check"] - programs0
    widths = {w for w, n in
              monitor.length_split_counters()["rows_by_width"].items()
              if n > split0.get(w, 0)}
    wide = str(WIDE_STR_LEN)
    if mix == "all-short":
        assert programs == 1 and wide not in widths
    elif mix == "all-long":
        assert programs == 1 and widths == {wide}
    else:
        assert programs == 2 and wide in widths and len(widths) == 2


def test_the_parts_byte_planes_are_staged_in_one_put(served, monkeypatch):
    """On an accelerator the dispatcher stages a batch's byte plane
    ahead of the launch (overlap_h2d, off on the CPU by default): a
    split batch's parts go in ONE device_put, and are served the same."""
    import jax

    d = served["srv"].controller.dispatcher
    puts = []
    put = jax.device_put
    monkeypatch.setattr(d, "overlap_h2d", True)
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: puts.append(x) or put(x, *a, **k))
    requests = [d_ for _, d_, _ in CASES]
    assert _pump(served["srv"], requests, BUCKET) == \
        [status for _, _, status in CASES]
    assert len(puts) == 1 and isinstance(puts[0], list)
    assert sorted(p.shape[2] for p in puts[0]) == [NARROW, WIDE_STR_LEN]


def test_the_narrow_path_is_the_parents(served):
    """The six step shapes every batch had are still warmed, beside
    ONE more; a batch without a long row is never cut."""
    plan = served["plan"]
    full = json.loads((CONFIGS / "routelong10k.json").read_text())
    pairs = plan.all_warm_shapes(tuple(full["buckets"]))
    assert pairs[:6] == [(b, t) for b in (64, 256, 2048)
                         for t in (32, 128)]
    assert pairs[6:] == [(256, WIDE_STR_LEN)]
    assert plan.str_tiers == (32, NARROW)
    d = served["srv"].controller.dispatcher
    bags = pad_to_bucket(_wire_bags(
        served["srv"], CONFIG.base.make_requests(SIZES, 40, 3)), (BUCKET,))
    batch, ns_ids = d._tensorize_for_device(bags)
    assert batch.wide.count == 0
    assert d._split_by_length(plan, batch, ns_ids, 40) is None


def test_the_python_tensorizers_batches_are_split_the_same(served):
    """Bags without wire bytes take the python tensorizer: its WideRows
    feed the same split, and the cases come out as the snapshot has
    them."""
    got = served["srv"].controller.dispatcher.check(
        [bag_from_mapping(d) for _, d, _ in CASES])
    assert [int(r.status_code) for r in got] == \
        [status for _, _, status in CASES]


def test_both_tensorizers_keep_the_same_long_rows(served):
    """The python tensorizer's WideRows are the shim's."""
    plan, srv = served["plan"], served["srv"]
    requests = served["requests"][ROWS:ROWS + 24]
    native = plan.native.tensorize_wire(
        [b.wire for b in _wire_bags(srv, requests)])
    python = srv.controller.dispatcher.snapshot.tensorizer.tensorize(
        [bag_from_mapping(d) for d in requests])
    assert native.wide.count == python.wide.count > 0
    k = native.wide.count
    np.testing.assert_array_equal(native.wide.row, python.wide.row)
    np.testing.assert_array_equal(native.wide.lens[:k], python.wide.lens)
    np.testing.assert_array_equal(native.wide.data[:k], python.wide.data)
    np.testing.assert_array_equal(native.str_lens, python.str_lens)


@pytest.mark.parametrize("scale", ["full", "smoke"])
def test_the_generators_length_shares(scale):
    """ISSUE 35: 53 % of rows carry a subject past 128 bytes, 16 % one
    past 512, 0.5 % one past the wide plane; the classes' own shares
    and ranges; header shares as routematch10k's."""
    sizes = json.loads((CONFIGS / "routelong10k.json").read_text())
    if scale == "smoke":
        sizes.update(sizes["smoke"])
    n = 16384
    requests = CONFIG.make_requests(sizes, n, SEED)
    assert len({json.dumps(d, sort_keys=True) for d in requests}) == n
    paths = [len(d["request.path"]) for d in requests]
    cookies = [len(d["request.headers"]["cookie"]) for d in requests
               if "cookie" in d["request.headers"]]
    longest = [max(len(d["request.path"]),
                   len(d["request.headers"].get("cookie", "")))
               for d in requests]
    for past, share, slack in ((NARROW, 0.53, 0.02), (512, 0.16, 0.015),
                               (WIDE_STR_LEN, 0.005, 0.002)):
        assert abs(sum(x > past for x in longest) / n - share) < slack
    shares = sizes["assumed"]["length_shares"]
    for lens, classes in ((cookies, shares["cookie"]),
                          (paths, shares["request.path"])):
        assert all(any(lo <= x <= hi for lo, hi, _ in classes.values())
                   for x in lens)
        for name, (lo, hi, share) in classes.items():
            got = sum(lo <= x <= hi for x in lens) / len(lens)
            assert abs(got - share) < 0.02, (name, got)
    assert abs(len(cookies) / n - 0.75) < 0.02
    versions = sum("x-version" in d["request.headers"] for d in requests)
    assert abs(versions / n - 0.5) < 0.02
    # user=group<n> lies past the cap about as often as before it
    at = [d["request.headers"]["cookie"].index("user=") for d in requests
          if len(d["request.headers"].get("cookie", "")) > 512]
    assert 0.35 < sum(x >= 512 for x in at) / len(at) < 0.6


def _at_the_cap_rbac1k(sizes, config):
    """A request line past the cap that role 6's `*/6.html` allows
    (suffix glob: undecidable on a truncated row), and one it does
    not."""
    base = {"source.user": "user6",
            "source.labels": {"group": "group999", "version": "v0"},
            "destination.namespace": "default",
            "destination.service": "svc6.default.svc.cluster.local",
            "request.method": "PUT",
            "request.headers": {"version": "v0"}}
    return [({**base, "request.path": "/static/" + "a" * 130 + "/6.html"},
             0),
            ({**base, "request.path": "/static/" + "a" * 130 + "/7.html"},
             config.DENIED)]


def _at_the_cap_mixer10k(sizes, config):
    """A request line whose match of rule 9's path regex lies past the
    cap (unanchored: a miss on a truncated row is undecidable), and one
    of the same length without it."""
    base = {**config.make_requests(sizes, 1, 0)[0],
            "destination.service": "svc9.ns9.svc.cluster.local",
            "source.namespace": "ns1"}
    return [({**base, "request.path": "/" + "a" * 130 + "/products/12/v1"},
             config.DENIED),
            ({**base, "request.path": "/" + "a" * 130 + "/products/x/v1"},
             0)]


@pytest.mark.parametrize("name, cases", [
    ("rbac1k", _at_the_cap_rbac1k), ("mixer10k", _at_the_cap_mixer10k)])
def test_the_row_at_the_cap_in_the_other_deployments(name, cases):
    """The bug was not routematch's alone: a subject at the cap under
    rbac1k's `*/<n>.html` and under mixer10k's path regex is answered
    as the snapshot answers it."""
    sizes, config = _load(name)
    rows = cases(sizes, config)
    requests = config.make_requests(sizes, 30, SEED) + [d for d, _ in rows]
    srv = _server(sizes, config)
    try:
        undecided0 = sum(
            monitor.length_split_counters()["undecided"].values())
        got = _pump(srv, requests, 64)
        oracle = [int(r.status_code) for r in
                  srv.controller.dispatcher.check_host_oracle(
                      [bag_from_mapping(d) for d in requests])]
        assert sum(monitor.length_split_counters()["undecided"]
                   .values()) == undecided0      # the device decided
    finally:
        srv.close()
    expected = [config.reference(sizes)(d) for d in requests]
    assert expected[30:] == [status for _, status in rows]
    assert got == expected == oracle
