"""fold's referenced / presence signature dedup (runtime/fused.py
dedup_bit_rows, used by Dispatcher._fold_respond): exact, on packed
integer keys. The reference is the dedup it replaced,
np.unique(signature, axis=0) over the unpacked byte matrix."""
import numpy as np
import pytest

from istio_tpu.api.wire import referenced_to_proto
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs
from istio_tpu.runtime.fused import dedup_bit_rows

ROWS = {
    "no_rows": lambda rng, width: np.zeros((0, width), bool),
    "one_row": lambda rng, width: rng.random((1, width)) < 0.5,
    "all_equal": lambda rng, width: np.repeat(
        rng.random((1, width)) < 0.5, 97, axis=0),
    # row i carries i in its low bits (and differs nowhere else), so
    # every row is distinct as far as the width allows
    "all_distinct": lambda rng, width: (
        (np.arange(min(97, 2 ** min(width, 7)))[:, None]
         >> np.arange(width)[None, :]) & 1).astype(bool),
    "random": lambda rng, width: (rng.random((5, width)) < 0.5)[
        rng.integers(0, 5, 300)],
}


def _split(bits: np.ndarray, width: int) -> list[np.ndarray]:
    """The [B, width] rows as four planes side by side, one of them
    empty: fold's (referenced, present, map_present, overlay)."""
    cuts = [0, width // 3, width // 3, (2 * width) // 3, width]
    return [bits[:, a:b] for a, b in zip(cuts, cuts[1:])]


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("width", [0, 1, 7, 8, 60, 64, 65, 200])
def test_packed_key_dedup_is_the_byte_matrix_dedup(width, rows):
    rng = np.random.default_rng(width * 31 + len(rows))
    bits = ROWS[rows](rng, width)
    planes = _split(bits, width)
    assert [p.shape[1] for p in planes].count(0) >= 1
    first, inverse = dedup_bit_rows(planes)
    want_rows, want_inverse = np.unique(
        bits.astype(np.uint8), axis=0, return_inverse=True)
    want_inverse = want_inverse.reshape(-1)
    assert len(first) == len(want_rows)
    assert inverse.shape == (len(bits),)
    # the same partition of the rows, up to the classes' labels
    pairs = set(zip(inverse.tolist(), want_inverse.tolist()))
    assert len(pairs) == len(first) == len({a for a, _ in pairs}) \
        == len({b for _, b in pairs})
    # each class's representative carries the class's bits, plane by
    # plane, and is one of its rows
    assert inverse[first].tolist() == list(range(len(first)))
    for plane in planes:
        assert np.array_equal(plane[first][inverse], plane)


def _store() -> MemStore:
    s = MemStore()
    s.set(("handler", "istio-system", "denyall"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("handler", "istio-system", "uablacklist"), {
        "adapter": "list",
        "params": {"overrides": ["badbot"], "blacklist": True}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("instance", "istio-system", "ua"), {
        "template": "listentry",
        "params": {"value": 'request.headers["user-agent"]'}})
    s.set(("instance", "istio-system", "srcns"), {
        "template": "listentry", "params": {"value": "source.namespace"}})
    s.set(("rule", "istio-system", "r0-admin"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "denyall", "instances": ["nothing"]}]})
    s.set(("rule", "istio-system", "r1-ua"), {
        "match": "connection.mtls",
        "actions": [{"handler": "uablacklist", "instances": ["ua"]}]})
    # host-fallback predicates (dynamic map key): overlay columns whose
    # activity adds their instance's attributes on the host; r2 mesh-
    # wide, r3 visible in namespace prod only
    s.set(("rule", "istio-system", "r2-dynkey"), {
        "match": 'request.headers[request.method] == "x"',
        "actions": [{"handler": "uablacklist", "instances": ["ua"]}]})
    s.set(("rule", "prod", "r3-prodkey"), {
        "match": 'request.headers[request.scheme] == "y"',
        "actions": [{"handler": "uablacklist.istio-system",
                     "instances": ["srcns.istio-system"]}]})
    return s


CASES = {
    "plain": {"request.path": "/a"},
    # differs from `plain` in a present attribute
    "no_path": {},
    "mtls": {"request.path": "/a", "connection.mtls": True},
    # differ in a map's presence, then in one key's
    "headers_no_key": {"request.path": "/a",
                       "request.headers": {"accept": "*"}},
    "headers_key": {"request.path": "/a",
                    "request.headers": {"user-agent": "chrome"}},
    # differs from `headers_get_miss` in r2's activity alone
    "headers_get_hit": {"request.path": "/a", "request.method": "GET",
                        "request.headers": {"GET": "x"}},
    "headers_get_miss": {"request.path": "/a", "request.method": "GET",
                         "request.headers": {"GET": "z"}},
    # the same attributes in two namespaces: r3 is active in prod and
    # not visible in dev
    "prod_hit": {"destination.service": "api.prod.svc.cluster.local",
                 "request.scheme": "http",
                 "request.headers": {"http": "y"}},
    "dev_hit": {"destination.service": "api.dev.svc.cluster.local",
                "request.scheme": "http",
                "request.headers": {"http": "y"}},
}


def test_fold_shares_one_object_per_signature_and_matches_the_oracle():
    srv = RuntimeServer(_store(), ServerArgs(batch_window_s=0.001,
                                             fused=True))
    try:
        d = srv.controller.dispatcher
        plan = d.fused
        assert plan is not None and plan.n_ref_words
        assert len(plan.unmapped_instance_attrs) == 2   # r2, r3
        names = [n for n in sorted(CASES) for _ in range(3)]
        bags = [srv.preprocess(bag_from_mapping(CASES[n])) for n in names]
        got = d._check_fused(bags)
        want = d.check_host_oracle(bags)
        assert len(got) == len(want) == len(bags)
        for name, bag, r, o in zip(names, bags, got, want):
            assert r.status_code == o.status_code, name
            assert r.referenced == o.referenced, name
            assert set(r.referenced_presence) <= set(r.referenced), name
            # the fused presence bits say what the bag itself says
            # (the oracle's response carries none: the wire layer
            # reads the bag)
            assert referenced_to_proto(
                r.referenced, bag, r.referenced_presence) == \
                referenced_to_proto(o.referenced, bag, None), name
        by_name: dict = {}
        for name, r in zip(names, got):
            by_name.setdefault(name, []).append(r)
        # rows of one signature share one object ...
        for name, rs in by_name.items():
            assert all(r.referenced is rs[0].referenced and
                       r.referenced_presence is rs[0].referenced_presence
                       for r in rs), name
        # ... rows of different signatures do not, and every case
        # above is a signature of its own
        firsts = [rs[0] for _, rs in sorted(by_name.items())]
        assert len({id(r.referenced_presence) for r in firsts}) == \
            len(CASES)
        # overlay activity, under the namespace mask: an active host-
        # fallback rule adds its instance's attributes (r2 mesh-wide,
        # r3 only where it is visible)
        ua = ("request.headers", "user-agent")
        assert ua in by_name["headers_get_hit"][0].referenced
        assert ua not in by_name["headers_get_miss"][0].referenced
        assert "source.namespace" in by_name["prod_hit"][0].referenced
        assert "source.namespace" not in by_name["dev_hit"][0].referenced
    finally:
        srv.close()
