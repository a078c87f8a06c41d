"""Zero-copy decode path (ISSUE 13): the C++ wire decoder writes
straight into persistent, page-aligned slot-tensor staging buffers —
these tests pin (a) the staging-ring lifecycle contract (rotation,
zero-on-reuse, LRU cap, alignment, bool-view aliasing) with NO
toolchain dependency, and (b) byte-exact parity of the staged decode
vs the Python `compiler/layout.Tensorizer` fallback across seeded
manifests including long strings that overflow a byte tier, map
attributes, and absent-attribute defaults (toolchain-gated like
test_native_shim — the fallback stays the conformance oracle)."""
import datetime

import numpy as np
import pytest

from istio_tpu.attribute.types import ValueType as V
from istio_tpu.compiler.layout import (InternTable, Tensorizer,
                                       build_layout)
from istio_tpu.native.tensorizer import NativeTensorizer

try:
    from istio_tpu.native import ensure_built
    ensure_built()
    HAVE_NATIVE = True
except Exception:      # toolchain missing → parity half skips
    HAVE_NATIVE = False

MANIFEST = {
    "destination.service": V.STRING, "source.namespace": V.STRING,
    "request.size": V.INT64, "request.path": V.STRING,
    "request.headers": V.STRING_MAP, "request.time": V.TIMESTAMP,
    "score": V.DOUBLE,
}


def _layout(max_str_len=32):
    return build_layout(
        MANIFEST,
        derived_keys=[("request.headers", "cookie"),
                      ("request.headers", ":authority")],
        byte_sources=["request.path", ("request.headers", "cookie")],
        max_str_len=max_str_len)


def _ring_only(layout, depth=4) -> NativeTensorizer:
    """A NativeTensorizer with ONLY the staging machinery live (no
    C++ shim handle) — the ring contract is pure python and must be
    testable in environments without the protoc toolchain."""
    t = NativeTensorizer.__new__(NativeTensorizer)
    t.layout = layout
    t.staging_depth = depth
    t._staging = {}
    t._staged_decodes = 0
    t._h = None              # __del__ guard
    return t


# ---------------------------------------------------------------------------
# staging-ring lifecycle (no toolchain needed)
# ---------------------------------------------------------------------------


def test_aligned_zeros_page_aligned_and_shaped():
    for shape, dtype in (((7, 3), np.int32), ((5, 2, 32), np.uint8),
                         ((4, 0), np.int32)):
        a = NativeTensorizer._aligned_zeros(shape, dtype)
        assert a.shape == shape and a.dtype == dtype
        assert not a.any()
        if a.nbytes:
            assert a.ctypes.data % 4096 == 0, "staging must be " \
                "page-aligned (DMA-mappable without a bounce copy)"


def test_ring_rotation_and_reuse_bound():
    """Consecutive decodes of one shape get DISTINCT buffer slots up
    to staging_depth; slot K is reused (and zeroed) exactly at decode
    K+depth — the reuse bound the serving pipeline relies on."""
    t = _ring_only(_layout(), depth=3)
    sets = [t._buffers_for(8) for _ in range(3)]
    ptrs = [s["ids"].ctypes.data for s in sets]
    assert len(set(ptrs)) == 3, "slots within the depth must not alias"
    # dirty slot 0, then rotate back to it: must come back zeroed
    sets[0]["ids"][...] = 7
    sets[0]["str_bytes"][...] = 9
    s4 = t._buffers_for(8)
    assert s4["ids"].ctypes.data == ptrs[0], "round-robin reuse"
    assert not s4["ids"].any() and not s4["str_bytes"].any(), \
        "reused slot must be zeroed before the shim writes"
    assert t.staging_stats()["staged_decodes"] == 4
    assert t.staging_stats()["shapes"] == {8: 3}


def test_ring_lru_cap_evicts_coldest_shape():
    """The shape→ring map is LRU-bounded: a new shape past
    _STAGING_SHAPES evicts the least-recently-used ring (so warmup's
    arbitrary sizes can never permanently pin the rings away from
    the hot bucket shapes), a re-used shape moves to the MRU end,
    and an evicted shape's old buffers are NOT reused when it comes
    back — in-flight batches keep them alive untouched."""
    cap = NativeTensorizer._STAGING_SHAPES
    t = _ring_only(_layout(), depth=2)
    first = t._buffers_for(1)           # shape 1 = the LRU candidate
    for n in range(2, cap + 1):
        t._buffers_for(n)
    t._buffers_for(2)                   # touch: 2 becomes MRU
    t._buffers_for(99)                  # over the cap: evicts shape 1
    shapes = set(t.staging_stats()["shapes"])
    assert 1 not in shapes and 99 in shapes and 2 in shapes
    # shape 1 re-admitted later: fresh buffers, never the old slot
    # (which an in-flight batch may still be reading)
    first["ids"][...] = 7
    again = t._buffers_for(1)
    assert again["ids"].ctypes.data != first["ids"].ctypes.data
    assert not again["ids"].any()
    assert (first["ids"] == 7).all(), \
        "eviction must never clobber a live buffer"


def test_bool_views_alias_staging_bytes():
    """The presence planes returned to the engine are dtype VIEWS of
    the staging bytes (zero copies), shaped like the python
    tensorizer's bool planes."""
    t = _ring_only(_layout())
    s = t._buffers_for(4)
    v = s["present_u8"].view(bool)
    assert v.dtype == bool and v.shape == s["present_u8"].shape
    s["present_u8"][1, 0] = 1
    assert bool(v[1, 0]), "view must alias the staging buffer"


# ---------------------------------------------------------------------------
# byte-exact parity vs the python tensorizer (toolchain-gated)
# ---------------------------------------------------------------------------

pytestmark_parity = pytest.mark.skipif(
    not HAVE_NATIVE, reason="native shim toolchain unavailable")


def _worlds(seed: int, n: int, max_str_len: int) -> list[dict]:
    """Seeded request dicts stressing the decode corners the parity
    gate owes: long strings OVERFLOWING the byte tier (truncation
    contract), map attributes (derived + byte pair slots), and
    absent-attribute defaults (rows missing most of the manifest)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        d = {}
        r = rng.random()
        if r < 0.25:     # absent-attribute rows: only one attr set
            d["request.size"] = int(rng.integers(0, 1 << 30))
        else:
            d["destination.service"] = \
                f"svc{rng.integers(0, 5)}.ns{i % 3}.svc.cluster.local"
            if rng.random() < 0.7:
                # every third long path OVERFLOWS max_str_len — the
                # slice/truncation parity leg
                length = int(rng.integers(1, max_str_len * 3))
                d["request.path"] = "/" + "x" * length
            if rng.random() < 0.6:
                d["request.headers"] = {
                    "cookie": "c" * int(rng.integers(1,
                                                     max_str_len * 2)),
                    ":authority": f"web{i % 4}"}
            if rng.random() < 0.4:
                d["score"] = float(np.round(rng.random(), 6))
            if rng.random() < 0.3:
                d["request.time"] = datetime.datetime(
                    2018, 3, int(rng.integers(1, 28)), 6, 0, 1,
                    tzinfo=datetime.timezone.utc)
        out.append(d)
    return out


@pytestmark_parity
@pytest.mark.parametrize("seed,max_str_len", [(0, 32), (1, 32),
                                              (2, 16), (3, 64)])
def test_staged_decode_parity_vs_python_fallback(seed, max_str_len):
    """Property: for seeded worlds over seeded layouts, the staged
    zero-copy decode is BYTE-EXACT vs the python tensorizer on every
    plane — including repeat decodes through the same ring slots
    (batch k and batch k+depth land in the same buffers)."""
    from istio_tpu.api.wire import bag_to_compressed
    from istio_tpu.attribute.bag import bag_from_mapping

    layout = _layout(max_str_len=max_str_len)
    interner = InternTable()
    native = NativeTensorizer(layout, interner, staging_depth=3)
    oracle = Tensorizer(layout, interner)
    # MORE batches than the ring depth: every slot gets dirtied by an
    # earlier batch and must decode later batches byte-identically
    for k in range(5):
        dicts = _worlds(seed * 10 + k, 24, max_str_len)
        records = [bag_to_compressed(d).SerializeToString()
                   for d in dicts]
        got = native.tensorize_wire(records)
        want = oracle.tensorize([bag_from_mapping(d) for d in dicts])
        np.testing.assert_array_equal(np.asarray(got.present),
                                      np.asarray(want.present),
                                      err_msg=f"batch {k} present")
        np.testing.assert_array_equal(np.asarray(got.map_present),
                                      np.asarray(want.map_present),
                                      err_msg=f"batch {k} map_present")
        np.testing.assert_array_equal(np.asarray(got.str_bytes),
                                      np.asarray(want.str_bytes),
                                      err_msg=f"batch {k} str_bytes")
        np.testing.assert_array_equal(np.asarray(got.str_lens),
                                      np.asarray(want.str_lens),
                                      err_msg=f"batch {k} str_lens")
        # ids: constants share exact non-negative ids; ephemeral
        # (negative) ids must DECODE to the same value
        gi, oi = np.asarray(got.ids), np.asarray(want.ids)
        gp = np.asarray(got.present)
        from istio_tpu.compiler.layout import _normalize
        for r, c in zip(*np.nonzero(gp)):
            a, b = int(gi[r, c]), int(oi[r, c])
            if a >= 0 or b >= 0:
                assert a == b, (k, r, c)
            else:
                assert _normalize(got.value_of(a, interner)) == \
                    _normalize(want.value_of(b, interner)), (k, r, c)
    stats = native.staging_stats()
    assert stats["staged_decodes"] == 5
    assert stats["shapes"] == {24: 3}, "ring must have rotated"


@pytestmark_parity
def test_staged_batches_do_not_alias_within_depth():
    """Two in-flight batches (the pipeline bound) must never share
    buffers — batch N's planes stay intact while batch N+1 decodes."""
    from istio_tpu.api.wire import bag_to_compressed

    layout = _layout()
    native = NativeTensorizer(layout, InternTable(), staging_depth=4)
    rec_a = [bag_to_compressed(
        {"destination.service": "a.ns1.svc"}).SerializeToString()] * 4
    rec_b = [bag_to_compressed(
        {"request.size": 7}).SerializeToString()] * 4
    ba = native.tensorize_wire(rec_a)
    snapshot = np.asarray(ba.present).copy()
    bb = native.tensorize_wire(rec_b)
    assert np.asarray(ba.present).ctypes.data != \
        np.asarray(bb.present).ctypes.data
    np.testing.assert_array_equal(np.asarray(ba.present), snapshot,
                                  err_msg="batch A mutated by batch B")


# ---------------------------------------------------------------------------
# staged h2d on the SERVED path (Dispatcher._stage_h2d). The server turns
# it on by itself only where the backend is not the CPU, so nothing in
# tier-1 ran it before chip_smoke.py did on the chip; forced on here.
# ---------------------------------------------------------------------------

def _staged_server(**kw):
    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.testing import workloads

    return RuntimeServer(workloads.make_store(96), ServerArgs(
        default_manifest=workloads.MESH_MANIFEST, buckets=(32,),
        max_batch=32, initial_prewarm=False, overlap_h2d=True, **kw))


def _wire_bags(srv, dicts):
    from istio_tpu.api.wire import LazyWireBag, bag_to_compressed

    return [srv.preprocess(LazyWireBag(
        bag_to_compressed(d).SerializeToString())) for d in dicts]


@pytest.mark.skipif(not HAVE_NATIVE, reason="native toolchain missing")
def test_served_parity_with_staged_h2d_forced_on(monkeypatch):
    """Exact oracle parity through Dispatcher._check_fused with the
    overlapped h2d ON: every string plane reaches the step as a
    committed device array staged from the zero-copy ring, across more
    batches than the ring is deep (slot reuse under the async copy)."""
    import jax

    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.runtime.dispatcher import Dispatcher
    from istio_tpu.testing import workloads

    staged = []
    real = Dispatcher._stage_h2d

    def spy(plan, batch):
        out = real(plan, batch)
        staged.append(out.str_bytes)
        return out

    monkeypatch.setattr(Dispatcher, "_stage_h2d", staticmethod(spy))
    srv = _staged_server()
    try:
        assert srv._overlap_h2d is True
        disp = srv.controller.dispatcher
        depth = disp.fused.native.staging_depth
        for seed in range(depth + 2):
            dicts = workloads.make_request_dicts(32, seed=100 + seed)
            got = [r.status_code for r in
                   srv.check_batch_preprocessed(_wire_bags(srv, dicts))]
            want = [r.status_code for r in disp.check_host_oracle(
                [bag_from_mapping(d) for d in dicts])]
            assert got == want, seed
        assert len(staged) == depth + 2
        assert all(isinstance(s, jax.Array) for s in staged)
    finally:
        srv.close()


@pytest.mark.skipif(not HAVE_NATIVE, reason="native toolchain missing")
def test_staging_error_is_counted_not_swallowed(monkeypatch):
    """A failing staged h2d is a device-path failure: it reaches
    ResilientChecker (retry, then the oracle answers) and shows in the
    resilience counters — it no longer degrades in silence."""
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.runtime import monitor
    from istio_tpu.runtime.dispatcher import Dispatcher
    from istio_tpu.testing import workloads

    def boom(plan, batch):
        raise RuntimeError("injected staging failure")

    srv = _staged_server()
    try:
        disp = srv.controller.dispatcher
        dicts = workloads.make_request_dicts(32, seed=7)
        want = [r.status_code for r in disp.check_host_oracle(
            [bag_from_mapping(d) for d in dicts])]
        monkeypatch.setattr(Dispatcher, "_stage_h2d", staticmethod(boom))
        base = monitor.resilience_counters()
        got = [r.status_code for r in
               srv.check_batch_preprocessed(_wire_bags(srv, dicts))]
        now = monitor.resilience_counters()
        assert got == want          # served by the oracle fallback
        assert now["device_retries_total"] == \
            base["device_retries_total"] + 1
        assert now["fallback_total"] == \
            base["fallback_total"] + len(dicts)   # counted per row
    finally:
        srv.close()
