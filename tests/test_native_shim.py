"""Native C++ wire→tensor shim conformance: byte-for-byte equality with
the Python Tensorizer on randomized wire batches and on the five
benchmark configurations' own traffic, a table of odd records (what a
protobuf parser accepts, skips and rejects), intern-table mirror
consistency across calls, flushes and threads, and a throughput sanity
check."""
import datetime
import struct
import sys
import threading
import time

import numpy as np
import pytest

from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.wire import bag_to_compressed
from istio_tpu.attribute.bag import bag_from_mapping
from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST
from istio_tpu.attribute.types import ValueType as V
from istio_tpu.compiler.layout import (WIDE_STR_LEN, InternTable, Tensorizer,
                                       _normalize, build_layout,
                                       canonical_bytes, stable_hash31)
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.testing import workloads

try:
    from istio_tpu.native import NativeBuildError, NativeTensorizer, \
        ensure_built
    ensure_built()
    HAVE_NATIVE = True
except Exception as exc:      # toolchain missing → skip, not fail
    HAVE_NATIVE = False
    SKIP_REASON = str(exc)

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="native shim unavailable")

MANIFEST = {
    "destination.service": V.STRING, "source.namespace": V.STRING,
    "source.ip": V.IP_ADDRESS, "request.size": V.INT64,
    "request.time": V.TIMESTAMP, "response.duration": V.DURATION,
    "connection.mtls": V.BOOL, "request.path": V.STRING,
    "request.headers": V.STRING_MAP, "score": V.DOUBLE,
}


def _world(seed=0, n=64):
    rng = np.random.default_rng(seed)
    dicts = []
    for i in range(n):
        d = {
            "destination.service":
                f"svc{rng.integers(0, 9)}.ns{i % 5}.svc.cluster.local",
            "request.size": int(rng.integers(0, 1 << 40)),
            "connection.mtls": bool(rng.random() < 0.5),
        }
        if rng.random() < 0.8:
            d["source.namespace"] = f"ns{rng.integers(0, 6)}"
        if rng.random() < 0.6:
            d["request.path"] = f"/api/v{i % 3}/items/{i}"
        if rng.random() < 0.5:
            d["request.headers"] = {"cookie": f"u={i % 7}",
                                    ":authority": "web"}
        if rng.random() < 0.5:
            d["source.ip"] = b"\x00" * 10 + b"\xff\xff" + \
                bytes(rng.integers(0, 255, 4, dtype=np.uint8).tolist())
        if rng.random() < 0.4:
            d["request.time"] = datetime.datetime(
                2018, 1, int(rng.integers(1, 28)), 12, 0, 5,
                tzinfo=datetime.timezone.utc)
        if rng.random() < 0.4:
            d["response.duration"] = datetime.timedelta(
                milliseconds=int(rng.integers(1, 5000)))
        if rng.random() < 0.3:
            d["score"] = float(np.round(rng.random(), 6))
        dicts.append(d)
    return dicts


def _rig():
    finder = AttributeDescriptorFinder(MANIFEST)
    layout = build_layout(
        MANIFEST,
        derived_keys=[("request.headers", "cookie"),
                      ("request.headers", ":authority")],
        byte_sources=["request.path", ("request.headers", "cookie")])
    interner = InternTable()
    # pre-seed some compile-time constants (the engine does this)
    for v in ("svc0.ns0.svc.cluster.local", "GET", 42):
        interner.intern(v)
    return layout, interner


def test_numeric_order_key_byte_slots_match_python():
    """Ordered comparisons read 8-byte order keys from the byte planes
    (layout.order_key_bytes); the shim must emit IDENTICAL bytes for
    INT64/DOUBLE/DURATION/TIMESTAMP slots — including the NaN (empty)
    and malformed-payload (len-1) markers — or device `<`/`>` verdicts
    would differ by ingest path."""
    layout = build_layout(
        MANIFEST,
        byte_sources=["request.size", "score", "response.duration",
                      "request.time", "request.path"])
    interner = InternTable()
    native = NativeTensorizer(layout, interner)
    dicts = _world(seed=5, n=96)
    dicts += [
        {"request.size": -(1 << 40), "score": -0.0},
        {"score": float("nan"), "request.size": 0},
        {"score": 1.5e308, "request.size": (1 << 62)},
        {"response.duration": datetime.timedelta(microseconds=1)},
    ]
    records = [bag_to_compressed(d).SerializeToString() for d in dicts]
    got = native.tensorize_wire(records)
    want = Tensorizer(layout, interner).tensorize(
        [bag_from_mapping(d) for d in dicts])
    np.testing.assert_array_equal(np.asarray(got.str_lens),
                                  np.asarray(want.str_lens))
    np.testing.assert_array_equal(np.asarray(got.str_bytes),
                                  np.asarray(want.str_bytes))
    # malformed: a STRING value arriving under the numeric attr name
    from istio_tpu.api import mixer_pb2 as pb
    req = pb.CompressedAttributes()
    req.words.append("request.size")   # message-local word 0
    req.words.append("junk")           # message-local word 1
    req.strings[0] = 1                 # request.size = "junk" (STRING)
    got2 = native.tensorize_wire([req.SerializeToString()])
    bcol = layout.byte_slots["request.size"]
    assert int(np.asarray(got2.str_lens)[0, bcol]) == 1  # error marker


def _assert_same_planes(got, oracle, interner):
    """Every plane of a native batch against the python Tensorizer's
    (built with hash_slots="all") over the same requests."""
    # constants share exact non-negative ids; runtime values get
    # per-batch ephemeral ids whose DECODED values must agree; within
    # each batch the id ↔ value mapping must be a bijection
    gi, oi = np.asarray(got.ids), np.asarray(oracle.ids)
    gp = np.asarray(got.present)
    gh = np.asarray(got.hash_ids)
    assert gi.shape == oi.shape
    id_to_val: dict[int, tuple] = {}
    val_to_id: dict[tuple, int] = {}
    for r, c in np.argwhere(gp):
        a, b = int(gi[r, c]), int(oi[r, c])
        # canonical bytes: a NaN equals itself there
        va = canonical_bytes(_normalize(got.value_of(a, interner)))
        if a >= 0 or b >= 0:
            assert a == b, (r, c, a, b)
        else:
            assert va == canonical_bytes(_normalize(
                oracle.value_of(b, interner))), (r, c)
        # bijection: same id ⇔ same value across the whole batch
        assert id_to_val.setdefault(a, va) == va, (r, c, a)
        assert val_to_id.setdefault(va, a) == a, (r, c, va)
        # the stable hash plane matches the python formula
        assert int(gh[r, c]) == \
            stable_hash31(got.value_of(a, interner)), (r, c)
    np.testing.assert_array_equal(gp, np.asarray(oracle.present))
    np.testing.assert_array_equal(gh * gp,
                                  np.asarray(oracle.hash_ids) *
                                  np.asarray(oracle.present))
    np.testing.assert_array_equal(np.asarray(got.map_present),
                                  np.asarray(oracle.map_present))
    np.testing.assert_array_equal(np.asarray(got.str_bytes),
                                  np.asarray(oracle.str_bytes))
    np.testing.assert_array_equal(np.asarray(got.str_lens),
                                  np.asarray(oracle.str_lens))
    if oracle.wide is None:
        assert got.wide is None
        return
    # the wide rows: the same rows claim one, in row order, and hold
    # the same bytes
    count = oracle.wide.count
    assert got.wide.count == count
    np.testing.assert_array_equal(got.wide.row, oracle.wide.row)
    np.testing.assert_array_equal(got.wide.lens[:count],
                                  oracle.wide.lens[:count])
    np.testing.assert_array_equal(got.wide.data[:count],
                                  oracle.wide.data[:count])


def _assert_like_python(native, dicts, records=None):
    """`records` (default: the dicts' own encoding) through the shim
    equal the dicts through the python Tensorizer."""
    if records is None:
        records = [bag_to_compressed(d).SerializeToString() for d in dicts]
    got = native.tensorize_wire(records)
    oracle = Tensorizer(native.layout, native.interner,
                        hash_slots="all").tensorize(
        [bag_from_mapping(d) for d in dicts])
    _assert_same_planes(got, oracle, native.interner)
    return got


def test_wire_conformance_vs_python_tensorizer():
    layout, interner = _rig()
    _assert_like_python(NativeTensorizer(layout, interner), _world(n=128))


def test_repeated_batches_share_interns():
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    recs = [bag_to_compressed(d).SerializeToString()
            for d in _world(seed=1, n=16)]
    b1 = native.tensorize_wire(recs)
    size_after_first = len(interner)
    b2 = native.tensorize_wire(recs)      # same values → no new ids
    assert len(interner) == size_after_first
    np.testing.assert_array_equal(np.asarray(b1.ids),
                                  np.asarray(b2.ids))


def test_intern_table_bounded_by_flush():
    """ADVICE r1: distinct runtime values must not grow the shared
    intern table, and the shim's own table flushes at the threshold
    while in-flight batches keep resolving their values."""
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    native._flush_threshold = 32
    size0 = len(interner)
    batches = []
    for seed in range(4):
        dicts = _world(seed=seed, n=32)
        recs = [bag_to_compressed(d).SerializeToString() for d in dicts]
        batches.append(native.tensorize_wire(recs))
    assert len(interner) == size0          # python table: zero growth
    # shim table flushed at least once (runtime entries dropped)
    assert len(native._runtime_values) <= 3 * native._flush_threshold
    # earlier batches still resolve their ephemeral ids
    first = batches[0]
    ids = np.asarray(first.ids)
    present = np.asarray(first.present)
    r, c = np.argwhere(ids < 0)[0]
    assert present[r, c]
    assert first.value_of(int(ids[r, c]), interner) is not None


def test_parse_error_reported():
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    with pytest.raises(ValueError, match="parse failure"):
        native.tensorize_wire([b"\xff\xff\xff\xff garbage"])


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_throughput_exceeds_python():
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    dicts = _world(seed=2, n=512)
    records = [bag_to_compressed(d).SerializeToString() for d in dicts]
    bags = [bag_from_mapping(d) for d in dicts]
    native.tensorize_wire(records)        # warm interns

    # best-of-N on both sides: scheduler noise from other tests'
    # background threads must not fail a relative-speed assertion
    t_native = min(
        _timed(lambda: native.tensorize_wire(records)) for _ in range(5))
    py = Tensorizer(layout, interner)
    t_py = min(_timed(lambda: py.tensorize(bags)) for _ in range(5))
    speedup = t_py / t_native
    # require 2×; typically far higher — and the python figure EXCLUDES
    # its share of wire decode. (3× flaked at 2.78× under full-suite
    # load on a 1-core box after the python tensorizer got faster —
    # ADVICE r2; the margin guards "native is pointless", not a perf SLO)
    assert speedup > 2, f"native only {speedup:.1f}× python"


# -- the five benchmark configurations' own traffic ---------------------

def _served_rig(store, manifest=None):
    """(layout, interner) of the snapshot a RuntimeServer compiles from
    `store`: what the served path hands its NativeTensorizer."""
    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.runtime import RuntimeServer, ServerArgs

    names = manifest if manifest is not None else GLOBAL_MANIFEST
    srv = RuntimeServer(store, ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in names},
        buckets=(64,), max_batch=64, initial_prewarm=False))
    try:
        native = srv.controller.dispatcher.fused.native
        return native.layout, native.interner
    finally:
        srv.close()


def _mixer():
    return (*_served_rig(workloads.make_store(200, 100)),
            workloads.make_request_dicts(192, seed=3))


def _rbac():
    return (*_served_rig(workloads.make_rbac_store(120, n_users=40,
                                                   n_services=32)),
            workloads.make_rbac_request_dicts(192, n_users=40,
                                              n_services=32))


def _full_mesh():
    engine, _, _, _, meta = workloads.make_full_mesh(n_services=64,
                                                     n_roles=16)
    return (engine.ruleset.layout, engine.ruleset.interner,
            workloads.make_full_mesh_requests(
                192, 64, n_roles=16, rules_by_host=meta["rules_by_host"]))


def _bench_config(name):
    # the benchmark's files, loaded by path as benchmark/run.py does
    from test_routematch_config import SEED, _load

    sizes, config = _load(name)
    return (*_served_rig(config.make_store(sizes), sizes["manifest"]),
            config.make_requests(sizes, 192, SEED))


CONFIGURATIONS = {
    "mixer": _mixer, "rbac": _rbac, "fullmesh": _full_mesh,
    "routematch10k": lambda: _bench_config("routematch10k"),
    "routelong10k": lambda: _bench_config("routelong10k"),
}


@pytest.mark.parametrize("name", list(CONFIGURATIONS))
def test_configuration_traffic_matches_python(name):
    """Each benchmark configuration's generator at smoke size: every
    plane the shim fills equals the python Tensorizer's (ids through
    value_of, hash_ids as stable_hash31, the wide rows of routelong's
    long subjects), cold and again with every value interned."""
    layout, interner, dicts = CONFIGURATIONS[name]()
    native = NativeTensorizer(layout, interner)
    first = _assert_like_python(native, dicts)
    if name == "routelong10k":
        assert first.wide.count > 0      # the branch the others skip
    _assert_like_python(native, dicts)


# -- odd records ------------------------------------------------------------

def _wide_rig():
    """_rig's layout with byte slots of every kind."""
    layout = build_layout(
        MANIFEST,
        derived_keys=[("request.headers", "cookie")],
        byte_sources=["request.path", ("request.headers", "cookie"),
                      "request.size", "score", "response.duration",
                      "request.time", "source.ip"])
    return layout, InternTable()


def _g(word: str) -> int:
    """The global-dictionary index of `word`."""
    return -(GLOBAL_WORD_LIST.index(word) + 1)


def _msg(**maps) -> pb.CompressedAttributes:
    """CompressedAttributes(words=[...], strings={...}, ...)."""
    m = pb.CompressedAttributes()
    m.words.extend(maps.pop("words", ()))
    for field, entries in maps.items():
        for k, v in entries.items():
            if field in ("timestamps", "durations"):
                getattr(m, field)[k].seconds, getattr(m, field)[k].nanos = v
            elif field == "string_maps":
                for mk, mv in v.items():
                    m.string_maps[k].entries[mk] = mv
            else:
                getattr(m, field)[k] = v
    return m


def _raw(*parts) -> bytes:
    """Messages and raw bytes back to back: on the wire, one record."""
    return b"".join(p if isinstance(p, bytes) else p.SerializeToString()
                    for p in parts)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _key(index: int) -> bytes:
    """A map entry's key field: 1, sint32."""
    return b"\x08" + _varint((index << 1) ^ (index >> 31))


def _ld(field: int, payload: bytes) -> bytes:
    """A length-delimited field."""
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


PATH, SIZE, NS = _g("request.path"), _g("request.size"), \
    _g("source.namespace")
# unknown fields, one a wire type: varint, fixed64, length-delimited,
# a group that holds a varint and a nested group, fixed32
UNKNOWN = (b"\x78\x96\x01", b"\x79" + b"12345678", b"\x7a\x03abc",
           b"\x7b\x08\x01\x83\x01\x84\x01\x7c", b"\x7d" + b"1234")
EPOCH = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)

# name → (records, the dicts whose python tensorization they equal)
ODD_RECORDS = {
    "a name under two typed maps, the later type wins: strings, then "
    "int64s": (
        [_msg(words=["junk"], strings={SIZE: 0}, int64s={SIZE: 7})],
        [{"request.size": 7}]),
    "a name under two typed maps: strings, then bytes": (
        [_msg(words=["ns1"], strings={NS: 0}, bytes={NS: b"\x01\x02"})],
        [{"source.namespace": b"\x01\x02"}]),
    "a key twice in one map, the last wins": (
        [_raw(_msg(words=["x" * 200, "/short"], strings={PATH: 0}),
              _msg(strings={PATH: 1}),
              _msg(int64s={SIZE: 1}), _msg(int64s={SIZE: 2}))],
        [{"request.path": "/short", "request.size": 2}]),
    "a key twice in a string map, and the string map twice": (
        [_raw(_msg(words=["a=1", "b=2", "c=3"],
                   string_maps={_g("request.headers"):
                                {_g("cookie"): 0, _g(":authority"): 0}}),
              _msg(string_maps={_g("request.headers"): {_g("cookie"): 1}}),
              # a value field twice in one entry merges its entries:
              # :authority → a=1 (words[0]), then cookie → c=3 (words[2])
              _ld(9, _key(_g("request.headers"))
                  + _ld(2, _ld(1, _key(_g(":authority")) + b"\x10\x00"))
                  + _ld(2, _ld(1, _key(_g("cookie")) + b"\x10\x04"))))],
        [{"request.headers": {"cookie": "c=3", ":authority": "a=1"}}]),
    "names and values as message-local words": (
        [_msg(words=["request.path", "/local", "my.unknown", "cookie",
                     "request.headers", "u=1"],
              strings={0: 1, 2: 1}, string_maps={4: {3: 5}})],
        [{"request.path": "/local",
          "request.headers": {"cookie": "u=1"}}]),
    "indices out of range: name, value, map key, map value": (
        [_msg(words=["w"], strings={-100000: 0, 7: 0, PATH: 9,
                                    -2**31: 0, NS: -2**31},
              int64s={-100000: 1, 7: 2, SIZE: 3},
              string_maps={_g("request.headers"):
                           {99: 0, _g("cookie"): -5000},
                           -70000: {_g("cookie"): 0}})],
        [{"request.size": 3, "request.headers": {}}]),
    "unknown fields of each wire type, in the record and in an entry": (
        [_raw(*UNKNOWN, _msg(words=["/p"], strings={PATH: 0}), *UNKNOWN,
              # int64s entry {key, unknowns, value}
              _ld(3, _key(SIZE) + b"".join(UNKNOWN) + b"\x10\x05"),
              # known field numbers under another wire type are unknown
              b"\x08\x01", b"\x15" + b"1234", b"\x49" + b"12345678")],
        [{"request.path": "/p", "request.size": 5}]),
    "a Timestamp before 1970 and a negative Duration": (
        None,
        [{"request.time": EPOCH - datetime.timedelta(seconds=5.5),
          "response.duration": datetime.timedelta(seconds=-5.5)},
         {"request.time": EPOCH - datetime.timedelta(microseconds=1),
          "response.duration": datetime.timedelta(microseconds=-1)}]),
    "NaN and -0.0 under a double slot, an int under it, ints' ends": (
        None,
        [{"score": float("nan")}, {"score": -0.0},
         {"score": 7}, {"score": float("-inf")},
         {"request.size": -2**63}, {"request.size": 2**63 - 1},
         {"request.size": 1.5}]),
    "a string and bytes under numeric slots": (
        None,
        [{"request.size": "junk"}, {"score": b"\x01"},
         {"request.time": "now"}, {"response.duration": b""}]),
    "a 4-byte and a 16-byte IP": (
        None,
        [{"source.ip": bytes([10, 0, 0, 1])},
         {"source.ip": b"\x00" * 10 + b"\xff\xff" + bytes([10, 0, 0, 1])},
         {"source.ip": bytes(range(16))}, {"source.ip": b""}]),
    "subjects at max_str_len and past wide_str_len": (
        None,
        [{"request.path": "/" + "a" * n, "request.size": n,
          "request.headers": {"cookie": "c" * m}}
         for n, m in ((126, 0), (127, 3), (128, 127), (3, 128),
                      (WIDE_STR_LEN - 2, 1), (WIDE_STR_LEN - 1, 200),
                      (WIDE_STR_LEN, WIDE_STR_LEN + 9), (5, 5))]),
    "set long, then short; the slot set twice": (
        # request.path under strings (long), then under bytes (short /
        # long again): the wide row holds what the narrow plane ends as
        [_msg(words=["L" * 300], strings={PATH: 0},
              bytes={PATH: b"short"}),
         _msg(words=["L" * 300], strings={PATH: 0},
              bytes={PATH: b"M" * 150})],
        None),
    "an empty record is a padding row": (
        [b"", _msg(words=["/p"], strings={PATH: 0}), b""],
        [{}, {"request.path": "/p"}, {}]),
}


@pytest.mark.parametrize("case", list(ODD_RECORDS))
def test_odd_record(case):
    records, dicts = ODD_RECORDS[case]
    native = NativeTensorizer(*_wide_rig())
    if dicts is not None:
        _assert_like_python(native, dicts, records and [
            _raw(r) for r in records])
        return
    # "set long, then short": no python bag says it; the planes by hand
    got = native.tensorize_wire([_raw(r) for r in records])
    bcol = native.layout.byte_slots["request.path"]
    assert got.wide.count == 2 and list(got.wide.row) == [0, 1]
    assert got.str_lens[0, bcol] == 5 and got.str_lens[1, bcol] == 128
    assert bytes(got.str_bytes[0, bcol, :6]) == b"shortL"   # as the tree
    assert got.wide.lens[0, bcol] == 5 and got.wide.lens[1, bcol] == 150
    assert bytes(got.wide.data[0, bcol, :5]) == b"short"
    assert not got.wide.data[0, bcol, 5:].any()
    assert bytes(got.wide.data[1, bcol, :150]) == b"M" * 150
    assert not got.wide.data[1, bcol, 150:].any()
    value = got.value_of(int(got.ids[0, native.layout.slots[
        "request.path"]]), native.interner)
    assert value == b"short"


GOOD = _msg(words=["/p"], strings={PATH: 0}).SerializeToString()
TIMESTAMP = _msg(timestamps={_g("request.time"): (5, 7)}).SerializeToString()
# what protobuf's ParseFromArray rejects
REJECTED = {
    "cut inside a tag": b"\xfa",
    "cut inside a value varint": GOOD + b"\x78\x96",
    "cut inside a length": GOOD + b"\x0a\x85",
    "a length past the end": GOOD + b"\x0a\x05abc",
    "an entry longer than its field": b"\x12\x02\x08",
    "a nested Timestamp cut": TIMESTAMP[:-1],
    "a Timestamp longer than its entry":
        _ld(6, _key(1) + b"\x12\x04\x08\x05") + GOOD,
    "cut inside a fixed64": GOOD + b"\x79" + b"1234",
    "cut inside a fixed32": GOOD + b"\x7d" + b"12",
    "a group that never closes": GOOD + b"\x7b\x08\x01",
    "a group closed by another field": GOOD + b"\x7b\x84\x01",
    "an end-group with no group": GOOD + b"\x7c",
    "field number 0": GOOD + b"\x00",
    "field number 0, length-delimited": GOOD + b"\x02\x00",
    "wire type 6": GOOD + b"\x7e",
    "wire type 7": GOOD + b"\x7f",
    "a tag of six bytes": GOOD + b"\xf8\x80\x80\x80\x80\x00\x00",
    "a value varint of eleven bytes": GOOD + b"\x78" + b"\x80" * 10 + b"\x00",
    "a length of six bytes": GOOD + b"\x0a\x80\x80\x80\x80\x80\x00",
    "words that are not UTF-8": b"\x0a\x02\xc3\x28",
    "a surrogate in words": b"\x0a\x03\xed\xa0\x80",
    "an overlong form in words": b"\x0a\x02\xc0\xaf",
    "a group nested past protobuf's depth":
        b"\x7b" * 101 + b"\x7c" * 101,
}


@pytest.mark.parametrize("case", list(REJECTED))
def test_rejected_record(case):
    """→ `parse failure at record i`; the rows before it are written,
    that row and the rows after it are not."""
    native = NativeTensorizer(*_wide_rig())
    with pytest.raises(ValueError, match="parse failure at record 1$"):
        native.tensorize_wire([GOOD, REJECTED[case], GOOD])
    planes = native._staging[3]["slots"][0]
    present = planes["present_u8"]
    assert present[0].any() and not present[1:].any()
    assert planes["str_lens"][0].any() and not planes["str_lens"][1:].any()
    # and the handle serves the next batch
    _assert_like_python(native, [{"request.path": "/p"}], [GOOD])


def test_accepted_where_protobuf_accepts():
    """The limits' near side: a group nested 100 deep, a ten-byte
    varint whose last byte overflows, a five-byte tag, bytes that are
    no UTF-8 where no string is."""
    native = NativeTensorizer(*_wide_rig())
    deep = b"\x7b" * 100 + b"\x7c" * 100
    wide_varint = _ld(3, _key(SIZE) + b"\x10" + b"\xff" * 9
                      + b"\x7f")                     # int64 -1, bits dropped
    tag5 = b"\xf8\xff\xff\xff\x0f\x01"               # field 2**29 - 1
    not_text = _msg(bytes={_g("source.ip"): b"\xc3\x28\xff\xfe"})
    _assert_like_python(
        native,
        [{"request.path": "/p", "request.size": -1,
          "source.ip": b"\xc3\x28\xff\xfe"}],
        [_raw(deep, GOOD, wide_varint, tag5, not_text)])


# -- interns across calls -----------------------------------------------------

def _strings(lo: int, hi: int) -> list[dict]:
    return [{"destination.service": f"svc{i}.ns.svc.cluster.local",
             "request.size": 10_000 + i, "score": i + 0.5,
             "source.ip": struct.pack(">I", i)} for i in range(lo, hi)]


def test_flush_then_the_same_values_again():
    """shim_flush_interns drops the runtime values; the same values
    then get ids again, `_runtime_values` and the shim's table agree,
    and a batch from before the flush still resolves its own."""
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    native._flush_threshold = 100
    dicts = _strings(0, 64)
    before = _assert_like_python(native, dicts)          # 256 values: flush
    kept = [before.value_of(int(i), interner)
            for i in np.asarray(before.ids)[np.asarray(before.present)]]
    assert native._runtime_values == []
    assert native._lib.shim_intern_count(native._h) == native._seed_count
    after = _assert_like_python(native, dicts[:16])      # 64 values: kept
    assert len(native._runtime_values) == 64
    assert native._lib.shim_intern_count(native._h) == \
        native._seed_count + 64
    again = native.tensorize_wire(
        [bag_to_compressed(d).SerializeToString() for d in dicts[:16]])
    np.testing.assert_array_equal(np.asarray(after.ids),
                                  np.asarray(again.ids))
    assert len(native._runtime_values) == 64
    assert kept == [before.value_of(int(i), interner)
                    for i in np.asarray(before.ids)[
                        np.asarray(before.present)]]


def test_seeds_found_and_growth_past_a_resize():
    """Compile-time constants (enough of them that the seeding itself
    resizes the index) keep their python ids; thousands of runtime
    values, each met twice, keep theirs from one call to the next."""
    layout, interner = _rig()
    seeds = _strings(0, 3000)
    for d in seeds:
        for v in d.values():
            interner.intern(v)
    native = NativeTensorizer(layout, interner)
    assert native._seed_count == len(interner)
    got = _assert_like_python(native, seeds[::7] + _strings(3000, 3100))
    ids = np.asarray(got.ids)
    col = layout.slots["destination.service"]
    assert (ids[:len(seeds[::7]), col] >= 3).all()       # the seeds' own
    assert (ids[len(seeds[::7]):, col] < 0).all()        # runtime values
    runtime = _strings(5000, 9000)
    first = _assert_like_python(native, runtime)
    first_ids = np.asarray(first.ids).copy()
    second = _assert_like_python(native, runtime)
    np.testing.assert_array_equal(first_ids, np.asarray(second.ids))
    assert len(native._runtime_values) == 4 * 100 + 4 * 4000


def test_two_threads_one_tensorizer():
    """Two threads call tensorize_wire on one tensorizer (the two pumps
    do): `_call_lock` gives each its own right planes, and values both
    meet share an id. Each thread has a batch shape of its own, so its
    staging ring turns only under its own calls."""
    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    py = Tensorizer(layout, interner, hash_slots="all")
    failures: list = []

    def pump(rows: int, seed: int) -> None:
        try:
            for turn in range(12):
                dicts = _world(seed=seed + turn % 3, n=rows) + \
                    _strings(turn * 50, turn * 50 + 40)
                records = [bag_to_compressed(d).SerializeToString()
                           for d in dicts]
                got = native.tensorize_wire(records)
                _assert_same_planes(
                    got, py.tensorize([bag_from_mapping(d) for d in dicts]),
                    interner)
        except BaseException as exc:   # noqa: BLE001 — handed to the test
            failures.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=pump, args=(rows, seed))
                   for rows, seed in ((24, 100), (56, 200), (88, 100))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures
    assert len(native._runtime_values) == \
        native._lib.shim_intern_count(native._h) - native._seed_count


def test_call_wait_span_is_the_wait_for_the_call_lock():
    """Span `tensorize.call_wait`: one observation a decode, and its
    wall is the time another caller held `_call_lock`, not the
    decode's own."""
    from istio_tpu.runtime import monitor

    layout, interner = _rig()
    native = NativeTensorizer(layout, interner)
    records = [bag_to_compressed(d).SerializeToString()
               for d in _world(seed=4, n=32)]

    def waited(base) -> dict:
        return monitor.latency_snapshot(
            since=base)["spans"]["tensorize.call_wait"]

    base = monitor.stage_baseline()
    native.tensorize_wire(records)
    alone = waited(base)
    assert alone["count"] == 1 and alone["sum_ms"] < 50

    base = monitor.stage_baseline()
    native._call_lock.acquire()
    caller = threading.Thread(target=native.tensorize_wire,
                              args=(records,))
    caller.start()
    time.sleep(0.2)
    native._call_lock.release()
    caller.join(timeout=30)
    assert not caller.is_alive()
    queued = waited(base)
    assert queued["count"] == 1 and queued["sum_ms"] >= 150
