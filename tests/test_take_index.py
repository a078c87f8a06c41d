"""The take blob: native/httpd.cpp take_impl writes a fixed-width row
index in front of the rows' bytes, and the native front reads it in
place (api/take.TakenRows). Held here on real takes: a grpcio client
sends, the test stands where a pump stands (h2srv_take through the
front's own _take) and reads the blob three ways: the columnar reader,
a per-row walk of the blob (the front's old parser, kept here as the
plain reference), and what was sent.
"""
import ctypes
import struct
import time
import types

import grpc
import numpy as np
import pytest

from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.take import CHECK, REPORT, TakenRows
from istio_tpu.api.wire import RawCheckRequest, bag_to_compressed
from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST

TRACEPARENT = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"


def parse_take_rows(blob: bytes) -> list[tuple]:
    """The plain reference: one row at a time, one struct.unpack_from a
    field, → [(tag, kind, payload, gwc, dedup, quotas{name: (amount,
    best_effort)}, traceparent)], as the front's per-row parser gave
    them before the index."""
    items = []
    (_, n) = struct.unpack_from("<II", blob, 0)
    for row in range(n):
        at = 8 + 48 * row
        (tag,) = struct.unpack_from("<Q", blob, at)
        (poff, plen, gwc, doff, dlen, toff, tlen, qoff) = \
            struct.unpack_from("<8I", blob, at + 8)
        (nq, kind) = struct.unpack_from("<HB", blob, at + 40)
        quotas = {}
        for _ in range(nq):
            (nlen,) = struct.unpack_from("<I", blob, qoff)
            qname = blob[qoff + 4:qoff + 4 + nlen].decode()
            amount, be = struct.unpack_from("<qB", blob, qoff + 4 + nlen)
            quotas[qname] = (amount, bool(be))
            qoff += 4 + nlen + 9
        items.append((tag, kind, blob[poff:poff + plen], gwc,
                      blob[doff:doff + dlen].decode("utf-8", "replace"),
                      quotas,
                      blob[toff:toff + tlen].decode("utf-8", "replace")))
    return items


def rows_of(taken: TakenRows) -> list[tuple]:
    """The same tuples through the columnar reader's accessors."""
    sent = dict(zip(np.flatnonzero(
        taken.index["traceparent_len"]).tolist(), taken.traceparents()))
    return [(tag, int(taken.index["kind"][row]), taken.payload(row),
             int(taken.index["gwc"][row]), taken.dedup_id(row),
             taken.quotas(row), sent.get(row, ""))
            for row, tag in enumerate(taken.tags.tolist())]


@pytest.fixture()
def front():
    """A native front whose pump is the test: the C++ server listens,
    no pump thread runs, and whoever calls _take gets the batch."""
    runtime = types.SimpleNamespace(preprocess_batch=lambda bags: bags)
    native = NativeMixerServer(runtime, max_batch=64, min_fill=64,
                               window_us=1000, pumps=1)
    native._pumps = []          # never started: nothing to join
    channel = grpc.insecure_channel(f"127.0.0.1:{native.port}")
    calls = {name: channel.unary_unary(
        f"/istio.mixer.v1.Mixer/{name}", request_serializer=lambda b: b,
        response_deserializer=lambda b: b) for name in ("Check", "Report")}
    yield types.SimpleNamespace(native=native, calls=calls)
    channel.close()
    native.stop(grace=0.2)


def _send(front, requests: list[tuple]) -> list:
    """[(method, bytes, traceparent)] → the calls' futures, once the
    front has decoded every one (so one take holds them all)."""
    before = front.native.counters()["requests_decoded"]
    futures = [front.calls[method].future(
        raw, metadata=[("traceparent", tp)] if tp else None, timeout=30)
        for method, raw, tp in requests]
    deadline = time.monotonic() + 20
    while front.native.counters()["requests_decoded"] - before \
            < len(requests):
        assert time.monotonic() < deadline, "requests never arrived"
        time.sleep(0.005)
    return futures


def _check(i: int, payload: bytes | None = None, **fields) -> bytes:
    request = pb.CheckRequest(**fields)
    if payload is None:
        payload = bag_to_compressed({
            "destination.service": f"svc{i}.ns.svc.cluster.local",
            "request.path": "/p" * (i + 1)}).SerializeToString()
    request.attributes.ParseFromString(payload)
    return request.SerializeToString()


def _mixed() -> list[tuple]:
    q = pb.CheckRequest.QuotaParams
    report = pb.ReportRequest(default_words=["w0", "w1"])
    report.attributes.add().words.append("destination.service")
    return [
        ("Check", _check(0), ""),
        ("Check", _check(1, deduplication_id="dedup-é-1"), ""),
        ("Check", _check(2), TRACEPARENT),
        ("Report", report.SerializeToString(), TRACEPARENT),
        ("Check", _check(3, deduplication_id="d3",
                         quotas={"rq": q(amount=7, best_effort=True)}),
         ""),
        ("Check", _check(4, quotas={
            "rq": q(amount=2), "other.quota": q(amount=-5,
                                                best_effort=True)}), ""),
        ("Check", _check(5, payload=b""), ""),
        ("Check", _check(6, global_word_count=len(GLOBAL_WORD_LIST)),
         "not-a-traceparent"),
        ("Check", _check(7, global_word_count=12), ""),
        ("Report", pb.ReportRequest(
            global_word_count=3).SerializeToString(), ""),
    ]


def _expected(requests: list[tuple]) -> dict:
    """payload → (kind, gwc, dedup, quotas, traceparent), from the
    bytes sent, split by the python envelope reader."""
    out = {}
    for method, raw, tp in requests:
        if method == "Report":
            out[raw] = (REPORT, 0, "", {}, tp)
            continue
        env = RawCheckRequest(raw)
        out[env.attributes_raw] = (
            CHECK, env.global_word_count, env.deduplication_id,
            {name: (p.amount, p.best_effort)
             for name, p in env.quotas.items()}, tp)
    assert len(out) == len(requests)
    return out


def test_the_index_gives_the_rows_the_per_row_parser_gave(front):
    requests = _mixed()
    futures = _send(front, requests)
    native = front.native
    # a buffer the take outgrows: -need, nothing popped, then the
    # front's _take grows it and gets the same batch whole
    small = ctypes.create_string_buffer(64)
    need = -native._lib.h2srv_take(native._h, 200, small, len(small))
    assert need > 64
    take = [small]
    n = native._take(take)
    assert n == need and len(take[0]) == 2 * need
    blob = take[0].raw[:n]
    taken = native._read_take(take[0])
    reference = parse_take_rows(blob)
    assert rows_of(taken) == reference
    assert len(reference) == len(requests)
    assert len({row[0] for row in reference}) == len(requests)
    assert {row[2]: row[1:2] + row[3:] for row in reference} \
        == _expected(requests)
    # the two kinds, split by the kind column, keep take order
    checks, reports = taken.of_kind(CHECK), taken.of_kind(REPORT)
    assert rows_of(checks) == [r for r in reference if r[1] == CHECK]
    assert rows_of(reports) == [r for r in reference if r[1] == REPORT]
    assert checks.asking() == [
        i for i, r in enumerate(rows_of(checks)) if r[5]]
    # only the rows that sent a header are looked at, in row order
    assert list(checks.traceparents()) == \
        [r[6] for r in reference if r[1] == CHECK and r[6]]
    # a row's bag holds the bytes the client sent (the empty ones too),
    # and says so where the C++ decoder cannot read them
    sent = [RawCheckRequest(raw) for m, raw, _ in requests
            if m == "Check"]
    by_payload = {env.attributes_raw: env for env in sent}
    for row in range(len(checks)):
        bag = checks[row]
        env = by_payload[checks.payload(row)]
        native_ok = env.global_word_count in (0, len(GLOBAL_WORD_LIST))
        assert bag.wire == (env.attributes_raw if native_ok else None)
    assert checks.wire_spans() is None          # the gwc-12 row
    native._send_completions([(tag, 0, b"") for tag in
                              taken.tags.tolist()])
    assert [f.result() for f in futures] == [b""] * len(requests)


def test_the_spans_point_at_the_payloads_where_they_lie(front):
    requests = [("Check", _check(i), "") for i in range(5)] + \
        [("Check", _check(5, payload=b""), "")]
    futures = _send(front, requests)
    take = [ctypes.create_string_buffer(1 << 16)]
    front.native._take(take)
    taken = front.native._read_take(take[0]).pad_to(8)
    base, offsets, lengths = taken.wire_spans()
    assert base == ctypes.addressof(take[0])
    assert offsets.dtype == np.uint64 and lengths.dtype == np.int64
    assert len(offsets) == len(lengths) == 8
    assert lengths[6:].tolist() == [0, 0]       # padding rows
    for row in range(6):
        assert ctypes.string_at(base + int(offsets[row]),
                                int(lengths[row])) == taken.payload(row)
    front.native._send_completions([(tag, 0, b"") for tag in
                                    taken.tags.tolist()])
    assert all(f.result() == b"" for f in futures)


def test_the_belt_answers_exactly_the_tags_no_completion_named(front):
    """Over a real take: rows answered by a record array, by a tuple
    (one of them twice) and by a deferred quota row stay as they are;
    every other tag of the take gets its INTERNAL, once."""
    native = front.native
    requests = [("Check", _check(i), "") for i in range(11)] + \
        [("Report", pb.ReportRequest().SerializeToString(), "")]
    futures = _send(front, requests)
    take = [ctypes.create_string_buffer(1 << 16)]
    native._take(take)
    tags = native._read_take(take[0]).tags.copy()
    payloads = [native._read_take(take[0]).payload(i) for i in range(12)]

    def inner(taken, checks, bags, completions, deferred):
        assert len(checks) == 11 and len(taken) == 12
        completions.frame(tags[:4], b"framed")
        completions.extend((int(t), 0, b"row") for t in
                           (tags[4], tags[5], tags[5]))
        deferred.add(int(tags[6]))
        raise RuntimeError("a fault after some rows were answered")

    patch = pytest.MonkeyPatch()
    try:
        patch.setattr(native, "_run_batch_inner", inner)
        native._run_batch(take[0])
    finally:
        patch.undo()
    native._send_completions([(int(tags[6]), 0, b"deferred")])
    by_payload = {}
    for (method, raw, _), fut in zip(requests, futures):
        key = raw if method == "Report" else \
            RawCheckRequest(raw).attributes_raw
        try:
            by_payload[key] = fut.result()
        except grpc.RpcError as exc:
            by_payload[key] = (exc.code(), exc.details())
    got = [by_payload[p] for p in payloads]
    belt = (grpc.StatusCode.INTERNAL, "internal: batch processing failed")
    assert got == [b"framed"] * 4 + [b"row", b"row", b"deferred"] \
        + [belt] * 5
