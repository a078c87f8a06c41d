"""A taken batch stays one buffer (api/take.TakenRows): the native
front hands the dispatcher the rows of a take where they lie, the C++
tensorizer reads their payloads in place, and a row becomes a bag only
where something on the host asks for it. Held here through the
counter (`mixer_front_bags_materialised_total`): which rows are made
into a bag and that no other is, on a batch the device decides, on
quota rows, on rows under a host action, on a row the host decides, and
under an APA; that such a bag outlives the pump's buffer; and that the
batch pads and trims as a list does.

The test stands where a pump stands: a take blob written in python
(testing/take_blob.encode_take) goes through the front's own
_run_batch, the C++ library's h2srv_complete replaced by a parser of
the completions.
"""
import ctypes
import ipaddress
import struct
import types

import numpy as np
import pytest

from istio_tpu.api import mixer_pb2 as pb
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.api.take import TakenRows
from istio_tpu.api.wire import (LazyWireBag, bag_to_compressed,
                                compressed_to_dict)
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.compiler.layout import WIDE_STR_LEN
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs, monitor
from istio_tpu.runtime.batcher import PadBag, pad_to_bucket, trim_pads
from istio_tpu.runtime.dispatcher import Dispatcher
from istio_tpu.testing import workloads
from istio_tpu.testing.take_blob import encode_take

from test_respond_classes import OVERLAY_RULES, OVERLAY_SERVICES, \
    _overlay_requests
from test_routelong_config import CONFIG as LONG_CONFIG, SIZES as LONG_SIZES

ROWS = 96                       # over dispatcher.RESPOND_CLASS_MIN_ROWS
BUCKETS = (64, 256)


def _wire(d: dict) -> bytes:
    return bag_to_compressed(d).SerializeToString()


def _items(requests: list[dict], quotas: dict | None = None,
           first_tag: int = 5000) -> list[tuple]:
    quotas = quotas or {}
    return [(first_tag + row, 0, _wire(d), 0, "", quotas.get(row, {}), "")
            for row, d in enumerate(requests)]


class Front:
    """A RuntimeServer behind a native front whose pump is the test
    and whose completions land in `answers` {tag: (status, bytes)}."""

    def __init__(self, srv: RuntimeServer):
        self.srv = srv
        self.native = NativeMixerServer(srv, max_batch=BUCKETS[-1])
        self.native._pumps = []         # never started: nothing to join
        self.lib = self.native._lib
        self.native._lib = types.SimpleNamespace(
            h2srv_complete=self._complete)
        self.answers: dict[int, tuple[int, bytes]] = {}

    def _complete(self, _handle, blob: bytes, n: int) -> None:
        (count,) = struct.unpack_from("<I", blob, 0)
        off = 4
        for _ in range(count):
            tag, status, length = struct.unpack_from("<QiI", blob, off)
            assert tag not in self.answers
            self.answers[tag] = (status, blob[off + 16:off + 16 + length])
            off += 16 + length
        assert off == n == len(blob)

    def pump(self, buf) -> int:
        """One batch through _run_batch → bags it made."""
        before = monitor.front_bag_counters()
        self.native._run_batch(buf)
        now = monitor.front_bag_counters()
        self.rows = now["rows"] - before["rows"]
        return now["materialised"] - before["materialised"]

    def statuses(self, tags) -> list[int]:
        out = []
        for tag in tags:
            status, raw = self.answers[tag]
            assert status == 0
            out.append(pb.CheckResponse.FromString(raw)
                       .precondition.status.code)
        return out

    def reference(self, requests: list[dict]) -> list[int]:
        """The same requests as a list of bags through the pump's
        entry: the path the other fronts take."""
        bags = [LazyWireBag(_wire(d)) for d in requests]
        out = self.srv.check_batch_preprocessed(pad_to_bucket(
            bags, self.srv.batcher.buckets))[:len(bags)]
        return [int(r.status_code) for r in out]

    def close(self) -> None:
        self.native._lib = self.lib
        self.native.stop(grace=0.2)
        self.srv.close()


def _mesh_server(**over) -> RuntimeServer:
    return RuntimeServer(
        workloads.make_store(over.pop("rules", 200), 100, **over),
        ServerArgs(default_manifest=workloads.MESH_MANIFEST,
                   buckets=BUCKETS, max_batch=BUCKETS[-1],
                   initial_prewarm=False, rule_telemetry=False))


def _long_server(**over) -> RuntimeServer:
    """routelong's table at a size a CPU scans 2 048-byte subjects at
    (tests/test_routelong_config.py)."""
    return RuntimeServer(LONG_CONFIG.make_store(LONG_SIZES), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k]
                          for k in LONG_SIZES["manifest"]},
        buckets=tuple(LONG_SIZES["buckets"]),
        max_batch=LONG_SIZES["max_batch"], initial_prewarm=False, **over))


def _mesh_requests(n: int, seed: int) -> list[dict]:
    """The generator's mix, every other request aimed at a rule of
    make_store(200, 100), deny rules (every third) among them."""
    out = workloads.make_request_dicts(n, seed=seed)
    for j in range(0, n, 2):
        i = (7 * j + seed) % 200
        out[j] = {
            "destination.service": f"svc{i % 100}.ns{i % 23}"
                                   ".svc.cluster.local",
            "source.namespace": f"ns{j % 5}", "request.method": "GET",
            "request.path": f"/api/v{i % 3}/items"}
    return out


@pytest.fixture(scope="module")
def mesh():
    front = Front(_mesh_server())
    yield front
    front.close()


def test_a_batch_the_device_decides_makes_no_bag(mesh):
    requests = _mesh_requests(ROWS, 11)
    items = _items(requests)
    assert mesh.pump(encode_take(items)) == 0
    assert mesh.rows == ROWS
    got = mesh.statuses(tag for tag, *_ in items)
    assert got == mesh.reference(requests) and len(set(got)) > 1


def test_an_exemplar_asks_for_the_rows_it_keeps():
    """With rule telemetry on, a denied row is drawn for; the bag is
    asked for only where the reservoir keeps the row (a fresh
    reservoir keeps its first four a rule)."""
    srv = RuntimeServer(
        workloads.make_store(200, 100),
        ServerArgs(default_manifest=workloads.MESH_MANIFEST,
                   buckets=BUCKETS, max_batch=BUCKETS[-1],
                   initial_prewarm=False))
    front = Front(srv)
    try:
        requests = _mesh_requests(ROWS, 11)
        items = _items(requests)
        made = front.pump(encode_take(items))
        tele = srv.controller.dispatcher.fused.telemetry
        kept = sum(len(v) for v in tele._ex.values())
        denied = sum(s != 0 for s in
                     front.statuses(tag for tag, *_ in items))
        assert 0 < made == kept <= denied < ROWS
    finally:
        front.close()


def test_a_quota_row_is_made_alone_and_outlives_the_buffer(mesh):
    """Rows that ask for a quota and are OK take the quota path with
    their bag; the bag owns its bytes: it decodes after the pump's
    buffer is overwritten."""
    requests = _mesh_requests(ROWS, 12)
    asking = {row: {"q": (1, True)} for row in range(0, ROWS, 7)}
    items = _items(requests, asking, first_tag=7000)
    blob = encode_take(items)
    buf = ctypes.create_string_buffer(len(blob))
    ctypes.memmove(buf, blob, len(blob))
    held = {}
    patch = pytest.MonkeyPatch()
    try:
        patch.setattr(
            mesh.native, "_defer_quota_row",
            lambda tag, bag, result, subs: held.__setitem__(tag, bag))
        made = mesh.pump(buf)
    finally:
        patch.undo()
    want = mesh.reference(requests)
    ok_asking = [row for row in asking if want[row] == 0]
    assert 0 < len(ok_asking) < len(asking)
    assert made == len(ok_asking)
    assert sorted(held) == [7000 + row for row in ok_asking]
    # every other row was answered, the deferred ones not yet
    answered = [7000 + row for row in range(ROWS) if row not in ok_asking]
    assert mesh.statuses(answered) == \
        [want[row] for row in range(ROWS) if row not in ok_asking]
    assert not set(held) & set(mesh.answers)
    # the next take lands in the same buffer
    ctypes.memset(buf, 0xFF, len(buf))
    for row in ok_asking:
        bag = held[7000 + row]
        assert {name: bag.get(name)[0] for name in bag.names()} == \
            compressed_to_dict(
                pb.CompressedAttributes.FromString(_wire(requests[row])))
    monitor.CHECK_RESPONSES.inc(len(held))      # the ledger's balance


def test_rows_under_a_host_action_are_made_alone():
    srv = RuntimeServer(
        workloads.make_store(OVERLAY_RULES, OVERLAY_SERVICES,
                             host_overlay_every=5),
        ServerArgs(default_manifest=workloads.MESH_MANIFEST,
                   buckets=BUCKETS, max_batch=BUCKETS[-1],
                   initial_prewarm=False, rule_telemetry=False))
    front = Front(srv)
    seen = {}
    overlay_active = Dispatcher._overlay_active

    def spy(self, *args, **kwargs):
        seen["active_sub"], seen["col_pos"] = out = \
            overlay_active(self, *args, **kwargs)
        return out

    patch = pytest.MonkeyPatch()
    try:
        requests = _overlay_requests(ROWS)
        items = _items(requests)
        patch.setattr(Dispatcher, "_overlay_active", spy)
        made = front.pump(encode_take(items))
        patch.undo()
        plan = srv.controller.dispatcher.fused
        host_rows = seen["active_sub"][:, [
            seen["col_pos"][int(r)] for r in plan.host_rule_idx]
        ].any(axis=1)
        assert 8 < host_rows.sum() < ROWS
        assert made == host_rows.sum()
        assert front.statuses(tag for tag, *_ in items) == \
            front.reference(requests)
    finally:
        patch.undo()
        front.close()


def test_a_row_the_host_decides_is_made_alone():
    """routelong's table at a CPU's size: a cookie past the widest
    byte plane under a rule that reads it is the host's to decide; it
    is the one row made into a bag."""
    front = Front(_long_server(rule_telemetry=False))
    try:
        requests = LONG_CONFIG.make_requests(LONG_SIZES, 40, 2147484471)
        cookie = next(r for r in range(LONG_SIZES["rules"])
                      if LONG_CONFIG.family_of(LONG_SIZES, r) == 2
                      and r % 3 == 0)
        long_rows = (5, 23)
        for row in long_rows:
            requests[row] = {
                "destination.service": LONG_CONFIG.host_of(
                    LONG_SIZES, cookie % LONG_SIZES["services"]),
                "source.namespace": "ns1", "request.method": "GET",
                "request.path": "/static/assets/1",
                "request.headers": {
                    ":authority": "x",
                    "cookie": "sid=" + "b" * (WIDE_STR_LEN + 50)
                              + f";user=group{cookie}"}}
        items = _items(requests)
        undecided0 = sum(
            monitor.length_split_counters()["undecided"].values())
        made = front.pump(encode_take(items))
        undecided = sum(
            monitor.length_split_counters()["undecided"].values()) \
            - undecided0
        got = front.statuses(tag for tag, *_ in items)
        assert got == front.reference(requests)
        assert [got[row] for row in long_rows] == \
            [LONG_CONFIG.DENIED] * 2
        assert undecided >= len(long_rows)
        assert made == undecided
    finally:
        front.close()


def test_an_apa_snapshot_makes_every_row():
    s = MemStore()
    s.set(("handler", "", "kube"), {
        "adapter": "kubernetesenv",
        "params": {"pods": {"web.default": {
            "pod_name": "web-1", "namespace": "default",
            "pod_ip": "10.0.0.9", "service_account_name": "web-sa"}}}})
    s.set(("instance", "", "kubeattrs"), {
        "template": "kubernetes",
        "params": {"source_ip": "source.ip",
                   "attribute_bindings": {
                       "source.name": "$out.source_pod_name",
                       "source.namespace": "$out.source_namespace"}}})
    s.set(("rule", "", "kubeapa"), {
        "match": "",
        "actions": [{"handler": "kube", "instances": ["kubeattrs"]}]})
    s.set(("handler", "", "deny"), {"adapter": "denier", "params": {}})
    s.set(("instance", "", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "", "denypod"), {
        "match": 'source.name == "web-1"',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    front = Front(RuntimeServer(s, ServerArgs(
        batch_window_s=0.001, max_batch=8, rule_telemetry=False)))
    try:
        assert front.srv.controller.dispatcher.has_apa
        requests = [{"source.ip": ipaddress.ip_address(
            f"10.0.0.{9 if row % 3 == 0 else 7}").packed,
            "destination.service": "x.default.svc"} for row in range(6)]
        items = _items(requests)
        assert front.pump(encode_take(items)) == 6
        assert front.statuses(tag for tag, *_ in items) == \
            [7 if row % 3 == 0 else 0 for row in range(6)]
    finally:
        front.close()


@pytest.mark.parametrize("n", [0, 1, 5, 64, 65, 256, 300])
def test_it_pads_and_trims_as_a_list_does(n):
    """len, the real prefix, the pad count and the chunks a front
    cuts agree with pad_to_bucket + trim_pads over a list of bags,
    and none of it makes a bag."""
    requests = [{"request.path": f"/r{row}"} for row in range(n)]
    rows = TakenRows.read(encode_take(_items(requests)))
    bags = [LazyWireBag(_wire(d)) for d in requests]
    before = monitor.front_bag_counters()["materialised"]
    padded, listed = pad_to_bucket(rows, BUCKETS), \
        pad_to_bucket(bags, BUCKETS)
    assert len(padded) == len(listed) == (n if n > 256 else
                                          64 if n <= 64 else 256)
    assert padded.pads == sum(isinstance(b, PadBag) for b in listed)
    assert len(trim_pads(padded)) == len(trim_pads(listed)) == n
    assert len(padded.real) == len(padded[:n]) == n
    assert padded[:n].pads == 0
    for lo in range(0, len(listed), 64):         # a front's chunks
        mine, theirs = padded[lo:lo + 64], listed[lo:lo + 64]
        assert len(mine) == len(theirs)
        assert mine.pads == sum(isinstance(b, PadBag) for b in theirs)
        assert len(trim_pads(mine)) == len(trim_pads(theirs))
    spans = padded.wire_spans()
    assert spans[2].tolist() == [len(b.wire) for b in listed]
    assert monitor.front_bag_counters()["materialised"] == before
    if n:
        # a row asked for twice is one object, through any view
        assert padded[n - 1] is rows[n - 1] is padded[:n][-1]
        assert padded[n - 1].wire == bags[n - 1].wire
        assert monitor.front_bag_counters()["materialised"] == before + 1
    if padded.pads:
        assert isinstance(padded[n], PadBag)
        assert isinstance(padded[-1], PadBag)
    with pytest.raises(IndexError):
        padded[len(padded)]


def test_the_span_entry_gives_the_planes_tensorize_wire_gives():
    """NativeTensorizer.tensorize_spans over a taken batch equals
    tensorize_wire over the same records held as bytes, plane for
    plane, padding rows and a row on the wide plane included."""
    srv = _long_server()
    try:
        native = srv.controller.dispatcher.fused.native
        requests = LONG_CONFIG.make_requests(LONG_SIZES, 40, 2147484999)
        requests[3] = {**requests[3], "request.headers": {
            ":authority": "x",
            "cookie": "sid=" + "c" * 700 + ";user=group1"}}
        requests[9] = {}
        rows = pad_to_bucket(
            TakenRows.read(encode_take(_items(requests))), (64,))
        listed = [LazyWireBag(_wire(d)).wire for d in requests] \
            + [PadBag.wire] * 24
        got = native.tensorize_spans(*rows.wire_spans())
        want = native.tensorize_wire(listed)
        assert got.wide.count == want.wide.count >= 1
        for plane in ("ids", "present", "map_present", "str_bytes",
                      "str_lens", "hash_ids"):
            a, b = getattr(got, plane), getattr(want, plane)
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes(), plane
        assert got.wide.row.tobytes() == want.wide.row.tobytes()
        claimed = want.wide.count
        assert got.wide.lens[:claimed].tobytes() == \
            want.wide.lens[:claimed].tobytes()
        assert got.wide.data[:claimed].tobytes() == \
            want.wide.data[:claimed].tobytes()
    finally:
        srv.close()


def test_the_e2e_histogram_counts_the_real_rows(mesh):
    """check_batch_preprocessed over a padded batch: the e2e count
    rises by the real rows, the sum by rows x the batch's wall, the
    live window by as many copies; padding rows carry no caller."""
    requests = _mesh_requests(70, 13)
    rows = pad_to_bucket(TakenRows.read(encode_take(_items(requests))),
                         BUCKETS)
    assert len(rows) == 256
    _, sum0, n0 = monitor.CHECK_E2E_SECONDS.state()
    total0 = monitor.CHECK_WINDOW.total
    out = mesh.srv.check_batch_preprocessed(rows)
    _, sum1, n1 = monitor.CHECK_E2E_SECONDS.state()
    assert len(out) == 70
    assert n1 - n0 == 70 == monitor.CHECK_WINDOW.total - total0
    wall = (sum1 - sum0) / 70
    assert 0 < wall < 60
    with monitor.CHECK_WINDOW._lock:
        newest = list(monitor.CHECK_WINDOW._buf)[-70:]
    assert newest == pytest.approx([wall] * 70)
