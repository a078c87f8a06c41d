"""`correct` holds a quota grant: the plain memquota against cases
written by hand, the client against a canned stream of replies, the
payloads of a quota-free mix against the parent's, and the harness's
own phases at smoke size, sound and with the timed path broken."""
import hashlib
import io
import json
import socket
import struct
import subprocess
import threading
import types
from pathlib import Path

import pytest

import faults
import run

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmark"
CELL = "mixer10k-quota-deep"
SEED = 4000000039
# sha256 of each quota-free cell's payload file at smoke size, 512
# requests of SEED, as PR 38's write_payloads (cf306ec) wrote it
PARENT_DIGESTS = json.loads(
    (Path(__file__).with_name("parent_payload_digests.json")).read_text())


# -- the plain reference --------------------------------------------------

def memquota(limit, **kw):
    plain = run.load_module(BENCH / "configs" / "memquota_plain.py")
    return plain.MemQuota(
        "rq", limit, expected_status=lambda r: r.get("status", 0),
        key_of=lambda r: r.get("user", "anon"), **kw)


def test_reference_grants_by_hand():
    q = memquota(10)
    alice, bob = {"user": "alice"}, {"user": "bob"}
    assert q.grant(alice, "rq", 4, True, "a1") == 4
    assert q.grant(alice, "rq", 7, False, "a2") == 0     # all or nothing
    assert q.grant(alice, "rq", 7, True, "a3") == 6      # what is left
    assert q.grant(alice, "rq", 1, True, "a4") == 0      # exhausted
    assert q.grant(bob, "rq", 10, False, "b1") == 10     # a key of its own
    assert q.grant({}, "rq", 11, False, "c1") == 0       # "anon", over max
    assert q.grant({}, "rq", 0, False, "c2") == 0


def test_reference_replays_the_first_answer_and_consumes_nothing():
    q = memquota(10)
    alice = {"user": "alice"}
    assert q.grant(alice, "rq", 6, True, "x") == 6
    assert q.grant(alice, "rq", 6, True, "x") == 6       # replayed
    assert q.grant(alice, "rq", 6, True, "y") == 4       # 6 used, not 12
    assert q.grant(alice, "rq", 6, True, "y") == 4
    assert q.grant(alice, "rq", 6, True, "") == 0        # no id: no replay


def test_reference_denied_precondition_and_unserved_quota():
    q = memquota(10, rule_matches=lambda r: r.get("mtls", True))
    assert q.grant({"status": 7}, "rq", 1, True, "d") is None
    # no active rule serves it: granted freely, nothing consumed
    assert q.grant({"mtls": False}, "rq", 99, False, "e") == 99
    assert q.grant({}, "other", 5, True, "f") == 5
    assert q.grant({}, "rq", 10, False, "g") == 10
    assert not q.consumes({"status": 7}, "rq")
    assert not q.consumes({"mtls": False}, "rq")
    assert not q.consumes({}, "other") and q.consumes({}, "rq")


def test_reference_counter_is_read_back_by_one_more_than_is_left():
    """What parity_quota and read_back_quota ask: refused while the
    counter holds what was granted, granted where it lags."""
    q = memquota(1 << 30)
    alice = {"user": "alice"}
    for k in range(5):
        assert q.grant(alice, "rq", 1, True, f"s{k}") == 1
    assert q.in_use(alice) == 5 and q.in_use({"user": "bob"}) == 0
    assert q.grant(alice, "rq", (1 << 30) - 5 + 1, False, "lags") == 0
    assert q.in_use(alice) == 5                         # nothing consumed
    q.consume(alice, 3)                                 # the client's count
    assert q.grant(alice, "rq", (1 << 30) - 8, False, "ahead") \
        == (1 << 30) - 8
    lagging = memquota(1 << 30)                         # consumed nothing
    assert lagging.grant(alice, "rq", (1 << 30) - 5 + 1, False, "l") > 0


@pytest.mark.parametrize("module", ["mixer", "fullmesh"])
def test_configurations_quota_reference_is_fresh_and_keyed_by_user(module):
    config = run.load_module(BENCH / "configs" / f"{module}.py")
    name = {"mixer": "mixer10k", "fullmesh": "fullmesh5k"}[module]
    sizes = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert sizes["quota_exhausts"] is False
    sizes.update(sizes["smoke"])
    requests = config.make_requests(sizes, 512, SEED)
    status = config.reference(sizes)
    first, second = config.quota_reference(sizes), \
        config.quota_reference(sizes)
    assert first is not second and first.max_amount == config.QUOTA_MAX
    ok = next(r for r in requests if status(r) == 0)
    no = next(r for r in requests if status(r) != 0)
    assert first.grant(no, "rq", 1, True, "n") is None
    assert first.grant(ok, "rq", 3, True, "k") == 3
    assert first.used == {ok["source.user"]: 3} and second.used == {}
    assert first.key_of(ok) == ok["source.user"] and first.in_use(ok) == 3
    assert first.grant(ok, "rq", config.QUOTA_MAX, False, "l") == 0


# -- the client against canned replies ---------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7f) | (0x80 if n > 0x7f else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, payload):
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def check_response(status=0, entries=()):
    """CheckResponse bytes by hand: an unknown field in front, the
    precondition with a Duration and the status, then the entries
    (name, granted or None for an empty QuotaResult)."""
    pre = field(2, field(1, 60)) + field(3, 10000)
    if status:
        pre = field(1, field(1, status) + field(2, b"denied")) + pre
    out = field(9, b"ignored") + field(2, pre)
    for name, granted in entries:
        value = field(1, field(1, 5))
        if granted:
            value += field(2, granted)
        out += field(3, field(1, name.encode()) + field(2, value))
    return out


# payload index -> (status, entries): a grant, a short grant, a missing
# entry, a denied row, an entry of another name, a wrong status, an
# entry beside a denial; index 7 asks for nothing
CANNED = {0: (0, [("rq", 2)]), 1: (0, [("rq", 1)]), 2: (0, []),
          3: (7, []), 4: (0, [("other", 2)]), 5: (7, []),
          6: (7, [("rq", 2)])}
EXPECT = {0: 0, 1: 0, 2: 0, 3: 7, 4: 0, 5: 0, 6: 7}


def frame(kind, flags, stream, payload=b""):
    return struct.pack(">I", len(payload))[1:] + bytes([kind, flags]) + \
        struct.pack(">I", stream) + payload


def literal(name, value):
    return bytes([0, len(name)]) + name + bytes([len(value)]) + value


class CannedServer(threading.Thread):
    """One h2c connection: answers every request by its payload's first
    attribute word, and keeps each request's deduplication_id."""

    def __init__(self):
        super().__init__(daemon=True)
        self.listener = socket.create_server(("127.0.0.1", 0))
        self.port = self.listener.getsockname()[1]
        self.ids = []      # (payload index, id) in arrival order

    def run(self):
        conn, _ = self.listener.accept()
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        buf, bodies = b"", {}
        conn.sendall(frame(4, 0, 0))
        preface = len(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n")
        try:
            while True:
                data = conn.recv(65536)
                if not data:
                    return
                buf += data
                if preface:
                    if len(buf) < preface:
                        continue
                    buf, preface = buf[preface:], 0
                out = b""
                while len(buf) >= 9:
                    n = int.from_bytes(buf[:3], "big")
                    if len(buf) < 9 + n:
                        break
                    kind, flags = buf[3], buf[4]
                    stream = int.from_bytes(buf[5:9], "big")
                    payload, buf = buf[9:9 + n], buf[9 + n:]
                    if kind == 0:
                        bodies[stream] = bodies.get(stream, b"") + payload
                        if flags & 1:
                            out += self.answer(stream, bodies.pop(stream))
                if out:
                    conn.sendall(out)
        except OSError:
            return
        finally:
            conn.close()
            self.listener.close()

    def answer(self, stream, body):
        request = body[5:]
        index = request[-1]                  # the payloads end in it
        if index in CANNED:
            at = request.index(b"\x1a\x10") + 2
            self.ids.append((index, request[at:at + 16].decode()))
        status, entries = CANNED.get(index, (0, []))
        reply = check_response(status, entries)
        data = b"\0" + struct.pack(">I", len(reply)) + reply
        # the DATA in two frames: the client has to join them
        return (frame(1, 4, stream, literal(b":status", b"200"))
                + frame(0, 0, stream, data[:7]) + frame(0, 0, stream, data[7:])
                + frame(1, 5, stream, literal(b"grpc-status", b"0")))


def canned_payloads(tmp_path):
    """Eight payloads: a deduplication_id (field 3) where one asks, an
    unknown trailing field whose last byte is the payload's index. Two
    read-back keys: payloads 0 and 1 count against the first, 6 (an
    entry beside a denial) against the second."""
    payloads, table = tmp_path / "p.bin", tmp_path / "p.quota"
    rows = ["quota rq 16 2"]
    key = {0: 0, 1: 0, 6: 1}
    with payloads.open("wb") as out:
        for i in range(8):
            raw = field(2, 7)
            if i in CANNED:
                raw += field(3, b"#dedup-%09d" % i)
                rows.append(f"{i} {raw.index(b'#dedup-')} 2 {EXPECT[i]} "
                            f"{key.get(i, -1)}")
            raw += field(15, i)
            out.write(struct.pack("<I", len(raw)) + raw)
    table.write_text("\n".join(rows) + "\n")
    return payloads, table


def test_client_reads_the_reply_of_a_quota_row(tmp_path):
    payloads, table = canned_payloads(tmp_path)
    server = CannedServer()
    server.start()
    done = subprocess.run(
        [str(run.build_client()), str(server.port), str(payloads), "0.4",
         "4", "0.1", "/istio.mixer.v1.Mixer/Check", str(table)],
        capture_output=True, text=True, timeout=30)
    assert done.returncode == 0, done.stderr
    line = json.loads(done.stdout.strip().splitlines()[-1])
    asked = line["quota_asked"]
    assert asked > 70 and line["attempted"] >= asked
    # seven kinds of reply in turn: each a seventh of the quota rows
    each = lambda key: abs(line[key] - asked / 7) <= 2
    assert each("quota_missing") is False      # two kinds read as missing
    assert abs(line["quota_missing"] - 2 * asked / 7) <= 3
    assert each("short_grants") and each("status_mismatches")
    assert each("quota_unexpected")             # and counted as denied too
    assert abs(line["quota_denied"] - 2 * asked / 7) <= 3
    assert line["replies_malformed"] == 0
    granted = line["quota_granted"]
    assert abs(granted - 3 * asked / 7) <= 6 and granted > asked / 3
    assert line["failed"] == line["quota_missing"] + \
        line["status_mismatches"] + line["quota_unexpected"]
    # an id a send: no two the server saw are the same
    ids = [i for _, i in server.ids]
    assert all(len(i) == 16 and int(i, 16) > 0 for i in ids)
    assert len(set(ids)) == len(ids)
    # (the last few the client stamped were in flight when it closed)
    assert 0 <= line["quota_ids_sent"] - len(ids) <= 4
    assert line["quota_ids_sent"] >= asked
    # the read-back keys, warm-up included: what was asked of each and
    # what its replies granted (payload 0 grants 2, payload 1 grants 1)
    seen = [index for index, _ in server.ids]
    sent, granted = line["readback_sent"], line["readback_granted"]
    assert 0 <= sent[0] - 2 * (seen.count(0) + seen.count(1)) <= 8
    assert 0 <= sent[1] - 2 * seen.count(6) <= 8
    assert 0 <= 2 * seen.count(0) + seen.count(1) - granted[0] <= 6
    assert 0 <= 2 * seen.count(6) - granted[1] <= 8
    assert granted[0] > line["quota_granted"] / 2    # more than the window's


def test_client_without_a_table_reads_no_reply(tmp_path):
    payloads, _ = canned_payloads(tmp_path)
    server = CannedServer()
    server.start()
    done = subprocess.run(
        [str(run.build_client()), str(server.port), str(payloads), "0.3",
         "4", "0.1"], capture_output=True, text=True, timeout=30)
    assert done.returncode == 0, done.stderr
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["quota_asked"] == 0 and line["quota_ids_sent"] == 0
    assert line["readback_sent"] == line["readback_granted"] == []
    assert {i for _, i in server.ids} == {
        "#dedup-%09d" % i for i in CANNED}     # sent as the file has them


# -- the payloads -----------------------------------------------------------

def cell_of(name):
    return run.resolve_cell(name, smoke=True)


def payload_bytes(cell, seed, n=512):
    requests = cell.config.make_requests(cell.sizes, n, seed)
    out, table = io.BytesIO(), io.StringIO()
    reference = cell.config.quota_reference(cell.sizes) \
        if cell.mix["quota_every"] else None
    keys = run.write_payloads(requests, cell, out, table, reference)
    return out.getvalue(), table.getvalue(), keys


def test_a_quota_free_mix_writes_the_parents_payloads_and_no_table():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    free = [w["name"] for w in manifest["workloads"]
            if not cell_of(w["name"]).mix["quota_every"]]
    assert len(free) >= 6 and set(PARENT_DIGESTS) <= set(free)
    for name in PARENT_DIGESTS:
        raw, table, keys = payload_bytes(cell_of(name), SEED)
        assert hashlib.sha256(raw).hexdigest() == PARENT_DIGESTS[name], name
        assert table == "" and keys == []


def test_a_quota_mix_marks_its_payloads_and_nothing_else():
    from istio_tpu.api import mixer_pb2 as pb

    cell, plain = cell_of(CELL), cell_of("mixer10k-check-deep")
    every = cell.mix["quota_every"]
    raw, table, keys = payload_bytes(cell, SEED)
    was, _, _ = payload_bytes(plain, SEED)
    head, *rows = table.splitlines()
    assert head == f"quota rq 16 {run.READBACK_KEYS}"
    assert len(rows) == len(range(0, 512, every)) and len(keys) == 8
    status = cell.config.reference(cell.sizes)
    requests = cell.config.make_requests(cell.sizes, 512, SEED)
    users = [r["source.user"] for r in keys]
    assert len(set(users)) == 8 and all(status(r) == 0 for r in keys)

    def split(blob):
        out, at = [], 0
        while at < len(blob):
            (n,) = struct.unpack_from("<I", blob, at)
            out.append(blob[at + 4:at + 4 + n])
            at += 4 + n
        return out

    ours, theirs = split(raw), split(was)
    table_rows = {int(r.split()[0]): [int(x) for x in r.split()[1:]]
                  for r in rows}
    assert sorted(table_rows) == list(range(0, 512, every))
    for i, (mine, other) in enumerate(zip(ours, theirs)):
        if i % every:
            assert mine == other
            continue
        req = pb.CheckRequest.FromString(mine)
        offset, amount, expect, key = table_rows[i]
        assert mine[offset:offset + 16].decode() == req.deduplication_id
        assert (req.quotas["rq"].amount, req.quotas["rq"].best_effort) \
            == (amount, True) == (1, True)
        assert expect == status(requests[i])
        # a read-back key's number on every payload that consumes from it
        user = requests[i].get("source.user", "anon")
        assert key == (users.index(user)
                       if user in users and expect == 0 else -1)
        req.ClearField("quotas")
        req.ClearField("deduplication_id")
        assert req.SerializeToString() == other


def test_a_quota_mix_on_a_module_without_the_reference_stops(monkeypatch,
                                                             capsys):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["workloads"].append({
        "name": "rbac1k-quota-deep", "config": "rbac1k",
        "traffic": "quota-deep", "chips": 1, "why": "a test"})
    monkeypatch.setattr(run.json, "loads", lambda text, _l=json.loads: (
        manifest if '"workloads"' in text and '"configs"' in text
        else _l(text)))
    with pytest.raises(SystemExit) as stop:
        run.resolve_cell("rbac1k-quota-deep", smoke=True)
    assert "quota_reference" in str(stop.value) \
        and "quota_exhausts" in str(stop.value)
    assert run.resolve_cell("rbac1k-check-deep", smoke=True).mix[
        "quota_every"] == 0


def test_a_configuration_whose_quota_exhausts_stops(monkeypatch):
    """`correct` holds every grant in full: a file that says its keys
    exhaust is refused before anything is served."""
    load = json.loads

    def loads(text):
        sizes = load(text)
        if sizes.get("module") == "mixer":
            sizes["quota_exhausts"] = True
        return sizes

    monkeypatch.setattr(run.json, "loads", loads)
    with pytest.raises(SystemExit, match="exhausts"):
        run.resolve_cell(CELL, smoke=True)
    assert run.resolve_cell("mixer10k-check-deep", smoke=True)


def test_no_seed_makes_a_one_sided_parity_set():
    status = lambda d: 7 if d["n"] in (5, 2000) else 0
    requests = [{"n": n} for n in range(3000)] + [{"n": 1}]    # one repeat
    wire, big = run.parity_sets(requests, status, 256)
    assert [d["n"] for d in wire] == list(range(512))           # as before
    assert [d["n"] for d in big] == list(range(512, 768))
    status = lambda d: 7 if d["n"] in (600, 2000) else 0        # 600: in big
    wire, big = run.parity_sets(requests, status, 256)
    assert [d["n"] for d in wire] == list(range(511)) + [2000]
    assert [d["n"] for d in big] == list(range(512, 768))
    with pytest.raises(run.BenchFailure, match="one-sided"):
        run.parity_sets(requests, lambda d: 0, 256)
    with pytest.raises(run.BenchFailure, match="cannot fill"):
        run.parity_sets(requests[:700], status, 256)


# -- the harness's own phases, sound and broken -------------------------------

def drive(fault=None, when=None, tweak=None, base=CELL, held=None):
    """serve_and_measure at smoke size; a `base` cell of another mix is
    driven under quota-deep's (the cell `fullmesh5k-quota-deep` will
    be)."""
    cell = cell_of(base)
    cell.mix = cell_of(CELL).mix
    if tweak:
        tweak(cell)
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=0)
    ctx = types.SimpleNamespace(client={}, trace=None, setup_s=None,
                                on_chip=False,
                                held=run.Held() if held is None else held)
    if fault is None:
        run.serve_and_measure(cell, args, ctx)
    else:
        with faults.installed(fault, when, run):
            run.serve_and_measure(cell, args, ctx)
    return ctx


@pytest.mark.parametrize("base", [CELL, "fullmesh5k-check-deep"])
def test_the_sound_path_holds_every_number(base):
    ctx = drive(base=base)
    held = ctx.held
    assert held["quota_parity_mismatches"] == [0, "<=", 0]
    assert held["quota_replay_mismatches"] == [0, "<=", 0]
    assert held["parity_quota_replays"][0] == 32
    assert held["parity_quota_denied"][0] >= 1
    asked = ctx.client["quota_asked"]
    assert asked == held["quota_asked"][0] > 0
    every = cell_of(CELL).mix["quota_every"]
    assert abs(asked - ctx.client["attempted"] / every) <= 0.02 * asked + 64
    assert ctx.client["quota_granted"] + ctx.client["quota_denied"] == asked
    assert ctx.client["short_grants"] == ctx.client["failed"] == 0
    assert held["client_failed"] == [0, "<=", 0]
    assert held["short_grants"] == [0, "<=", 0]
    assert held["quota_ids_sent"][0] >= asked
    # the counter read back: parity's own key, then the read-back keys
    assert held["parity_quota_refused"][0] == 2
    assert held["parity_quota_own_key_in_use"][0] >= 1
    assert held["readback_keys"] == [8, ">=", 1]
    granted, sent = ctx.client["readback_granted"], \
        ctx.client["readback_sent"]
    assert len(granted) == 8 and 0 < sum(granted) <= sum(sent)
    assert held["quota_counter_lags_mismatches"] == [0, "<=", 0]
    assert held["quota_counter_ahead_mismatches"] == [0, "<=", 0]


@pytest.mark.parametrize("fault, when, number", [
    ("grant_zero", "from_start", "quota_parity_mismatches"),
    ("omit_quotas", "from_start", "quota_parity_mismatches"),
    ("alter_status", "from_start", "quota_parity_mismatches"),
    ("no_consume", "from_start", "quota_parity_mismatches"),
    ("grant_zero", "in_window", "short_grants"),
    ("omit_quotas", "in_window", "quota_missing"),
    ("alter_status", "in_window", "status_mismatches"),
    ("no_consume", "in_window", "quota_counter_lags_mismatches"),
])
def test_a_broken_path_is_not_correct(fault, when, number):
    held = run.Held()
    with pytest.raises(run.BenchFailure, match=number) as failed:
        drive(fault, when, held=held)
    value, op, limit = held[number]
    assert value > limit == 0 and op == "<="
    if "mismatches" in number and number != "status_mismatches":
        # the message names the first rows
        assert "first [{" in str(failed.value) and "'row': " in str(
            failed.value)
