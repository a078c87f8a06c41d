"""The five readers of the pump watch (pump_stall_s, lock_wait_ms_mean,
front_starved_s, client_silent_s, gc_young_share): over synthetic
snapshots, on a program without the counter, and their manifest
entries by name, not by the list's length."""
import json
import time
import types
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
NAMES = ("pump_stall_s", "lock_wait_ms_mean", "front_starved_s",
         "client_silent_s", "gc_young_share")
READERS = {n: load_module(ROOT / "benchmark" / "layer_metrics" / f"{n}.py")
           for n in NAMES}
CELLS = ["mixer10k-check-deep", "mixer10k-check-shallow",
         "rbac1k-check-deep", "fullmesh5k-check-deep",
         "routematch10k-check-deep", "routelong10k-check-deep"]
SPANS = ("take_wait", "wire_decode", "queue_wait", "tensorize", "h2d",
         "device_step", "fold", "respond", "serialize", "send")


def _watch(stalls=(), events=(), count=1000, sum_s=0.5, max_s=0.004):
    seen = {name: {"count": 0, "sum_s": 0.0} for name in SPANS}
    for name, seconds in stalls:
        seen[name]["count"] += 1
        seen[name]["sum_s"] += seconds
    return {"t": 1.0, "stalls": seen, "events": list(events),
            "lock_wait": {"count": count, "sum_s": sum_s, "max_s": max_s}}


def _front(**sum_ns):
    gaps = {kind: {"count": int(bool(sum_ns.get(kind))),
                   "sum_ns": sum_ns.get(kind, 0)}
            for kind in ("starved", "silent", "io")}
    return types.SimpleNamespace(
        client={"duration_s": 50.0},
        native=types.SimpleNamespace(gaps=lambda: gaps))


def _lines(capsys) -> list:
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


EVENT = {"cause": "host", "span": "fold", "nested": "fold.signature",
         "pump": 1, "seconds": 1.52, "lock_late_s": 0.001,
         "starved_s": 0.0, "silent_s": 0.0, "io_s": 0.0, "t0_ns": 1,
         "t1_ns": 2, "others": [], "n": 1, "stacks": [
             {"thread": "mixer-native-pump-1",
              "frames": [f"frame{i}" for i in range(12)]}]}
# the heartbeat woke late and no pump was stalled: it names no span.
# Once in the client's window (begin at t = 1 s, 50 s long), once while
# the harness reduced the trace
LATE = EVENT | {"cause": "process", "span": None, "nested": None,
                "pump": None, "lock_late_s": 4.6, "seconds": 4.6,
                "t0_ns": 20_000_000_000, "t1_ns": 24_600_000_000}
AFTER = LATE | {"cause": "lock", "t0_ns": 70_000_000_000,
                "t1_ns": 70_500_000_000}


@pytest.mark.parametrize("stalls, events, value", [
    ((), (), 0.0),                              # none: 0.0, not nothing
    ((), (LATE, AFTER), 0.0),                   # no span: not in the sum
    ((("fold", 1.52),), (EVENT,), 1.52),
    ((("fold", 1.5), ("take_wait", 0.25)), (EVENT, AFTER), 1.75)])
def test_pump_stall_s_sums_the_spans_and_prints_the_events(
        monkeypatch, capsys, stalls, events, value):
    monkeypatch.setattr(
        monitor, "pump_watch_snapshot",
        lambda since=None: _watch(stalls, events), raising=False)
    reader = READERS["pump_stall_s"]
    got = reader.read(_front(), reader.begin(_front()))
    assert got == pytest.approx(value) and isinstance(got, float)
    (line,) = _lines(capsys)
    assert line["phase"] == "stalls"
    assert [(e["cause"], e["span"], e["after_the_client"])
            for e in line["events"]] == [
        (e["cause"], e["span"], e is AFTER) for e in events]
    for shown in line["events"]:
        assert shown["stacks"][0]["frames"] == [f"frame{i}" for i in range(5)]
        assert "t0_ns" not in shown


@pytest.mark.parametrize("count, sum_s, value", [
    (0, 0.0, None), (1000, 0.5, 0.5), (990, 1.98, 2.0)])
def test_lock_wait_ms_mean_divides_and_prints_the_max(
        monkeypatch, capsys, count, sum_s, value):
    monkeypatch.setattr(
        monitor, "pump_watch_snapshot",
        lambda since=None: _watch(count=count, sum_s=sum_s, max_s=1.25),
        raising=False)
    reader = READERS["lock_wait_ms_mean"]
    got = reader.read(_front(), reader.begin(_front()))
    assert got == (None if value is None else pytest.approx(value))
    if value is not None:
        assert _lines(capsys) == [
            {"phase": "lock_wait", "samples": count, "max_s": 1.25}]


def test_lock_wait_ms_mean_reads_the_clients_window_not_the_harnesss(
        monkeypatch, capsys):
    """Not begin -> read: the harness stops and reduces the trace under
    the lock before readers run, and those heartbeats are not the
    server's."""
    clock = [100.0]

    def snapshot(since=None):       # 20 s a call, 20 heartbeats a second
        seen = _watch(count=int(20 * (clock[0] - 100.0)),
                      sum_s=0.01 * (clock[0] - 100.0))
        seen["t"] = clock[0]
        clock[0] += 20.0
        return seen

    monkeypatch.setattr(monitor, "pump_watch_snapshot", snapshot,
                        raising=False)
    reader, ctx = READERS["lock_wait_ms_mean"], _front()
    monkeypatch.setattr(reader, "PERIOD_S", 0.01)
    ctx.client = {}                 # as run.py has it at begin
    token = reader.begin(ctx)       # at t = 100 s
    while len(token[2]) < 6:        # kept at 120, 140, ... 220 s
        time.sleep(0.01)
    ctx.client = {"duration_s": 50.0}
    assert reader.read(ctx, token) == pytest.approx(0.5)
    # the first snapshot past 150 s, not the last one kept
    assert _lines(capsys) == [
        {"phase": "lock_wait", "samples": 1200, "max_s": 0.004}]


@pytest.mark.parametrize("name, kind", [("front_starved_s", "starved"),
                                        ("client_silent_s", "silent")])
@pytest.mark.parametrize("moved_ns, value", [(0, 0.0),
                                             (1_500_000_000, 1.5)])
def test_the_gap_readers_delta_their_kind_alone(capsys, name, kind,
                                                moved_ns, value):
    reader = READERS[name]
    other = "silent" if kind == "starved" else "starved"
    base = reader.begin(_front(**{kind: 250_000_000, other: 7}))
    got = reader.read(_front(**{kind: 250_000_000 + moved_ns,
                                other: 9_000_000_000}), base)
    assert got == value
    lines = _lines(capsys)
    if name == "client_silent_s" and moved_ns:
        assert "harness" in lines[0]["whose"]     # and says so
    else:
        assert lines == []


def test_gc_young_share_is_the_young_sum_over_the_readers_wall(monkeypatch):
    reader = READERS["gc_young_share"]
    young = {"count": 10, "sum_s": 0.25}
    monkeypatch.setattr(
        monitor, "gc_pause_snapshot",
        lambda since=None: {"count": 0, "sum_s": 0.0, "young": dict(young)})
    clock = iter([100.0, 150.0])
    monkeypatch.setattr(reader.time, "perf_counter", lambda: next(clock))
    assert reader.read(None, reader.begin(None)) == pytest.approx(0.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_reads_nothing(monkeypatch, name):
    monkeypatch.delattr(monitor, "pump_watch_snapshot", raising=False)
    monkeypatch.setattr(monitor, "gc_pause_snapshot",
                        lambda since=None: {"count": 0, "sum_s": 0.0,
                                            "frozen": 0, "settles": {}})
    ctx = types.SimpleNamespace(native=types.SimpleNamespace(),
                                client={"duration_s": 50.0})
    reader = READERS[name]
    assert reader.read(ctx, reader.begin(ctx)) is None


def test_the_readers_read_the_program_as_it_is():
    if not hasattr(monitor, "pump_watch_snapshot"):
        pytest.skip("a program from before the pump watch")
    monitor.pump_watch_start()
    try:
        ctx = _front()
        ctx.client = {}         # as run.py has it until the client ends
        tokens = {n: READERS[n].begin(ctx) for n in NAMES}
        time.sleep(0.25)        # a few heartbeats of 50 ms
        ctx.client = {"duration_s": 0.25}
        values = {n: READERS[n].read(ctx, tokens[n]) for n in NAMES}
    finally:
        monitor.pump_watch_stop()
    assert values["pump_stall_s"] == 0.0
    assert values["front_starved_s"] == values["client_silent_s"] == 0.0
    assert values["lock_wait_ms_mean"] >= 0.0
    assert values["gc_young_share"] >= 0.0


def test_the_manifest_names_the_five_in_the_six_cells():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    assert cells[:6] == CELLS
    entries = {m["name"]: m | {"workloads": m["workloads"][:6]}
               for m in manifest["per_layer"] if m["name"] in NAMES}
    common = {"better": "lower", "moves": "check_rate", "workloads": CELLS}
    assert entries == {
        "pump_stall_s": common | {
            "name": "pump_stall_s", "unit": "s",
            "source": "program_span", "layer": "pump"},
        "lock_wait_ms_mean": common | {
            "name": "lock_wait_ms_mean", "unit": "ms",
            "source": "program_counter", "layer": "pump"},
        "front_starved_s": common | {
            "name": "front_starved_s", "unit": "s",
            "source": "program_counter", "layer": "wire front"},
        "client_silent_s": common | {
            "name": "client_silent_s", "unit": "s",
            "source": "program_counter", "layer": "wire front"},
        "gc_young_share": common | {
            "name": "gc_young_share", "unit": "%",
            "source": "program_counter", "layer": "pump"}}
    for name in NAMES:
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").exists()
