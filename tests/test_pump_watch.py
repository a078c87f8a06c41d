"""The pump watch (monitor.pump_watch_start): each pump's open span in
a slot, a heartbeat that measures the wait for the interpreter lock,
the C++ front's gap counters, and one `pump.stall` event a stall with
a cause.

No test here bounds a wall from above: a count is equal, a duration is
at least what was slept. A scenario that the machine itself disturbed
(a heartbeat late under six busy workers) proves nothing and is run
again.
"""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.runtime import RuntimeServer, ServerArgs, monitor
from istio_tpu.testing import workloads
from istio_tpu.utils import metrics as hostmetrics

WAIT_S = 30.0       # how long a test waits for what must come


def _until(what, poll_s: float = 0.005):
    deadline = time.monotonic() + WAIT_S
    while time.monotonic() < deadline:
        got = what()
        if got:
            return got
        time.sleep(poll_s)
    return what()


def _events(base: dict, pump: int) -> list:
    return [e for e in monitor.pump_watch_snapshot(since=base)["events"]
            if e.get("pump") == pump]


def _as_pump(pump: int, body) -> None:
    """Run `body` on a thread registered as pump number `pump`."""
    def run():
        monitor.pump_enter(pump)
        try:
            with monitor.span("pump_cycle"):
                body()
        finally:
            monitor.pump_leave()

    thread = threading.Thread(target=run, name=f"test-pump-{pump}")
    thread.start()
    thread.join(WAIT_S)
    assert not thread.is_alive()


@pytest.fixture
def watch(monkeypatch):
    """The watch alone in the process, at a fifth of its thresholds."""
    assert monitor._WATCH is None, "an earlier test left a front serving"
    monkeypatch.setattr(monitor, "STALL_S", 0.05)
    monkeypatch.setattr(monitor, "_TICK_S", 0.01)
    monitor.pump_watch_start()
    yield
    monitor.pump_watch_stop()
    assert monitor._WATCH is None


# -- (a) a lent stall: the pump sleeps with the lock on offer ----------

@pytest.mark.parametrize("enter, name, cause", [
    (monitor.span, "tensorize", "host"),
    (monitor.stage, "device_step", "device")])
def test_a_lent_stall_is_one_event_with_its_span_and_cause(
        watch, enter, name, cause):
    def sleep_in_the_span():
        with enter(name):
            with monitor.span(name + ".inner"):
                time.sleep(0.3)

    for _ in range(5):
        base = monitor.pump_watch_snapshot()
        _as_pump(70, sleep_in_the_span)
        events = _until(lambda: _events(base, 70))
        if events and events[0]["lock_late_s"] < monitor.STALL_S:
            break       # else the machine stalled too: again
    (event,) = events
    assert event["span"] == name and event["nested"] == name + ".inner"
    assert event["cause"] == cause
    assert event["seconds"] >= 0.3
    assert event["t1_ns"] - event["t0_ns"] >= 0.3e9
    assert any("sleep_in_the_span" in frame for stack in event["stacks"]
               for frame in stack["frames"])
    moved = monitor.pump_watch_snapshot(since=base)["stalls"]
    assert moved[name]["count"] == 1 and moved[name]["sum_s"] >= 0.3
    assert sum(v["count"] for v in moved.values()) == 1


def test_a_pump_with_nothing_to_take_is_idle_not_stalled(watch):
    """take_wait is the one span whose residence has no bound by
    design: with no row waiting, no silence behind answered work and
    the lock on offer it is no stall, however long (a front before its
    first request; the other pump's turn)."""
    def wait_for_rows():
        with monitor.span("take_wait"):
            time.sleep(0.3)

    for _ in range(5):
        base = monitor.pump_watch_snapshot()
        _as_pump(76, wait_for_rows)
        _until(lambda: monitor.pump_watch_snapshot(
            since=base)["lock_wait"]["count"] > 2)
        seen = monitor.pump_watch_snapshot(since=base)
        if seen["lock_wait"]["max_s"] < monitor.STALL_S:
            break       # else the machine stalled: again
    assert [e for e in seen["events"] if e.get("pump") == 76] == []
    assert seen["stalls"]["take_wait"] == {"count": 0, "sum_s": 0.0}


# -- (b) a held lock: nobody runs python, the heartbeat wakes late -----

def _hold_the_lock(n: int) -> float:
    t0 = time.perf_counter()
    sum(range(n))           # one C call: the lock is never on offer
    return time.perf_counter() - t0


def _hold_then_wait(n: int, held: list, gate) -> None:
    held.append(_hold_the_lock(n))
    gate.wait(WAIT_S)


def _sit_in_a_span_while_the_lock_is_held(n: int, span: str = "fold",
                                          pump: int = 71) -> float:
    """A registered pump sits in `span`; another thread holds the lock
    over sum(range(n)) and then waits in the function that held it, as
    a serving thread would go on in its batch. Returns the hold's
    seconds."""
    in_span, gate = threading.Event(), threading.Event()
    held = []

    def sit_in_a_span():
        with monitor.span(span):
            in_span.set()
            gate.wait(WAIT_S)

    pump = threading.Thread(target=_as_pump, args=(pump, sit_in_a_span))
    pump.start()
    assert in_span.wait(WAIT_S)
    holder = threading.Thread(target=_hold_then_wait, args=(n, held, gate),
                              name="test-lock-holder", daemon=True)
    holder.start()
    assert _until(lambda: held)
    time.sleep(0.05)    # a few ticks: the late one has taken the stacks
    gate.set()
    holder.join(WAIT_S)
    pump.join(WAIT_S)
    return held[0]


def test_a_held_lock_is_a_late_heartbeat_and_names_its_holder(watch):
    n = 20_000_000
    for _ in range(6):      # sized here: ≥ 0.3 s on this machine, now
        base = monitor.pump_watch_snapshot()
        if _sit_in_a_span_while_the_lock_is_held(n) >= 0.3:
            break
        _until(lambda: _events(base, 71))
        n *= 2
    (event,) = _until(lambda: _events(base, 71))
    assert event["span"] == "fold" and event["cause"] == "lock"
    assert event["lock_late_s"] >= monitor.STALL_S
    seen = monitor.pump_watch_snapshot(since=base)
    assert seen["lock_wait"]["max_s"] >= monitor.STALL_S
    assert seen["lock_wait"]["max_s"] >= event["lock_late_s"]
    # taken as the heartbeat woke, of every thread (the holder is a
    # daemon here): where the holder stood just after it let go
    frames = {stack["thread"]: stack["frames"] for stack in event["stacks"]}
    assert any("_hold_then_wait" in f for f in frames["test-lock-holder"])


# -- (c) quiet: short spans are nobody's stall -------------------------

def test_short_spans_on_two_pumps_raise_nothing(watch):
    def many_short_spans():
        for _ in range(500):
            with monitor.span("tensorize"):
                with monitor.span("tensorize.decode"):
                    pass
            with monitor.stage("fold"):
                pass

    for _ in range(5):
        base = monitor.pump_watch_snapshot()
        with ThreadPoolExecutor(2) as pool:
            for pump in (72, 73):
                pool.submit(_as_pump, pump, many_short_spans)
        _until(lambda: monitor.pump_watch_snapshot(
            since=base)["lock_wait"]["count"] > 2)
        seen = monitor.pump_watch_snapshot(since=base)
        if seen["lock_wait"]["max_s"] < monitor.STALL_S:
            break       # else the machine stalled: again
    assert not _events(base, 72) and not _events(base, 73)
    assert all(v == {"count": 0, "sum_s": 0.0}
               for v in seen["stalls"].values())
    assert seen["lock_wait"]["count"] > 0


def test_an_idle_pump_under_a_held_lock_is_no_stall_of_take_wait(watch):
    """A pump with nothing to take sits in take_wait for as long as the
    night is quiet; a lock held meanwhile is the heartbeat's own event,
    for the hold's seconds, and adds no residence to
    mixer_pump_stall_seconds{take_wait}."""
    n = 20_000_000
    for _ in range(6):
        base = monitor.pump_watch_snapshot()
        if _sit_in_a_span_while_the_lock_is_held(n, "take_wait", 77) >= 0.3:
            break
        n *= 2
    late = _until(lambda: [
        e for e in monitor.pump_watch_snapshot(since=base)["events"]
        if e["span"] is None and e["lock_late_s"] >= monitor.STALL_S
        and any("_hold_then_wait" in frame for stack in e["stacks"]
                for frame in stack["frames"])])
    event = late[0]
    assert event["cause"] == "lock" and event["pump"] is None
    assert event["seconds"] == pytest.approx(event["lock_late_s"])
    assert [(o["pump"], o["span"]) for o in event["others"]] == [
        (77, "take_wait")]
    seen = monitor.pump_watch_snapshot(since=base)
    assert not _events(base, 77)
    assert seen["stalls"]["take_wait"] == {"count": 0, "sum_s": 0.0}


class _Front:
    """A front's gaps(), moved by hand."""

    def __init__(self):
        self.ns = dict.fromkeys(("starved", "silent", "io"), 0)

    def __call__(self) -> dict:
        return {kind: {"count": int(ns > 0), "sum_ns": ns}
                for kind, ns in self.ns.items()}


@pytest.mark.parametrize("kind, cause, pump", [
    ("silent", "client", None),     # every pump was idle: nobody's
    ("starved", "front", 78)])      # rows waited while pump 78 asked
def test_a_gap_of_the_fronts_is_a_take_wait_stall_of_its_own_seconds(
        watch, kind, cause, pump):
    """The pump sits in take_wait for 0.6 s; the front reports a gap of
    0.3 s that ended meanwhile: the stall is the gap's 0.3 s, not the
    residence."""
    front = _Front()
    monitor.pump_watch_start(front)

    def wait_for_rows():
        with monitor.span("take_wait"):
            time.sleep(0.5)
            front.ns[kind] += 300_000_000
            time.sleep(0.1)

    try:
        for _ in range(5):
            base = monitor.pump_watch_snapshot()
            _as_pump(78, wait_for_rows)
            events = _until(lambda: _events(base, pump))
            if events and events[0]["lock_late_s"] < monitor.STALL_S:
                break       # else the machine stalled too: again
    finally:
        monitor.pump_watch_stop(front)
    (event,) = events
    assert event["span"] == "take_wait" and event["cause"] == cause
    assert event["seconds"] == pytest.approx(0.3)
    assert event[kind + "_s"] == pytest.approx(0.3)
    moved = monitor.pump_watch_snapshot(since=base)["stalls"]
    assert moved["take_wait"]["count"] == 1
    assert moved["take_wait"]["sum_s"] == pytest.approx(0.3)
    assert sum(v["count"] for v in moved.values()) == 1


def test_rows_that_waited_while_no_pump_asked_are_no_stall_of_a_span(watch):
    """Pumps away in one short span after another leave rows waiting
    and no span to blame: the front's counter has the seconds, no
    event is made."""
    front = _Front()
    monitor.pump_watch_start(front)
    try:
        base = monitor.pump_watch_snapshot()
        front.ns["starved"] += 300_000_000
        _until(lambda: monitor.pump_watch_snapshot(
            since=base)["lock_wait"]["count"] > 2)
    finally:
        monitor.pump_watch_stop(front)
    seen = monitor.pump_watch_snapshot(since=base)
    assert [e for e in seen["events"] if e["span"] is not None] == []
    assert all(v == {"count": 0, "sum_s": 0.0}
               for v in seen["stalls"].values())


# -- (d) slot discipline ----------------------------------------------

@pytest.fixture
def slot():
    monitor.pump_enter(74)
    yield monitor._PUMP.slot
    monitor.pump_leave()


def _names(entry) -> list:
    names = []
    while entry is not None:
        names.append(entry[0])
        entry = entry[2]
    return names


def test_nested_spans_restore_their_parent(slot):
    with monitor.span("pump_cycle"):
        with monitor.stage("tensorize"):
            with monitor.span("tensorize.decode"):
                assert _names(slot.open) == [
                    "tensorize.decode", "tensorize", "pump_cycle"]
                top, inner = monitor._open_spans(slot.open)
                assert top[0] == "tensorize" and inner == "tensorize.decode"
            assert _names(slot.open) == ["tensorize", "pump_cycle"]
        assert _names(slot.open) == ["pump_cycle"]
        assert monitor._open_spans(slot.open) == (None, "pump_cycle")
    assert slot.open is None


def test_a_span_that_raises_restores_its_parent(slot):
    with monitor.span("pump_cycle"):
        with pytest.raises(ValueError):
            with monitor.stage("fold"):
                raise ValueError("boom")
        assert _names(slot.open) == ["pump_cycle"]
    assert slot.open is None


def test_a_span_that_is_off_writes_nothing(slot):
    with monitor.span("pump_cycle"):
        with monitor.stage("h2d", on=False), monitor.span("x", on=False):
            assert _names(slot.open) == ["pump_cycle"]


def test_a_thread_that_is_no_pump_writes_nothing(slot):
    seen = []

    def not_a_pump():
        with monitor.span("tensorize"):
            seen.append((monitor._PUMP.slot, slot.open))

    thread = threading.Thread(target=not_a_pump)
    thread.start()
    thread.join(WAIT_S)
    assert seen == [(None, None)]
    assert [s.pump for s in monitor._PUMP_SLOTS.values()].count(74) == 1


def test_pump_leave_clears_the_slot():
    monitor.pump_enter(75)
    ident = threading.get_ident()
    assert monitor._PUMP_SLOTS[ident].pump == 75
    monitor.pump_leave()
    assert ident not in monitor._PUMP_SLOTS and monitor._PUMP.slot is None
    monitor.pump_leave()        # a second leave is nothing
    with monitor.span("tensorize"):     # and spans go on working
        pass


@pytest.mark.parametrize("first_out", [0, 1])
def test_two_users_share_one_watch_and_leave_no_thread(first_out):
    assert monitor._WATCH is None
    gaps = [lambda: {}, lambda: {}]
    for reader in gaps:
        monitor.pump_watch_start(reader)
    watchers = [t for t in threading.enumerate()
                if t.name == "mixer-pump-watch"]
    assert len(watchers) == 1           # one a process, two users
    monitor.pump_watch_stop(gaps[first_out])
    assert watchers[0].is_alive() and monitor._WATCH.fronts == (
        gaps[1 - first_out],)
    monitor.pump_watch_stop(gaps[1 - first_out])
    assert monitor._WATCH is None and not watchers[0].is_alive()
    monitor.pump_watch_stop()           # one stop too many is nothing


# -- the cause table, row by row ---------------------------------------

@pytest.mark.parametrize("span, late, io, silent, starved, taking, cause", [
    ("fold", 0.3, 0.4, 0.0, 0.0, False, "process"),
    ("take_wait", 0.2, 0.3, 0.3, 0.0, True, "process"),
    ("fold", 0.3, 0.0, 0.0, 0.0, False, "lock"),
    (None, 1.0, 0.0, 0.0, 0.0, False, "lock"),
    ("take_wait", 0.0, 0.0, 0.5, 0.0, True, "client"),
    ("h2d", 0.1, 0.0, 0.0, 0.0, False, "device"),
    ("device_step", 0.0, 0.0, 0.0, 0.3, True, "device"),
    ("send", 0.0, 0.0, 0.0, 0.0, False, "front"),
    ("take_wait", 0.0, 0.0, 0.0, 0.4, True, "front"),
    ("tensorize", 0.0, 0.0, 0.0, 0.4, True, "front"),
    ("tensorize", 0.0, 0.0, 0.0, 0.4, False, "host"),
    ("take_wait", 0.0, 0.0, 0.0, 0.0, True, "host"),
    ("respond", 0.19, 0.0, 0.0, 0.0, False, "host")])
def test_the_cause_is_the_first_row_that_holds(span, late, io, silent,
                                               starved, taking, cause):
    assert monitor.stall_cause(span, late, io, silent, starved,
                               taking) == cause


def test_the_spans_clock_is_the_fronts():
    """A slot's t0 and an event's t0_ns / t1_ns are perf_counter's;
    the C++ front stamps its rows by CLOCK_MONOTONIC: one clock."""
    assert (time.get_clock_info("perf_counter").implementation
            == time.get_clock_info("monotonic").implementation)
    assert abs(time.perf_counter() - time.monotonic()) < 1.0


# -- (e) the C++ front's gaps, through a real front --------------------

@pytest.fixture(scope="module")
def runtime():
    srv = RuntimeServer(workloads.make_store(12), ServerArgs(
        batch_window_s=0.0005, max_batch=8, buckets=(8,),
        default_manifest=workloads.MESH_MANIFEST))
    plan = srv.controller.dispatcher.fused
    if plan is not None:
        plan.prewarm((8,))
    yield srv
    srv.close()


@pytest.fixture
def front(runtime):
    native = NativeMixerServer(runtime, max_batch=8, min_fill=1,
                               window_us=200)
    client = MixerClient(f"127.0.0.1:{native.port}",
                         enable_check_cache=False)
    yield native, client
    client.close()
    if native._watched:     # started: stop() joins the pumps
        native.stop()
    assert not [t for t in threading.enumerate()
                if t.name == "mixer-pump-watch"]


def _request() -> dict:
    return workloads.make_request_dicts(1)[0]


def test_rows_that_no_pump_took_are_a_starved_gap(front):
    native, client = front
    before = native.gaps()
    assert before["starved"] == {"count": 0, "sum_ns": 0}
    with ThreadPoolExecutor(1) as pool:
        reply = pool.submit(client.check, _request())
        # the C++ server listens from the constructor; no pump takes
        assert _until(lambda: native.counters()["requests_decoded"] >= 1)
        time.sleep(0.3)
        native.start()
        reply.result(WAIT_S)
    after = native.gaps()
    assert after["starved"]["count"] == 1
    assert after["starved"]["sum_ns"] >= 0.3e9
    assert after["silent"] == before["silent"]
    assert after["silent"]["count"] == 0


def test_a_client_that_sends_nothing_is_a_silent_gap(front):
    native, client = front
    native.start()
    base = monitor.pump_watch_snapshot()
    client.check(_request())
    assert native.gaps()["silent"]["count"] == 0    # no response before it
    assert _until(lambda: not native.counters()["in_flight"]) is not None
    time.sleep(0.3)
    client.check(_request())
    gaps = native.gaps()
    assert gaps["silent"]["count"] == 1
    assert gaps["silent"]["sum_ns"] >= 0.3e9
    assert gaps["starved"]["count"] == 0
    # and the python side joins it to the pumps that sat in take_wait
    events = _until(lambda: [
        e for e in monitor.pump_watch_snapshot(since=base)["events"]
        if e["cause"] == "client"])
    assert events and events[-1]["span"] == "take_wait"
    assert sum(e["silent_s"] for e in events) >= 0.3
    # for the silence's own seconds: the pumps' residence in take_wait
    # (since start()) is no stall
    stalled = monitor.pump_watch_snapshot(since=base)["stalls"]["take_wait"]
    assert stalled["sum_s"] == pytest.approx(gaps["silent"]["sum_ns"] / 1e9)


def test_a_stopped_front_answers_gaps_from_its_last_reading(front):
    native, _ = front
    native.start()
    live = native.gaps()
    native.stop()
    assert native.gaps() == live
    assert set(live) == {"starved", "silent", "io"}


# -- (f) exposition ----------------------------------------------------

@pytest.mark.parametrize("family, labelled", [
    ("mixer_lock_wait_seconds", ()),
    ("mixer_gc_young_seconds", ()),
    ("mixer_pump_stall_seconds", monitor.PUMP_TOP_LEVEL)])
def test_the_new_histograms_expose_their_zero_series(family, labelled):
    text = hostmetrics.default_registry.expose_text()
    assert f"# TYPE {family} histogram" in text
    for suffix in ("_bucket", "_sum", "_count"):
        assert f"\n{family}{suffix}" in text
    assert len(monitor.PUMP_TOP_LEVEL) == 10
    for span in labelled:       # there before the first stall
        assert f'{family}_count{{span="{span}"}}' in text
        assert f'{family}_bucket{{le="+Inf",span="{span}"}}' in text
