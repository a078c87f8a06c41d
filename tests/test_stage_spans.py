"""The one span API of the served Check path (monitor.stage /
monitor.span): one `with` feeds the histogram, the forensics stage tap
and the zipkin tracer, and holds a `mixer/<name>` TraceAnnotation on the
profiler's clock; the native pump's cycle is tiled by top-level spans;
full garbage collections and the C++ queue wait are counted.
"""
import gc
import glob
import os
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from istio_tpu.api import MixerClient
from istio_tpu.api.native_server import NativeMixerServer
from istio_tpu.runtime import MemStore, RuntimeServer, ServerArgs, monitor
from istio_tpu.utils import tracing

PUMP_SPANS = ("take_wait", "wire_decode", "serialize", "send")
# the native front forms its batches in C++: its wait for a pump is
# queue_wait(), not the batcher fronts' `queue_wait` stage
NATIVE_STAGES = tuple(s for s in monitor.CHECK_STAGES if s != "queue_wait")


@pytest.fixture
def taps():
    """A recording stage tap and a MemoryReporter tracer, both undone."""
    marks = []
    prev_tap, prev_tracer = monitor._STAGE_TAP, tracing._global
    mem = tracing.MemoryReporter()
    monitor.set_stage_tap(lambda stage, s: marks.append((stage, s)))
    tracing._global = tracing.Tracer(reporter=mem)
    yield marks, mem
    monitor.set_stage_tap(prev_tap)
    tracing._global = prev_tracer


def _stage_count(stage):
    return monitor.CHECK_STAGE_SECONDS.count(stage=stage)


def test_stage_feeds_histogram_tap_and_tracer_from_one_call(taps):
    marks, mem = taps
    before = _stage_count("tensorize")
    with monitor.stage("tensorize", batch=3) as held:
        time.sleep(0.002)
    assert _stage_count("tensorize") == before + 1
    assert held.seconds >= 0.002
    assert marks == [("tensorize", held.seconds)]
    (span,) = mem.spans
    assert span["name"] == "serve.tensorize"
    assert span["tags"] == {"batch": "3"}
    assert span["duration"] == int(held.seconds * 1e6)


@pytest.mark.parametrize("kind", ["stage", "span"])
def test_a_block_that_raises_observes_nothing_and_leaks_nothing(taps, kind):
    marks, mem = taps
    name = "tensorize" if kind == "stage" else "device"
    enter = getattr(monitor, kind)
    base = monitor.stage_baseline()
    with pytest.raises(ValueError, match="boom"):
        with enter(name):
            raise ValueError("boom")
    seen = monitor.latency_snapshot(since=base)
    assert name not in seen["stages"] and name not in seen["spans"]
    assert marks == []
    assert [s["tags"].get("error") for s in mem.spans] == ["boom"]
    with enter(name):      # the site still works afterwards
        pass
    seen = monitor.latency_snapshot(since=base)
    assert seen["stages" if kind == "stage" else "spans"][name]["count"] == 1


def test_off_times_nothing(taps):
    marks, mem = taps
    base = monitor.stage_baseline()
    with monitor.stage("h2d", on=False), monitor.span("x.off", on=False):
        pass
    seen = monitor.latency_snapshot(since=base)
    assert "h2d" not in seen["stages"] and "x.off" not in seen["spans"]
    assert marks == [] and mem.spans == []


def test_new_names_land_in_spans_never_in_stages(taps):
    marks, mem = taps
    base = monitor.stage_baseline()
    with monitor.stage("fold"):
        with monitor.span("dispatch.step"):
            pass
        with monitor.span("grant", tap=True):
            pass
    seen = monitor.latency_snapshot(since=base)
    assert set(seen["stages"]) == {"fold"}
    assert set(seen["spans"]) == {"dispatch.step", "grant"}
    assert seen["spans"]["grant"]["count"] == 1
    assert seen["spans"]["grant"]["sum_ms"] <= seen["stages"]["fold"]["sum_ms"]
    # only stages and tapped spans mark the flight recorder's tape, and
    # only the three legacy names reach the zipkin tracer
    assert [m[0] for m in marks] == ["grant", "fold"]
    assert mem.spans == []
    # a later window starts from zero
    assert monitor.latency_snapshot(
        since=monitor.stage_baseline())["spans"] == {}


@pytest.mark.parametrize("generation, pauses", [(2, 1), (0, 0), (1, 0)])
def test_gc_hook_counts_full_collections_only(generation, pauses):
    monitor.install_gc_hook()
    try:
        gc.collect()                      # settle the young generations
        before = monitor.gc_pause_snapshot()
        gc.collect(generation)
        moved = monitor.gc_pause_snapshot(since=before)
    finally:
        monitor.remove_gc_hook()
    assert moved["count"] == pauses
    assert (moved["sum_s"] > 0) == bool(pauses)


def test_gc_hook_is_refcounted_and_removed():
    hooks = gc.callbacks.count(monitor._on_gc)
    monitor.install_gc_hook()
    monitor.install_gc_hook()
    assert gc.callbacks.count(monitor._on_gc) == hooks + 1
    monitor.remove_gc_hook()
    assert gc.callbacks.count(monitor._on_gc) == hooks + 1
    monitor.remove_gc_hook()
    assert gc.callbacks.count(monitor._on_gc) == hooks


def test_compile_phase_seconds_move_with_a_compile():
    import jax
    import jax.numpy as jnp

    from istio_tpu.compiler import cache as compile_cache

    compile_cache.install_event_counters()
    before = compile_cache.phase_seconds()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    after = compile_cache.phase_seconds()
    assert set(after) == {"trace_s", "lower_s", "backend_s"}
    assert all(after[k] > before[k] for k in after)


def test_compile_cache_keeps_what_the_benchmark_prewarm_reads():
    # benchmark/run.py's prewarm reads the lookup counters around every
    # shape and the set-up readers read the phases: a tree without one
    # of them fails every cell before its first request
    from istio_tpu.compiler import cache as compile_cache

    assert set(compile_cache.cache_event_counts()) == {"hits", "misses"}
    assert callable(compile_cache.phase_seconds)


@pytest.mark.parametrize("arrivals, union", [
    # a nested trace closes before the one that holds it: counted once
    ([(1.0, 2.0), (3.0, 4.0), (0.0, 5.0)], [(0.0, 5.0)]),
    # disjoint spans stay apart, touching ones join
    ([(0.0, 1.0), (2.0, 3.0), (3.0, 4.0)], [(0.0, 1.0), (2.0, 4.0)]),
    # another thread's span arrives late and lies before the tail
    ([(0.0, 1.0), (6.0, 7.0), (2.0, 3.0)],
     [(0.0, 1.0), (2.0, 3.0), (6.0, 7.0)]),
    ([(0.0, 1.0), (6.0, 7.0), (0.5, 3.0)], [(0.0, 3.0), (6.0, 7.0)]),
])
def test_compile_phase_spans_merge_as_they_arrive(arrivals, union):
    from istio_tpu.compiler import cache as compile_cache

    spans: list = []
    for start, end in arrivals:
        compile_cache._merge_span(spans, start, end)
    assert spans == union


def _store() -> MemStore:
    s = MemStore()
    s.set(("handler", "istio-system", "deny"), {
        "adapter": "denier", "params": {"status_code": 7}})
    s.set(("instance", "istio-system", "nothing"), {
        "template": "checknothing", "params": {}})
    s.set(("rule", "istio-system", "r0"), {
        "match": 'request.path.startsWith("/admin")',
        "actions": [{"handler": "deny", "instances": ["nothing"]}]})
    return s


@pytest.fixture(scope="module")
def front():
    runtime = RuntimeServer(_store(), ServerArgs(batch_window_s=0.001,
                                                 max_batch=64))
    native = NativeMixerServer(runtime, max_batch=64, min_fill=8,
                               window_us=500)
    client = MixerClient(f"127.0.0.1:{native.start()}",
                         enable_check_cache=False)
    yield native, client
    client.close()
    native.stop()
    runtime.close()


def _burst(client, n=96):
    requests = [{"request.path": ("/admin/" if i % 3 == 0 else "/ok/")
                 + str(i), "destination.service": "a.b.svc"}
                for i in range(n)]
    with ThreadPoolExecutor(max_workers=16) as pool:
        replies = list(pool.map(client.check, requests))
    assert [r.precondition.status.code for r in replies] == \
        [7 if i % 3 == 0 else 0 for i in range(n)]


def test_served_burst_tiles_the_pump_cycle(front):
    native, client = front
    base = monitor.stage_baseline()
    _burst(client)
    # a cycle is observed when its completions have gone out, which is
    # a moment after the last reply is read
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        seen = monitor.latency_snapshot(since=base)
        cycles = seen["spans"].get("pump_cycle", {"count": 0})["count"]
        # no cycle still open: every take that returned rows has closed
        if cycles and cycles == seen["spans"].get(
                "take_wait", {"count": 0})["count"]:
            break
        time.sleep(0.01)
    stages, spans = seen["stages"], seen["spans"]
    assert set(NATIVE_STAGES) <= set(stages), stages
    assert "queue_wait" not in stages
    for name in PUMP_SPANS:
        assert spans[name]["count"] == cycles, (name, spans)
    for name in NATIVE_STAGES:
        assert stages[name]["count"] == cycles, (name, stages)
    inside = sum(spans[n]["sum_ms"] for n in PUMP_SPANS) + \
        sum(stages[n]["sum_ms"] for n in NATIVE_STAGES)
    assert 0 < inside <= spans["pump_cycle"]["sum_ms"]
    # the sub-spans split their stage
    for parts, whole in ((("tensorize.decode", "tensorize.ns_ids"),
                          stages["tensorize"]),
                         (("dispatch.step",), stages["h2d"]),
                         (("fold.signature",), stages["fold"])):
        assert all(spans[p]["count"] == cycles for p in parts), spans
        assert sum(spans[p]["sum_ms"] for p in parts) <= \
            whole["sum_ms"] + 1e-3
    # one program a Check batch: nothing is launched behind the step
    assert "dispatch.rulestats" not in spans
    assert "dispatch.pack" not in spans
    # the tracer's grouping spans are off with no reporter configured
    assert not monitor.zipkin_on()
    assert "device" not in spans and "overlay" not in spans


@pytest.mark.parametrize("path, programs, behind_the_step", [
    # FusedPlan.packed_check: step, rule-telemetry fold and packer are
    # one jit, so `dispatch.step` is the whole launch
    ("check", 1, ()),
    # an in-step quota batch launches them apart, each under its span
    ("instep", 4, ("dispatch.rulestats", "dispatch.pack")),
])
def test_dispatch_spans_and_counter_say_what_a_batch_launched(
        front, path, programs, behind_the_step):
    import jax.numpy as jnp
    import numpy as np

    from istio_tpu.runtime.device_quota import _TICKS_PER_WINDOW

    plan = front[0].runtime.controller.dispatcher.fused
    assert plan.mesh is None and plan.telemetry is not None
    b = 8
    batch, ns = plan._dummy_batch(b, min(plan.str_tiers)), \
        np.zeros(b, np.int32)
    zeros = {k: np.zeros(b, np.int32) for k in (
        "buckets", "amounts", "mx", "ticks", "lasts")}
    flags = {k: np.zeros(b, bool) for k in ("be", "active", "rolling")}
    q = {**zeros, **flags, "rule_idx": np.full(b, -1, np.int32)}
    counts = jnp.zeros((4, _TICKS_PER_WINDOW), jnp.int32)

    def trip(n_real, observe=True):
        if path == "check":
            plan.packed_check(batch, ns, observe=observe, n_real=n_real)
        else:
            np.asarray(plan.packed_check_instep(
                batch, ns, q, counts, n_real=n_real)[0])

    # a dummy trip (prewarm) compiles, and neither counts nor is timed
    before = monitor.device_program_counters()
    base = monitor.stage_baseline()
    trip(0, observe=False)
    assert monitor.device_program_counters() == before
    assert not monitor.latency_snapshot(since=base)["spans"]
    trip(b)
    spans = monitor.latency_snapshot(since=base)["spans"]
    assert set(spans) == {"dispatch.step", *behind_the_step}, spans
    after = monitor.device_program_counters()
    assert {k: after[k] - before[k] for k in after} == {
        **dict.fromkeys(after, 0), path: programs}


def test_zipkin_groups_exist_only_under_a_reporter(front, taps):
    native, client = front
    marks, mem = taps
    base = monitor.stage_baseline()
    _burst(client, n=32)
    seen = monitor.latency_snapshot(since=base)
    stages, spans = seen["stages"], seen["spans"]
    assert spans["device"]["sum_ms"] >= \
        stages["h2d"]["sum_ms"] + stages["device_step"]["sum_ms"] - 1e-3
    assert spans["overlay"]["sum_ms"] >= \
        stages["fold"]["sum_ms"] + stages["respond"]["sum_ms"] - 1e-3
    assert {"serve.tensorize", "serve.device", "serve.overlay"} <= \
        {s["name"] for s in mem.spans}
    # the dedup's span says how many (referenced, presence) objects
    # its batch shares
    keyed = [s["tags"] for s in mem.spans
             if s["name"] == "serve.fold.signature"]
    assert keyed and all(1 <= int(t["distinct"]) <= int(t["batch"])
                         for t in keyed), keyed


def test_queue_wait_counts_every_row_handed_to_a_pump(front):
    native, client = front
    before, rows_before = native.queue_wait(), native.counters()["batch_rows"]
    _burst(client, n=48)
    after, rows_after = native.queue_wait(), native.counters()["batch_rows"]
    assert after["rows"] - before["rows"] == rows_after - rows_before == 48
    assert after["rows"] == rows_after
    assert after["sum_ns"] > before["sum_ns"]


def test_spans_lie_on_the_profilers_host_plane(front, tmp_path):
    import jax
    from jax.profiler import ProfileData

    native, client = front
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        started = time.monotonic()
        while time.monotonic() - started < 0.3:
            _burst(client, n=32)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert paths, "the profiler wrote no xplane file"
    names = {event.name
             for plane in ProfileData.from_file(paths[0]).planes
             for line in plane.lines for event in line.events
             if event.name.startswith("mixer/")}
    if not names:
        pytest.skip("this backend's profiler records no host TraceMe "
                    "plane: no mixer/* event in the capture")
    assert {"mixer/pump_cycle", "mixer/tensorize", "mixer/h2d",
            "mixer/device_step", "mixer/serialize"} <= names, names
