"""benchmark/run.py — one cell of BENCHMARK.json, measured on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip. It serves in-process as
chip_smoke.py does (RuntimeServer -> blocking prewarm of the
deployment's own step shapes -> NativeMixerServer), proves the
verdicts exact outside the window, then drives the loopback socket
with the benchmark's own C++ client (a child that never touches JAX)
for `--seconds`. Progress is one JSON line per phase; the last line of
stdout is the result the contract fixes (README.md).

The cell's configuration, traffic mix and per-layer readers are files
found by the names in BENCHMARK.json; nothing here knows a cell.

Without a TPU it measures nothing: exit code 2 and no result line.
`--smoke` rehearses every phase at the configuration's `smoke` sizes
on whatever JAX finds; off a TPU it prints counts and parity only,
`correct: false`, exit code 1 — never a rate or a device number.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from process start

import argparse
import importlib.util
import json
import struct
import subprocess
import sys
import tempfile
import traceback
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

import observe  # noqa: E402  (sibling: sys.path[0] is this directory)

METHODS = {"Check": "/istio.mixer.v1.Mixer/Check"}
PARITY_WIRE = 512
TRACE_S = 3.0
UNMOVED = ("fallback_total", "device_retries_total",
           "batch_failures_total", "shed_total")


class BenchFailure(RuntimeError):
    """One of the run's own checks did not hold: `correct` is false."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "at_s": round(
        time.perf_counter() - T0, 2), **fields}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve_cell(name: str, smoke: bool) -> types.SimpleNamespace:
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in manifest["workloads"] if w["name"] == name),
                None)
    if cell is None:
        sys.exit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    sizes = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads(
        (HERE / "mixes" / f"{cell['traffic']}.json").read_text())
    if smoke:
        sizes.update(sizes["smoke"])
        mix["depth"] = min(mix["depth"], 2 * sizes["max_batch"])
        mix["distinct_requests"] = 2048

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return types.SimpleNamespace(
        chips=cell["chips"], sizes=sizes, mix=mix,
        config=load_module(HERE / "configs" / f"{sizes['module']}.py"),
        end_to_end=[m for m in manifest["end_to_end"] if reported(m)],
        per_layer=[m for m in manifest["per_layer"] if reported(m)])


def build_client() -> Path:
    """g++ the client into benchmark/build/ unless it is there and
    newer than its sources."""
    sources = [HERE / "client" / "h2load.cpp", HERE / "client" / "h2_frame.h"]
    out = HERE / "build" / "h2load"
    if not out.exists() or out.stat().st_mtime < max(
            s.stat().st_mtime for s in sources):
        out.parent.mkdir(exist_ok=True)
        subprocess.run(["g++", "-O2", "-std=c++17", "-o", str(out),
                        str(sources[0])], check=True)
    return out


def prewarm(plan, buckets) -> None:
    """Blocking prewarm of every step shape the deployment can serve,
    one at a time so each shape's seconds and cache hits print."""
    from istio_tpu.compiler import cache as compile_cache

    for pair in plan.all_warm_shapes(buckets):
        ev0 = compile_cache.cache_event_counts()
        t0 = time.perf_counter()
        plan.warm_shapes([pair])
        ev1 = compile_cache.cache_event_counts()
        say("prewarm", shape=f"{pair[0]}x{pair[1]}",
            seconds=round(time.perf_counter() - t0, 2),
            cache_hits=ev1["hits"] - ev0["hits"],
            cache_misses=ev1["misses"] - ev0["misses"])
        require(not plan.swap_warm_pending(plan._dummy_batch(*pair)),
                f"step shape {pair} still warm-pending after a blocking "
                "prewarm: the host oracle would serve it")


def statuses(responses) -> list[int]:
    return [int(r.status_code) for r in responses]


def agree(what: str, **sides) -> None:
    """Every named list of statuses equals the others, row for row."""
    names = list(sides)
    rows = list(zip(*sides.values()))
    bad = [i for i, row in enumerate(rows) if len(set(row)) > 1]
    require(not bad, f"{what}: {len(bad)}/{len(rows)} rows differ, first "
            f"{[dict(zip(names, rows[i]), row=i) for i in bad[:5]]}")


def parity(port: int, srv, requests: list, expected_status, top: int) -> None:
    """Outside the window. PARITY_WIRE distinct requests through the
    socket (MixerClient, check cache off), then one top-bucket batch
    through the pump's own entry: wire status == the configuration's
    plain reference == Dispatcher.check_host_oracle, row for row."""
    from concurrent.futures import ThreadPoolExecutor

    from istio_tpu.api import MixerClient
    from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.runtime.batcher import pad_to_bucket

    distinct = list({json.dumps(d, sort_keys=True): d
                     for d in requests}.values())
    wire, big = distinct[:PARITY_WIRE], distinct[PARITY_WIRE:PARITY_WIRE + top]
    require(len(big) == top, f"{len(distinct)} distinct requests cannot "
            f"fill {PARITY_WIRE} + a {top}-row batch")
    oracle = srv.controller.dispatcher.check_host_oracle
    client = MixerClient(f"127.0.0.1:{port}", enable_check_cache=False)
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            replies = list(pool.map(client.check, wire))
    finally:
        client.close()
    got = [int(r.precondition.status.code) for r in replies]
    agree("wire parity", wire=got,
          reference=[expected_status(d) for d in wire],
          oracle=statuses(oracle([bag_from_mapping(d) for d in wire])))
    hist = {code: got.count(code) for code in sorted(set(got))}
    require(len(hist) > 1, f"parity set is one-sided: {hist}")
    say("parity_wire", requests=len(wire), mismatches=0, status_hist=hist)

    bags = [srv.preprocess(LazyWireBag(
        bag_to_compressed(d).SerializeToString())) for d in big]
    padded = pad_to_bucket(bags, (top,))
    agree("top-bucket parity",
          device=statuses(srv.check_batch_preprocessed(padded)[:top]),
          reference=[expected_status(d) for d in big],
          oracle=statuses(oracle([bag_from_mapping(d) for d in big])))
    say("parity_top_bucket", rows=top, bucket=len(padded), mismatches=0)


def write_payloads(requests: list, mix: dict, quota_name, out) -> None:
    """Serialized CheckRequests, u32-length-prefixed, for the client."""
    from istio_tpu.api import mixer_pb2 as pb
    from istio_tpu.api.wire import bag_to_compressed
    from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST

    every = mix["quota_every"]
    for i, values in enumerate(requests):
        req = pb.CheckRequest(global_word_count=len(GLOBAL_WORD_LIST))
        bag_to_compressed(values, msg=req.attributes)
        if every and quota_name and i % every == 0:
            req.quotas[quota_name].amount = 1
            req.quotas[quota_name].best_effort = True
        raw = req.SerializeToString()
        out.write(struct.pack("<I", len(raw)) + raw)
    out.flush()


def run_window(client_bin: Path, port: int, payloads: str, mix: dict,
               seconds: float, ctx, readers: dict, trace_dir) -> dict:
    """The client's warm-up, then its window. Baselines are taken the
    instant the client says it records; everything is read back after
    the client has exited and the server has drained."""
    import jax

    from istio_tpu.runtime import monitor

    res0 = monitor.resilience_counters()
    proc = subprocess.Popen(
        [str(client_bin), str(port), payloads, str(seconds),
         str(mix["depth"]), str(mix["warmup_s"]), METHODS[mix["rpc"]]],
        stdout=subprocess.PIPE, text=True)
    try:
        first = proc.stdout.readline()
        require('"recording"' in first, "the client ended in its warm-up")
        ctx.setup_s = time.perf_counter() - T0
        tokens = {name: reader.begin(ctx) for name, reader in readers.items()
                  if hasattr(reader, "begin")}
        if trace_dir is not None:
            span = min(TRACE_S, seconds / 2)
            time.sleep((seconds - span) / 2)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host python untouched
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            time.sleep(span)
            jax.profiler.stop_trace()
        out, _ = proc.communicate(timeout=seconds + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    require(proc.returncode == 0, f"client exit code {proc.returncode}")
    ctx.client = json.loads(out.strip().splitlines()[-1])
    deadline = time.monotonic() + 10.0
    while ctx.native.counters()["in_flight"] and time.monotonic() < deadline:
        time.sleep(0.01)
    require(not ctx.native.counters()["in_flight"],
            "the server did not drain within 10 s of the client's exit")
    res1 = monitor.resilience_counters()
    if trace_dir is not None:
        path = observe.find_trace(str(trace_dir))
        ctx.trace = observe.reduce_trace(path) if path else None
        require(ctx.trace is not None, "the trace holds no device program")
    values = {name: reader.read(ctx, tokens.get(name))
              for name, reader in readers.items()}
    if ctx.on_chip:
        say("window", client=ctx.client, setup_s=round(ctx.setup_s, 3),
            layers={k: v for k, v in values.items() if v is not None})
    else:   # a rehearsal prints counts, never a rate or a time
        say("window", attempted=ctx.client["attempted"],
            failed=ctx.client["failed"],
            layers_read=sorted(k for k, v in values.items() if v is not None))
    moved = {k: res1[k] - res0[k] for k in UNMOVED}
    require(not any(moved.values()) and res1["breaker_state"] == 0,
            f"a request left the device path or was shed: {moved}, "
            f"breaker_state={res1['breaker_state']}")
    require(ctx.client["failed"] == 0 and ctx.client["attempted"] > 0,
            f"client: {ctx.client['failed']} failed of "
            f"{ctx.client['attempted']}")
    return values


def serve_and_measure(cell, args, ctx) -> dict:
    from istio_tpu.api.native_server import NativeMixerServer
    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.runtime import RuntimeServer, ServerArgs

    sizes, mix = cell.sizes, cell.mix
    buckets, top = tuple(sizes["buckets"]), sizes["max_batch"]
    client_bin = build_client()
    t0 = time.perf_counter()
    srv = RuntimeServer(cell.config.make_store(sizes), ServerArgs(
        default_manifest={k: GLOBAL_MANIFEST[k] for k in sizes["manifest"]},
        buckets=buckets, max_batch=top, initial_prewarm=False))
    native = None
    try:
        plan = srv.controller.dispatcher.fused
        require(plan is not None and plan.native is not None,
                "no fused plan with a native tensorizer: another path "
                "than the one under test would serve")
        say("build", seconds=round(time.perf_counter() - t0, 2),
            rule_rows=int(plan.engine.ruleset.rule_ns.shape[0]),
            buckets=list(buckets), str_tiers=list(plan.str_tiers))
        prewarm(plan, buckets)
        native = NativeMixerServer(srv, max_batch=top)
        port = native.start()
        requests = cell.config.make_requests(
            sizes, mix["distinct_requests"], args.seed)
        parity(port, srv, requests, cell.config.reference(sizes), top)
        ctx.srv, ctx.native = srv, native
        names = [m["name"] for m in cell.per_layer] if args.trace else []
        readers = {n: load_module(HERE / "layer_metrics" / f"{n}.py")
                   for n in names}
        with tempfile.NamedTemporaryFile(suffix=".bin") as payloads, \
                tempfile.TemporaryDirectory() as trace_dir:
            write_payloads(requests, mix, sizes["quota_name"], payloads)
            layers = run_window(
                client_bin, port, payloads.name, mix, args.seconds, ctx,
                readers,
                Path(trace_dir) if args.trace and ctx.on_chip else None)
    finally:
        if native is not None:
            native.stop()
        srv.close()
    if args.trace:
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        return {k: {"value": v, "unit": units[k]}
                for k, v in layers.items() if v is not None}
    taken = {"check_rate": ctx.client["check_rate"],
             "check_p50_ms": ctx.client["p50_ms"],
             "check_p99_ms": ctx.client["p99_ms"], "setup_s": ctx.setup_s}
    return {m["name"]: {"value": taken[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    cell = resolve_cell(args.workload, args.smoke)

    import jax

    from istio_tpu.compiler import cache as compile_cache

    cache_dir = compile_cache.configure_persistent_cache()
    compile_cache.install_event_counters()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu" and len(devs) >= cell.chips
    if not on_chip and not args.smoke:
        print(f"no TPU with {cell.chips} chip(s): jax sees {device}; "
              "nothing is measured off the chip", file=sys.stderr)
        return 2
    say("device", **device, jax=jax.__version__, cache_dir=cache_dir,
        smoke=args.smoke)
    ctx = types.SimpleNamespace(client={}, trace=None, setup_s=None,
                                on_chip=on_chip)
    metrics, correct = {}, False
    try:
        metrics = serve_and_measure(cell, args, ctx)
        correct = on_chip
    except Exception:   # the boundary: report the run as not correct
        traceback.print_exc()
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    result = {"correct": correct,
              "attempted": ctx.client.get("attempted", 0),
              "failed": ctx.client.get("failed", 0),
              "metrics": metrics if correct else {},
              "device": device}
    if ctx.trace is not None and correct:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {k: ctx.trace[k]
                               for k in ("device_ops", "idle_gaps")}
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
