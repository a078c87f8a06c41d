"""chip_smoke.py — the quickest proof that the served Check path still
starts on the chip.

One process, no platform forced in code. Drives the north-star
deployment end to end through the entry points a user would call —
RuntimeServer + NativeMixerServer in front of Dispatcher._check_fused →
FusedPlan.packed_check → one device step — and checks what comes out
by the repo's own means (the whole-snapshot CPU oracle, the report
conservation ledger, the resilience counters).

    python chip_smoke.py                       # one chip, full size
    JAX_PLATFORMS=cpu python chip_smoke.py --rules 200 --buckets 64,256
                                               # rehearsal: every phase
                                               # runs, can never end ok
    python chip_smoke.py --chips 4             # the mesh_shape=(2, 2)
                                               # path and its one-chip
                                               # comparison, nothing else

Every phase prints one JSON line. Rates are a SMOKE (did it serve, with
what errors), never a measurement: `benchmark/run.py` measures. This
script stays beside it because no benchmark cell sends a quota or a
Report yet: the quota on every fourth Check and the native Report
phase here are the only rehearsal of those two paths on the chip
(ROADMAP Design 1). The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only when every phase passed AND the platform is
`tpu`: a CPU run is never reported as a chip run. A failing phase
raises; nothing catches it and carries on.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

REPORT = "/istio.mixer.v1.Mixer/Report"


class SmokeFailure(RuntimeError):
    """A phase's own check did not hold."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def median(xs: list) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def device_sync_ms(reps: int = 20) -> dict:
    """Median wall of a tiny dispatch + block_until_ready, and of the
    same ending in the device→host pull the served path syncs with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bump = jax.jit(lambda x: x + 1)
    x = bump(jnp.zeros(8, jnp.int32)).block_until_ready()
    sync, pull = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        x = bump(x).block_until_ready()
        sync.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        np.asarray(bump(x))
        pull.append((time.perf_counter() - t0) * 1e3)
    return {"device_sync_ms": median(sync), "device_pull_ms": median(pull),
            "reps": reps}


def prewarm_blocking(plan, buckets) -> float:
    """plan.prewarm, one shape at a time so each shape's compile
    seconds and persistent-cache hits/misses print. Returns total s."""
    from istio_tpu.compiler import cache as compile_cache

    total = 0.0
    for pair in plan.all_warm_shapes(buckets):
        ev0 = compile_cache.cache_event_counts()
        t0 = time.perf_counter()
        plan.warm_shapes([pair])
        dt = time.perf_counter() - t0
        ev1 = compile_cache.cache_event_counts()
        total += dt
        say("prewarm", shape=f"{pair[0]}x{pair[1]}", seconds=round(dt, 2),
            cache_hits=ev1["hits"] - ev0["hits"],
            cache_misses=ev1["misses"] - ev0["misses"])
        require(not plan.swap_warm_pending(plan._dummy_batch(*pair)),
                f"shape {pair} still warm-pending after a blocking "
                "prewarm: it would serve from the CPU oracle bridge")
    return total


def statuses_of(responses) -> list:
    return [int(r.status_code) for r in responses]


def phase_parity(port: int, srv, n: int, buckets) -> None:
    """Exact wire-vs-oracle parity OUTSIDE the load window: n distinct
    requests through the socket (MixerClient, check cache off, every
    4th carrying the standard mix's quota), then a bucket-filling
    batch through the pump's own entry so the LARGEST compiled shape
    is verified too — each compared status for status with
    Dispatcher.check_host_oracle on the same bags."""
    from concurrent.futures import ThreadPoolExecutor

    from istio_tpu.api import MixerClient
    from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.runtime.batcher import pad_to_bucket
    from istio_tpu.testing import workloads

    top = buckets[-1]
    dicts = list({json.dumps(d, sort_keys=True): d for d in
                  workloads.make_request_dicts(n + top, seed=22)}.values())
    wire_dicts, big_dicts = dicts[:n], dicts[n:n + top]
    require(len(wire_dicts) >= n, f"only {len(wire_dicts)} distinct "
            f"parity requests, need {n}")
    disp = srv.controller.dispatcher
    client = MixerClient(f"127.0.0.1:{port}", enable_check_cache=False)
    try:
        def one(i: int):
            quotas = {"rq": 1} if i % 4 == 0 else None
            return client.check(wire_dicts[i], quotas=quotas)

        with ThreadPoolExecutor(max_workers=32) as ex:
            resps = list(ex.map(one, range(n)))
    finally:
        client.close()
    got = [int(r.precondition.status.code) for r in resps]
    want = statuses_of(disp.check_host_oracle(
        [bag_from_mapping(d) for d in wire_dicts]))
    bad = [i for i in range(n) if got[i] != want[i]]
    require(not bad, f"wire parity broke on {len(bad)}/{n} requests, "
            f"first {bad[:5]}: got {[got[i] for i in bad[:5]]} want "
            f"{[want[i] for i in bad[:5]]}")
    granted = [int(r.quotas["rq"].granted_amount)
               for i, r in enumerate(resps)
               if i % 4 == 0 and got[i] == 0]
    require(granted and all(g == 1 for g in granted),
            f"quota grants on OK rows: {granted[:8]} (want all 1)")
    hist = {c: got.count(c) for c in sorted(set(got))}
    require(len(hist) > 1, f"parity set is one-sided: {hist}")
    say("parity_wire", requests=n, mismatches=0, status_hist=hist,
        quota_rows_granted=len(granted))

    wire_bags = []
    for d in big_dicts:
        msg = bag_to_compressed(d)
        wire_bags.append(srv.preprocess(
            LazyWireBag(msg.SerializeToString())))
    padded = pad_to_bucket(wire_bags, buckets)
    got_big = statuses_of(
        srv.check_batch_preprocessed(padded)[:len(wire_bags)])
    want_big = statuses_of(disp.check_host_oracle(
        [bag_from_mapping(d) for d in big_dicts]))
    bad = [i for i, (a, b) in enumerate(zip(got_big, want_big)) if a != b]
    require(not bad, f"bucket-{len(padded)} parity broke on "
            f"{len(bad)}/{len(big_dicts)} rows, first {bad[:5]}")
    say("parity_top_bucket", rows=len(big_dicts), bucket=len(padded),
        mismatches=0)


def phase_load(port: int, native, n_checks: int, depth: int) -> None:
    """A few thousand Checks (quota on every 4th) from the C++ client,
    then the proof that the chip did the work inside that window."""
    from istio_tpu.compiler import cache as compile_cache
    from istio_tpu.runtime import monitor
    from istio_tpu.testing import perf, workloads

    payloads = perf.make_check_payloads(
        workloads.make_request_dicts(512), quota_every=4)
    warm = perf.run_h2load(port, payloads, max(n_checks // 8, 64),
                           depth, 0.5)
    require(warm["errors"] == 0, f"warm-up h2load errors: {warm}")
    ev0 = compile_cache.cache_event_counts()
    stage0 = monitor.stage_baseline()
    res0 = monitor.resilience_counters()
    nat0 = native.counters()
    rep = perf.run_h2load(port, payloads, n_checks, depth, 0.5)
    nat1 = native.counters()
    res1 = monitor.resilience_counters()
    ev1 = compile_cache.cache_event_counts()
    stages = monitor.latency_snapshot(since=stage0)["stages"]
    say("load_smoke", note="smoke, not a measurement", h2load=rep)
    require(rep["errors"] == 0, f"h2load errors: {rep['errors']}")
    batches = nat1["batches_formed"] - nat0["batches_formed"]
    steps = stages.get("device_step", {}).get("count", 0)
    compiles = (ev1["hits"] - ev0["hits"]) + \
        (ev1["misses"] - ev0["misses"])
    moved = {k: res1[k] - res0[k] for k in
             ("fallback_total", "device_retries_total",
              "batch_failures_total")}
    say("load_proof", native_batches=batches, device_step_observations=steps,
        compiles_in_window=compiles, resilience_delta=moved,
        breaker_state=res1["breaker_state"],
        stage_p50_ms={s: v["p50_ms"] for s, v in stages.items()})
    require(batches > 0 and steps >= batches,
            f"{steps} device_step observations for {batches} served "
            "batches: some batch did not run on the device")
    require(compiles == 0, f"{compiles} compile-cache lookups inside "
            "the load window: a shape was not prewarmed")
    require(not any(moved.values()) and res1["breaker_state"] == 0,
            f"resilience path moved under clean load: {moved}, "
            f"breaker_state={res1['breaker_state']}")


def phase_report(port: int, n_rpcs: int) -> None:
    """Native Report on the same server, ending in the exact
    conservation identity accepted == exported + rejected."""
    from istio_tpu.runtime import monitor
    from istio_tpu.testing import perf, workloads

    payloads = perf.make_report_payloads(
        workloads.make_request_dicts(512), records_per_request=64)
    base = monitor.report_conservation()
    # an RPC the bounded coalescer sheds answers a typed error and its
    # records count as rejected: the identity, not zero errors, is the
    # contract (ack-after-enqueue lets a client outrun a slow device)
    rep = perf.run_h2load(port, payloads, n_rpcs, 8, 0.5, method=REPORT)
    end = time.monotonic() + 60.0
    cons = monitor.report_conservation(since=base)
    while cons["in_flight"] and time.monotonic() < end:
        time.sleep(0.02)
        cons = monitor.report_conservation(since=base)
    say("report_smoke", note="smoke, not a measurement",
        rpcs_per_sec=rep["checks_per_sec"], rpc_errors=rep["errors"],
        conservation=cons)
    require(cons["exact"] and cons["accepted"] > 0 and
            cons["accepted"] == cons["exported"] + cons["rejected_total"],
            f"report conservation violated: {cons}")
    require(cons["exported"] > 0, f"no record exported: {cons}")


def run_one_chip(args, platform: str) -> None:
    import jax

    from istio_tpu.api.native_server import NativeMixerServer
    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.testing import workloads

    say("device_sync", **device_sync_ms())
    buckets = args.buckets
    t0 = time.perf_counter()
    srv = RuntimeServer(workloads.make_store(args.rules), ServerArgs(
        default_manifest=workloads.MESH_MANIFEST, buckets=buckets,
        max_batch=buckets[-1], initial_prewarm=False))
    native = None
    try:
        plan = srv.controller.dispatcher.fused
        require(plan is not None, "no fused plan: the generic host "
                "dispatch path would serve")
        require(plan.native is not None, "native tensorizer did not "
                "build: the python wire decoder would serve")
        # auto-resolved: on for every accelerator backend, off on cpu
        # (the rehearsal then serves without the staged h2d)
        require(srv._overlap_h2d == (platform != "cpu"),
                f"overlap_h2d resolved to {srv._overlap_h2d} on "
                f"{platform}")
        say("build", rules=args.rules, buckets=list(buckets),
            seconds=round(time.perf_counter() - t0, 2),
            rule_rows=int(plan.engine.ruleset.rule_ns.shape[0]),
            str_tiers=list(plan.str_tiers),
            overlap_h2d=srv._overlap_h2d)
        say("prewarm_total",
            seconds=round(prewarm_blocking(plan, buckets), 2))
        native = NativeMixerServer(srv, max_batch=buckets[-1])
        port = native.start()
        phase_parity(port, srv, args.parity, buckets)
        phase_load(port, native, args.checks, 2 * buckets[-1])
        phase_report(port, args.report_rpcs)
        stats = jax.devices()[0].memory_stats() or {}
        say("device_memory",
            peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                        "not reported"),
            bytes_limit=stats.get("bytes_limit", "not reported"))
    finally:
        if native is not None:
            native.stop()
        srv.close()


def run_four_chips(args) -> None:
    """The served multi-device path users would turn on —
    ServerArgs(mesh_shape=(2, 2)) — against a single-device
    RuntimeServer on the same bags, and nothing else."""
    import jax

    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.testing import workloads

    require(len(jax.devices()) >= 4,
            f"--chips 4 needs four devices, jax sees "
            f"{len(jax.devices())}")
    bucket = args.buckets[-1]

    def server(**kw):
        return RuntimeServer(
            workloads.make_store(args.rules), ServerArgs(
                default_manifest=workloads.MESH_MANIFEST,
                buckets=(bucket,), max_batch=bucket,
                initial_prewarm=False, **kw))

    mesh_srv = server(mesh_shape=(2, 2))
    one_srv = None
    try:
        one_srv = server()
        for name, srv in (("mesh_2x2", mesh_srv), ("one_device", one_srv)):
            say("prewarm_total", server=name, seconds=round(
                prewarm_blocking(srv.controller.dispatcher.fused,
                                 (bucket,)), 2))
        eng = mesh_srv.controller.dispatcher.fused.engine
        from istio_tpu.parallel.mesh import param_shardings
        specs = param_shardings(mesh_srv.controller.mesh, eng)
        for key in ("conj_m_idx", "eqc_col"):
            arr = eng.params[key]
            say("param_placement", param=key, shape=list(arr.shape),
                resident_shards=[
                    {"device": s.device.id, "rows": _rows(s.index)}
                    for s in arr.addressable_shards],
                step_input_spec=str(specs[key].spec),
                step_input_shards=[
                    {"device": d.id, "rows": _rows(idx)} for d, idx in
                    specs[key].devices_indices_map(arr.shape).items()])
        bags = workloads.make_bags(bucket, seed=22)
        got = mesh_srv.check_many(bags)
        want = one_srv.check_many(bags)
        keyed = [[(r.status_code, r.valid_duration_s, r.valid_use_count)
                  for r in rs] for rs in (got, want)]
        bad = [i for i, (a, b) in enumerate(zip(*keyed)) if a != b]
        require(not bad, f"mesh verdicts diverge from one device on "
                f"{len(bad)}/{bucket} rows, first {bad[:5]}")
        hist = {c: statuses_of(got).count(c)
                for c in sorted(set(statuses_of(got)))}
        say("mesh_parity", rows=bucket, mismatches=0, status_hist=hist)
    finally:
        mesh_srv.close()
        if one_srv is not None:
            one_srv.close()
    report_shard_bank_placement(args)


def _rows(index) -> str:
    """Leading-axis slice of a shard index, as 'lo:hi'."""
    lead = index[0]
    return f"{lead.start or 0}:{lead.stop if lead.stop is not None else ''}"


def report_shard_bank_placement(args) -> None:
    """Print — not fix — where istio_tpu/sharding places its banks
    with shards=4 (ROADMAP Design 5 says every bank is on device 0)."""
    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.testing import workloads

    bucket = args.buckets[0]
    srv = RuntimeServer(
        workloads.make_fleet_store(max(args.rules // 10, 64), 16, seed=1),
        ServerArgs(default_manifest=workloads.MESH_MANIFEST,
                   buckets=(bucket,), max_batch=bucket, shards=4,
                   initial_prewarm=False))
    try:
        banks = srv._sharded["banks"]
        placement = []
        for i, bank in enumerate(banks):
            params = bank.dispatcher.fused.engine.params
            devs = sorted({d.id for v in params.values()
                           for d in v.devices()})
            placement.append({"bank": i, "devices": devs})
        say("shard_bank_placement", shards=4, banks=placement)
    finally:
        srv.close()


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rules", type=int, default=10_000)
    ap.add_argument("--buckets", default="256,2048",
                    type=lambda s: tuple(sorted(int(b)
                                                for b in s.split(","))))
    ap.add_argument("--checks", type=int, default=8000,
                    help="Checks recorded in the load window")
    ap.add_argument("--parity", type=int, default=512,
                    help="distinct requests compared through the wire")
    ap.add_argument("--report-rpcs", type=int, default=200,
                    help="64-record Report RPCs recorded")
    return ap.parse_args(argv)


def main(argv) -> int:
    args = parse_args(argv)
    import importlib.metadata as md

    import jax

    from istio_tpu.compiler import cache as compile_cache

    cache_dir = compile_cache.configure_persistent_cache()
    compile_cache.install_event_counters()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu"
    say("device", **device, jax=jax.__version__,
        libtpu=md.version("libtpu"), cache_dir=cache_dir,
        mode="chip run" if on_chip else
        f"rehearsal on {device['platform']}: cannot end ok")
    ok = False
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args)
        else:
            run_one_chip(args, device["platform"])
        ok = on_chip
        say("done", phases_passed=True,
            wall_s=round(time.perf_counter() - t0, 1))
    except BaseException:
        traceback.print_exc()
        say("done", phases_passed=False,
            wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
