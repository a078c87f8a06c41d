"""benchmark/run.py — one cell of BENCHMARK.json, measured on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip. It serves in-process as
chip_smoke.py does (RuntimeServer -> blocking prewarm of the
deployment's own step shapes -> NativeMixerServer), proves the
verdicts exact outside the window (and, for a mix that asks for a
quota, its grants: row for row against the configuration's plain
quota reference), then drives the loopback socket with the benchmark's
own C++ client (a child that never touches JAX) for `--seconds`; the
client reads the reply of every quota row it sends, and once the window
has closed the server's quota counter is read back at the wire and held
to what the client saw granted. Progress is one
JSON line per phase; the last line of stdout is the result the contract
fixes (README.md), and its last key, `compared`, is every number the
run held beside its limit (the same lines end standard error).

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
PARITY_QUOTA = 256      # of them, sent again in order with the mix's quota
PARITY_DENIED = 16      # at most so many of those with a denied precondition
REPLAY_EVERY = 8        # one in eight re-sent at once under the same id
ID_WIDTH = 16           # a payload's deduplication_id, stamped by the client
READBACK_KEYS = 8       # instance keys whose counter is read back
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


class Held(dict):
    """Every number of `correct` beside its limit, for the result line:
    name -> [number, "<=" or ">=", limit]."""

    def hold(self, name: str, value, op: str, limit,
             detail: str = "") -> None:
        """Kept whether it holds or not, then required."""
        self[name] = [value, op, limit]
        require(value <= limit if op == "<=" else value >= limit,
                f"{name}: {value} is not {op} {limit}"
                f"{detail and ': ' + detail}")


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
    config = load_module(HERE / "configs" / f"{sizes['module']}.py")
    if mix["quota_every"]:
        lacks = [k for k in ("quota_amount", "best_effort")
                 if k not in mix] + \
            [k for k in ("quota_name", "quota_exhausts")
             if sizes.get(k) is None]
        if not hasattr(config, "quota_reference"):
            lacks.append(f"configs/{sizes['module']}.py:quota_reference")
        if lacks:
            sys.exit(f"{name}: mix {cell['traffic']!r} asks for a quota "
                     f"(quota_every {mix['quota_every']}) but nothing could "
                     f"hold a grant to its guarantee: missing {lacks}")
        if sizes["quota_exhausts"]:
            sys.exit(f"{name}: {entry['file']} says its quota exhausts; "
                     "`correct` holds every grant in full and has no bound "
                     "for a short one yet: the cell that needs one brings it")

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return types.SimpleNamespace(
        chips=cell["chips"], sizes=sizes, mix=mix, config=config,
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


def agree(held: Held, what: str, **sides) -> None:
    """Every named list (of statuses, of grants) equals the others, row
    for row."""
    names = list(sides)
    rows = list(zip(*sides.values()))
    bad = [i for i, row in enumerate(rows) if len(set(row)) > 1]
    held.hold(what.replace(" ", "_").replace("-", "_") + "_mismatches",
              len(bad), "<=", 0, f"of {len(rows)} rows, first "
              f"{[dict(zip(names, rows[i]), row=i) for i in bad[:5]]}")


def check_request(values: dict, quota: tuple | None = None,
                  dedup_id: str = ""):
    """The CheckRequest a sidecar sends for `values`; with `quota` =
    (name, amount, best_effort) it asks for that too, under
    `dedup_id`."""
    from istio_tpu.api import mixer_pb2 as pb
    from istio_tpu.api.wire import bag_to_compressed
    from istio_tpu.attribute.global_dict import GLOBAL_WORD_LIST

    req = pb.CheckRequest(global_word_count=len(GLOBAL_WORD_LIST))
    bag_to_compressed(values, msg=req.attributes)
    if quota is not None:
        name, amount, best_effort = quota
        req.deduplication_id = dedup_id
        req.quotas[name].amount = amount
        req.quotas[name].best_effort = best_effort
    return req


def quota_asker(client, cell, reference):
    """ask(values, amount, best_effort, dedup_id) -> (what the reply
    carries under the configuration's quota, or None; what `reference`
    grants the same ask)."""
    name = cell.sizes["quota_name"]

    def ask(values, amount, best_effort, dedup_id):
        reply = client._check(check_request(
            values, (name, amount, best_effort), dedup_id))
        require(set(reply.quotas) <= {name}, f"quota: the reply carries "
                f"entries {sorted(reply.quotas)} beside {name!r}")
        entry = reply.quotas[name].granted_amount \
            if name in reply.quotas else None
        return entry, reference.grant(values, name, amount, best_effort,
                                      dedup_id)

    return ask


def parity_quota(client, wire: list, got: list, cell, reference,
                 held: Held) -> None:
    """The third phase, for a mix that asks for a quota. PARITY_QUOTA of
    the parity set, one after the other on one connection (a grant
    depends on what was granted before), each asking as the mix's
    payloads will, under an id of its own; one in REPLAY_EVERY sent
    again at once under the same id. Then two asks no payload makes,
    all or nothing, on the first OK row's key: for more than the
    limit, and for one more than that key has left by the reference,
    which reads the server's counter back: a counter that did not move
    with the grants before would grant it. Both are granted 0 and
    consume nothing. Presence of quotas[name] and granted_amount ==
    the configuration's plain quota reference, row for row; a replay's
    answer == its first."""
    mix = cell.mix
    ask = quota_asker(client, cell, reference)
    denied = [i for i, code in enumerate(got) if code][:PARITY_DENIED]
    granted = [i for i, code in enumerate(got) if not code]
    rows = sorted(denied + granted[:PARITY_QUOTA - len(denied)])
    asked = (mix["quota_amount"], mix["best_effort"])
    served, expected, replayed, first, again = [], [], [], [], []
    for k, i in enumerate(rows):
        reply, want = ask(wire[i], *asked, f"parity-{k}")
        served.append(reply)
        expected.append(want)
        if k % REPLAY_EVERY == 0:
            reply2, want2 = ask(wire[i], *asked, f"parity-{k}")
            first.append(reply)
            replayed.append(reply2)
            again.append(want2)
    limit = reference.max_amount
    own = next(wire[i] for i in granted
               if reference.consumes(wire[i], cell.sizes["quota_name"]))
    in_use = reference.in_use(own)
    for k, amount in enumerate((limit + 1, limit - in_use + 1)):
        reply, want = ask(own, amount, False, f"parity-own-{k}")
        served.append(reply)
        expected.append(want)
    agree(held, "quota parity", wire=served, reference=expected)
    agree(held, "quota replay", replay=replayed, first=first,
          reference=again)
    counts = {"requests": len(rows),
              "granted": sum(1 for g in served[:len(rows)] if g),
              "denied": sum(1 for g in served[:len(rows)] if g is None),
              "replays": len(replayed),
              "refused": sum(1 for g in served[len(rows):] if g == 0)}
    for key in ("granted", "denied", "replays", "refused"):
        held.hold(f"parity_quota_{key}", counts[key], ">=", 1)
    held.hold("parity_quota_own_key_in_use", in_use, ">=", 1)
    say("parity_quota", **counts, mismatches=0, limit=limit,
        own_key_in_use=in_use)


def read_back_quota(port: int, cell, reference, keys: list, client: dict,
                    held: Held) -> None:
    """Once the window has closed and the server has drained: the
    counter of each read-back key, the only state the deployment keeps
    on the device, read at the wire by two all-or-nothing asks. The
    key has consumed at least what parity took and the client saw
    granted on this connection (warm-up included), and at most what
    parity took and the client asked: so one more than the limit less
    the first is refused (granted: the counter lags its grants), and
    the limit less the second is granted in full (refused: the counter
    ran ahead of what was asked). The reference answers both, moved on
    by the client's two sums."""
    from istio_tpu.api import MixerClient

    limit = reference.max_amount
    sent, granted = client["readback_sent"], client["readback_granted"]
    wire = MixerClient(f"127.0.0.1:{port}", enable_check_cache=False)
    try:
        ask = quota_asker(wire, cell, reference)
        lags, lag_wants, ahead, ahead_wants = [], [], [], []
        for k, values in enumerate(keys):
            reference.consume(values, granted[k])
            reply, want = ask(values, limit - reference.in_use(values) + 1,
                              False, f"readback-{k}-lags")
            lags.append(reply)
            lag_wants.append(want)
            reference.consume(values, sent[k] - granted[k])
            reply, want = ask(values, limit - reference.in_use(values),
                              False, f"readback-{k}-ahead")
            ahead.append(reply)
            ahead_wants.append(want)
    finally:
        wire.close()
    held.hold("readback_keys", len(keys), ">=", 1)
    held.hold("readback_granted", sum(granted), ">=", 1)
    agree(held, "quota counter lags", wire=lags, reference=lag_wants)
    agree(held, "quota counter ahead", wire=ahead, reference=ahead_wants)
    say("read_back_quota", keys=len(keys), granted=granted, sent=sent)


def parity_sets(requests: list, expected_status, top: int) -> tuple:
    """(the PARITY_WIRE requests of the wire phase, the `top` of the
    top-bucket batch): the first distinct requests of the seed. Where
    those of the wire phase all have one status by the reference (one
    seed in some hundreds, at mixer10k's 2 % of denials), the last
    gives way to the seed's first request of another status, so that
    no seed makes a one-sided parity set."""
    distinct = list({json.dumps(d, sort_keys=True): d
                     for d in requests}.values())
    wire, big = distinct[:PARITY_WIRE], distinct[PARITY_WIRE:PARITY_WIRE + top]
    require(len(big) == top, f"{len(distinct)} distinct requests cannot "
            f"fill {PARITY_WIRE} + a {top}-row batch")
    seen = {expected_status(d) for d in wire}
    if len(seen) == 1:
        other = next((d for d in distinct[PARITY_WIRE + top:]
                      if expected_status(d) not in seen), None)
        require(other is not None, "every request of the seed has status "
                f"{seen}: the parity set is one-sided")
        wire[-1] = other
    return wire, big


def parity(port: int, srv, requests: list, cell, top: int, reference,
           held: Held) -> None:
    """Outside the window. PARITY_WIRE distinct requests through the
    socket (MixerClient, check cache off), then one top-bucket batch
    through the pump's own entry: wire status == the configuration's
    plain reference == Dispatcher.check_host_oracle, row for row. For a
    mix that asks for a quota, parity_quota on the same connection."""
    from concurrent.futures import ThreadPoolExecutor

    from istio_tpu.api import MixerClient
    from istio_tpu.api.wire import LazyWireBag, bag_to_compressed
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.runtime.batcher import pad_to_bucket

    expected_status = cell.config.reference(cell.sizes)
    wire, big = parity_sets(requests, expected_status, top)
    oracle = srv.controller.dispatcher.check_host_oracle
    client = MixerClient(f"127.0.0.1:{port}", enable_check_cache=False)
    try:
        with ThreadPoolExecutor(max_workers=32) as pool:
            replies = list(pool.map(client.check, wire))
        got = [int(r.precondition.status.code) for r in replies]
        agree(held, "wire parity", wire=got,
              reference=[expected_status(d) for d in wire],
              oracle=statuses(oracle([bag_from_mapping(d) for d in wire])))
        hist = {code: got.count(code) for code in sorted(set(got))}
        require(len(hist) > 1, f"parity set is one-sided: {hist}")
        say("parity_wire", requests=len(wire), mismatches=0,
            status_hist=hist)
        if cell.mix["quota_every"]:
            parity_quota(client, wire, got, cell, reference, held)
    finally:
        client.close()

    bags = [srv.preprocess(LazyWireBag(
        bag_to_compressed(d).SerializeToString())) for d in big]
    padded = pad_to_bucket(bags, (top,))
    agree(held, "top-bucket parity",
          device=statuses(srv.check_batch_preprocessed(padded)[:top]),
          reference=[expected_status(d) for d in big],
          oracle=statuses(oracle([bag_from_mapping(d) for d in big])))
    say("parity_top_bucket", rows=top, bucket=len(padded), mismatches=0)


def write_payloads(requests: list, cell, out, table, reference) -> list:
    """Serialized CheckRequests, u32-length-prefixed, for the client.
    For a mix with `quota_every`, every such payload asks for the
    configuration's quota as a sidecar does, under a deduplication_id
    of ID_WIDTH characters that the client overwrites at every send;
    `table` gets a line for each (h2load.cpp says what it holds), and
    the first READBACK_KEYS instance keys that such payloads consume
    from are returned, a request of each: the client sums what it asks
    of them and what they are granted. A mix without one writes no
    table, and its payloads carry neither."""
    sizes, mix = cell.sizes, cell.mix
    every = mix["quota_every"]
    keys: dict = {}     # instance key -> (its number, a request of it)
    if every:
        expected_status = cell.config.reference(sizes)
        quota = (sizes["quota_name"], mix["quota_amount"],
                 mix["best_effort"])
        for values in requests[::every]:
            if len(keys) < READBACK_KEYS and reference.consumes(
                    values, quota[0]):
                keys.setdefault(reference.key_of(values),
                                (len(keys), values))
        table.write(f"quota {quota[0]} {ID_WIDTH} {len(keys)}\n")
    for i, values in enumerate(requests):
        if every and i % every == 0:
            marker = f"#dedup-{i:09d}".encode()
            raw = check_request(values, quota,
                                marker.decode()).SerializeToString()
            require(len(marker) == ID_WIDTH and raw.count(marker) == 1,
                    f"payload {i}: no one place for its deduplication_id")
            key = keys.get(reference.key_of(values), (-1,))[0] \
                if reference.consumes(values, quota[0]) else -1
            table.write(f"{i} {raw.index(marker)} {quota[1]} "
                        f"{expected_status(values)} {key}\n")
        else:
            raw = check_request(values).SerializeToString()
        out.write(struct.pack("<I", len(raw)) + raw)
    out.flush()
    table.flush()
    return [values for _, values in keys.values()]


def hold_quota_window(client: dict, held: Held) -> None:
    """What the client read in the replies of the window's quota rows:
    the status the reference gives, every OK one granted, and in full
    (resolve_cell admits only a configuration whose quota never
    exhausts); an id a send."""
    held.hold("quota_asked", client["quota_asked"], ">=", 1)
    for key in ("status_mismatches", "quota_missing", "quota_unexpected",
                "replies_malformed", "short_grants"):
        held.hold(key, client[key], "<=", 0)
    held.hold("quota_ids_sent", client["quota_ids_sent"], ">=",
              client["quota_asked"])
    say("window_quota", **{k: client[k] for k in (
        "quota_asked", "quota_granted", "quota_denied", "short_grants",
        "quota_ids_sent")})


def run_window(client_bin: Path, port: int, payloads: str, table, cell,
               seconds: float, ctx, readers: dict, trace_dir) -> dict:
    """The client's warm-up, then its window (`table`: the quota
    table's path, or None for a mix that asks for none). Baselines are
    taken the instant the client says it records; everything is read
    back after the client has exited and the server has drained."""
    import jax

    from istio_tpu.runtime import monitor

    mix = cell.mix
    res0 = monitor.resilience_counters()
    proc = subprocess.Popen(
        [str(client_bin), str(port), payloads, str(seconds),
         str(mix["depth"]), str(mix["warmup_s"]), METHODS[mix["rpc"]],
         *([table] if table else [])], stdout=subprocess.PIPE, text=True)
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
    ctx.held.hold("left_device_path_or_shed",
                  sum(moved.values()) + abs(res1["breaker_state"]), "<=", 0,
                  f"{moved}, breaker_state={res1['breaker_state']}")
    ctx.held.hold("client_attempted", ctx.client["attempted"], ">=", 1)
    if mix["quota_every"]:
        hold_quota_window(ctx.client, ctx.held)
        read_back_quota(port, cell, ctx.quota_reference, ctx.readback_keys,
                        ctx.client, ctx.held)
    ctx.held.hold("client_failed", ctx.client["failed"], "<=", 0,
                  f"of {ctx.client['attempted']}")
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
        reference = cell.config.quota_reference(sizes) \
            if mix["quota_every"] else None
        parity(port, srv, requests, cell, top, reference, ctx.held)
        ctx.srv, ctx.native, ctx.quota_reference = srv, native, reference
        names = [m["name"] for m in cell.per_layer] if args.trace else []
        readers = {n: load_module(HERE / "layer_metrics" / f"{n}.py")
                   for n in names}
        with tempfile.NamedTemporaryFile(suffix=".bin") as payloads, \
                tempfile.NamedTemporaryFile("w", suffix=".quota") as table, \
                tempfile.TemporaryDirectory() as trace_dir:
            ctx.readback_keys = write_payloads(requests, cell, payloads,
                                               table, reference)
            layers = run_window(
                client_bin, port, payloads.name,
                table.name if mix["quota_every"] else None, cell,
                args.seconds, ctx, readers,
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
                                on_chip=on_chip, held=Held())
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
    result["compared"] = ctx.held
    for name, (value, op, limit) in ctx.held.items():
        print(f"compared {name}: {value} {op} {limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
