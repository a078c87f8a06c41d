"""Delta smoke: config churn must be (nearly) free on a sharded
snapshot. Build a seeded fleet snapshot into K namespace banks with a
persistent XLA compilation cache configured, then FAIL (nonzero exit)
unless

  1. a ONE-NAMESPACE constant-only delta republishes by rebuilding
     exactly ONE bank: the other K-1 banks carry across the
     generation as the SAME objects (prewarmed shapes, breaker,
     telemetry bindings intact), the plan keeps every namespace on
     its shard (routing byte-identical), and the rebuild ledger +
     /debug/shards agree on reused-vs-recompiled counts;
  2. the delta actually TOOK EFFECT (a probe request flips from
     deny to allow across the republish) and the sharded path stays
     EXACTLY oracle-parity over the real gRPC front, before and
     after the delta;
  3. a SIMULATED RESTART (a fresh RuntimeServer over the mutated
     store, same process — new jit callables, cold in-memory caches)
     with the warm persistent compilation cache serves WITHOUT
     recompiling unchanged banks: zero XLA cache misses and nonzero
     hits across the whole rebuild, no new artifacts on disk, and
     exact oracle parity again.

The edit is constant-only (a literal swap inside one rule's match) —
the dominant real config churn shape. Compiled programs take their
index tensors as traced ARGUMENTS (compiler/ruleset.py), so such an
edit keeps every HLO bit-identical: even the one recompiled bank's
XLA artifact comes out of the persistent cache, and the whole
republish cost is host-side (plan diff + one bank's trace).

Runnable under JAX_PLATFORMS=cpu; tier-1 invokes main() in-process
(tests/test_delta_smoke.py) at the platform scale from the issue
(100k rules tpu / 4k cpu).

Usage: JAX_PLATFORMS=cpu python scripts/delta_smoke.py \
           [--rules N] [--namespaces N] [--shards K] [--checks N] \
           [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _wire_parity(client, srv, dicts, failures, tag,
                 bag_from_mapping, oracle_check_statuses) -> int:
    """Serve `dicts` over the real gRPC front AND in-process, judge
    both against the SnapshotOracle exactly. Returns denies seen."""
    wire_codes = [int(client.check(d).precondition.status.code)
                  for d in dicts]
    bags = [bag_from_mapping(d) for d in dicts]
    local = srv.check_many(bags)
    snap = srv.controller.dispatcher.snapshot
    expected = oracle_check_statuses(
        snap, srv.controller.dispatcher.fused, bags)
    n_deny = 0
    for i, (want, got, code) in enumerate(
            zip(expected, local, wire_codes)):
        if got.status_code != want["status"]:
            failures.append(f"{tag} row {i}: sharded status "
                            f"{got.status_code} != oracle "
                            f"{want['status']}")
        if code != want["status"]:
            failures.append(f"{tag} row {i}: wire status {code} != "
                            f"oracle {want['status']}")
        if got.deny_rule != want["deny_rule"]:
            failures.append(f"{tag} row {i}: deny_rule "
                            f"{got.deny_rule} != oracle "
                            f"{want['deny_rule']}")
        if want["status"] != 0:
            n_deny += 1
        if len(failures) > 16:
            break
    if not n_deny:
        failures.append(f"{tag}: oracle saw zero denies — the "
                        f"traffic no longer exercises deny rules")
    return n_deny


def main(n_rules: int | None = None, n_namespaces: int | None = None,
         shards: int | None = None, n_checks: int = 48,
         seed: int = 7) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from istio_tpu.compiler import cache as compile_cache

    # a PRIVATE cache directory: the warm-restart leg counts cold
    # misses, so nothing else may have written it. The scope clears
    # JAX_COMPILATION_CACHE_DIR and restores the process's own cache
    # config on exit — BEFORE the tmpdir is deleted (later compiles in
    # this process must not write into a missing directory)
    cache_dir = tempfile.mkdtemp(prefix="delta_smoke_jax_cache_")
    try:
        with compile_cache.private_cache_dir(cache_dir):
            return _run(cache_dir, n_rules, n_namespaces, shards,
                        n_checks, seed)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def _run(cache_dir: str, n_rules: int | None, n_namespaces: int | None,
         shards: int | None, n_checks: int, seed: int) -> int:
    import time

    import jax

    from istio_tpu.api.client import MixerClient
    from istio_tpu.api.grpc_server import MixerGrpcServer
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.compiler import cache as compile_cache
    from istio_tpu.introspect import IntrospectServer
    from istio_tpu.runtime import RuntimeServer, ServerArgs
    from istio_tpu.runtime.store import Event
    from istio_tpu.sharding import oracle_check_statuses
    from istio_tpu.testing import workloads
    from istio_tpu.testing.workloads import _fleet_ns_assignment
    from istio_tpu.utils import tracing

    on_tpu = jax.devices()[0].platform == "tpu"
    n_rules = n_rules or (100_000 if on_tpu else 4_000)
    n_namespaces = n_namespaces or (512 if on_tpu else 64)
    shards = shards or (8 if on_tpu else 4)

    failures: list[str] = []
    compile_cache.install_event_counters()
    srv = srv2 = intro = g = client = None
    try:
        store = workloads.make_fleet_store(n_rules, n_namespaces,
                                           seed)
        args = ServerArgs(
            batch_window_s=0.0005, max_batch=16, buckets=(16,),
            shards=shards, replicas=1,
            rule_telemetry=False, initial_prewarm=False,
            default_manifest=workloads.MESH_MANIFEST,
            jax_compile_cache_dir=cache_dir)
        t0 = time.perf_counter()
        srv = RuntimeServer(store, args)
        build_s = time.perf_counter() - t0

        state = srv._sharded
        if state["mode"] != "sharded":
            failures.append(f"expected sharded mode, got "
                            f"{state['mode']} "
                            f"({state['fallback_reason']})")
        st = dict(srv._rebuild_status)
        if st["rebuilds"] != 1 or st["banks_reused"] != 0 \
                or st["banks_recompiled"] != shards \
                or st["last_error"] is not None:
            failures.append(f"first-build ledger wrong: {st}")
        plan0 = state["plan"]
        banks0 = {b.shard_id: b for b in state["banks"]}

        # -- the probe rule: denier action + a source-namespace
        #    literal we can constant-swap (i%3==0 picks the denier
        #    action in make_fleet_store, i%4<2 the != conjunct) -----
        probe_i = next(i for i in range(0, n_rules, 12)
                       if i % 3 == 0 and i % 4 < 2)
        ns_of = _fleet_ns_assignment(n_rules, n_namespaces, seed)
        probe_ns = f"ns{int(ns_of[probe_i])}"
        probe = {
            "destination.service":
                f"svc{probe_i}.{probe_ns}.svc.cluster.local",
            "source.namespace": "probe-team",
            "source.user": "sidecar-probe",
            "request.method": "GET",
            "connection.mtls": True,
            "request.path": "/probe",
        }

        intro = IntrospectServer(runtime=srv)
        intro_port = intro.start()
        g = MixerGrpcServer(runtime=srv)
        grpc_port = g.start()
        client = MixerClient(f"127.0.0.1:{grpc_port}",
                             enable_check_cache=False)

        dicts = workloads.make_fleet_traffic(
            n_checks, n_rules, n_namespaces, seed)
        _wire_parity(client, srv, dicts, failures, "pre-delta",
                     bag_from_mapping, oracle_check_statuses)
        pre_code = int(client.check(probe)
                       .precondition.status.code)
        if pre_code != 7:
            failures.append(f"probe rule fleet{probe_i} should deny "
                            f"(7) pre-delta, got {pre_code}")

        # -- ONE-namespace constant-only delta ----------------------
        key = ("rule", probe_ns, f"fleet{probe_i}")
        spec = dict(store.get(key))
        locked = f'"locked{probe_i % 5}"'
        if locked not in spec["match"]:
            failures.append(f"probe rule match has no {locked}: "
                            f"{spec['match']}")
        spec["match"] = spec["match"].replace(locked, '"probe-team"')
        # quiet apply + one explicit rebuild: the republish under
        # test is deterministic, not racing the debounce timer
        store.apply_events([Event(key, spec)], notify=False)
        t0 = time.perf_counter()
        srv.controller.rebuild()
        delta_s = time.perf_counter() - t0

        state = srv._sharded
        st = dict(srv._rebuild_status)
        delta = state["delta"]
        want_shard = plan0.shard_of(probe_ns)
        if st["banks_reused"] != shards - 1 \
                or st["banks_recompiled"] != 1:
            failures.append(f"delta ledger: expected {shards - 1} "
                            f"reused / 1 recompiled, got {st}")
        if delta["recompiled"] != [want_shard]:
            failures.append(f"recompiled banks {delta['recompiled']}"
                            f" != [{want_shard}] (the probe ns's "
                            f"shard)")
        plan1 = state["plan"]
        if plan1.ns_to_shard != plan0.ns_to_shard:
            moved = {ns for ns in set(plan0.ns_to_shard)
                     | set(plan1.ns_to_shard)
                     if plan0.ns_to_shard.get(ns)
                     != plan1.ns_to_shard.get(ns)}
            failures.append(f"plan moved namespaces under a pure "
                            f"edit: {sorted(moved)[:8]}")
        # carried banks are shallow copies sharing the COMPILED
        # artifact (dispatcher + fused plan + checker) — the old
        # generation keeps its own index map while batches drain
        carried = {b.shard_id: b for b in state["banks"]}
        for k in range(shards):
            if k == want_shard:
                if carried[k].dispatcher is banks0[k].dispatcher:
                    failures.append(f"bank {k} should have been "
                                    f"recompiled, compiled artifact "
                                    f"carried")
            else:
                if carried[k].dispatcher is not banks0[k].dispatcher:
                    failures.append(f"bank {k} was rebuilt — expected "
                                    f"the carried compiled artifact")
                if carried[k].checker is not banks0[k].checker:
                    failures.append(f"bank {k} breaker/checker did "
                                    f"not carry across the delta")

        post_code = int(client.check(probe)
                        .precondition.status.code)
        if post_code != 0:
            failures.append(f"probe should flip to allow (0) after "
                            f"the delta, got {post_code}")
        _wire_parity(client, srv, dicts, failures, "post-delta",
                     bag_from_mapping, oracle_check_statuses)

        # -- /debug/shards agreement --------------------------------
        with urllib.request.urlopen(
                f"http://127.0.0.1:{intro_port}/debug/shards",
                timeout=30) as r:
            view = json.loads(r.read().decode())
        vre = view.get("rebuild", {})
        if vre.get("banks_reused") != shards - 1 \
                or vre.get("banks_recompiled") != 1:
            failures.append(f"/debug/shards rebuild ledger disagrees:"
                            f" {vre}")
        if view.get("delta", {}).get("recompiled") != [want_shard]:
            failures.append(f"/debug/shards delta block disagrees: "
                            f"{view.get('delta')}")
        if "xla_cache_events" not in view.get("compile_cache", {}):
            failures.append(f"/debug/shards compile_cache block "
                            f"missing: {view.get('compile_cache')}")

        client.close(); client = None
        g.stop(); g = None
        intro.close(); intro = None
        srv.close(); srv = None

        # -- simulated restart with the warm persistent cache -------
        entries0 = compile_cache.persistent_cache_entries(cache_dir)
        if entries0 <= 0:
            failures.append("persistent cache is empty after the "
                            "first server's lifetime — nothing was "
                            "cached")
        ev0 = compile_cache.cache_event_counts()
        t0 = time.perf_counter()
        srv2 = RuntimeServer(store, args)
        restart_s = time.perf_counter() - t0
        ev1 = compile_cache.cache_event_counts()
        new_misses = ev1["misses"] - ev0["misses"]
        new_hits = ev1["hits"] - ev0["hits"]
        if new_misses != 0:
            failures.append(f"restart recompiled {new_misses} XLA "
                            f"programs — the warm persistent cache "
                            f"should have served every unchanged "
                            f"bank ({new_hits} hits)")
        if new_hits <= 0:
            failures.append("restart produced zero persistent-cache "
                            "hits — the cache is not being consulted")
        entries1 = compile_cache.persistent_cache_entries(cache_dir)
        if entries1 != entries0:
            failures.append(f"restart grew the cache "
                            f"{entries0}->{entries1} — new artifacts "
                            f"mean recompiles happened")
        bags = [bag_from_mapping(d) for d in dicts]
        local = srv2.check_many(bags)
        snap2 = srv2.controller.dispatcher.snapshot
        expected = oracle_check_statuses(
            snap2, srv2.controller.dispatcher.fused, bags)
        for i, (want, got) in enumerate(zip(expected, local)):
            if got.status_code != want["status"] \
                    or got.deny_rule != want["deny_rule"]:
                failures.append(
                    f"restart row {i}: ({got.status_code}, "
                    f"{got.deny_rule}) != oracle ({want['status']}, "
                    f"{want['deny_rule']})")
                if len(failures) > 16:
                    break
    finally:
        for closer in (client, g, intro):
            try:
                if closer is not None:
                    (closer.close if not hasattr(closer, "stop")
                     else closer.stop)()
            except Exception:
                pass
        for s in (srv, srv2):
            try:
                if s is not None:
                    s.close()
            except Exception:
                pass
        tracing.shutdown()

    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    if not failures:
        print(f"delta smoke ok: {n_rules} rules / {n_namespaces} ns "
              f"-> {shards} shards; initial build {build_s:.1f}s, "
              f"one-namespace delta republish {delta_s:.2f}s "
              f"reusing {shards - 1}/{shards} banks (compiled "
              f"artifacts + breakers carried, stable plan, EXACT "
              f"gRPC oracle parity, probe deny->allow flip "
              f"observed), warm restart {restart_s:.1f}s with "
              f"{entries0} cached XLA artifacts, 0 misses / "
              f"{new_hits} hits, parity exact")
    return 1 if failures else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=None)
    ap.add_argument("--namespaces", type=int, default=None)
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--checks", type=int, default=48)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.exit(main(args.rules, args.namespaces, args.shards,
                  args.checks, args.seed))
