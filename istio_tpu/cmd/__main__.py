"""One multi-tool CLI: `python -m istio_tpu.cmd <command> ...`."""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def _serve_forever() -> None:
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass


def cmd_mixs(args: argparse.Namespace) -> int:
    """mixer server (cmd/mixs: server/server.go assembly)."""
    from istio_tpu.api import MixerGrpcServer
    from istio_tpu.runtime import FsStore, MemStore, RuntimeServer, \
        ServerArgs
    if args.trace_zipkin_url or args.trace_log_spans:
        # pkg/tracing/config.go:87 Configure — spans cover the serving
        # pipeline stages (batch/queue-wait/tensorize/device/overlay)
        from istio_tpu.utils import tracing
        tracing.configure("mixs", zipkin_url=args.trace_zipkin_url,
                          log_spans=args.trace_log_spans)
    store = FsStore(args.config_store) if args.config_store else MemStore()
    runtime = RuntimeServer(store, ServerArgs(
        batch_window_s=args.batch_window_us / 1e6,
        max_batch=args.max_batch,
        # overload resilience (runtime/resilience.py + batcher
        # admission control)
        default_check_deadline_ms=args.default_check_deadline_ms,
        check_queue_cap=args.check_queue_cap,
        report_queue_cap=args.report_queue_cap,
        brownout=args.brownout,
        check_fail_policy=args.check_fail_policy,
        breaker_failures=args.breaker_failures,
        breaker_reset_s=args.breaker_reset_ms / 1e3,
        # adapter-executor plane (runtime/executor.py): host actions
        # bulkheaded per handler, deadline-bounded, breaker-guarded
        host_fail_policy=args.host_fail_policy,
        executor_workers=args.executor_workers,
        executor_queue_cap=args.executor_queue_cap,
        host_action_timeout_ms=args.host_action_timeout_ms,
        host_executor=not args.no_host_executor,
        host_breaker_failures=args.host_breaker_failures,
        host_breaker_reset_s=args.host_breaker_reset_ms / 1e3,
        # config canary (istio_tpu/canary): record live traffic,
        # shadow-replay rebuilt snapshots, veto divergent swaps
        canary=args.canary,
        canary_max_divergence=args.canary_max_divergence,
        canary_capacity=args.canary_capacity,
        canary_sample_every=args.canary_sample_every,
        canary_replay_limit=args.canary_replay_limit,
        canary_waivers=tuple(args.canary_waive or ()),
        # sharded serving + delta compilation (istio_tpu/sharding,
        # compiler/cache.py)
        shards=args.shards,
        replicas=args.replicas,
        jax_compile_cache_dir=args.jax_compile_cache_dir,
        delta_compile=not args.no_delta_compile,
        shard_rebalance_budget=args.shard_rebalance_budget,
        # latency plane: continuous batching + check-cache grants
        continuous_batching=args.continuous_batching,
        continuous_depth=args.continuous_depth,
        check_grants=args.check_grants,
        grant_ttl_floor_s=args.grant_ttl_floor_s,
        grant_ttl_cap_s=args.grant_ttl_cap_s,
        # tail-latency forensics (runtime/forensics.py): flight
        # recorder threshold/mode + the /debug/profile capture dir
        flight_recorder=not args.no_flight_recorder,
        slow_threshold_ms=args.slow_threshold_ms,
        slow_adaptive=args.slow_adaptive,
        profile_dir=args.profile_dir,
        # mesh audit plane (runtime/audit.py): background invariant
        # auditor + fault explainability; /debug/audit + /debug/slo
        audit=not args.no_audit,
        audit_interval_s=args.audit_interval_ms / 1e3,
        # secure serving plane (istio_tpu/secure): mTLS posture +
        # CA-driven workload identity rotation parameters
        mtls=args.mtls,
        mtls_identity=args.mtls_identity,
        mtls_cert_ttl_minutes=args.mtls_cert_ttl_minutes,
        mtls_rotation_fraction=args.mtls_rotation_fraction))
    tls = None
    wi = None
    if args.mtls != "off":
        from istio_tpu.secure.mtls import ServingCerts

        def _read(path: str) -> bytes:
            with open(path, "rb") as f:
                return f.read()

        if args.tls_key and args.tls_cert and args.tls_root:
            # static operator-provisioned serving certs (no rotation)
            tls = ServingCerts(_read(args.tls_key),
                               _read(args.tls_cert),
                               _read(args.tls_root))
        elif args.ca_address:
            # CA-driven: obtain the serving bundle over the CSR flow,
            # rotate on the adapter-executor maintenance lane; every
            # rotation hot-swaps the live fronts AND revokes grants
            # keyed to the rotated identity (sign → swap → revoke)
            from istio_tpu.secure.identity import WorkloadIdentity
            from istio_tpu.security.ca_service import CAClient
            ca_root = _read(args.ca_root_cert) if args.ca_root_cert \
                else None
            credential = _read(args.bootstrap_cert) \
                if args.bootstrap_cert else b""
            wi = WorkloadIdentity(
                CAClient(args.ca_address, root_cert_pem=ca_root),
                args.mtls_identity,
                ttl_minutes=args.mtls_cert_ttl_minutes,
                rotation_fraction=args.mtls_rotation_fraction,
                credential=credential,
                dns_names=(args.tls_dns,))
            try:
                key_pem, cert_pem, root_pem = wi.ensure()
            except Exception as exc:
                print(f"mixs: initial serving-cert issuance failed "
                      f"({exc}); refusing to serve {args.mtls} without "
                      "credentials", file=sys.stderr)
                runtime.close()
                return 2
            tls = ServingCerts(key_pem, cert_pem, root_pem)
            wi.subscribe(lambda b: tls.rotate(b[0], b[1], b[2]))
            if runtime.grants is not None:
                wi.subscribe(lambda b: runtime.grants
                             .on_identity_rotate(wi.identity))
            if runtime.executor is not None:
                runtime.executor.register_refreshable(
                    "workload_identity", wi)
        else:
            print("mixs: --mtls needs serving credentials: either "
                  "--tls-key/--tls-cert/--tls-root or --ca-address",
                  file=sys.stderr)
            runtime.close()
            return 2
    server = MixerGrpcServer(runtime, f"{args.address}:{args.port}",
                             tls=tls, mtls_mode=args.mtls)
    port = server.start()
    print(f"mixs: istio.mixer.v1 on {args.address}:{port} "
          f"(config={'fs:' + args.config_store if args.config_store else 'memory'}"
          f"{', mtls=' + args.mtls if args.mtls != 'off' else ''})")
    intro = None
    if args.monitoring_port:
        # the reference's :9093 self-monitoring port, upgraded to the
        # full introspection surface (istio_tpu/introspect/): /metrics
        # merges BOTH registries, plus /healthz /readyz /debug/*
        from istio_tpu.introspect import IntrospectServer
        # trace ring OFF unless asked: enabling it flips the global
        # tracer to recording, and span construction (2x uuid per
        # span) is hot-path work no measured p99 pays
        intro = IntrospectServer(runtime=runtime,
                                 port=args.monitoring_port,
                                 host=args.monitoring_host,
                                 trace_capacity=args.trace_ring,
                                 tls=tls if args.introspect_tls
                                 else None)
        intro.start()
        print(f"mixs: introspection on "
              f"{args.monitoring_host}:{intro.port} "
              "(/metrics /healthz /readyz /debug/config /debug/queues"
              " /debug/cache /debug/traces /debug/resilience"
              " /debug/analysis /debug/rulestats /debug/canary"
              " /debug/slow /debug/events /debug/profile"
              " /debug/threads)")
    _serve_forever()
    server.stop()
    if intro is not None:
        intro.close()
    runtime.close()
    return 0


def cmd_rule_dump(args: argparse.Namespace) -> int:
    """Disassemble a config snapshot's compiled ruleset; optionally
    step one synthetic request through it (the il/text + Stepper
    tooling, mixer/pkg/il/text/write.go + interpreter/stepper.go)."""
    from istio_tpu.attribute.bag import bag_from_mapping
    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
    from istio_tpu.compiler.disasm import Stepper, disassemble
    from istio_tpu.runtime import FsStore
    from istio_tpu.runtime.config import SnapshotBuilder

    store = FsStore(args.config_store)
    snapshot = SnapshotBuilder(GLOBAL_MANIFEST).build(store)
    for err in snapshot.errors:
        print(f"# config error: {err}")
    print(disassemble(snapshot.ruleset), end="")
    if args.explain:
        values = {}
        for pair in args.explain:
            name, _, value = pair.partition("=")
            values[name] = value
        print()
        print(Stepper(snapshot.ruleset, snapshot.finder).explain(
            bag_from_mapping(values)), end="")
    return 0


def cmd_analyze(args: argparse.Namespace) -> int:
    """Static snapshot verification (istio_tpu/analysis): build the
    snapshot a server would serve from this config store and run the
    full analyzer — expression checking, rule shadowing/conflicts with
    oracle-confirmed witnesses, NFA/tile budget prediction. Exits 1
    when any ERROR-severity finding is present (CI-gateable), 0 on a
    clean or warning-only config."""
    from istio_tpu.analysis import analyze_store
    from istio_tpu.runtime import FsStore

    store = FsStore(args.config_store)
    report = analyze_store(store)
    if args.json:
        print(json.dumps(report.to_dict(), indent=1, default=str))
    else:
        for f in sorted(report.findings,
                        key=lambda f: -int(f.severity)):
            rules = f" [{', '.join(f.rules)}]" if f.rules else ""
            print(f"{f.severity.name:7s} {f.code}{rules}: {f.message}")
            if f.witness:
                print(f"        witness: {f.witness}")
        print(f"analyze: {len(report.errors)} error(s), "
              f"{len(report.warnings)} warning(s), "
              f"{len(report.findings)} finding(s) over "
              f"{report.n_rules} rule(s) in {report.wall_ms:.0f}ms")
    return 1 if report.has_errors else 0


def cmd_lint(args: argparse.Namespace) -> int:
    """meshlint — the CODE-side sibling of `analyze`: run the
    concurrency & discipline passes (lock order, inferred hot-path
    reachability, metric zero-shaping, typed rejections) over the
    package's own source. Exits 1 when any ERROR-severity finding is
    present (CI-gateable) or when --selftest finds a violation class
    the analyzer no longer detects."""
    from istio_tpu.analysis.meshlint import fixtures, run_meshlint

    if args.selftest:
        problems = fixtures.selftest()
        for p in problems:
            print(f"lint selftest: {p}")
        if not problems:
            print(f"lint selftest: ok "
                  f"({len(fixtures.FIXTURES)} fixtures)")
        return 1 if problems else 0
    root = args.root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    report = run_meshlint(root=root)
    if args.json:
        print(json.dumps(report.to_dict(), indent=1, default=str))
    else:
        for f in report.findings:
            print(f)
        print(f"lint: {len(report.errors)} error(s), "
              f"{len(report.warnings)} warning(s), "
              f"{len(report.findings)} finding(s) over "
              f"{report.n_functions} function(s) in "
              f"{report.n_modules} module(s) in "
              f"{report.wall_ms:.0f}ms")
    return 1 if report.has_errors else 0


def cmd_canary(args: argparse.Namespace) -> int:
    """Offline canary replay (the dynamic sibling of `analyze`): load
    a recorded live-traffic corpus (saved by a serving mixs via
    /debug/canary tooling or canary.save_corpus) and shadow-replay it
    through the candidate config store's compiled snapshot. Prints the
    divergence report; exits 1 when the non-waived divergence rate
    exceeds --max-divergence (CI-gateable: a config PR that flips
    recorded production decisions fails before rollout)."""
    from istio_tpu.canary import (diff_decisions, load_corpus,
                                  replay_entries)
    from istio_tpu.runtime import FsStore
    from istio_tpu.runtime.config import SnapshotBuilder
    from istio_tpu.runtime.fused import build_fused_plan
    from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST

    entries = load_corpus(args.corpus)
    if args.limit and len(entries) > args.limit:
        entries = entries[-args.limit:]
    if not entries:
        print("canary: corpus is empty", file=sys.stderr)
        return 2
    store = FsStore(args.config_store)
    snapshot = SnapshotBuilder(GLOBAL_MANIFEST).build(store)
    for err in snapshot.errors:
        print(f"# config error: {err}", file=sys.stderr)
    plan = build_fused_plan(snapshot, rule_telemetry=False)
    if plan is None:
        print("canary: candidate snapshot has no rules to replay "
              "against", file=sys.stderr)
        return 2
    replay = replay_entries(snapshot, plan, entries,
                            identity_attr=args.identity_attr)
    report = diff_decisions(entries, replay,
                            waivers=tuple(args.waive or ()))
    report.mode = "gate"
    report.threshold = args.max_divergence
    gated = report.divergence_rate > args.max_divergence
    report.verdict = "veto" if gated else "publish"
    if args.json:
        print(json.dumps(report.to_dict(), indent=1, default=str))
    else:
        for rule in report.diverging_rules():
            c = report.per_rule[rule]
            print(f"DIVERGE {rule}: {c['total']} rows "
                  f"(status_flip={c['status_flip']} "
                  f"precondition={c['precondition']} "
                  f"quota={c['quota']})")
        print(f"canary: {report.n_divergent}/{report.n_rows} rows "
              f"diverge (rate {report.divergence_rate:.4f}, "
              f"{report.n_waived} waived) at "
              f"{report.replay_rows_per_s:.0f} rows/s — "
              f"{report.verdict.upper()}")
    return 1 if gated else 0


def cmd_mixc(args: argparse.Namespace) -> int:
    """mixer client (cmd/mixc check/report)."""
    from istio_tpu.api import MixerClient
    attrs = {}
    for kv in args.string_attributes or []:
        k, _, v = kv.partition("=")
        attrs[k] = v
    for kv in args.int64_attributes or []:
        k, _, v = kv.partition("=")
        attrs[k] = int(v)
    client = MixerClient(args.mixer)
    if args.command == "check":
        resp = client.check(attrs)
        print(json.dumps({
            "status_code": resp.precondition.status.code,
            "status_message": resp.precondition.status.message,
            "valid_use_count": resp.precondition.valid_use_count}))
        return 0 if resp.precondition.status.code == 0 else 1
    client.report([attrs])
    print("{}")
    return 0


def cmd_pilot_discovery(args: argparse.Namespace) -> int:
    """pilot-discovery (bootstrap/server.go assembly): initMesh →
    config stores → service registries → discovery."""
    from istio_tpu.pilot import MemoryConfigStore, MemoryRegistry
    from istio_tpu.pilot.discovery import DiscoveryService
    from istio_tpu.pilot.mesh import init_mesh
    from istio_tpu.pilot.registry import AggregateRegistry

    # initMesh (server.go:245): defaults ← file ← flag overrides
    mesh = init_mesh(
        config_file=args.mesh_config,
        overrides={"mixer_address": args.mixer_address},
        on_warn=lambda msg: print(f"pilot-discovery: {msg}"))
    proxy_defaults = mesh["default_config"]
    # flat view: the envoy config generators read the proxy-level
    # fields at top level (envoy_config.py)
    mesh_view = {**mesh,
                 "discovery_address": proxy_defaults["discovery_address"],
                 "admin_port": proxy_defaults["proxy_admin_port"],
                 "zipkin_address": mesh["zipkin_address"] or
                 proxy_defaults["zipkin_address"]}

    memory = MemoryRegistry()
    store = MemoryConfigStore()
    if args.registry_file:
        _load_world(memory, store, args.registry_file)
    backends = [memory]
    # platform registries (bootstrap/server.go:360 initServiceControllers)
    if args.consul_address:
        from istio_tpu.pilot.consul import ConsulRegistry
        consul = ConsulRegistry(args.consul_address)
        consul.start()
        backends.append(consul)
    if args.eureka_address:
        from istio_tpu.pilot.eureka import EurekaRegistry
        eka = EurekaRegistry(args.eureka_address)
        eka.start()
        backends.append(eka)
    registry = backends[0] if len(backends) == 1 \
        else AggregateRegistry(backends)
    ds = DiscoveryService(registry, store, mesh_view)
    reload_stop = None
    if args.registry_file:
        # live reload: istioctl register/deregister edits the file and
        # must take effect without a restart (the reference writes to
        # the live registry; here the file IS the registry backend).
        # The watcher starts AFTER the DiscoveryService so a reload's
        # per-service add/remove storm coalesces into ONE snapshot
        # publish (ds.hold_publishes) instead of a full-world rebuild
        # per service.
        reload_stop = _watch_registry_file(memory, args.registry_file,
                                           ds)
    port = ds.start(args.address, args.port)
    print(f"pilot-discovery: v1 xDS on {args.address}:{port}")
    _serve_forever()
    if reload_stop is not None:
        reload_stop.set()
    ds.stop()
    return 0


def _watch_registry_file(memory, path: str, ds=None):
    """Poll the registry YAML's content; on change, rebuild the memory
    registry's service set (service handlers fire → scoped snapshot
    publish). `ds`: the DiscoveryService whose hold_publishes()
    coalesces the rebuild's event storm into one publish."""
    import contextlib
    import hashlib
    import threading
    import yaml
    from istio_tpu.pilot import Port, Service

    stop = threading.Event()

    def digest() -> bytes:
        try:
            with open(path, "rb") as f:
                return hashlib.sha256(f.read()).digest()
        except OSError:
            return b""

    last = digest()

    def loop() -> None:
        nonlocal last
        while not stop.wait(1.0):
            now = digest()
            if now == last:
                continue
            last = now
            try:
                with open(path, encoding="utf-8") as f:
                    world = yaml.safe_load(f) or {}
            except (OSError, yaml.YAMLError) as exc:
                print(f"pilot-discovery: registry reload failed: {exc}")
                continue
            wanted = {}
            for s in world.get("services") or ():
                svc = Service(
                    hostname=s["hostname"],
                    address=s.get("address", "0.0.0.0"),
                    ports=tuple(Port(p["name"], int(p["port"]),
                                     p.get("protocol", "HTTP"))
                                for p in s.get("ports") or ()))
                wanted[svc.hostname] = (svc, [
                    (e["address"], e.get("labels", {}))
                    for e in s.get("endpoints") or ()])
            hold = ds.hold_publishes() if ds is not None \
                else contextlib.nullcontext()
            with hold:
                for host in [svc.hostname
                             for svc in memory.services()]:
                    if host not in wanted:
                        memory.remove_service(host)
                for svc, endpoints in wanted.values():
                    memory.add_service(svc, endpoints)

    t = threading.Thread(target=loop, daemon=True,
                         name="registry-reload")
    t.start()
    return stop


def _register_endpoint(args: argparse.Namespace) -> int:
    """istioctl register <svc> <ip> [name:port...] /
    deregister <svc> <ip> over the registry YAML."""
    import yaml
    path = args.registry_file
    try:
        with open(path, encoding="utf-8") as f:
            world = yaml.safe_load(f) or {}
    except FileNotFoundError:
        world = {}
    # normalize null-valued keys (a hand-written "services:" with no
    # value loads as None)
    world["services"] = services = list(world.get("services") or ())
    hostname = args.kind        # positional reuse: <svc> <ip>
    address = args.name
    if not hostname or not address:
        print("usage: istioctl register <service> <ip> [name:port ...]",
              file=sys.stderr)
        return 2
    svc = next((s for s in services if s.get("hostname") == hostname),
               None)
    if args.command == "register":
        ports = []
        specs = [p for p in (args.ports or "http:80").split(",") if p]
        for spec in specs:
            name, sep, num = spec.partition(":")
            if not sep or not num.isdigit():
                print(f"bad port spec {spec!r}: expected name:port",
                      file=sys.stderr)
                return 2
            ports.append({"name": name, "port": int(num)})
        if svc is None:
            svc = {"hostname": hostname, "ports": ports, "endpoints": []}
            services.append(svc)
        else:
            # reconcile ports on an existing service like the
            # reference RegisterEndpoint (register.go:126-136)
            existing = {p.get("name") for p in (svc.get("ports") or ())}
            svc["ports"] = list(svc.get("ports") or ()) + \
                [p for p in ports if p["name"] not in existing]
        svc["endpoints"] = eps = list(svc.get("endpoints") or ())
        if not any(e.get("address") == address for e in eps):
            eps.append({"address": address})
        print(f"registered {address} -> {hostname}")
    else:
        if svc is None:
            print(f"unknown service {hostname}", file=sys.stderr)
            return 1
        svc["endpoints"] = [e for e in (svc.get("endpoints") or ())
                            if e.get("address") != address]
        print(f"deregistered {address} from {hostname}")
    with open(path, "w", encoding="utf-8") as f:
        yaml.safe_dump(world, f, sort_keys=False)
    return 0


def cmd_generate_key_cert(args: argparse.Namespace) -> int:
    """generate_cert / generate_csr (security/cmd): standalone key +
    self-signed cert or CSR for an identity."""
    from istio_tpu.security import pki
    key = pki.generate_key()
    key_pem = pki.key_to_pem(key)
    if args.mode == "csr":
        out = pki.generate_csr(key, args.identity, org=args.org)
    else:
        from istio_tpu.security.ca import IstioCA
        ca = IstioCA.new_self_signed(org=args.org)
        out = ca.sign(pki.generate_csr(key, args.identity, org=args.org))
        with open(args.out_root, "wb") as f:
            f.write(ca.get_root_certificate())
    with open(args.out_key, "wb") as f:
        f.write(key_pem)
    with open(args.out_cert, "wb") as f:
        f.write(out)
    print(f"wrote {args.out_key} + {args.out_cert}")
    return 0


def _load_world(registry, store, path: str) -> None:
    """Topology + config from one YAML file: {services: [...],
    configs: [...]} — the file-based registry mode."""
    import yaml
    from istio_tpu.pilot import Config, ConfigMeta, Port, Service
    with open(path, encoding="utf-8") as f:
        world = yaml.safe_load(f) or {}
    for s in world.get("services", ()):
        svc = Service(hostname=s["hostname"],
                      address=s.get("address", "0.0.0.0"),
                      ports=tuple(Port(p["name"], int(p["port"]),
                                       p.get("protocol", "HTTP"))
                                  for p in s.get("ports", ())))
        registry.add_service(svc, [(e["address"], e.get("labels", {}))
                                   for e in s.get("endpoints", ())])
    for c in world.get("configs", ()):
        meta = c.get("metadata", {})
        store.create(Config(ConfigMeta(type=c["kind"],
                                       name=meta.get("name", ""),
                                       namespace=meta.get("namespace",
                                                          "default")),
                            c.get("spec", {})))


def cmd_pilot_agent(args: argparse.Namespace) -> int:
    """pilot-agent proxy (cmd/pilot-agent/main.go:71)."""
    import subprocess
    from istio_tpu.pilot.agent import Agent, CertWatcher, Proxy

    class EnvoyProxy(Proxy):
        def run(self, config, epoch, abort):
            # config is (path, cert_hash): the hash participates in the
            # agent's config comparison so cert rotation forces an epoch
            path, _cert_hash = config
            cmd = [args.binary_path, "--restart-epoch", str(epoch),
                   "--drain-time-s", str(args.drain_duration),
                   "-c", path]
            proc = subprocess.Popen(cmd)
            while proc.poll() is None:
                if abort.wait(0.2):
                    proc.terminate()
                    proc.wait(timeout=10)
                    return
            if proc.returncode != 0:
                raise RuntimeError(f"envoy exited {proc.returncode}")

    agent = Agent(EnvoyProxy())
    agent.schedule_config_update((args.config_path, ""))
    watcher = CertWatcher([args.cert_dir],
                          lambda h: agent.schedule_config_update(
                              (args.config_path, h))) \
        if args.cert_dir else None
    if watcher:
        watcher.start()
    print(f"pilot-agent: managing {args.binary_path} epochs")
    _serve_forever()
    if watcher:
        watcher.stop()
    agent.close()
    return 0


def cmd_istioctl(args: argparse.Namespace) -> int:
    """istioctl create/get/delete/kube-inject/register/deregister over
    an FsStore-style config dir (the reference talks to k8s CRDs; the
    file store is this build's durable backend)."""
    import os
    import yaml
    from istio_tpu.pilot.model import IstioConfigTypes, ValidationError
    if args.command == "kube-inject":
        from istio_tpu.pilot.inject import InjectParams, into_resource_file
        with open(args.filename, encoding="utf-8") as f:
            print(into_resource_file(InjectParams(), f.read()))
        return 0
    if args.command in ("register", "deregister"):
        # VM endpoint (de)registration (serviceregistry/kube/
        # register.go:120: create the Service if absent, then add or
        # remove the endpoint address) — against the registry file
        # pilot-discovery serves from
        return _register_endpoint(args)
    cfg_dir = args.config_dir
    if args.command in ("create", "replace"):
        with open(args.filename, encoding="utf-8") as f:
            docs = list(yaml.safe_load_all(f))
        for doc in docs:
            if not doc:
                continue
            kind = doc.get("kind", doc.get("type", ""))
            schema = IstioConfigTypes.get(kind)
            if schema is None:
                print(f"unknown config kind {kind}", file=sys.stderr)
                return 1
            try:
                schema.validate(doc.get("spec", {}))
            except ValidationError as exc:
                print(f"invalid {kind}: {exc}", file=sys.stderr)
                return 1
            meta = doc.get("metadata", {})
            name = meta.get("name", "unnamed")
            ns = meta.get("namespace", "default")
            path = os.path.join(cfg_dir, f"{kind}-{ns}-{name}.yaml")
            if args.command == "create" and os.path.exists(path):
                print(f"{kind} {name} already exists", file=sys.stderr)
                return 1
            with open(path, "w", encoding="utf-8") as f:
                yaml.safe_dump(doc, f, sort_keys=False)
            print(f"{args.command}d {kind} {name}.{ns}")
        return 0
    if args.command == "get":
        import glob
        for path in sorted(glob.glob(os.path.join(cfg_dir, "*.yaml"))):
            with open(path, encoding="utf-8") as f:
                for doc in yaml.safe_load_all(f):
                    if doc and (args.kind in ("all", doc.get("kind"))):
                        meta = doc.get("metadata", {})
                        print(f"{doc.get('kind')}\t{meta.get('name')}"
                              f"\t{meta.get('namespace', 'default')}")
        return 0
    if args.command == "delete":
        import glob
        pattern = f"{args.kind}-{args.namespace}-{args.name}.yaml"
        hits = glob.glob(os.path.join(cfg_dir, pattern))
        for path in hits:
            os.unlink(path)
            print(f"deleted {args.kind} {args.name}.{args.namespace}")
        return 0 if hits else 1
    return 2


def cmd_istio_ca(args: argparse.Namespace) -> int:
    """istio_ca (security/cmd/istio_ca/main.go:146)."""
    import pickle
    from istio_tpu.security import IstioCA
    from istio_tpu.security.ca_service import CAGrpcServer
    secrets: dict = {}
    if args.secret_file:
        try:
            with open(args.secret_file, "rb") as f:
                secrets.update(pickle.load(f))
        except FileNotFoundError:
            pass
    ca = IstioCA.new_self_signed(secrets)
    if args.secret_file:
        with open(args.secret_file, "wb") as f:
            pickle.dump(secrets, f)
    if args.insecure_allow_all:
        from istio_tpu.security.ca_service import (
            allow_any_identity_authorizer,
            insecure_allow_all_authenticator)
        print("WARNING: --insecure-allow-all signs ANY identity for ANY "
              "caller over plaintext; never use outside tests")
        server = CAGrpcServer(
            ca, authenticator=insecure_allow_all_authenticator,
            authorizer=allow_any_identity_authorizer,
            address=f"{args.address}:{args.port}", insecure_port=True)
    else:
        # onprem flow: callers present an existing cert signed by this
        # root; they may renew only their own SPIFFE identity. With
        # --trusted-tokens-file, gcp/aws bearer credentials map to
        # identities from the operator-provisioned token table.
        import json as _json
        from istio_tpu.security.ca_service import (
            cert_authenticator, composite_authenticator,
            token_authenticator)
        authenticator = cert_authenticator(ca.get_root_certificate())
        if args.trusted_tokens_file:
            with open(args.trusted_tokens_file) as f:
                authenticator = composite_authenticator(
                    authenticator, token_authenticator(_json.load(f)))
        server = CAGrpcServer(
            ca, authenticator=authenticator,
            address=f"{args.address}:{args.port}")
    port = server.start()
    print(f"istio_ca: CSR service on {args.address}:{port}")
    _serve_forever()
    server.stop()
    return 0


def cmd_node_agent(args: argparse.Namespace) -> int:
    """node_agent (security/cmd/node_agent): the bootstrap credential
    comes from a platform fetcher (security/pkg/platform client.go)."""
    import os
    from istio_tpu.security.ca_service import CAClient, NodeAgent
    from istio_tpu.security.workload import (SecretConfig,
                                             SecretFileServer)
    os.makedirs(args.cert_dir, exist_ok=True)
    sink = SecretFileServer(SecretConfig(
        service_identity_cert_file=os.path.join(args.cert_dir,
                                                "cert-chain.pem"),
        service_identity_private_key_file=os.path.join(args.cert_dir,
                                                       "key.pem")))

    def write_certs(key_pem: bytes, cert_pem: bytes, root_pem: bytes):
        sink.set_service_identity_private_key(key_pem)
        sink.set_service_identity_cert(cert_pem)
        with open(os.path.join(args.cert_dir, "root-cert.pem"),
                  "wb") as f:
            f.write(root_pem)

    root_pem = None
    credential = b""
    cred_type = args.platform
    if args.platform != "onprem":
        # gcp/aws need a metadata endpoint; hermetic runs inject one
        # from a JSON path→value file
        import json as _json
        from istio_tpu.security.platform import (PlatformError,
                                                 new_platform_client)

        class _FileMetadata:
            def __init__(self, path: str):
                with open(path) as f:
                    self._data = _json.load(f)

            def available(self) -> bool:
                return True

            def fetch(self, path: str, audience: str = "") -> str:
                value = self._data.get(path, "")
                if isinstance(value, str):
                    return value
                return _json.dumps(value)   # nested docs stay valid JSON

        if not args.platform_metadata_file:
            print("node_agent: --platform-metadata-file is required for "
                  f"platform {args.platform} (no metadata service here)")
            return 2
        if not args.root_cert and not args.insecure_ca:
            print("node_agent: --root-cert is required (the bearer "
                  "credential must not travel in cleartext); pass "
                  "--insecure-ca only against a test CA")
            return 2
        try:
            cfg = {
                "ca_addr": args.ca_address,
                "metadata": _FileMetadata(args.platform_metadata_file),
                "root_ca_cert_file": args.root_cert}
            if args.platform == "aws":
                # AwsClient fails closed without a PKCS7 verifier and
                # none ships in this build — the operator must opt out
                # explicitly (mirrors --insecure-ca's posture)
                if not args.skip_identity_verify:
                    print("node_agent: --platform aws requires "
                          "--skip-identity-verify (no PKCS7 verifier "
                          "in this build; identity signature would "
                          "fail closed)")
                    return 2
                cfg["verify"] = False
            pc = new_platform_client(args.platform, cfg)
            credential = pc.get_agent_credential()
            cred_type = pc.get_credential_type()
        except (OSError, ValueError, PlatformError) as exc:
            print(f"node_agent: platform credential fetch failed: {exc}")
            return 2
    elif not args.insecure_ca and not (args.root_cert and
                                       args.bootstrap_cert):
        print("node_agent: --root-cert and --bootstrap-cert are required"
              " (the CA serves TLS and authenticates onprem credentials);"
              " pass --insecure-ca only against a test CA running with"
              " --insecure-allow-all")
        return 2
    if args.root_cert:
        with open(args.root_cert, "rb") as f:
            root_pem = f.read()
    if args.bootstrap_cert and not credential:
        with open(args.bootstrap_cert, "rb") as f:
            credential = f.read()
    client = CAClient(args.ca_address, root_cert_pem=root_pem)
    agent = NodeAgent(client, args.identity, write_certs,
                      ttl_minutes=args.ttl_minutes,
                      credential=credential, credential_type=cred_type)
    agent.start()
    print(f"node_agent: rotating {args.identity} certs in {args.cert_dir}")
    _serve_forever()
    agent.stop()
    client.close()
    return 0


def cmd_brkcol(args: argparse.Namespace) -> int:
    """brkcol (broker/cmd/brkcol): broker-config collector — read the
    service-class / service-plan kinds out of a config store, assemble
    the OSB catalog exactly as a serving brks would (controller.go:48
    via BrokerConfigStore.catalog), and print it. The offline
    collection/inspection half of the broker pair: run it against the
    store a broker will mount to see the catalog it would serve."""
    from istio_tpu.broker.model import BrokerConfigStore
    from istio_tpu.runtime import FsStore

    store = FsStore(args.config_store)
    bcs = BrokerConfigStore(store)
    classes = bcs.service_classes()
    plans = bcs.service_plans()
    catalog = bcs.catalog().to_wire()
    if args.json:
        print(json.dumps({"service_classes": sorted(classes),
                          "service_plans": sorted(plans),
                          "catalog": catalog}, indent=1))
    else:
        print(f"brkcol: {len(classes)} service-class(es), "
              f"{len(plans)} service-plan(s), "
              f"{len(catalog['services'])} catalog service(s)")
        for key in sorted(classes):
            print(f"  class {key}")
        for key, plan in sorted(plans.items()):
            svcs = ",".join(plan.get("services") or ())
            print(f"  plan  {key} -> [{svcs}]")
    return 0


def cmd_brks(args: argparse.Namespace) -> int:
    """brks (broker/cmd/brks)."""
    import yaml
    from istio_tpu.broker import BrokerServer
    services = []
    if args.catalog:
        with open(args.catalog, encoding="utf-8") as f:
            services = (yaml.safe_load(f) or {}).get("services", [])
    broker = BrokerServer(services)
    port = broker.start(args.address, args.port)
    print(f"brks: OSB v2 on {args.address}:{port}")
    _serve_forever()
    broker.stop()
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="istio-tpu",
                                description=__doc__)
    sub = p.add_subparsers(dest="tool", required=True)

    s = sub.add_parser("mixs", help="mixer (policy) server")
    s.add_argument("--address", default="127.0.0.1")
    s.add_argument("--port", type=int, default=9091)
    s.add_argument("--monitoring-port", type=int, default=9093)
    s.add_argument("--monitoring-host", default="127.0.0.1",
                   help="introspection bind address (loopback by "
                        "default; 0.0.0.0 restores the reference's "
                        "network-scrapable :9093)")
    s.add_argument("--trace-ring", type=int, default=0,
                   help="/debug/traces ring capacity; 0 (default) "
                        "keeps span recording OFF the serving hot "
                        "path")
    s.add_argument("--config-store", default="",
                   help="YAML config dir (FsStore); empty = memory")
    s.add_argument("--batch-window-us", type=int, default=300)
    s.add_argument("--max-batch", type=int, default=1024)
    s.add_argument("--default-check-deadline-ms", type=float,
                   default=0.0,
                   help="server-side Check deadline for fronts whose "
                        "wire carries none (the native front); "
                        "expired requests answer DEADLINE_EXCEEDED "
                        "before tensorize. 0 = off")
    s.add_argument("--check-queue-cap", type=int, default=None,
                   help="check batcher queue cap: submits past it "
                        "shed RESOURCE_EXHAUSTED (default "
                        "8*max-batch; 0 = unbounded)")
    s.add_argument("--report-queue-cap", type=int, default=None,
                   help="report record coalescer admission cap: the "
                        "ack-after-enqueue contract's bound — records "
                        "past it shed typed RESOURCE_EXHAUSTED "
                        "(default 16*max-batch; 0 = unbounded)")
    s.add_argument("--brownout", action="store_true",
                   help="shed the newest check requests while the "
                        "live p99 gauge is over the SLO target and "
                        "the queue is half full")
    s.add_argument("--check-fail-policy", default="closed",
                   choices=("open", "closed"),
                   help="answer when device AND oracle check paths "
                        "are down: open = OK (Mixer-client fail-"
                        "open), closed = UNAVAILABLE")
    s.add_argument("--breaker-failures", type=int, default=3,
                   help="consecutive failed device batches that trip "
                        "the circuit breaker onto the CPU oracle path")
    s.add_argument("--breaker-reset-ms", type=float, default=5000.0,
                   help="how long the breaker stays open before a "
                        "half-open device probe")
    s.add_argument("--host-fail-policy", default="closed",
                   choices=("open", "closed"),
                   help="verdict an unresolvable host adapter action "
                        "(deadline overrun, bulkhead shed, open "
                        "lane breaker) contributes: open = OK with a "
                        "1s/1-use TTL, closed = UNAVAILABLE")
    s.add_argument("--executor-workers", type=int, default=2,
                   help="worker threads per handler lane in the "
                        "adapter executor (the bulkhead's "
                        "concurrency share)")
    s.add_argument("--executor-queue-cap", type=int, default=256,
                   help="pending host actions per handler lane; "
                        "overflow sheds typed RESOURCE_EXHAUSTED "
                        "semantics onto the fail policy")
    s.add_argument("--host-action-timeout-ms", type=float,
                   default=0.0,
                   help="extra per-host-action wall bound even when "
                        "the request carries no deadline (0 = bound "
                        "by the request deadline only)")
    s.add_argument("--no-host-executor", action="store_true",
                   help="run host adapter work inline on the batch "
                        "worker (the pre-executor loop) instead of "
                        "the bulkheaded executor plane")
    s.add_argument("--host-breaker-failures", type=int, default=3,
                   help="consecutive failed/overrun actions that trip "
                        "a handler lane's circuit breaker")
    s.add_argument("--host-breaker-reset-ms", type=float,
                   default=5000.0,
                   help="how long an open handler-lane breaker waits "
                        "before a half-open probe")
    s.add_argument("--canary", default="off",
                   choices=("off", "warn", "gate"),
                   help="config canary: shadow-replay recorded live "
                        "traffic through every rebuilt snapshot "
                        "before the atomic publish; gate vetoes "
                        "divergent swaps (the old config keeps "
                        "serving), warn publishes but records the "
                        "report on /debug/canary")
    s.add_argument("--canary-max-divergence", type=float, default=0.0,
                   help="divergence rate (non-waived divergent rows /"
                        " replayed rows) beyond which gate mode "
                        "vetoes; 0 = any divergence vetoes")
    s.add_argument("--canary-capacity", type=int, default=2048,
                   help="recorder sampling-ring capacity")
    s.add_argument("--canary-sample-every", type=int, default=1,
                   help="record every k-th check request")
    s.add_argument("--canary-replay-limit", type=int, default=1024,
                   help="newest recorded rows replayed per candidate "
                        "evaluation")
    s.add_argument("--canary-waive", action="append", metavar="RULE",
                   help="qualified rule name (ns/name) whose "
                        "divergences never gate (repeatable)")
    s.add_argument("--shards", type=int, default=0,
                   help="partition the snapshot by namespace into "
                        "this many compiled banks (the sharded "
                        "serving plane, istio_tpu/sharding); 0 = "
                        "monolithic")
    s.add_argument("--replicas", type=int, default=1,
                   help="replica-parallel serving lanes behind the "
                        "one front (sticky-by-namespace)")
    s.add_argument("--jax-compile-cache-dir", default=None,
                   metavar="DIR",
                   help="JAX persistent compilation cache directory: "
                        "restarts and rolling deploys skip warm XLA "
                        "compiles for unchanged banks "
                        "(compiler/cache.py). The "
                        "JAX_COMPILATION_CACHE_DIR env var wins when "
                        "set; neither = <checkout>/.jax_cache")
    s.add_argument("--no-delta-compile", action="store_true",
                   help="kill switch for delta compilation: every "
                        "config publish rebuilds every shard bank "
                        "instead of diffing by content hash")
    s.add_argument("--shard-rebalance-budget", type=int, default=0,
                   help="namespaces the delta planner may relocate "
                        "per republish to chase LPT balance (each "
                        "move recompiles two banks; 0 = perfect plan "
                        "stability)")
    s.add_argument("--continuous-batching", action="store_true",
                   help="latency lane: the check batcher dispatches "
                        "a batch the moment an in-flight slot under "
                        "--continuous-depth frees instead of holding "
                        "for window/occupancy fill "
                        "(runtime/batcher.py)")
    s.add_argument("--continuous-depth", type=int, default=2,
                   help="in-flight step bound for continuous "
                        "batching (default 2: one step executing, "
                        "one dispatching)")
    s.add_argument("--no-flight-recorder", action="store_true",
                   help="disable the per-request flight recorder "
                        "(/debug/slow stays empty; the event "
                        "timeline keeps recording)")
    s.add_argument("--slow-threshold-ms", type=float, default=0.0,
                   help="flight-recorder capture threshold in ms "
                        "(0 = the live SLO target)")
    s.add_argument("--slow-adaptive", action="store_true",
                   help="adaptive threshold: track the live window "
                        "p99 (never below the configured base)")
    s.add_argument("--profile-dir", default=None,
                   help="directory for /debug/profile jax.profiler "
                        "captures (default: MIXS_PROFILE_DIR env or "
                        "a per-capture tempdir)")
    s.add_argument("--no-audit", action="store_true",
                   help="disable the background mesh audit plane "
                        "(invariant auditor + fault-explainability "
                        "scorer; /debug/audit reports enabled=false)")
    s.add_argument("--audit-interval-ms", type=float, default=500.0,
                   help="audit evaluation cadence in ms (the quota "
                        "recount samples every 8th evaluation)")
    s.add_argument("--check-grants", action="store_true",
                   help="server-issued check-cache grants: "
                        "valid_duration/valid_use_count derived from "
                        "config-generation age (runtime/grants.py) — "
                        "repeat traffic serves from the client cache "
                        "and a config delta revokes within "
                        "--grant-ttl-floor-s")
    s.add_argument("--grant-ttl-floor-s", type=float, default=1.0,
                   help="grant TTL right after a config change (the "
                        "revocation window)")
    s.add_argument("--grant-ttl-cap-s", type=float, default=5.0,
                   help="grant TTL ceiling for a long-stable config")
    s.add_argument("--trace-zipkin-url", default="",
                   help="zipkin v2 collector (POST /api/v2/spans)")
    s.add_argument("--trace-log-spans", action="store_true",
                   help="log every span (pkg/tracing LogTraceSpans)")
    s.add_argument("--mtls", default="off",
                   choices=("off", "permissive", "strict"),
                   help="secure serving plane (istio_tpu/secure): "
                        "strict = TLS fronts REQUIRE a CA-signed "
                        "client cert at handshake and its SPIFFE "
                        "identity feeds source.user/connection.mtls "
                        "into the compiled RBAC plane (a verified "
                        "cert with no SPIFFE SAN answers typed "
                        "UNAUTHENTICATED); permissive = TLS "
                        "encryption only, client certs optional and "
                        "no identity flows; off = plaintext")
    s.add_argument("--mtls-identity",
                   default="spiffe://cluster.local/ns/istio-system"
                           "/sa/istio-mixer",
                   help="SPIFFE identity on the serving certificate")
    s.add_argument("--tls-dns", default="mixer.local",
                   help="DNS SAN on the serving certificate (clients "
                        "match their target-name override against "
                        "this)")
    s.add_argument("--tls-key", default="",
                   help="static serving key PEM (with --tls-cert/"
                        "--tls-root; no rotation)")
    s.add_argument("--tls-cert", default="",
                   help="static serving cert chain PEM")
    s.add_argument("--tls-root", default="",
                   help="static client-verification root PEM")
    s.add_argument("--ca-address", default="",
                   help="CSR service (istio-ca) to obtain + rotate "
                        "the serving bundle from; rotation runs on "
                        "the adapter-executor maintenance lane and "
                        "hot-swaps live fronts with zero dropped "
                        "requests")
    s.add_argument("--ca-root-cert", default="",
                   help="root PEM for TLS to the CA service")
    s.add_argument("--bootstrap-cert", default="",
                   help="existing cert presented as the onprem CSR "
                        "credential")
    s.add_argument("--mtls-cert-ttl-minutes", type=int, default=60,
                   help="requested serving-cert TTL")
    s.add_argument("--mtls-rotation-fraction", type=float,
                   default=0.5,
                   help="rotate when less than this fraction of the "
                        "TTL remains")
    s.add_argument("--introspect-tls", action="store_true",
                   help="wrap the introspection HTTP port in TLS "
                        "from the same serving bundle (client certs "
                        "optional — scrapers rarely hold workload "
                        "identities)")
    s.set_defaults(fn=cmd_mixs)

    s = sub.add_parser("rule-dump",
                       help="disassemble a compiled config snapshot")
    s.add_argument("--config-store", required=True,
                   help="config directory (k8s-style YAML docs)")
    s.add_argument("--explain", nargs="*", metavar="attr=value",
                   help="step one request (string attrs) through the "
                        "ruleset and show per-atom/per-rule verdicts")
    s.set_defaults(fn=cmd_rule_dump)

    s = sub.add_parser("analyze",
                       help="static snapshot verification (exit 1 on "
                            "ERROR findings)")
    s.add_argument("--config-store", required=True,
                   help="config directory (k8s-style YAML docs)")
    s.add_argument("--json", action="store_true",
                   help="machine-readable report")
    s.set_defaults(fn=cmd_analyze)

    s = sub.add_parser("lint",
                       help="meshlint: concurrency & discipline "
                            "static analysis over the package source "
                            "(exit 1 on ERROR findings)")
    s.add_argument("--root", default=None,
                   help="repo root holding the istio_tpu package "
                        "(default: the installed package's parent)")
    s.add_argument("--selftest", action="store_true",
                   help="run the seeded violation corpus instead of "
                        "the tree (proves every violation class is "
                        "still detected)")
    s.add_argument("--json", action="store_true",
                   help="machine-readable report")
    s.set_defaults(fn=cmd_lint)

    s = sub.add_parser("canary",
                       help="offline shadow replay: recorded corpus "
                            "vs candidate config (exit 1 on "
                            "divergence past the threshold)")
    s.add_argument("--config-store", required=True,
                   help="candidate config directory (k8s-style YAML)")
    s.add_argument("--corpus", required=True,
                   help="recorded corpus file (canary.save_corpus)")
    s.add_argument("--max-divergence", type=float, default=0.0,
                   help="gate threshold (0 = any divergence fails)")
    s.add_argument("--limit", type=int, default=0,
                   help="replay only the newest N corpus rows")
    s.add_argument("--waive", action="append", metavar="RULE",
                   help="qualified rule name excluded from gating "
                        "(repeatable)")
    s.add_argument("--identity-attr", default="destination.service",
                   help="namespace-targeting identity attribute — "
                        "must match the serving server's "
                        "ServerArgs.identity_attr the corpus was "
                        "recorded under")
    s.add_argument("--json", action="store_true",
                   help="machine-readable report")
    s.set_defaults(fn=cmd_canary)

    s = sub.add_parser("mixc", help="mixer client")
    s.add_argument("command", choices=["check", "report"])
    s.add_argument("--mixer", default="127.0.0.1:9091")
    s.add_argument("-s", "--string-attributes", action="append")
    s.add_argument("-i", "--int64-attributes", action="append")
    s.set_defaults(fn=cmd_mixc)

    s = sub.add_parser("pilot-discovery", help="discovery server")
    s.add_argument("--address", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8080)
    s.add_argument("--registry-file", default="",
                   help="YAML world file: {services: [], configs: []}")
    s.add_argument("--mixer-address", default="")
    s.add_argument("--mesh-config", default="",
                   help="mesh config YAML (defaults applied; bad file "
                        "falls back to defaults with a warning)")
    s.add_argument("--consul-address", default="",
                   help="consul agent addr (host:port) to federate")
    s.add_argument("--eureka-address", default="",
                   help="eureka server URL to federate")
    s.set_defaults(fn=cmd_pilot_discovery)

    s = sub.add_parser("pilot-agent", help="sidecar agent")
    s.add_argument("--binary-path", default="/usr/local/bin/envoy")
    s.add_argument("--config-path", default="/etc/istio/proxy/envoy.json")
    s.add_argument("--cert-dir", default="")
    s.add_argument("--drain-duration", type=int, default=45)
    s.set_defaults(fn=cmd_pilot_agent)

    s = sub.add_parser("istioctl", help="config CRUD + kube-inject + "
                                        "VM registration")
    s.add_argument("command",
                   choices=["create", "replace", "get", "delete",
                            "kube-inject", "register", "deregister"])
    s.add_argument("-f", "--filename", default="")
    s.add_argument("--config-dir", default=".")
    s.add_argument("--registry-file", default="registry.yaml",
                   help="registry YAML for register/deregister")
    s.add_argument("--ports", default="",
                   help="comma-separated name:port pairs for register")
    s.add_argument("kind", nargs="?", default="all",
                   help="config kind, or <service> for register")
    s.add_argument("name", nargs="?", default="",
                   help="config name, or <ip> for register")
    s.add_argument("-n", "--namespace", default="default")
    s.set_defaults(fn=cmd_istioctl)

    s = sub.add_parser("generate-cert",
                       help="standalone key + CA-signed cert")
    s.add_argument("--identity", required=True)
    s.add_argument("--org", default="istio_tpu")
    s.add_argument("--out-key", default="key.pem")
    s.add_argument("--out-cert", default="cert.pem")
    s.add_argument("--out-root", default="root-cert.pem")
    s.set_defaults(fn=cmd_generate_key_cert, mode="cert")

    s = sub.add_parser("generate-csr", help="standalone key + CSR")
    s.add_argument("--identity", required=True)
    s.add_argument("--org", default="istio_tpu")
    s.add_argument("--out-key", default="key.pem")
    s.add_argument("--out-cert", default="csr.pem")
    s.set_defaults(fn=cmd_generate_key_cert, mode="csr")

    s = sub.add_parser("istio-ca", help="certificate authority")
    s.add_argument("--address", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8060)
    s.add_argument("--secret-file", default="",
                   help="persist the self-signed root here")
    s.add_argument("--insecure-allow-all", action="store_true",
                   help="TEST ONLY: plaintext port, no authn/authz")
    s.add_argument("--trusted-tokens-file", default="",
                   help="JSON token→identity map for gcp/aws bearer "
                        "credentials")
    s.set_defaults(fn=cmd_istio_ca)

    s = sub.add_parser("node-agent", help="workload cert rotation")
    s.add_argument("--ca-address", default="127.0.0.1:8060")
    s.add_argument("--identity", required=True)
    s.add_argument("--cert-dir", default="/etc/certs")
    s.add_argument("--ttl-minutes", type=int, default=60)
    s.add_argument("--root-cert", default="",
                   help="CA root for TLS to the CA service")
    s.add_argument("--bootstrap-cert", default="",
                   help="existing cert presented as the onprem credential")
    s.add_argument("--insecure-ca", action="store_true",
                   help="TEST ONLY: plaintext CA without credentials")
    s.add_argument("--platform", default="onprem",
                   choices=("onprem", "gcp", "aws"),
                   help="bootstrap credential fetcher")
    s.add_argument("--platform-metadata-file", default="",
                   help="JSON path→value metadata fixture for gcp/aws")
    s.add_argument("--skip-identity-verify", action="store_true",
                   help="INSECURE: accept the aws instance-identity "
                        "document without PKCS7 signature verification "
                        "(no verifier is available in this build; "
                        "required for --platform aws)")
    s.set_defaults(fn=cmd_node_agent)

    s = sub.add_parser("brks", help="OSB broker")
    s.add_argument("--address", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8090)
    s.add_argument("--catalog", default="")
    s.set_defaults(fn=cmd_brks)

    s = sub.add_parser("brkcol",
                       help="broker-config collector: assemble + "
                            "print the OSB catalog a broker would "
                            "serve from this config store")
    s.add_argument("--config-store", required=True,
                   help="directory of YAML config documents "
                        "(service-class / service-plan kinds)")
    s.add_argument("--json", action="store_true",
                   help="machine-readable output")
    s.set_defaults(fn=cmd_brkcol)
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
