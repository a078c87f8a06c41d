"""The soak rig: build the whole mesh in-process, run the phases,
gate the recovery. scripts/soak_smoke.py (tier-1 scale) is a thin
wrapper over run_soak().

The harness owns every mutable endpoint so the mid-soak restart is
just "replace what I own": the fleet reads ports through closures and
reconnects on its own, exactly like sidecars through a control-plane
bounce. The restart rides the ordered-shutdown doctrine
(scripts/lifecycle_smoke.py): fronts stop first, the runtime drains
and reaps its threads, then a fresh server + fronts come up over the
SAME stores — counters are process-global, so conservation is checked
straight across the quiesce.
"""
from __future__ import annotations

import dataclasses
import logging
import time

log = logging.getLogger("istio_tpu.soak.harness")

WEDGED = "cilist.istio-system"
QUOTA_NAME = "rq.istio-system"
DEADLINE_MS = 600.0


@dataclasses.dataclass
class SoakConfig:
    seed: int = 0
    n_rules: int = 32
    n_services: int = 12
    n_namespaces: int = 4
    replicas: int = 2
    n_sidecars_grpc: int = 3
    n_sidecars_native: int = 1
    warmup_s: float = 1.0
    storm_s: float = 6.0
    recovery_timeout_s: float = 30.0
    pace_s: float = 0.002
    quota_every: int = 5
    report_every: int = 7
    restart: bool = True
    canary: bool = False
    min_fault_kinds: int = 3
    buckets: tuple = (8, 16)


def overlay_request(i: int, n_services: int) -> dict:
    """Request matching make_store(host_overlay_every=5) rule i (the
    executor_smoke convention — i % 5 == 2, k == 0 → cilist): the
    traffic that makes a wedged cilist lane observable."""
    return {
        "destination.service":
            f"svc{i % n_services}.ns{i % 23}.svc.cluster.local",
        "source.namespace": "ns2",
        "request.method": "GET",
        "request.path": f"/api/v{i % 3}/items",
    }


class SoakHarness:
    """Owns the mesh: mixer store + RuntimeServer + both fronts +
    introspect, and the discovery world + in-process
    DiscoveryService. Implements the choreographer's event surface."""

    wedged_handler = WEDGED
    quota_name = QUOTA_NAME

    def __init__(self, cfg: SoakConfig):
        from istio_tpu.pilot.discovery import DiscoveryService
        from istio_tpu.testing import workloads

        self.cfg = cfg
        self.store = workloads.make_store(cfg.n_rules,
                                          host_overlay_every=5,
                                          seed=cfg.seed)
        (self.registry, self.dstore, self.nodes,
         self.meta) = workloads.make_discovery_world(
            n_services=cfg.n_services,
            n_namespaces=cfg.n_namespaces,
            replicas=cfg.replicas, seed=cfg.seed)
        self.disc = DiscoveryService(self.registry, self.dstore)
        self.ns_ports = {f"ns{k}": p
                         for k, p in self.meta["ns_ports"].items()}
        self._churnable = sorted(self.meta["rules_by_ns"])
        self.srv = None
        self.g = self.native = self.intro = None
        self.gport = self.nport = self.http_port = 0
        self.restarts = 0
        self.restart_wall_s = 0.0
        self._build_server()

    def _args(self):
        from istio_tpu.runtime import ServerArgs
        from istio_tpu.testing import workloads
        cfg = self.cfg
        return ServerArgs(
            batch_window_s=0.0005, max_batch=16,
            buckets=cfg.buckets,
            default_check_deadline_ms=DEADLINE_MS,
            host_breaker_failures=2, host_breaker_reset_s=0.4,
            breaker_reset_s=1.5,
            audit_interval_s=0.2,
            # the explainability window must cover the WHOLE soak —
            # storm + recovery + settle — or early injections age out
            # of the matched-kinds reading before the final evaluate
            audit_explain_window_s=max(120.0, cfg.storm_s * 4 + 60.0),
            check_grants=True,
            canary="gate" if cfg.canary else "off",
            default_manifest=workloads.MESH_MANIFEST)

    def _build_server(self) -> None:
        from istio_tpu.api.grpc_server import MixerGrpcServer
        from istio_tpu.api.native_server import NativeMixerServer
        from istio_tpu.introspect import IntrospectServer

        from istio_tpu.runtime import RuntimeServer

        self.srv = RuntimeServer(self.store, self._args())
        if self.srv.audit is not None:
            self.srv.audit.attach_discovery(self.disc)
        plan = self.srv.controller.dispatcher.fused
        if plan is not None:
            plan.prewarm(self.cfg.buckets)
        self.g = MixerGrpcServer(runtime=self.srv)
        self.native = NativeMixerServer(self.srv, min_fill=8,
                                        window_us=500)
        self.intro = IntrospectServer(runtime=self.srv)
        self.gport = self.g.start()
        self.nport = self.native.start()
        self.http_port = self.intro.start()

    # -- choreographer event surface ----------------------------------

    def churn(self, ns: int, tick: int) -> None:
        from istio_tpu.testing import workloads
        k = self._churnable[ns % len(self._churnable)]
        workloads.churn_discovery_rule(self.dstore, self.meta, k, tick)

    def mixer_churn(self, tick: int) -> None:
        """Mixer config bump: re-setting a rule's spec fires the store
        event → debounced rebuild → atomic swap → pre-swap grant
        revocation (the revocation-storm lever, no verdict change)."""
        key = ("rule", "istio-system", "report-all")
        spec = self.store.get(key)
        if spec is not None:
            self.store.set(key, dict(spec))

    def poke_quota(self) -> None:
        """One host-path quota call (dispatcher.quota → executor mq
        lane → MemQuotaHandler): lands the armed quota-backend failure
        deterministically instead of waiting for the fleet to catch
        the device-outage window."""
        from istio_tpu.adapters.sdk import QuotaArgs
        from istio_tpu.attribute.bag import bag_from_mapping
        try:
            self.srv.quota(
                bag_from_mapping({
                    "source.user": "soak-poke",
                    "destination.service":
                        "svc0.ns0.svc.cluster.local"}),
                QUOTA_NAME, QuotaArgs(quota_amount=1))
        except Exception:
            pass    # an injected failure surfacing typed is the point

    def canary_poison(self) -> None:
        self.store.set(("rule", "istio-system", "soak-veto"), {
            "match": "",
            "actions": [{"handler": "denyall.istio-system",
                         "instances": ["nothing.istio-system"]}]})

    def canary_heal(self) -> None:
        self.store.delete(("rule", "istio-system", "soak-veto"))

    def restart(self) -> None:
        """Mid-soak quiesce→restart under live fleet traffic, riding
        the ordered-shutdown doctrine: fronts stop (clients see typed
        UNAVAILABLE, never hangs), the runtime drains and reaps, a
        fresh server + fronts replace them; the fleet reconnects via
        the port closures."""
        t0 = time.monotonic()
        try:
            self.native.stop()
            self.g.stop()
            self.srv.shutdown(deadline=5.0)
            self.intro.close()
        except Exception:
            log.exception("soak restart: teardown leg failed")
        self._build_server()
        self.restarts += 1
        self.restart_wall_s = round(time.monotonic() - t0, 3)

    def close(self) -> None:
        for step in (lambda: self.native.stop(),
                     lambda: self.g.stop(),
                     lambda: self.intro.close(),
                     lambda: self.srv.close()):
            try:
                step()
            except Exception:
                pass


def run_soak(cfg: SoakConfig) -> dict:
    """Build the mesh, run warmup → storm → recovery, stop the fleet,
    evaluate the gates. Chaos/ledger state is reset on entry; the
    caller owns the final reset (the smoke's `finally` block)."""
    from istio_tpu.runtime import monitor
    from istio_tpu.runtime.audit import INJECTIONS, SEAMS
    from istio_tpu.runtime.resilience import CHAOS
    from istio_tpu.soak import fleet as fleet_mod
    from istio_tpu.soak import gates as gates_mod
    from istio_tpu.soak import storm as storm_mod
    from istio_tpu.testing import workloads

    CHAOS.reset()
    INJECTIONS.reset()
    SEAMS.reset()
    CHAOS.seed = cfg.seed

    harness = SoakHarness(cfg)
    schedule = storm_mod.make_schedule(
        cfg.seed, cfg.storm_s, n_namespaces=cfg.n_namespaces,
        restart=cfg.restart, canary=cfg.canary)
    n_services = max(cfg.n_rules // 2, 1)
    ci_rules = [i for i in range(2, cfg.n_rules, 5)
                if (i // 5) % 3 == 0]
    requests = list(workloads.make_request_dicts(24, seed=cfg.seed))
    requests += [overlay_request(i, n_services) for i in ci_rules]

    fleets = []
    try:
        base = gates_mod.snapshot_baselines()
        stage_base = monitor.stage_baseline()
        fg = fleet_mod.FleetSimulator(
            lambda: f"127.0.0.1:{harness.gport}", requests,
            n_sidecars=cfg.n_sidecars_grpc, seed=cfg.seed,
            pace_s=cfg.pace_s, quota_every=cfg.quota_every,
            quota_name=QUOTA_NAME, report_every=cfg.report_every,
            enable_check_cache=True, discovery=harness.disc,
            nodes=harness.nodes, ns_ports=harness.ns_ports)
        fn = fleet_mod.FleetSimulator(
            lambda: f"127.0.0.1:{harness.nport}", requests,
            n_sidecars=cfg.n_sidecars_native, seed=cfg.seed + 1,
            pace_s=cfg.pace_s, enable_check_cache=False)
        fleets = [fg.start(), fn.start()]

        storm = storm_mod.StormChoreographer(
            harness, schedule, warmup_s=cfg.warmup_s,
            storm_s=cfg.storm_s)
        t_run0 = time.monotonic()
        storm_log = storm.run()
        recovery = gates_mod.wait_recovery(
            harness.srv.audit, timeout_s=cfg.recovery_timeout_s)

        fleet_totals = fleet_mod._merge_totals(
            [f.stop() for f in fleets])
        fleets = []
        run_wall_s = time.monotonic() - t_run0
        quiesced = gates_mod.wait_quiesce(base)
        verdict = gates_mod.evaluate_gates(
            harness.srv, fleet_totals, base, recovery=recovery,
            min_kinds=cfg.min_fault_kinds, restarted=cfg.restart)
        verdict["gates"]["quiesced"] = quiesced
        verdict["all_ok"] = all(verdict["gates"].values())
        lat = monitor.latency_snapshot(since=stage_base)
        return {
            "seed": cfg.seed,
            "schedule": storm_mod.schedule_signature(schedule),
            "storm_log": storm_log,
            "gates": verdict["gates"],
            "all_ok": verdict["all_ok"],
            "detail": verdict["detail"],
            "metrics": verdict["metrics"],
            "fleet": fleet_totals,
            "throughput_rps": round(
                fleet_totals["checks"] / run_wall_s, 1)
            if run_wall_s > 0 else 0.0,
            "latency": lat,
            "restarts": harness.restarts,
            "restart_wall_s": harness.restart_wall_s,
        }
    finally:
        for f in fleets:
            try:
                f.stop(grace_s=5.0)
            except Exception:
                pass
        harness.close()
