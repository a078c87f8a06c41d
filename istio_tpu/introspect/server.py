"""The introspection HTTP server (ControlZ / Mixer :9093 role).

stdlib http.server only — this image has no egress and the admin
surface must never add a dependency to the serving path. The server
binds loopback by default; every handler is read-only and built to be
safe to hit while the hot path is under load (scrape-rate work only:
no per-request state, quantile sorts happen here, not in serving).
"""
from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable

log = logging.getLogger("istio_tpu.introspect")


def _merged_metrics_text() -> str:
    """ONE Prometheus text exposition covering both registries: the
    prometheus_client REGISTRY (runtime/monitor.py — resolve/dispatch
    counters, batch-size histograms, config generation) and the
    homegrown utils/metrics registry (serving-stage decomposition,
    live percentile gauges, native wire counters). The live gauges are
    refreshed first so a scrape always sees percentiles over the
    current window."""
    from prometheus_client import generate_latest

    from istio_tpu.runtime import monitor
    from istio_tpu.utils import metrics as hostmetrics

    monitor.refresh_latency_gauges()
    prom = generate_latest(monitor.REGISTRY).decode("utf-8", "replace")
    home = hostmetrics.default_registry.expose_text()
    if prom and not prom.endswith("\n"):
        prom += "\n"
    return prom + home


class IntrospectServer:
    """Admin server over a RuntimeServer core (+ optional collaborators).

    `runtime`: the RuntimeServer whose controller/batcher/dispatcher
    the debug endpoints read (None → those endpoints degrade to
    minimal payloads instead of failing; /metrics always works).
    `native`: a NativeMixerServer whose counters() mirror into the
    shared registry on every /metrics scrape.
    `probe_controller`: a utils/probe.ProbeController aggregated into
    /healthz (reference: pkg/probe's controller).
    `trace_capacity`: size of the /debug/traces ring; 0 disables ring
    installation (use when the process owns its own reporters).
    """

    def __init__(self, runtime: Any = None, port: int = 0,
                 host: str = "127.0.0.1", native: Any = None,
                 probe_controller: Any = None,
                 trace_capacity: int = 256, discovery: Any = None,
                 tls: Any = None):
        self.runtime = runtime
        # secure.mtls.ServingCerts (or None): TLS-wrap every accepted
        # connection against the holder's CURRENT context — per-accept
        # wrapping is what makes a rotate() apply without a rebind
        self._tls = tls
        self.native = native
        self.probe_controller = probe_controller
        # pilot DiscoveryService whose debug_view() backs
        # /debug/discovery (None → {"enabled": false})
        self.discovery = discovery
        # a runtime with a live audit plane folds the discovery scope
        # program into its plane_agreement invariant — the introspect
        # server is where the two planes first meet in one process
        aud = getattr(runtime, "audit", None)
        if aud is not None and discovery is not None:
            aud.attach_discovery(discovery)
        self._ring = None
        # extra cache-stat providers: name -> zero-arg callable
        self._cache_stats: dict[str, Callable[[], Any]] = {}
        # /debug/analysis memo: (snapshot revision, report dict) — the
        # analyzer runs on first request per config generation, never
        # on the serving path or at swap time
        self._analysis_cache: tuple[int, dict] | None = None
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:          # noqa: N802 (stdlib API)
                outer._route(self)

            def log_message(self, fmt: str, *args: Any) -> None:
                log.debug("introspect: " + fmt, *args)

        # bind BEFORE touching the global tracer: a bind failure (port
        # in use) raises out of __init__ with no instance to close(),
        # and a ring installed first would leak on the hot path forever
        if tls is not None:
            class TlsHTTPServer(ThreadingHTTPServer):
                def get_request(self):   # per-accept TLS wrap
                    sock, addr = super().get_request()
                    return outer._tls.wrap_server_socket(sock), addr
            self._httpd = TlsHTTPServer((host, port), Handler)
        else:
            self._httpd = ThreadingHTTPServer((host, port), Handler)
        if trace_capacity:
            from istio_tpu.utils import tracing
            self._ring = tracing.enable_ring(trace_capacity)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="introspect-http")

    # -- lifecycle --

    def start(self) -> int:
        self._thread.start()
        log.info("introspect server on port %d", self.port)
        return self.port

    def close(self) -> None:
        # shutdown() blocks on an event only serve_forever() sets —
        # calling it when start() never ran (a pre-start failure's
        # cleanup path) would hang the caller forever
        started = self._thread.ident is not None
        if started:
            self._httpd.shutdown()
        self._httpd.server_close()
        if started:
            self._thread.join(timeout=5)
        if self._ring is not None:
            # restore the pre-introspect tracer: a closed admin server
            # must not leave span construction on the hot path (or
            # stack dead rings across create/close cycles)
            from istio_tpu.utils import tracing
            tracing.disable_ring(self._ring)
            self._ring = None

    def add_cache_stats(self, name: str,
                        fn: Callable[[], Any]) -> None:
        """Register an extra /debug/cache section (e.g. an API front's
        response memo)."""
        self._cache_stats[name] = fn

    # -- routing --

    _ROUTES = {
        "/metrics": "_h_metrics",
        "/healthz": "_h_healthz",
        "/readyz": "_h_readyz",
        "/debug/config": "_h_config",
        "/debug/queues": "_h_queues",
        "/debug/cache": "_h_cache",
        "/debug/traces": "_h_traces",
        "/debug/resilience": "_h_resilience",
        "/debug/executor": "_h_executor",
        "/debug/analysis": "_h_analysis",
        "/debug/rulestats": "_h_rulestats",
        "/debug/canary": "_h_canary",
        "/debug/roofline": "_h_roofline",
        "/debug/report": "_h_report",
        "/debug/shards": "_h_shards",
        "/debug/discovery": "_h_discovery",
        "/debug/slow": "_h_slow",
        "/debug/events": "_h_events",
        "/debug/audit": "_h_audit",
        "/debug/slo": "_h_slo",
        "/debug/identity": "_h_identity",
        "/debug/profile": "_h_profile",
        "/debug/threads": "_h_threads",
    }

    @staticmethod
    def _query(req: BaseHTTPRequestHandler) -> dict:
        """?k=v&... of the request path (single values, last wins)."""
        from urllib.parse import parse_qsl
        parts = req.path.split("?", 1)
        if len(parts) < 2:
            return {}
        return dict(parse_qsl(parts[1]))

    def _route(self, req: BaseHTTPRequestHandler) -> None:
        path = req.path.split("?", 1)[0]
        name = self._ROUTES.get(path)
        if name is None:
            body = ("not found; endpoints: " +
                    " ".join(sorted(self._ROUTES))).encode()
            self._send(req, 404, "text/plain; charset=utf-8", body)
            return
        try:
            getattr(self, name)(req)
        except Exception as exc:   # an admin page must never take the
            log.exception("introspect handler %s failed", path)
            self._send(req, 500, "text/plain; charset=utf-8",
                       f"{type(exc).__name__}: {exc}".encode())

    @staticmethod
    def _send(req: BaseHTTPRequestHandler, code: int, ctype: str,
              body: bytes) -> None:
        req.send_response(code)
        req.send_header("Content-Type", ctype)
        req.send_header("Content-Length", str(len(body)))
        req.end_headers()
        req.wfile.write(body)

    def _send_json(self, req: BaseHTTPRequestHandler, payload: Any,
                   code: int = 200) -> None:
        self._send(req, code, "application/json",
                   json.dumps(payload, indent=1, default=str).encode())

    # -- endpoints --

    def _h_metrics(self, req: BaseHTTPRequestHandler) -> None:
        if self.native is not None:
            try:
                self.native.counters()   # mirrors into the registry
            except Exception:
                log.exception("native counter mirror failed")
        self._send(req, 200,
                   "text/plain; version=0.0.4; charset=utf-8",
                   _merged_metrics_text().encode())

    def _probe_status(self) -> tuple[bool, str]:
        if self.probe_controller is None:
            return True, ""
        return self.probe_controller.status()

    def _batcher_health(self) -> tuple[bool, str]:
        """Flusher-thread watchdog (check + report coalescers): a dead
        flusher means new submits fail fast and health must go red —
        the load balancer has to stop sending traffic to a server that
        can no longer answer it."""
        if self.runtime is None:
            return True, ""
        for name, b in (("check", self.runtime.batcher),
                        ("report", self.runtime._report_batcher)):
            if b is None:
                continue
            healthy = getattr(b, "healthy", None)
            if healthy is None:
                continue
            ok, err = healthy()
            if not ok:
                return False, f"{name} batcher: {err}"
        return True, ""

    def _h_healthz(self, req: BaseHTTPRequestHandler) -> None:
        ok, err = self._probe_status()
        if ok:
            ok, err = self._batcher_health()
        payload = {"status": "ok" if ok else "unavailable"}
        if err:
            payload["error"] = err
        if self.runtime is not None:
            payload["config_generation"] = \
                self.runtime.controller.dispatcher.snapshot.revision
        self._send_json(req, payload, 200 if ok else 503)

    def _h_readyz(self, req: BaseHTTPRequestHandler) -> None:
        """Ready = a config snapshot is published, the batcher accepts
        work, and (when probes are wired) every probe is available —
        the gate a load balancer flips traffic on."""
        ok, err = self._probe_status()
        payload: dict[str, Any] = {}
        if self.runtime is not None:
            try:
                snap = self.runtime.controller.dispatcher.snapshot
                payload["config_generation"] = snap.revision
                payload["n_rules"] = len(snap.rules)
            except Exception as exc:
                ok, err = False, f"no published snapshot: {exc}"
            if self.runtime.batcher._closed:
                ok, err = False, "batcher closed"
            elif ok:
                ok, err = self._batcher_health()
        payload["status"] = "ready" if ok else "unready"
        if err:
            payload["error"] = err
        self._send_json(req, payload, 200 if ok else 503)

    def _h_config(self, req: BaseHTTPRequestHandler) -> None:
        if self.runtime is None:
            self._send_json(req, {"error": "no runtime attached"}, 503)
            return
        ctl = self.runtime.controller
        d = ctl.dispatcher
        snap = d.snapshot
        args = self.runtime.args
        payload = {
            "generation": snap.revision,
            "n_rules": len(snap.rules),
            "n_instances": len(snap.instances),
            "n_handlers": len(d.handlers),
            "errors": [str(e) for e in snap.errors],
            "identity_attr": d.identity_attr,
            "fused": d.fused is not None,
            "has_apa": d.has_apa,
            "buckets": list(d.buckets),
            "batch_window_s": args.batch_window_s,
            "pipeline": args.pipeline,
            "report_batching": args.report_batching,
            "quota_in_step": args.quota_in_step,
            "mesh_shape": args.mesh_shape,
        }
        if d.fused is not None:
            payload["fused_deny"] = d.fused.fused_deny
            payload["fused_lists"] = d.fused.fused_lists
            payload["host_overlay_rules"] = \
                len(d.fused.host_rule_idx)
        self._send_json(req, payload)

    def _h_queues(self, req: BaseHTTPRequestHandler) -> None:
        from istio_tpu.runtime import monitor

        payload: dict[str, Any] = {
            "latency": monitor.latency_snapshot(),
        }
        if self.runtime is not None:
            payload["check"] = self.runtime.batcher.stats()
            rb = self.runtime._report_batcher
            if rb is not None:
                payload["report"] = rb.stats()
        self._send_json(req, payload)

    def _h_roofline(self, req: BaseHTTPRequestHandler) -> None:
        """Roofline accounting for the LIVE snapshot's fused step
        (compiler/roofline.py): per serving bucket, bytes/op counts
        derived from the compiled shapes — and, when the stage
        decomposition has observations, the live device_step median
        judged against the platform roof (achieved GB/s / TOPS,
        fraction_of_roof, binding resource). ?batch=N models one
        extra shape."""
        import jax

        from istio_tpu.compiler import roofline
        from istio_tpu.runtime import monitor

        device = jax.devices()[0]
        payload: dict[str, Any] = {
            "platform": device.platform,
            "device_kind": device.device_kind,
            "peaks": roofline.peaks_for(device.device_kind),
        }
        d = self.runtime.controller.dispatcher \
            if self.runtime is not None else None
        if d is None or d.fused is None:
            payload["note"] = "no fused plan (generic path serving)"
            self._send_json(req, payload)
            return
        plan = d.fused
        buckets = list(d.buckets) or [self.runtime.args.max_batch]
        # the live p50 is judged against the largest SERVING bucket —
        # a ?batch=N model is what-if only (no served batch ever ran
        # at a non-bucket shape, so judging the p50 against it would
        # be nonsense)
        judged = max(buckets) if buckets else None
        try:
            extra = int(self._query(req).get("batch", 0))
        except ValueError:
            extra = 0
        if extra > 0:
            buckets = sorted(set(buckets) | {extra})
        dev = monitor.latency_snapshot()["stages"].get(
            "device_step", {})
        step_ms = dev.get("p50_ms")
        payload["device_step_p50_ms"] = step_ms
        payload["str_tiers"] = list(plan.str_tiers)
        # byte-plane width the served batches ACTUALLY ran (latency-
        # tier narrowing): judging the live p50 against the worst-case
        # max_str_len model when every batch was tier-narrowed inflates
        # achieved GB/s / fraction_of_roof for the byte-dominated
        # components. Use the dominant served width; fall back to the
        # full plane when nothing has been counted yet.
        tier_counts = dict(plan._tier_served)
        payload["tier_served_batches"] = {
            str(w): n for w, n in sorted(tier_counts.items())}
        live_width = max(tier_counts, key=tier_counts.get) \
            if tier_counts else None
        per: dict[str, Any] = {}
        # the device_step histogram aggregates EVERY served batch
        # shape, so judging each bucket's (very different) byte model
        # against the one p50 would mislabel all but the shape that
        # dominates the window — attach the live judgment only to the
        # largest serving bucket (what sustained load pads to)
        if step_ms:
            payload["vs_live_note"] = (
                "device_step_p50_ms aggregates all served batch "
                f"shapes; vs_live_device_step is attached to bucket "
                f"{judged} only (the shape sustained load pads to), "
                f"modeled at the dominant served byte-plane width "
                f"{live_width} — per-bucket walls need a shape-keyed "
                "histogram")
        for b in buckets:
            model = roofline.model_check_step(plan.engine, b,
                                              plan=plan)
            entry = model.asdict()
            if step_ms and b == judged:
                live_model = model if live_width is None else \
                    roofline.model_check_step(plan.engine, b,
                                              plan=plan,
                                              str_len=live_width)
                entry["vs_live_device_step"] = live_model.report(
                    step_ms / 1e3)
                entry["vs_live_str_len"] = live_width
            per[str(b)] = entry
        payload["buckets"] = per
        self._send_json(req, payload)

    def _h_report(self, req: BaseHTTPRequestHandler) -> None:
        """Telemetry ingestion plane view (the report analog of
        /debug/queues + /debug/resilience in one page): live six-stage
        pipeline p50/p95/p99 (wire_decode → coalesce_wait → tensorize
        → device_field_eval → intern_decode → adapter_dispatch),
        record-conservation state (accepted == exported + rejected;
        in_flight is the transient difference), coalescer occupancy,
        per-template record totals, per-exporter delivery/drop/lag
        stats, and the most recent typed-drop reasons. Serves
        zero-shaped before the first record — an idle plane must be
        distinguishable from a missing one."""
        from istio_tpu.runtime import monitor

        payload: dict[str, Any] = {
            **monitor.report_latency_snapshot(),
            **monitor.report_counters(),
        }
        if self.runtime is not None:
            rb = self.runtime._report_batcher
            payload["coalescer"] = rb.stats() if rb is not None \
                else {"inline": True,
                      "note": "report_batching=False — records "
                              "dispatch inline, no coalescer"}
            args = self.runtime.args
            payload["policy"] = {
                "report_batching": args.report_batching,
                # the coalescer's OWN normalized cap (None =
                # unbounded, no coalescer = no cap) — never re-derive
                # the default here and risk disagreeing with the
                # coalescer block above
                "report_queue_cap": rb.max_queue
                if rb is not None else None,
                "max_batch": args.max_batch,
                "buckets": list(getattr(
                    self.runtime.controller.dispatcher, "buckets",
                    ())),
            }
            d = self.runtime.controller.dispatcher
            if d.fused is not None:
                rl = d.fused.report_lowering
                payload["lowering"] = {
                    "report_rules": len(d.fused.report_rules),
                    "device_instances":
                        len(rl.specs) if rl is not None else 0,
                    "host_instances":
                        len(rl.host_instances) if rl is not None
                        else None,
                    "field_programs":
                        rl.n_fields if rl is not None else 0,
                }
        self._send_json(req, payload)

    def _h_cache(self, req: BaseHTTPRequestHandler) -> None:
        payload: dict[str, Any] = {}
        if self.runtime is not None:
            d = self.runtime.controller.dispatcher
            if d.fused is not None:
                payload["compile"] = d.fused.cache_stats()
            rs = d.snapshot.ruleset
            interner = getattr(rs, "interner", None)
            vals = getattr(interner, "_values", None)
            if vals is not None:
                # intern-table occupancy (compile-time constants; a
                # growing number here across swaps is config growth,
                # never request traffic — InternTable's contract)
                payload["interner_values"] = len(vals)
        for name, fn in self._cache_stats.items():
            try:
                payload[name] = fn()
            except Exception as exc:
                payload[name] = f"error: {exc}"
        if self.native is not None:
            payload["native_resp_memo"] = len(self.native._resp_memo)
            payload["native_ref_cache"] = len(self.native._ref_cache)
        self._send_json(req, payload)

    def _h_shards(self, req: BaseHTTPRequestHandler) -> None:
        """Sharded serving plane view (istio_tpu/sharding): the last
        shard-plan decision + balance, per-bank rule counts / resident
        bank bytes / rows routed, per-replica lane queue depth and
        batch-latency percentiles, and the router stage decomposition
        (shard_dispatch / bank_check / fold). Zero-shaped before the
        first routed batch per the promtext doctrine; {"enabled":
        false} on a monolithic server."""
        from istio_tpu.runtime import monitor

        payload: dict[str, Any] = {"enabled": False}
        rt = self.runtime
        state = getattr(rt, "_sharded", None) if rt is not None \
            else None
        rr = getattr(rt, "_replica_router", None) if rt is not None \
            else None
        if state is None or rr is None:
            self._send_json(req, payload)
            return
        plan = state["plan"]
        payload = {
            "enabled": True,
            "mode": state.get("mode"),
            "fallback_reason": state.get("fallback_reason") or None,
            "revision": state.get("revision"),
            "last_decision": {
                **plan.to_json(),
                "build_wall_ms": round(
                    state.get("build_wall_s", 0.0) * 1e3, 3),
                "built_wall": state.get("built_wall"),
            },
            # delta compilation (compiler/cache.py + the content-
            # addressed bank cache): which banks the last publish
            # carried vs recompiled, the cumulative rebuild ledger —
            # including the LAST REBUILD ERROR and the generation it
            # struck (a failed rebuild keeps the previous generation
            # serving; this is where that state is visible) — and the
            # persistent-cache / decomposition-memo counters
            "delta": state.get("delta") or {
                "reused": [], "recompiled": [], "plan_stability": {}},
            "rebuild": dict(getattr(rt, "_rebuild_status", {})),
            "banks": [b.stats() for b in state.get("banks", ())],
            "replicas": [],
            "stages": monitor.shard_latency_snapshot()["stages"],
        }
        try:
            from istio_tpu.compiler import cache as compile_cache
            cc = {"persistent_cache_dir":
                  getattr(rt, "_compile_cache_dir", None),
                  "xla_cache_events":
                      compile_cache.cache_event_counts()}
            dc = getattr(rt.controller.dispatcher.snapshot,
                         "decomp_cache", None)
            if dc is not None:
                cc["decomp_cache"] = dc.stats()
            payload["compile_cache"] = cc
        except Exception as exc:   # accounting never breaks the view
            payload["compile_cache"] = f"error: {exc}"
        rep_lat = monitor.replica_snapshot()
        routers = {r.replica: r for r in rr.routers}
        for i, lane in enumerate(rr.lanes):
            st = lane.stats()
            entry = {
                "replica": i,
                "queue_depth": st["depth"],
                "oldest_wait_ms": st["oldest_wait_ms"],
                "in_flight": st["in_flight"],
                "healthy": st["healthy"],
                # zero-shaped latency block before the first batch
                "batch_latency": rep_lat.get(str(i), {
                    "batches": 0, "sum_ms": 0.0, "p50_ms": 0.0,
                    "p95_ms": 0.0, "p99_ms": 0.0}),
            }
            r = routers.get(i)
            if r is not None:
                entry["router"] = r.stats()
            payload["replicas"].append(entry)
        # cross-lane routing aggregate (rows per shard / occupancy /
        # misroutes): ReplicaRouter.routing_stats is the single home
        # shared with the shard smoke
        routing = rr.routing_stats()
        payload["rows_per_shard"] = routing["rows_per_shard"]
        payload["occupancy"] = routing["occupancy"]
        payload["misrouted"] = routing["misrouted"]
        self._send_json(req, payload)

    def _h_discovery(self, req: BaseHTTPRequestHandler) -> None:
        """Pilot discovery serving plane view (pilot/discovery.py):
        snapshot generation, cache occupancy + hit/miss/carried/
        invalidated accounting, node-group counts per endpoint, the
        namespace→shard scope plan (balance + stability), shard watch
        versions + parked watcher count, push fan-out percentiles and
        the pilot_discovery_stage_seconds decomposition. {"enabled":
        false} when no DiscoveryService is attached."""
        if self.discovery is None:
            self._send_json(req, {"enabled": False})
            return
        self._send_json(req, {"enabled": True,
                              **self.discovery.debug_view()})

    def _h_executor(self, req: BaseHTTPRequestHandler) -> None:
        """Adapter-executor plane view (runtime/executor.py): per-
        handler bulkhead lanes (queue depth / in-flight / oldest
        running / breaker state), the host-action conservation
        counters (submitted == sum of typed outcomes), the chaos seam
        state, and the maintenance registry — per-provider refresh
        totals/failures and last-refresh age (a provider gone stale
        must be visible here, because the last good list keeps
        serving silently). Zero-shaped before the first host action;
        {"enabled": false} when the executor is off."""
        from istio_tpu.runtime import monitor
        from istio_tpu.runtime.resilience import CHAOS

        payload: dict[str, Any] = {
            "enabled": False,
            "counters": monitor.host_action_counters(),
        }
        ex = getattr(self.runtime, "executor", None) \
            if self.runtime is not None else None
        if ex is not None:
            payload = {"enabled": True, **ex.snapshot()}
        payload["chaos"] = {
            k: v for k, v in CHAOS.snapshot().items()
            if k.startswith(("adapter", "injected_adapter"))}
        # per-handler provider freshness straight from the live
        # handlers (refresh_stats) — the maintenance registry above
        # carries the scheduler's view; this is the adapter's own
        if self.runtime is not None:
            providers: dict[str, Any] = {}
            try:
                d = self.runtime.controller.dispatcher
                for name, h in d.handlers.items():
                    stats = getattr(h, "refresh_stats", None)
                    if callable(stats):
                        st = stats()
                        if st.get("provider"):
                            providers[name] = st
            except Exception as exc:
                providers = {"error": str(exc)}
            payload["providers"] = providers
        self._send_json(req, payload)

    def _h_resilience(self, req: BaseHTTPRequestHandler) -> None:
        """Overload-resilience view: breaker state machine, shed /
        expired / fallback counters, admission-control config and the
        batcher watchdog — the page an on-call loads when the shed
        counters start moving."""
        from istio_tpu.runtime import monitor

        payload: dict[str, Any] = {
            "counters": monitor.resilience_counters(),
        }
        if self.runtime is not None:
            res = getattr(self.runtime, "resilience", None)
            if res is not None:
                payload.update(res.snapshot())
            # sharded serving bypasses the monolithic checker: the
            # page must say so and show the PER-BANK breakers that
            # actually see traffic (detail in /debug/shards)
            state = getattr(self.runtime, "_sharded", None)
            if state is not None:
                payload["sharded"] = {
                    "note": "sharded serving: check traffic rides "
                            "per-bank resilience (one breaker + "
                            "oracle fallback per bank); the "
                            "monolithic breaker above sees no "
                            "check batches",
                    "bank_breakers": {
                        str(b.shard_id): b.checker.breaker.snapshot()
                        for b in state.get("banks", ())
                        if b.checker is not None},
                }
            args = self.runtime.args
            payload["policy"] = {
                "default_check_deadline_ms":
                    getattr(args, "default_check_deadline_ms", 0.0),
                "check_queue_cap":
                    getattr(args, "check_queue_cap", None),
                "brownout": getattr(args, "brownout", False),
                "check_fail_policy":
                    getattr(args, "check_fail_policy", "closed"),
                "breaker_failures":
                    getattr(args, "breaker_failures", None),
                "breaker_reset_s":
                    getattr(args, "breaker_reset_s", None),
            }
            # stats() is the single home of batcher state (depth read
            # under the queue mutex, watchdog health included)
            st = self.runtime.batcher.stats()
            payload["batcher"] = {
                k: st.get(k) for k in ("depth", "max_queue",
                                       "brownout", "healthy",
                                       "health_error")}
        self._send_json(req, payload)

    def _analysis_for(self, snap) -> dict:
        """Memoized analyzer report for `snap` (one run per config
        generation — shared by /debug/analysis and the rulestats
        never-hit cross-check)."""
        cached = self._analysis_cache
        if cached is None or cached[0] != snap.revision:
            from istio_tpu.analysis import analyze_snapshot
            report = analyze_snapshot(snap, pair_budget=50_000)
            cached = (snap.revision, report.to_dict())
            self._analysis_cache = cached
        return cached[1]

    def _h_analysis(self, req: BaseHTTPRequestHandler) -> None:
        """Static-analysis report for the LAST published snapshot
        (istio_tpu/analysis): findings with severities, rule ids and
        oracle-confirmed witnesses. Computed on first request per
        config generation and memoized — an admin page must never put
        analysis cost on the serving path."""
        if self.runtime is None:
            self._send_json(req, {"error": "no runtime attached"}, 503)
            return
        snap = self.runtime.controller.dispatcher.snapshot
        payload = self._analysis_for(snap)
        self._send_json(req, {"generation": snap.revision, **payload})

    def _h_rulestats(self, req: BaseHTTPRequestHandler) -> None:
        """Rule-level telemetry view (runtime/rulestats.py): top-K hot
        rules with per-namespace deny rates and decision exemplars
        (trace ids join /debug/traces), plus never-hit rules
        cross-checked against the static analyzer's shadowed-rule
        findings — a dead rule shows whether it is provably dead
        (analyzer agrees) or merely unexercised. Query params:
        `k` (top-K size, default 10), `shadow=0` (skip the analyzer
        cross-check — it runs the memoized per-generation analysis).
        The handler drains on demand, so the view is current even
        between the background drainer's intervals."""
        if self.runtime is None:
            self._send_json(req, {"error": "no runtime attached"}, 503)
            return
        agg = getattr(self.runtime, "rulestats", None)
        if agg is None:
            self._send_json(req,
                            {"error": "rule telemetry not wired"}, 503)
            return
        q = self._query(req)
        try:
            agg.drain()
        except Exception:
            log.exception("on-demand rulestats drain failed")
        shadowed: set = set()
        if q.get("shadow", "1") != "0":
            try:
                snap = self.runtime.controller.dispatcher.snapshot
                report = self._analysis_for(snap)
                for f in report.get("findings", ()):
                    if f.get("code") == "shadowed-rule" and \
                            f.get("rules"):
                        # rules=(covering, shadowed); analyzer names
                        # are bare — snapshot() matches them against
                        # qualified names with an ambiguity guard
                        shadowed.add(f["rules"][-1])
            except Exception:
                log.exception("rulestats analyzer cross-check failed")
        payload = agg.snapshot(
            top_k=int(q.get("k", 0) or 0) or None, shadowed=shadowed)
        self._send_json(req, payload)

    def _h_canary(self, req: BaseHTTPRequestHandler) -> None:
        """Config-canary view (istio_tpu/canary): recorder occupancy,
        gate config, and the last N shadow-replay reports — per-rule
        divergence counts with exemplars whose trace ids join
        /debug/traces and whose `bag` field replays via `mixs canary`.
        Diverging rules are cross-checked against the memoized static
        analysis (`analyzer_overlap`): a rule that both flips recorded
        decisions AND carries a shadow/overlap/plane finding is drift
        with independent static evidence. `?shadow=0` skips the
        cross-check (the analysis run is memoized per generation but
        not free)."""
        if self.runtime is None:
            self._send_json(req, {"error": "no runtime attached"}, 503)
            return
        canary = getattr(self.runtime, "canary", None)
        if canary is None:
            self._send_json(
                req, {"error": "canary not enabled "
                               "(ServerArgs.canary / --canary)"}, 503)
            return
        payload = canary.snapshot()
        ctl = self.runtime.controller
        rej = getattr(ctl, "last_canary_rejection", None)
        if rej is not None:
            payload["last_rejection"] = str(rej)
        if self._query(req).get("shadow", "1") != "0":
            try:
                snap = ctl.dispatcher.snapshot
                analysis = self._analysis_for(snap)
                # analyzer findings name compiler rules "name.ns"
                # (config._qualify); canary per_rule keys are "ns/name"
                # (Snapshot.qualified_rule_names) — index findings
                # under both forms plus the bare name so the join
                # works regardless of which surface produced the id
                def _canon(rid: str) -> str:
                    name, sep, ns = rid.rpartition(".")
                    return f"{ns}/{name}" if sep else rid

                flagged: dict[str, list] = {}
                for f in analysis.get("findings", ()):
                    if f.get("code") not in (
                            "shadowed-rule", "allow-deny-conflict",
                            "plane-divergence"):
                        continue
                    for r in f.get("rules") or ():
                        for key in {r, _canon(r)}:
                            flagged.setdefault(key, []).append(
                                f["code"])
                for rep in payload["reports"]:
                    overlap = []
                    for name in rep.get("per_rule", {}):
                        # exact forms only: a bare-name fallback would
                        # attach a default-namespace finding to a
                        # same-named rule in ANY namespace — a wrong
                        # cross-link an operator may act on
                        codes = flagged.get(name)
                        if codes:
                            overlap.append({"rule": name,
                                            "codes": sorted(set(codes))})
                    rep["analyzer_overlap"] = overlap
            except Exception:
                log.exception("canary analyzer cross-check failed")
        self._send_json(req, payload)

    def _h_traces(self, req: BaseHTTPRequestHandler) -> None:
        """Recent finished spans, chronological (RingReporter).
        `?status=X` filters by the span `status` tag: `status=failed`
        keeps every span whose status is set and not ok/0 (the check
        spans tag their google.rpc code), a specific value keeps exact
        matches. `?min_ms=N` keeps spans at least that long (the tail
        complement of ?status — a slow span is rarely a failed one),
        and `?trace=ID` keeps one trace's spans — the deep link the
        /debug/slow exemplars carry."""
        if self._ring is None:
            self._send_json(req, {"error": "trace ring not installed"},
                            503)
            return
        # filter over the FULL retained ring, THEN truncate: a failed
        # span must stay visible in ?status=failed for as long as the
        # ring holds it, even behind a burst of newer ok spans
        spans = self._ring.snapshot()
        q = self._query(req)
        want = q.get("status")
        if want == "failed":
            spans = [s for s in spans
                     if (s.get("tags") or {}).get("status")
                     not in (None, "ok", "0")]
        elif want:
            spans = [s for s in spans
                     if (s.get("tags") or {}).get("status") == want]
        trace = q.get("trace")
        if trace:
            spans = [s for s in spans if s.get("traceId") == trace]
        try:
            min_ms = float(q.get("min_ms", 0) or 0)
        except ValueError:
            min_ms = 0.0
        if min_ms > 0:
            # span durations are zipkin µs
            spans = [s for s in spans
                     if s.get("duration", 0) >= min_ms * 1000.0]
        self._send_json(req, {
            "dropped": self._ring.dropped,
            "spans": spans[-128:],
        })

    # -- forensics plane (runtime/forensics.py) ------------------------

    def _h_slow(self, req: BaseHTTPRequestHandler) -> None:
        """Flight-recorder view: the top-K slowest retained requests,
        each with its per-stage attribution (queue_wait / tensorize /
        h2d / device_step / fold / grant / respond / per-handler host
        waits / wire_decode), the control-plane events that overlapped
        its lifetime, and a /debug/traces deep link by trace id.
        `?k=N` sizes the list (default 10). Zero-shaped on a clean
        server: threshold/config always serve, `slowest` is empty."""
        from istio_tpu.runtime import forensics

        q = self._query(req)
        try:
            k = int(q.get("k", 10) or 10)
        except ValueError:
            k = 10
        self._send_json(req, forensics.RECORDER.snapshot(top_k=k))

    def _h_events(self, req: BaseHTTPRequestHandler) -> None:
        """Mesh event timeline: the bounded ring of control-plane
        events (config publishes, canary verdicts, bank rebuilds,
        prewarm start/end per shape, breaker transitions, quota
        flushes, grant revocations, provider refreshes, chaos arms,
        audit violations, quiesce/shutdown). `?kind=X` (alias
        `?type=X`) filters by event kind, `?since_s=S` keeps only
        events recorded within the last S seconds, `?n=N` bounds
        (default 128). The same ring annotates /debug/slow
        exemplars."""
        from istio_tpu.runtime import forensics, monitor

        q = self._query(req)
        try:
            n = int(q.get("n", 128) or 128)
        except ValueError:
            n = 128
        events = forensics.EVENTS.snapshot(
            kind=q.get("kind") or q.get("type"), limit=n)
        since_s = q.get("since_s")
        if since_s is not None:
            try:
                horizon = time.time() - float(since_s)
                events = [e for e in events if e["wall"] >= horizon]
            except ValueError:
                pass
        self._send_json(req, {
            "retained": len(forensics.EVENTS),
            "counters": monitor.forensics_counters(),
            "events": events,
        })

    # -- mesh audit plane (runtime/audit.py) ---------------------------

    def _h_audit(self, req: BaseHTTPRequestHandler) -> None:
        """Live invariant auditor: the six mesh-wide AuditCheck
        verdicts (report/check/quota conservation, grant coherence,
        plane agreement, shard routing) with evidence and the
        generation checked at, plus the fault-explainability scorer's
        records and rate. `?refresh=1` forces a fresh evaluation
        before serving (the background thread evaluates on its own
        interval otherwise). Serves `{"enabled": false}` when no
        audit plane is attached."""
        aud = getattr(self.runtime, "audit", None)
        if aud is None:
            self._send_json(req, {"enabled": False})
            return
        q = self._query(req)
        if q.get("refresh") or not aud.snapshot()["evaluations"]:
            self._send_json(req, aud.evaluate())
            return
        self._send_json(req, aud.snapshot())

    def _h_identity(self, req: BaseHTTPRequestHandler) -> None:
        """Secure-plane view: the zero-shaped mixer_identity_* counter
        families (issue/rotate/expiry × ok/failed, authenticated
        checks, typed UNAUTHENTICATED admissions), the serving
        WorkloadIdentity's live stats when one is registered on the
        executor maintenance lane, and this front's ServingCerts
        generation when TLS is on."""
        from istio_tpu.runtime import monitor
        payload: dict = {"counters": monitor.identity_counters()}
        if self._tls is not None:
            payload["serving_cert_generation"] = self._tls.generation
        ex = getattr(self.runtime, "executor", None)
        wi = None
        if ex is not None:
            wi = getattr(ex, "_persistent_refresh",
                         {}).get("workload_identity")
        if wi is not None and hasattr(wi, "stats"):
            payload["workload_identity"] = wi.stats()
        self._send_json(req, payload)

    def _h_slo(self, req: BaseHTTPRequestHandler) -> None:
        """One fused per-plane SLO scorecard: check wire p99 vs its
        target, report export lag + in-flight ledger, discovery push
        fan-out p99, quota flush age, and the audit plane's own
        healthy/explainability verdicts. Each plane reports
        ok / miss / no_data; `overall` is the worst verdict."""
        from istio_tpu.runtime import forensics, monitor
        from istio_tpu.runtime.slo import scorecard

        aud = getattr(self.runtime, "audit", None)
        self._send_json(req, scorecard(
            monitor, forensics,
            audit=aud.snapshot() if aud is not None else None,
            discovery=self.discovery))

    def _h_profile(self, req: BaseHTTPRequestHandler) -> None:
        """On-demand device profiling: `?seconds=N` (default 1, max
        60) drives one jax.profiler trace capture into the configured
        directory (ServerArgs.profile_dir / MIXS_PROFILE_DIR / a fresh
        tempdir) and returns the artifact listing. The handler thread
        blocks for the capture window (admin surface — serving is
        untouched); concurrent captures answer 409. Fail-soft where
        the profiler is unavailable ({"available": false})."""
        import os

        from istio_tpu.runtime import forensics

        q = self._query(req)
        try:
            seconds = float(q.get("seconds", 1.0) or 1.0)
        except ValueError:
            seconds = 1.0
        directory = None
        if self.runtime is not None:
            directory = getattr(self.runtime.args, "profile_dir",
                                None)
        # None → capture_profile mkdtemps lazily (only once the lock
        # is held and the profiler imports — no tempdir litter from
        # busy/unavailable polls)
        directory = directory or os.environ.get("MIXS_PROFILE_DIR") \
            or None
        try:
            payload = forensics.capture_profile(directory, seconds)
        except forensics.ProfileBusy as exc:
            self._send_json(req, {"error": str(exc)}, 409)
            return
        self._send_json(req, payload,
                        200 if payload.get("available") else 503)

    def _h_threads(self, req: BaseHTTPRequestHandler) -> None:
        """Host-side thread-stack dump (sys._current_frames): every
        live thread's python stack, keyed by name — the wedged-pump /
        wedged-lane diagnostic that otherwise needs gdb on a serving
        process."""
        from istio_tpu.runtime import forensics

        self._send_json(req, forensics.thread_stacks())
