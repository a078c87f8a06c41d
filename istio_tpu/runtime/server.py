"""Runtime server assembly (reference: mixer/pkg/server/server.go:92
newServer — store → runtime controller → dispatcher → API, plus
monitoring). The gRPC surface lives in istio_tpu/api; this class is the
in-process core those servers wrap (and what tests drive directly, the
reference's in-process e2e pattern mixer/test/e2e).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np

from istio_tpu.adapters.sdk import QuotaArgs, QuotaResult
from istio_tpu.attribute.bag import Bag
from istio_tpu.attribute.global_dict import GLOBAL_MANIFEST
from istio_tpu.attribute.types import ValueType
from istio_tpu.runtime.batcher import CheckBatcher
from istio_tpu.runtime.controller import Controller
from istio_tpu.runtime.dispatcher import (CheckResponse,
                                          DEFAULT_IDENTITY_ATTR)
from istio_tpu.runtime.store import Store

# RuntimeServer.quota_route's answer for a quota granted freely
QUOTA_FREE = "granted freely"


@dataclasses.dataclass
class ServerArgs:
    """mixer/pkg/server/args.go:32 analog."""
    identity_attr: str = DEFAULT_IDENTITY_ATTR
    default_manifest: Mapping[str, ValueType] | None = None
    batch_window_s: float = 0.0003
    max_batch: int = 1024
    # in-flight device batches (overlaps host↔device sync across
    # batches; see runtime/batcher.py)
    pipeline: int = 4
    # occupancy threshold for the batcher's adaptive window: batches
    # keep accumulating while >= hold_at trips are in flight. The
    # default (None → 1) serializes trips — right whenever trips
    # contend for one transport/core; a rig whose device genuinely
    # overlaps trips should set hold_at=pipeline to restore overlap
    # (runtime/batcher.py CheckBatcher)
    hold_at: int | None = None
    # coalesce report records across Report RPCs into shared device
    # trips (see RuntimeServer.report); False dispatches each call's
    # records as their own batch
    report_batching: bool = True
    # record coalescer admission bound: submits past it shed typed
    # RESOURCE_EXHAUSTED (the ack-after-enqueue contract's overflow
    # leg — the native front acks a Report once its records are
    # ADMITTED, so admission must be bounded or memory isn't).
    # None → 16×max_batch; 0 → unbounded.
    report_queue_cap: int | None = None
    # allocate quota IN the check trip (FusedPlan.packed_check_instep)
    # instead of a separate pool-flush trip serialized behind it —
    # gated: only the native front's pump consumes it, and only for
    # single-pool, single-rule-per-name snapshots
    # (RuntimeServer.instep_quota_target); everything else keeps the
    # classic defer path
    quota_in_step: bool = False
    # serving batch shapes (None → batcher.default_buckets(max_batch));
    # each is one jit trace, pre-warmed before config swaps
    buckets: tuple[int, ...] | None = None
    # -- sharded serving plane (istio_tpu/sharding) --------------------
    # >0: partition the snapshot's rules by namespace into this many
    # model-parallel banks (each its own compiled RuleSetProgram +
    # FusedPlan) and serve checks through the shard-routed path —
    # verdict-identical to the monolithic compile, which is then never
    # device-warmed (its XLA program is what 100k+ rule snapshots
    # cannot afford). 0 = monolithic serving (the default).
    shards: int = 0
    # replica-parallel serving lanes behind the one front: each lane
    # is its own CheckBatcher + dispatcher set (sticky-by-namespace
    # routing, so one namespace's traffic coalesces into one lane's
    # batches). With shards=0 each replica owns its own FusedPlan over
    # the full snapshot; with shards>0 the banks are shared and lane
    # selection follows the shard assignment. 1 = single lane.
    replicas: int = 1
    # False skips the background FIRST-build prewarm (the benchmark and
    # tests that call plan.prewarm explicitly — the duplicate compile
    # contends for the core); swap-time prewarm stays synchronous
    initial_prewarm: bool = True
    # -- delta compilation & bank cache (compiler/cache.py) ------------
    # True (default): config republishes under sharding diff the
    # incoming store against the live plan by content hash and rebuild
    # ONLY the banks whose namespaces (or the replicated global set)
    # changed — untouched banks carry across generations with their
    # prewarmed shapes, breaker state and rulestats bindings. False is
    # the kill switch: every publish rebuilds every bank.
    delta_compile: bool = True
    # namespaces the delta planner may RELOCATE per republish to chase
    # LPT balance — each move recompiles two banks, so this is an
    # explicit republish-latency vs balance trade (0 = perfect plan
    # stability, the default; see sharding/planner.plan_shards)
    shard_rebalance_budget: int = 0
    # JAX persistent compilation cache directory: restarts and rolling
    # deploys skip the XLA compile for every program whose HLO is
    # unchanged (our compiled programs take index tensors as traced
    # ARGUMENTS, so constant-only config edits keep the HLO
    # bit-identical). Honoured only while JAX_COMPILATION_CACHE_DIR is
    # unset (the environment places the cache when it speaks); None →
    # <checkout>/.jax_cache (compiler/cache.resolve_cache_dir; mixs
    # exposes --jax-compile-cache-dir).
    jax_compile_cache_dir: str | None = None
    max_str_len: int | None = None
    # serve checks through the fused device engine (runtime/fused.py);
    # False falls back to the generic host-adapter dispatch path
    fused: bool = True
    # multi-chip serving: (dp, mp) factorization of jax.devices() — the
    # snapshot engine jits under shard_engine_check (batch over dp,
    # rules over mp, one psum on the verdict fold; parallel/mesh.py).
    # None = single device. Requires fused=True and every serving
    # bucket divisible by dp.
    mesh_shape: tuple[int, int] | None = None
    # -- overload resilience (runtime/resilience.py + batcher
    #    admission control; mixs exposes these as CLI flags) ----------
    # default Check() deadline for fronts whose wire carries none (the
    # native front; the gRPC fronts prefer the client's RPC deadline).
    # 0 = no default deadline.
    default_check_deadline_ms: float = 0.0
    # check batcher queue cap: submits past it shed RESOURCE_EXHAUSTED.
    # None → 8×max_batch; 0 → unbounded (the pre-resilience behavior).
    check_queue_cap: int | None = None
    # brownout mode: when the live p99 gauge breaches the SLO target
    # and the queue is half full, shed the NEWEST requests first
    brownout: bool = False
    # what Check() answers when BOTH the device path and the CPU
    # oracle fallback are down: "open" → OK (Mixer-client fail-open),
    # "closed" → UNAVAILABLE
    check_fail_policy: str = "closed"
    # consecutive failed device batches that trip the circuit breaker,
    # and how long it stays open before a half-open probe
    breaker_failures: int = 3
    breaker_reset_s: float = 5.0
    # retry a failed device step once (jittered backoff) before it
    # counts as a breaker failure
    device_retry: bool = True
    # -- adapter-executor plane (runtime/executor.py) ------------------
    # route host adapter work (fused-path overlay CHECK actions, quota
    # adapter calls, provider refresh) through the bounded per-handler
    # executor; False = the pre-executor inline loop (the behavioral
    # oracle — shadow replay and the generic path always use it)
    host_executor: bool = True
    # worker threads per handler lane (the bulkhead's concurrency
    # share) and pending-action cap per lane (overflow sheds typed)
    executor_workers: int = 2
    executor_queue_cap: int = 256
    # what an unresolvable host action (deadline overrun, bulkhead
    # shed, open breaker) contributes to the response: "open" → OK
    # with a 1s/1-use TTL, "closed" → UNAVAILABLE (mixs exposes
    # --host-fail-policy)
    host_fail_policy: str = "closed"
    # extra per-action wall bound even when the request carries no
    # deadline (ms; 0 = bound by the request deadline only)
    host_action_timeout_ms: float = 0.0
    # per-handler circuit breaker: consecutive failed/overrun actions
    # that trip it, and the open window before a half-open probe
    host_breaker_failures: int = 3
    host_breaker_reset_s: float = 5.0
    # -- latency plane (measured wire-to-verdict; runtime/grants.py,
    #    batcher continuous lane, dispatcher staged h2d) --------------
    # begin the str_bytes h2d right after the C++ wire decode (async
    # device_put of the tier-narrowed plane from the zero-copy staging
    # buffers) so the dominant transfer overlaps the host-side
    # namespace extraction. None = auto: on for real accelerator
    # backends, off on cpu (where device_put may alias host memory
    # and overlapping buys nothing).
    overlap_h2d: bool | None = None
    # continuous batching on the latency tier: the check batcher
    # dispatches a batch the moment an in-flight slot under
    # `continuous_depth` frees — a request never waits for a batch to
    # fill or a window to expire. False keeps the occupancy-fill
    # policy (throughput-optimal on serialized transports).
    continuous_batching: bool = False
    continuous_depth: int = 2
    # server-issued check-cache grants: valid_duration/valid_use_count
    # derived from config-generation age (runtime/grants.GrantPolicy)
    # so repeat traffic serves from the CLIENT cache and a config
    # delta revokes outstanding grants within the TTL floor. Opt-in:
    # the emitted TTL becomes time-dependent, which byte-exact parity
    # surfaces (shard/mesh/canary TTL comparisons) must opt into
    # knowingly.
    check_grants: bool = False
    grant_ttl_floor_s: float = 1.0
    grant_ttl_cap_s: float = 5.0
    grant_ttl_ramp_per_s: float = 0.5
    # -- tail-latency forensics (runtime/forensics.py) -----------------
    # per-request flight recorder: requests whose e2e latency exceeds
    # the threshold capture a complete stage timeline (+ overlapping
    # control-plane events) into the bounded ring /debug/slow serves.
    # The fast path is one threshold compare per batch — the forensics
    # smoke gates the clean-traffic overhead at ≤2 % (CPU).
    flight_recorder: bool = True
    # capture threshold in ms; 0 = the live SLO target
    # (monitor.CHECK_P99_TARGET_MS — "slow" means "violates the p99
    # budget" by default)
    slow_threshold_ms: float = 0.0
    # adaptive mode: the threshold tracks max(base, live window p99),
    # refreshed at scrape rate — opt-in (an overloaded server would
    # otherwise stop capturing exactly when everything is slow, which
    # is sometimes what you want: only the OUTLIERS above the current
    # regime are exemplars)
    slow_adaptive: bool = False
    # jax.profiler trace capture directory for /debug/profile
    # (mixs --profile-dir; None → MIXS_PROFILE_DIR env → a tempdir
    # created per capture)
    profile_dir: str | None = None
    # -- rule-level telemetry (runtime/rulestats.py) -------------------
    # fold per-rule hit/deny/err counts into on-device accumulators
    # inside the fused check step (requires fused=True to do anything)
    rule_telemetry: bool = True
    # accumulator drain cadence: the background thread pulls deltas
    # device→host every this many seconds and feeds the aggregator /
    # counter families / adapter exporters. 0 disables the thread
    # (drains then happen only on demand: /debug/rulestats, tests).
    rulestats_drain_s: float = 0.5
    # -- config canary (istio_tpu/canary/) -----------------------------
    # shadow-replay recorded live Check() traffic through every
    # rebuilt snapshot before the atomic publish: "off" disables the
    # recorder + replay entirely; "warn" replays and records the diff
    # report but always publishes; "gate" VETOES a publish whose
    # divergence rate exceeds canary_max_divergence (the old
    # dispatcher keeps serving; CanaryRejected surfaces via
    # /debug/canary and Controller.last_canary_rejection)
    canary: str = "off"
    # recorder sampling ring: capacity bounds memory, sample_every=k
    # keeps every k-th request (uniform stride across batches)
    canary_capacity: int = 2048
    canary_sample_every: int = 1
    # newest recorded rows replayed per candidate evaluation
    canary_replay_limit: int = 1024
    # non-waived divergent rows / replayed rows beyond which `gate`
    # vetoes (strictly greater-than; 0.0 = any divergence vetoes)
    canary_max_divergence: float = 0.0
    # qualified rule names ("ns/name") whose divergences are reported
    # but never gate — the "this rule is SUPPOSED to change" hatch
    canary_waivers: tuple = ()

    # -- mesh audit plane (runtime/audit.py) ---------------------------
    # background invariant auditor: report/check/quota conservation,
    # grant coherence, plane agreement, shard routing — plus the
    # fault-explainability scorer. Strictly off the hot path (reads
    # existing counters/ledgers on its own thread); violations emit
    # audit_violation events, bump mixer_audit_* and flip the
    # mixer_audit_healthy gauge. /debug/audit + /debug/slo serve it.
    audit: bool = True
    # evaluation cadence
    audit_interval_s: float = 0.5
    # fault-explainability matching window: an injection unmatched to
    # a forensics exemplar/event past this long counts unexplained
    audit_explain_window_s: float = 10.0

    # -- secure serving plane (istio_tpu/secure/) ----------------------
    # off | permissive | strict (secure/mtls.py). The API fronts read
    # this plus a ServingCerts holder the operator/mixs wires; strict
    # without certs is a construction-time error in each front. The
    # runtime core itself stays transport-agnostic — the knob lives
    # here so mixs/operators configure one surface (mixs --mtls).
    mtls: str = "off"
    # workload identity the serving fronts present
    # (spiffe://<domain>/ns/<ns>/sa/<sa>); empty → the mixs default
    mtls_identity: str = ""
    # serving-cert TTL and rotation point (fraction of TTL remaining
    # at which the maintenance lane renews; node-agent half-life
    # policy) for the WorkloadIdentity the fronts serve from
    mtls_cert_ttl_minutes: int = 60
    mtls_rotation_fraction: float = 0.5


class RuntimeServer:
    def __init__(self, store: Store, args: ServerArgs | None = None):
        self.args = args or ServerArgs()
        # flipped FIRST in shutdown(): every background warm this
        # server starts (bank prewarm, in-step prewarm) polls it
        # between shapes so no thread compiles into teardown
        self._stopping = False
        # persistent XLA compilation cache (compiler/cache.py): wire
        # it BEFORE the first compile so the controller's initial
        # publish already reads/writes cached artifacts
        from istio_tpu.compiler import cache as compile_cache
        self._compile_cache_dir = \
            compile_cache.configure_persistent_cache(
                self.args.jax_compile_cache_dir)
        compile_cache.install_event_counters()
        # tail-latency forensics (runtime/forensics.py): arm the
        # process-wide flight recorder + event ring BEFORE the
        # controller's initial publish so the first generation's
        # events (publish, prewarm) land on the timeline
        from istio_tpu.runtime import forensics
        forensics.RECORDER.configure(
            enabled=self.args.flight_recorder,
            threshold_ms=self.args.slow_threshold_ms,
            adaptive=self.args.slow_adaptive)
        manifest = self.args.default_manifest
        if manifest is None:
            manifest = GLOBAL_MANIFEST
        from istio_tpu.runtime.batcher import default_buckets
        buckets = tuple(sorted(self.args.buckets)) if self.args.buckets \
            else default_buckets(self.args.max_batch)
        mesh = None
        if self.args.mesh_shape is not None:
            if not self.args.fused:
                raise ValueError("mesh serving requires fused=True")
            from istio_tpu.parallel.mesh import MeshSpec
            dp, mp = self.args.mesh_shape
            bad = [b for b in buckets if b % dp]
            if bad:
                raise ValueError(
                    f"serving buckets {bad} not divisible by dp={dp}")
            mesh = MeshSpec(dp=dp, mp=mp).build()
        # rule-level telemetry aggregator (runtime/rulestats.py):
        # created BEFORE the controller so the initial publish can
        # attach it; the drain thread below pulls the device
        # accumulators on the snapshot interval
        from istio_tpu.runtime.rulestats import (RuleStatsAggregator,
                                                 RuleStatsDrainer)
        self.rulestats = RuleStatsAggregator()
        # config canary (istio_tpu/canary): built before the
        # controller so the very first dispatcher already carries the
        # recorder tap — the gate itself only engages from the second
        # rebuild on (there is nothing recorded before traffic flows)
        self.canary = None
        if self.args.canary != "off":
            from istio_tpu.canary import CanaryConfig, ConfigCanary
            self.canary = ConfigCanary(CanaryConfig(
                mode=self.args.canary,
                max_divergence_rate=self.args.canary_max_divergence,
                waivers=tuple(self.args.canary_waivers),
                capacity=self.args.canary_capacity,
                sample_every=self.args.canary_sample_every,
                replay_limit=self.args.canary_replay_limit))
        # sharded serving plane (istio_tpu/sharding): when shards or
        # extra replicas are requested the check path serves through
        # namespace-sharded banks / replica lanes and the parent
        # monolithic plan stays un-warmed (metadata + oracle only)
        if self.args.shards < 0 or self.args.replicas < 1:
            raise ValueError(
                f"shards must be >= 0 and replicas >= 1, got "
                f"shards={self.args.shards} "
                f"replicas={self.args.replicas}")
        self._sharded_serving = (self.args.shards > 0
                                 or self.args.replicas > 1)
        if self._sharded_serving and not self.args.fused:
            raise ValueError("sharded/replica serving requires "
                             "fused=True")
        if self._sharded_serving and self.args.mesh_shape is not None:
            raise ValueError("sharded serving and mesh_shape are "
                             "mutually exclusive (banks own their "
                             "device leases)")
        self._sharded: dict | None = None
        # delta-compilation rebuild ledger — zero-shaped before the
        # first sharded publish (the promtext doctrine applied to
        # /debug/shards): per-generation and cumulative reused-vs-
        # recompiled bank counts, the last rebuild wall, and the last
        # rebuild ERROR with the generation it struck (satellite fix:
        # a swallowed bank-build failure must be loudly visible, not
        # one log line deep in the publish path)
        self._rebuild_status: dict = {
            "rebuilds": 0,
            "banks_reused": 0,
            "banks_recompiled": 0,
            "banks_reused_total": 0,
            "banks_recompiled_total": 0,
            "last_wall_s": 0.0,
            "revision": 0,
            "errors": 0,
            "last_error": None,
            "last_error_revision": None,
        }
        # adapter-executor plane (runtime/executor.py): built BEFORE
        # the controller so the initial publish's dispatcher already
        # runs host actions bulkheaded; lanes + breakers persist
        # across config swaps (handler identity outlives snapshots)
        self.executor = None
        if self.args.host_executor:
            from istio_tpu.runtime.executor import (AdapterExecutor,
                                                    ExecutorConfig)
            self.executor = AdapterExecutor(ExecutorConfig(
                workers=self.args.executor_workers,
                queue_cap=self.args.executor_queue_cap,
                fail_policy=self.args.host_fail_policy,
                action_timeout_s=self.args.host_action_timeout_ms
                / 1e3,
                breaker_failures=self.args.host_breaker_failures,
                breaker_reset_s=self.args.host_breaker_reset_s))
        # check-cache grant policy (runtime/grants.py): built before
        # the controller so the initial publish's dispatcher already
        # clamps TTLs; revocation fires from _on_config_publish with
        # the delta's changed-namespace set when sharding knows it
        self.grants = None
        if self.args.check_grants:
            from istio_tpu.runtime.grants import GrantPolicy
            self.grants = GrantPolicy(
                ttl_floor_s=self.args.grant_ttl_floor_s,
                ttl_cap_s=self.args.grant_ttl_cap_s,
                ttl_ramp_per_s=self.args.grant_ttl_ramp_per_s)
        # overlapped h2d: auto-resolve None → on for real accelerator
        # backends only (on cpu jax may alias the staging buffer
        # zero-copy and the "transfer" overlaps nothing)
        overlap = self.args.overlap_h2d
        if overlap is None:
            import jax
            overlap = jax.default_backend() != "cpu"
        self._overlap_h2d = bool(overlap)
        self.controller = Controller(
            store, default_manifest=manifest,
            identity_attr=self.args.identity_attr,
            max_str_len=self.args.max_str_len,
            fused=self.args.fused,
            prewarm_buckets=buckets,
            mesh=mesh,
            rule_telemetry=self.args.rule_telemetry,
            canary=self.canary,
            on_publish=self._on_config_publish,
            initial_prewarm=self.args.initial_prewarm,
            prewarm_hook=self._prewarm_instep_for,
            warm_parent_plans=not self._sharded_serving,
            executor=self.executor,
            grants=self.grants,
            overlap_h2d=self._overlap_h2d)
        self._rulestats_drainer = RuleStatsDrainer(
            self.rulestats, self.args.rulestats_drain_s) \
            if (self.args.rule_telemetry and self.args.fused
                and self.args.rulestats_drain_s > 0) else None
        # resilience layer in front of the device step: retry, circuit
        # breaker with CPU-oracle fallback, fail-open/closed policy
        # (runtime/resilience.py). Every serving entry routes its
        # batches through _run_check_batch and therefore through this.
        from istio_tpu.runtime.resilience import (ResilienceConfig,
                                                  ResilientChecker)
        if self.args.check_fail_policy not in ("open", "closed"):
            raise ValueError(
                f"check_fail_policy must be 'open' or 'closed', got "
                f"{self.args.check_fail_policy!r}")
        self.resilience = ResilientChecker(
            device=self._run_check_batch_device,
            oracle=self._run_check_batch_oracle,
            config=ResilienceConfig(
                fail_policy=self.args.check_fail_policy,
                breaker_failures=self.args.breaker_failures,
                breaker_reset_s=self.args.breaker_reset_s,
                retry=self.args.device_retry))
        cap = self.args.check_queue_cap
        max_queue = 8 * self.args.max_batch if cap is None else cap
        if self._sharded_serving:
            # N CheckBatcher lanes behind the one front attribute
            # every wire front / introspect surface reads; each lane's
            # admission control (cap, deadline, brownout) is the same
            # CheckBatcher machinery, per lane
            from istio_tpu.sharding import ReplicaRouter
            self._replica_router = ReplicaRouter(
                self.args.replicas, self.args.identity_attr,
                dict(window_s=self.args.batch_window_s,
                     max_batch=self.args.max_batch,
                     pipeline=self.args.pipeline,
                     buckets=buckets,
                     hold_at=self.args.hold_at,
                     max_queue=max_queue,
                     brownout=self.args.brownout,
                     continuous=self.args.continuous_batching,
                     continuous_depth=self.args.continuous_depth))
            self.batcher = self._replica_router
            # the controller's initial publish fired before the router
            # existed — build the first generation's banks now
            self._rebuild_sharded(self.controller.dispatcher)
        else:
            self._replica_router = None
            self.batcher = CheckBatcher(
                self._run_check_batch,
                window_s=self.args.batch_window_s,
                max_batch=self.args.max_batch,
                pipeline=self.args.pipeline,
                buckets=buckets,
                hold_at=self.args.hold_at,
                max_queue=max_queue,
                brownout=self.args.brownout,
                continuous=self.args.continuous_batching,
                continuous_depth=self.args.continuous_depth)
        # the REPORT coalescer: records from concurrent Report RPCs
        # share packed device trips (see report()). Separate instance
        # so report trips are separately counted and the two queues
        # can't starve each other's windows.
        from istio_tpu.runtime import monitor as _monitor
        rcap = self.args.report_queue_cap
        self._report_batcher = CheckBatcher(
            self._run_report_batch,
            window_s=self.args.batch_window_s,
            max_batch=self.args.max_batch,
            pipeline=self.args.pipeline,
            buckets=buckets,
            hold_at=self.args.hold_at,
            size_hist=_monitor.REPORT_BATCH_SIZE,
            # the fused report resolve pads per chunk itself — don't
            # allocate padding here just to trim it
            pad_batches=False,
            # report records must not feed the CHECK latency
            # decomposition / live p99 window — they feed the report
            # pipeline's own coalesce_wait stage instead
            observe_latency=False,
            stage_observer=lambda w: _monitor.observe_report_stage(
                "coalesce_wait", w),
            # bounded admission: the ack-after-enqueue contract needs
            # a typed RESOURCE_EXHAUSTED at overflow, never unbounded
            # memory behind an already-acked wire
            max_queue=16 * self.args.max_batch if rcap is None
            else rcap) \
            if self.args.report_batching else None
        # initial publish ran before this hook's dependencies existed;
        # warm the in-step quota program in the background like the
        # controller's own initial prewarm (swaps re-warm in-line via
        # _on_config_publish). close() flips the stop flag so a still-
        # running background warm exits between shapes instead of
        # compiling into interpreter teardown.
        self._instep_prewarm_stop = False
        try:
            self.prewarm_instep(background=True)
        except Exception:
            import logging
            logging.getLogger("istio_tpu.runtime.server").exception(
                "initial in-step quota prewarm failed")
        # mesh audit plane: background invariant auditor + fault
        # explainability scorer (runtime/audit.py). Created LAST so
        # every surface it reads (controller, batchers, grants,
        # routers) already exists; reads snapshots only — nothing on
        # the hot path learns it is being audited.
        self.audit = None
        if self.args.audit:
            from istio_tpu.runtime.audit import (AuditPlane,
                                                 install_chaos_observer)
            install_chaos_observer()
            self.audit = AuditPlane(
                self,
                interval_s=self.args.audit_interval_s,
                explain_window_s=self.args.audit_explain_window_s)
            self.audit.start()
        # full garbage collections stop every pump: time them for as
        # long as this server serves (shutdown() removes the hook).
        # Last in the constructor: a server that failed to build has
        # no close() to undo it.
        from istio_tpu.runtime import monitor as _monitor
        _monitor.install_gc_hook()
        # store, snapshot, handlers and the host rule compile are in
        # place and cannot die before the next publish: out of the
        # collector's way, set-up's garbage returned first
        _monitor.settle_heap("init", reclaim=True)

    # -- API surface (grpcServer.go Check/Report semantics) --
    # Preprocessing (the APA phase) happens exactly ONCE per request, in
    # the caller-facing entry points; everything downstream of the
    # batcher operates on already-preprocessed bags.

    def _on_config_publish(self, dispatcher) -> None:
        """Controller publish hook: rebind the rulestats aggregator to
        the fresh snapshot (draining the outgoing plan first so a
        config swap never drops in-flight counts). Must never raise —
        telemetry is an observer of the publish, not a participant."""
        # grant revocation ordering: the monolithic serving surface
        # revokes INSIDE the controller, immediately before the
        # dispatcher ref swap (a response from the new generation must
        # never carry an old-generation grant); the sharded serving
        # surface revokes inside _rebuild_sharded before set_routers,
        # delta-scoped when the bank diff attributes the change.
        # staging-ring reuse bound: the zero-copy decoder's buffer
        # lifecycle contract requires staging_depth > the number of
        # batches concurrently in flight — raise the ring depth to
        # cover the configured pipeline (growing is always safe: the
        # ring allocates slots lazily and never shrinks live ones)
        self._bound_staging_depth(dispatcher)
        try:
            self.rulestats.attach(dispatcher)
        except Exception:
            import logging
            logging.getLogger("istio_tpu.runtime.server").exception(
                "rulestats attach failed")
        # maintenance lane: (re)register the published handlers'
        # provider-refresh jobs (list_adapter's TTL loop) with the
        # executor's scheduler — refresh runs pinned off the timed
        # request window, and a failing provider keeps serving the
        # last good list while the counters say so
        if self.executor is not None:
            try:
                self.executor.register_refreshables(
                    dispatcher.handlers)
            except Exception:
                import logging
                logging.getLogger(
                    "istio_tpu.runtime.server").exception(
                    "refreshable registration failed")
        # sharded serving plane: rebuild the shard banks / replica
        # lanes for the freshly published snapshot and swap every lane
        # atomically (set_routers) — old banks keep serving while the
        # new generation compiles, so a config swap never drops or
        # stalls a queued request. Failure policy mirrors the canary's
        # fail-open: a bank build error keeps the previous generation
        # serving and surfaces loudly (log + /debug/shards revision
        # mismatch) instead of killing the publish.
        if getattr(self, "_replica_router", None) is not None:
            try:
                self._rebuild_sharded(dispatcher)
            except Exception as exc:
                # conservative revoke: a failed rebuild left grant
                # state un-attributed — shortening budgets is always
                # safe, a stale long grant is not
                if self.grants is not None:
                    self.grants.on_publish(None)
                # surfaced, not just logged: /debug/shards renders the
                # ledger so an on-call sees WHICH generation failed to
                # build banks and that the previous one keeps serving
                st = self._rebuild_status
                st["errors"] += 1
                st["last_error"] = f"{type(exc).__name__}: {exc}"
                st["last_error_revision"] = \
                    dispatcher.snapshot.revision
                import logging
                logging.getLogger(
                    "istio_tpu.runtime.server").exception(
                    "sharded serving rebuild failed for generation "
                    "%d; previous generation keeps serving",
                    dispatcher.snapshot.revision)
        # in-step quota prewarm backstop (ADVICE r5: fused.
        # prewarm_instep was defined but never called, so the first
        # quota-carrying batch paid its XLA trace in-band). The main
        # warm runs PRE-SWAP via the controller's prewarm_hook
        # (_prewarm_instep_for); this post-publish pass uses the
        # precise instep_quota_target eligibility and catches a pool
        # whose counts shape changed with the new config — already-
        # compiled shapes just re-execute cheap dummy trips. The
        # initial publish fires before self.controller exists and is
        # covered by prewarm_instep() at the end of __init__.
        try:
            if getattr(self, "controller", None) is not None:
                self.prewarm_instep()
        except Exception:
            import logging
            logging.getLogger("istio_tpu.runtime.server").exception(
                "in-step quota prewarm failed")
        # the outgoing snapshot, its handlers and traced programs were
        # frozen and have just lost their last owner: one full walk
        # here, on the rebuild thread, returns them and freezes the
        # incoming generation (the initial publish fires inside the
        # constructor, which settles at its own end)
        if getattr(self, "controller", None) is not None:
            from istio_tpu.runtime import monitor as _monitor
            _monitor.settle_heap("publish", reclaim=True)

    def _bound_staging_depth(self, dispatcher) -> None:
        """Keep the wire decoder's staging ring deeper than the
        number of batches that can be in flight against it (+2
        slack: the decode in progress and the batch a pump still
        holds). Under sharded serving every replica LANE shares the
        same bank — and therefore the same tensorizer — so the bound
        scales with replicas, not just the per-lane pipeline. Slots
        allocate lazily, so a deep bound costs nothing until used."""
        try:
            plan = getattr(dispatcher, "fused", None)
            native = getattr(plan, "native", None)
            if native is not None:
                lanes = max(self.args.replicas, 1)
                native.staging_depth = max(
                    native.staging_depth,
                    self.args.pipeline * lanes + 2)
        except Exception:
            pass   # decoder hardening must never break a publish

    def _rebuild_sharded(self, dispatcher) -> None:
        """Build the sharded serving generation for a published
        dispatcher and fan it across every surface coherently:
        plan (delta-stable against the live plan) → DIFF by bank
        content hash → compile only the banks whose namespaces (or
        the replicated global set) changed, carrying every untouched
        bank — prewarmed shapes, breaker state, rulestats bindings —
        across the generation (off-path; the previous generation
        keeps serving), prewarm the NEW banks' serving shapes, swap
        all replica lanes with one atomic set_routers, rebind the
        rulestats aggregator to the bank dispatchers (name-keyed
        counts merge globally), and record the plan decision + the
        reused-vs-recompiled ledger for /debug/shards.
        The canary recorder taps the bank dispatchers the same way it
        taps a monolithic one — bank-local rule indices resolve
        through the bank's own qualified_rule_names, which are the
        global names."""
        import time as _time

        from istio_tpu.sharding import (ReplicaRouter, ShardRouter,
                                        bank_content_key,
                                        compile_shard_bank,
                                        snapshot_static_digest)
        from istio_tpu.sharding.banks import (ShardingUnsupported,
                                              full_bank)
        from istio_tpu.sharding.planner import (costs_from_ruleset,
                                                plan_shards,
                                                trivial_plan)

        router: ReplicaRouter = self._replica_router
        snap = dispatcher.snapshot
        recorder = self.canary.recorder if self.canary is not None \
            else None
        buckets = self.controller.prewarm_buckets
        t0 = _time.perf_counter()
        n_lanes = router.n_replicas
        reason = ""
        bank_keys: list[str] = []
        reused_ids: list[int] = []
        if self.args.shards > 0:
            try:
                preds = snap.ruleset.rules[:snap.n_config_rules]
                # costs come from the decomposition compile_ruleset
                # just retained — never a second 100k-rule parse+DNF
                # pass on the rebuild thread
                costs = costs_from_ruleset(
                    snap.ruleset, snap.finder)[:snap.n_config_rules]
                # the content-addressed bank cache: the previous
                # generation's banks keyed by their ruleset-
                # decomposition hash. Delta planning keeps unchanged
                # namespaces on their current shards, so an unchanged
                # shard's key matches and its compiled bank carries
                # over; pop-on-use so two identical shards (possible
                # when both hold only replicated globals) never share
                # one bank object.
                prev = self._sharded if self.args.delta_compile \
                    else None
                prev_plan = None
                cache: dict[str, Any] = {}
                if prev is not None and prev.get("mode") == "sharded":
                    prev_plan = prev["plan"]
                    for b, key in zip(prev["banks"],
                                      prev.get("bank_keys", ())):
                        cache.setdefault(key, b)
                plan = plan_shards(
                    preds, snap.finder, self.args.shards, costs=costs,
                    revision=snap.revision, prev=prev_plan,
                    rebalance_budget=self.args.shard_rebalance_budget)
                static = snapshot_static_digest(
                    snap, identity_attr=self.args.identity_attr,
                    buckets=buckets,
                    rule_telemetry=self.args.rule_telemetry)
                banks = []
                for k in range(plan.n_shards):
                    key = bank_content_key(snap, plan, k, static)
                    bank_keys.append(key)
                    carried = cache.pop(key, None)
                    if carried is not None:
                        # carry the compiled artifact by SHALLOW COPY:
                        # the new generation's bank shares the
                        # dispatcher/snapshot/checker (the expensive,
                        # content-matched parts) but owns its
                        # local_to_global — the outgoing generation's
                        # routers keep the ORIGINAL object, so
                        # in-flight folds never see the incoming
                        # generation's rule numbering and a rebuild
                        # that fails on a later bank leaves serving
                        # state untouched
                        banks.append(dataclasses.replace(
                            carried, shard_id=k,
                            local_to_global=np.asarray(
                                plan.shard_rules[k], np.int64),
                            predicted_cost=float(plan.shard_cost[k])
                            if plan.shard_cost else 0.0))
                        reused_ids.append(k)
                    else:
                        b = compile_shard_bank(
                            snap, dispatcher.handlers, plan, k,
                            identity_attr=self.args.identity_attr,
                            buckets=buckets,
                            rule_telemetry=self.args.rule_telemetry,
                            recorder=recorder,
                            executor=self.executor,
                            grants=self.grants,
                            overlap_h2d=self._overlap_h2d)
                        b.content_key = key
                        banks.append(b)
                # grant revocation scoped to the DELTA: only the
                # recompiled banks' namespaces drop to the TTL floor
                # (reused banks' configs are content-identical — their
                # outstanding client grants stay valid); a scratch
                # rebuild (nothing reused) revokes globally. This runs
                # BEFORE the router swap below — new-generation
                # responses never carry old-generation grants.
                if self.grants is not None:
                    changed = {k for k in range(plan.n_shards)
                               if k not in reused_ids}
                    if reused_ids:
                        # union the OLD plan's namespaces for the
                        # changed shards: a namespace whose rules
                        # were entirely DELETED is absent from the
                        # new ns_to_shard but its cached verdicts
                        # still need revoking (shard ids are stable
                        # under delta planning, so the old map's
                        # shard numbering matches)
                        ns_maps = [plan.ns_to_shard]
                        if prev_plan is not None:
                            ns_maps.append(prev_plan.ns_to_shard)
                        self.grants.on_publish(
                            {ns for m in ns_maps
                             for ns, s in m.items() if s in changed})
                    else:
                        self.grants.on_publish(None)
                bank_map = {b.shard_id: b for b in banks}
                routers = [ShardRouter(bank_map, plan,
                                       self.args.identity_attr,
                                       replica=i)
                           for i in range(n_lanes)]
            except ShardingUnsupported as exc:
                # un-shardable snapshot (rbac pseudo-rules): fall back
                # to replica-only lanes over the monolithic plan —
                # the server keeps serving, /debug/shards says why
                reason = str(exc)
                plan = trivial_plan(n_lanes)
                banks = [full_bank(
                    snap, dispatcher.handlers, i,
                    identity_attr=self.args.identity_attr,
                    buckets=buckets,
                    rule_telemetry=self.args.rule_telemetry,
                    recorder=recorder,
                    dispatcher=dispatcher if i == 0 else None,
                    executor=self.executor,
                    grants=self.grants,
                    overlap_h2d=self._overlap_h2d)
                    for i in range(n_lanes)]
                # un-attributable rebuild: revoke every namespace
                # (the delta-scoped refinement only exists on the
                # sharded success path)
                if self.grants is not None:
                    self.grants.on_publish(None)
                routers = [
                    ShardRouter({s: banks[i]
                                 for s in range(plan.n_shards)},
                                plan, self.args.identity_attr,
                                replica=i)
                    for i in range(n_lanes)]
        else:
            # replica-only: each lane owns its own FusedPlan over the
            # full snapshot (lane 0 rides the published dispatcher)
            plan = trivial_plan(n_lanes)
            banks = [full_bank(
                snap, dispatcher.handlers, i,
                identity_attr=self.args.identity_attr,
                buckets=buckets,
                rule_telemetry=self.args.rule_telemetry,
                recorder=recorder,
                dispatcher=dispatcher if i == 0 else None,
                executor=self.executor,
                grants=self.grants,
                overlap_h2d=self._overlap_h2d)
                for i in range(n_lanes)]
            # replica-only publishes carry no delta attribution:
            # conservative global revoke, same as monolithic
            if self.grants is not None:
                self.grants.on_publish(None)
            routers = [
                ShardRouter({s: banks[i] for s in range(plan.n_shards)},
                            plan, self.args.identity_attr, replica=i)
                for i in range(n_lanes)]
        # each bank is its own device lease, so it carries its OWN
        # resilience wrap: retry → per-bank circuit breaker → the
        # bank's CPU-oracle fallback (Dispatcher.check_host_oracle
        # over the bank's rules) — a flapping bank degrades to
        # correct-but-slower answers without touching its siblings,
        # the same contract the monolithic ResilientChecker gives the
        # un-sharded path. The CHECKER is per generation (its device/
        # oracle callables belong to THIS generation's banks — an
        # in-flight batch on the old routers must finish on the old
        # banks, never be handed the new cold ones mid-window); only
        # the BREAKER persists across swaps, keyed by shard id: the
        # device behind a shard is the same physical lease, and a
        # fresh breaker per publish would re-pay breaker_failures
        # failed in-band batches on a device that is still down.
        from istio_tpu.runtime.resilience import (ResilienceConfig,
                                                  ResilientChecker)
        breakers = getattr(self, "_bank_breakers", {})
        reused_set = set(reused_ids)
        for b in banks:
            if b.shard_id in reused_set and b.checker is not None:
                # carried bank: its checker's device/oracle callables
                # ARE this bank's dispatcher — checker, breaker state
                # and all, it rides along untouched
                breakers[b.shard_id] = b.checker.breaker
                continue
            b.checker = ResilientChecker(
                device=b.dispatcher.check,
                oracle=b.dispatcher.check_host_oracle,
                config=ResilienceConfig(
                    fail_policy=self.args.check_fail_policy,
                    breaker_failures=self.args.breaker_failures,
                    breaker_reset_s=self.args.breaker_reset_s,
                    retry=self.args.device_retry),
                name=f"bank:{b.shard_id}")
            prev_brk = breakers.get(b.shard_id)
            if prev_brk is not None:
                b.checker.breaker = prev_brk
            else:
                breakers[b.shard_id] = b.checker.breaker
        self._bank_breakers = breakers
        # warm each NEW bank's serving shapes BEFORE the lane swap —
        # the previous generation serves meanwhile, so no request pays
        # a bank's first XLA trace in-band (the monolithic swap-warm
        # doctrine, per bank); on swaps the warm yields to live
        # serving between shapes exactly like the monolithic one.
        # Carried banks keep their already-compiled shape set — NOT
        # re-warmed, that is the delta-compilation win.
        from istio_tpu.runtime.controller import _serving_backoff
        first_build = self._sharded is None
        distinct = {id(b.dispatcher.fused): b for b in banks
                    if b.dispatcher.fused is not None
                    and b.shard_id not in reused_set}
        for b in distinct.values():
            b.dispatcher.fused.prewarm(
                buckets,
                should_stop=lambda: self._stopping,
                backoff=None if first_build else _serving_backoff)
        for b in banks:   # staging-ring depth >= pipeline bound
            self._bound_staging_depth(b.dispatcher)
        router.set_routers(routers, plan)
        # telemetry fan: bank plans' per-rule accumulators merge into
        # the one aggregator by qualified rule name (lane 0 in
        # replica-only mode IS the attached parent dispatcher — the
        # aggregator dedups by plan identity)
        try:
            self.rulestats.attach_lanes(
                [b.dispatcher for b in banks])
        except Exception:
            import logging
            logging.getLogger("istio_tpu.runtime.server").exception(
                "rulestats lane attach failed")
        wall = _time.perf_counter() - t0
        n_recompiled = len(banks) - len(reused_ids)
        self._sharded = {
            "plan": plan,
            "banks": banks,
            "bank_keys": bank_keys,
            "revision": snap.revision,
            "mode": "sharded" if self.args.shards > 0 and not reason
                    else "replica-only",
            "fallback_reason": reason,
            "build_wall_s": wall,
            "built_wall": _time.time(),
            "delta": {
                "reused": sorted(reused_ids),
                "recompiled": sorted(
                    b.shard_id for b in banks
                    if b.shard_id not in reused_set),
                "plan_stability": dict(plan.stability),
            },
        }
        st = self._rebuild_status
        st["rebuilds"] += 1
        st["banks_reused"] = len(reused_ids)
        st["banks_recompiled"] = n_recompiled
        st["banks_reused_total"] += len(reused_ids)
        st["banks_recompiled_total"] += n_recompiled
        st["last_wall_s"] = round(wall, 4)
        st["revision"] = snap.revision
        # mesh event timeline: which banks this generation carried vs
        # recompiled — the event a shard's cold-bank tail rides next to
        from istio_tpu.runtime import forensics
        forensics.record_event("bank_rebuild",
                               generation=snap.revision,
                               reused=len(reused_ids),
                               recompiled=n_recompiled,
                               wall_ms=round(wall * 1e3, 1))

    def _prewarm_instep_for(self, plan) -> None:
        """Controller prewarm_hook: compile the CANDIDATE plan's
        merged check+quota program BEFORE the dispatcher swap (old
        plan keeps serving), so no quota batch in the swap window
        traces in-band. Uses the live pool's counter shape — pools
        persist across swaps (quota state continuity); if the new
        config changes the shape, the post-publish backstop
        (_on_config_publish → prewarm_instep) compiles the real one."""
        if not self.args.quota_in_step or plan is None \
                or not plan.quota_actions:
            return
        pools = getattr(self.controller, "device_quotas", None) \
            if getattr(self, "controller", None) is not None else None
        if not pools or len(set(map(id, pools.values()))) != 1:
            return
        pool = next(iter(pools.values()))
        plan.prewarm_instep(
            self.controller.prewarm_buckets, pool.counts,
            should_stop=lambda: getattr(
                self, "_instep_prewarm_stop", False))

    def prewarm_instep(self, background: bool = False) -> None:
        """Compile the merged check+quota-alloc program for every
        serving bucket (and byte tier) BEFORE traffic selects it —
        only when the in-step quota path is actually configured and
        the live snapshot is in-step eligible. No-op otherwise."""
        if not self.args.quota_in_step:
            return
        d = self.controller.dispatcher
        plan = d.fused
        target = self.instep_quota_target()
        if plan is None or target is None:
            return
        pool, _ = target
        buckets = self.controller.prewarm_buckets

        def warm() -> None:
            try:
                plan.prewarm_instep(
                    buckets, pool.counts,
                    should_stop=lambda: self._instep_prewarm_stop)
            except Exception:
                import logging
                logging.getLogger(
                    "istio_tpu.runtime.server").exception(
                    "in-step quota prewarm failed")

        if background:
            import threading
            t = threading.Thread(target=warm, daemon=True,
                                 name="prewarm-instep")
            self._instep_prewarm_thread = t
            t.start()
        else:
            warm()

    def preprocess(self, bag: Bag) -> Bag:
        return self.preprocess_batch([bag])[0]

    def preprocess_batch(self, bags: Sequence[Bag]) -> Sequence[Bag]:
        """The APA phase over a pre-formed batch
        (Dispatcher.preprocess_batch). Whether there is one, and
        whether the step holds it, are properties of the snapshot, read
        once a batch: without one, or with the step binding its
        outputs, the batch comes back as it is, no row touched."""
        return self.controller.dispatcher.preprocess_batch(bags)

    def _run_check_batch(self, bags: Sequence[Bag],
                         deadline: float | None = None
                         ) -> Sequence[CheckResponse]:
        # pre-batched entries (check_many / BatchCheck) under sharded
        # serving route through the shard path too — a mixed-namespace
        # batch fans across banks inside the router; lane attribution
        # rides replica 0 (the submitting caller chose no lane)
        rr = self._replica_router
        if rr is not None and rr.routers:
            return rr.routers[0].check(bags, deadline=deadline)
        return self.resilience.run_batch(bags, deadline=deadline)

    def _run_check_batch_device(self, bags: Sequence[Bag],
                                deadline: float | None = None
                                ) -> Sequence[CheckResponse]:
        """The device serving path (ResilientChecker's primary).
        Resolved per call: a config swap publishes a new dispatcher and
        the breaker/fallback must follow it."""
        return self.controller.dispatcher.check(bags, deadline=deadline)

    def _run_check_batch_oracle(self, bags: Sequence[Bag]
                                ) -> Sequence[CheckResponse]:
        """The CPU oracle fallback (ResilientChecker's degraded path —
        no device step anywhere)."""
        return self.controller.dispatcher.check_host_oracle(bags)

    def _run_report_batch(self, bags: Sequence[Bag]) -> Sequence[None]:
        """Report batcher hook: dispatch the coalesced record batch
        (unpadded — the fused resolve pads per chunk); results are
        completion-only (Report returns empty)."""
        self.controller.dispatcher.report(bags)
        return [None] * len(bags)

    def check(self, bag: Bag,
              deadline: float | None = None) -> CheckResponse:
        """One request; coalesced into a device batch. `deadline`:
        absolute time.perf_counter() instant (see CheckBatcher.submit);
        expired/shed requests raise the typed CheckRejected errors from
        runtime/resilience.py."""
        return self.batcher.check(self.preprocess(bag),
                                  deadline=deadline)

    def check_preprocessed(self, bag: Bag,
                           deadline: float | None = None
                           ) -> CheckResponse:
        """Batcher entry for callers that already ran preprocess()
        (the gRPC server, which reuses the bag for the quota loop)."""
        return self.batcher.check(bag, deadline=deadline)

    def submit_check_preprocessed(self, bag: Bag, trace=None,
                                  deadline: float | None = None):
        """Non-blocking batcher entry → concurrent.futures.Future.
        The async gRPC front awaits it so an in-flight check holds no
        thread (the sync front burns one blocked thread per RPC for
        the whole batch round-trip). `trace`: the RPC's root span dict
        (the batch span parents under it — API-layer root spans).
        `deadline`: absolute perf_counter instant; expired requests
        resolve DEADLINE_EXCEEDED before tensorize."""
        return self.batcher.submit(bag, trace=trace, deadline=deadline)

    def check_many(self, bags: Sequence[Bag]) -> list[CheckResponse]:
        """Pre-batched entry (load tests / the C++ shim's batches).
        Observes the full stage decomposition: the preprocess+handoff
        time counts as this batch's queue-wait (no batcher queue in
        front of a pre-formed batch), and every request's wall time
        feeds the e2e histogram + live-percentile tracker."""
        import time as _time

        from istio_tpu.runtime import forensics
        from istio_tpu.runtime import monitor as _monitor

        t0 = _time.perf_counter()
        with _monitor.stage("queue_wait"):
            forensics.RECORDER.batch_begin()
            pre = self.preprocess_batch(bags)
        out = list(self._run_check_batch(pre))
        e2e = _time.perf_counter() - t0
        _monitor.observe_check_e2e(e2e, len(bags))
        forensics.RECORDER.note_direct(e2e, len(bags))
        return out

    def check_batch_preprocessed(self,
                                 bags: Sequence[Bag]
                                 ) -> list[CheckResponse]:
        """Pre-batched entry for callers that already ran preprocess()
        and padded to a bucket shape (the BatchCheck gRPC front)."""
        import time as _time

        from istio_tpu.runtime import forensics
        from istio_tpu.runtime import monitor as _monitor
        from istio_tpu.runtime.batcher import trim_pads

        t0 = _time.perf_counter()
        # flight recorder: the native pump / BatchCheck front's batch
        # tape — stage marks land on THIS thread (the dispatcher runs
        # inline below), and the front's wire-decode pre-mark is
        # absorbed here
        forensics.RECORDER.batch_begin()
        out = self._run_check_batch(bags)
        if not isinstance(out, list):   # a ClassedResponses stays one
            out = list(out)
        e2e = _time.perf_counter() - t0
        n_real = len(trim_pads(bags))   # padding rows carry no caller
        _monitor.observe_check_e2e(e2e, n_real)
        forensics.RECORDER.note_direct(e2e, n_real)
        return out

    def submit_report(self, bags: Sequence[Bag]) -> list:
        """Non-blocking report entry → concurrent Futures, one per
        record (empty when no batcher is configured — records already
        dispatched inline). Records coalesce ACROSS RPCs into shared
        device trips via the report batcher, so N concurrent 64-record
        Report RPCs form one bucket-sized packed pull instead of N
        separate trips — on a trip-serialized transport
        records/s = trips/s × batch size. The aio front awaits the
        futures so an in-flight Report holds no thread; the native
        front acks after ENQUEUE (inspecting only already-rejected
        futures) so its pump never waits out a device trip.

        Record conservation: every record is counted ACCEPTED here and
        counted exported or typed-rejected exactly once when its
        future resolves (monitor.report_record_done) — the batcher's
        lifecycle guarantees (watchdog, drain-on-close, typed
        admission sheds) mean no future is ever abandoned, so
        accepted == exported + rejected holds at quiescence."""
        from istio_tpu.runtime import monitor as _monitor

        bags = list(self.preprocess_batch(bags))
        rb = self._report_batcher
        if rb is None:
            # inline dispatch (report_batching=False): same
            # conservation accounting, no coalescer
            _monitor.report_accepted(len(bags))
            try:
                self.controller.dispatcher.report(bags)
            except Exception as exc:
                _monitor.report_rejected(
                    len(bags), "error",
                    f"{type(exc).__name__}: {exc}")
                raise
            _monitor.report_exported(len(bags))
            return []
        from concurrent.futures import Future

        from istio_tpu.runtime.resilience import (CheckRejected,
                                                  UnavailableError)
        futs = []
        for b in bags:
            _monitor.report_accepted(1)
            try:
                fut = rb.submit(b)
            except Exception as exc:
                # a CLOSED coalescer (post-shutdown submit) raises —
                # convert to a typed-rejected future so the record
                # stays on the conservation ledger (an accepted count
                # with no resolving future would leak in_flight
                # forever) and fronts answer UNAVAILABLE, not a stack
                # trace
                fut = Future()
                fut.set_exception(
                    exc if isinstance(exc, CheckRejected) else
                    UnavailableError(
                        f"report coalescer closed: "
                        f"{type(exc).__name__}: {exc}"))
            fut.add_done_callback(_monitor.report_record_done)
            futs.append(fut)
        return futs

    def report(self, bags: Sequence[Bag]) -> None:
        """Blocking report: returns after EVERY record's batch
        completed (grpcServer.go Report returns post-dispatch); the
        first batch error re-raises only after all futures resolved —
        abandoning later batches would leave records executing past
        the call and their exceptions unretrieved."""
        from concurrent.futures import wait as _wait

        futs = self.submit_report(bags)
        if not futs:
            return
        _wait(futs)
        first = next((e for e in (f.exception() for f in futs)
                      if e is not None), None)
        if first is not None:
            raise first

    def quota(self, bag: Bag, quota_name: str,
              args: QuotaArgs | None = None,
              preprocessed: bool = False,
              deadline: float | None = None) -> QuotaResult:
        """`deadline`: absolute perf_counter instant bounding the host
        quota adapter call (the executor plane); callers without one
        inherit the server default — a wedged shared-quota backend
        must never hold a front thread unbounded."""
        d = self.controller.dispatcher
        if not preprocessed:
            bag = self.preprocess(bag)
        if deadline is None and self.args.default_check_deadline_ms:
            import time as _time
            deadline = _time.perf_counter() + \
                self.args.default_check_deadline_ms / 1e3
        return d.quota(bag, quota_name, args or QuotaArgs(),
                       deadline=deadline)

    def quota_fused(self, bag: Bag, quota_name: str, args: QuotaArgs,
                    check_result):
        """Served quota via the device pools (runtime/device_quota.py):
        reuses the CHECK step's activity bits instead of re-resolving.
        Returns a QuotaFuture, a final QuotaResult (no device work
        needed), or None → the caller must take the dispatcher.quota
        fallback (generic path / non-memquota quota handler)."""
        target = self.quota_target(bag, quota_name, args, check_result)
        if isinstance(target, tuple):
            pool, inst_q, instance = target
            return pool.alloc(inst_q, instance, args)
        return target

    def _binds_for_quota(self):
        """The dispatcher in force where its step holds the APA and a
        quota instance reads what the APA binds, else None."""
        d = self.controller.dispatcher
        if d.apa_in_step and d.fused.apa.quota_bindings:
            return d
        return None

    def quota_bag(self, bag: Bag) -> Bag:
        """One Check's bag as its quota instances read it (the gRPC
        fronts, a request at a time): with the APA's outputs bound on
        the host where the step holds the APA and a quota instance
        reads what it binds; the bag itself elsewhere."""
        d = self._binds_for_quota()
        return bag if d is None else d._with_apa([bag])[0]

    def quota_bags(self, bags: Sequence[Bag], results,
                   rows: Sequence[int]) -> list | None:
        """The bags the quota instances of a served batch's `rows` are
        built on, where the step holds the APA and a quota instance
        reads what it binds: each row's bag with every binding applied
        from the pods the batch's stamp found (`results.apa_pods`,
        counted as mixer_apa_rows_total{path=quota}; the native front
        calls it inside span `quota.bind`), or by the host APA for a
        batch served without them (in chunks, or by the host oracle).
        None: the rows' own bags."""
        from istio_tpu.runtime import monitor as _monitor

        pods = getattr(results, "apa_pods", None)
        if pods is not None:
            out = pods.bind(bags, rows)
            _monitor.note_apa_rows("quota", len(out))
            return out
        d = self._binds_for_quota()
        return None if d is None else d._with_apa([bags[r] for r in rows])

    def quota_target(self, bag: Bag, quota_name: str, args: QuotaArgs,
                     check_result):
        """Where quota_fused sends one quota, without sending it:
        (device pool, instance name, built instance), a final
        QuotaResult, or None (the dispatcher.quota fallback). The
        native front submits a batch's pool targets a pool at a time
        (DeviceQuotaPool.alloc_many)."""
        route = self.quota_route(quota_name, check_result)
        if route is QUOTA_FREE:
            return QuotaResult(granted_amount=args.quota_amount)
        if route is None:
            return None
        return self.quota_build(bag, check_result, *route)

    def quota_route(self, quota_name: str, check_result):
        """The half of quota_target a check result decides, the same
        for every row of a verdict class: (device pool, instance name),
        QUOTA_FREE (granted freely: no quota action existed at check
        time, or none active matches the name), or None (the
        dispatcher.quota fallback: the generic path, a non-memquota
        handler)."""
        if check_result.active_quota_rules is None:
            return None
        # rule indices are positional within the snapshot that served
        # the check — use THAT dispatcher, not the current one (a config
        # swap mid-request would renumber rules under us)
        d = check_result.quota_context
        if d is None:
            # no quota actions existed at check time: grant freely
            # (dispatcher.quota tail — the reference returns empty)
            return QUOTA_FREE
        plan = d.fused
        if plan is None:
            return None
        active = set(check_result.active_quota_rules)
        for ridx, handler_q, inst_q, names in plan.quota_actions:
            if ridx not in active or quota_name not in names:
                continue
            pool = self.controller.device_quotas.get(handler_q)
            # limits are keyed by the handler config's quota names,
            # which match QUALIFIED instance names (memquota looks up
            # instance["name"] — see tests/test_runtime.py convention)
            if pool is None or not pool.knows(inst_q):
                return None   # non-memquota quota handler → host path
            return pool, inst_q
        # no matching active quota rule: grant freely
        return QUOTA_FREE

    @staticmethod
    def quota_build(bag: Bag, check_result, pool, inst_q: str):
        """The instance `inst_q` of a routed quota (quota_route) built
        on `bag` by the snapshot that served the check: (pool, inst_q,
        instance), or the INTERNAL QuotaResult of a build that
        raised."""
        from istio_tpu.expr.oracle import EvalError
        from istio_tpu.models.policy_engine import INTERNAL

        snap = check_result.quota_context.snapshot
        try:
            instance = snap.instances[inst_q].build(bag)
        except EvalError as exc:   # dispatcher.quota parity
            return QuotaResult(granted_amount=0,
                               status_code=INTERNAL,
                               status_message=str(exc))
        except Exception as exc:   # safeDispatch parity
            return QuotaResult(granted_amount=0,
                               status_code=INTERNAL,
                               status_message=f"instance build: "
                                              f"{exc}")
        return pool, inst_q, instance

    # -- in-step quota (gated: ServerArgs.quota_in_step) ---------------

    def instep_quota_target(self) -> tuple | None:
        """(pool, {name → (ridx, inst_q)}) when the CURRENT snapshot is
        in-step eligible: exactly one device pool, and every quota name
        resolving to exactly one quota action on that pool's handler
        whose rule predicate is device-evaluated (host-fallback rules'
        activity is invisible to the device gate). None → callers use
        the classic defer/pool-flush path."""
        if not self.args.quota_in_step:
            return None
        if self._replica_router is not None:
            # the in-step merge compiles ONE check+quota program per
            # pool; a rule set split across banks has no single
            # program to merge into — sharded serving keeps the
            # classic defer path (quota STATE still routes correctly:
            # pools are controller-owned and shared across banks)
            return None
        d = self.controller.dispatcher
        cached = getattr(self, "_instep_cache", None)
        if cached is not None and cached[0] is d.snapshot:
            return cached[1]
        target = self._build_instep_target(d)
        self._instep_cache = (d.snapshot, target)
        return target

    def _build_instep_target(self, d) -> tuple | None:
        plan = d.fused
        pools = self.controller.device_quotas
        if plan is None or not plan.quota_actions or not pools:
            return None
        if plan.apa is not None:
            # the in-step quota program is the engine's step alone: it
            # does not bind the APA's outputs the rows are judged on
            return None
        if len(set(map(id, pools.values()))) != 1:
            return None
        pool = next(iter(pools.values()))
        rs = d.snapshot.ruleset
        # the device alloc gates on the DEVICE status (a denied check
        # must not consume, grpcServer.go:188); host overlay actions
        # or host-fallback predicates could flip the final status
        # after the trip — such snapshots keep the classic path
        n_cfg = len(d.snapshot.rules)
        if plan.host_actions or \
                any(r < n_cfg for r in rs.host_fallback):
            return None
        by_name: dict[str, Any] = {}
        for ridx, handler_q, inst_q, names in plan.quota_actions:
            for name in names:
                by_name.setdefault(name, []).append(
                    (ridx, handler_q, inst_q))
        out: dict[str, tuple] = {}
        for name, cands in by_name.items():
            if len(cands) != 1:
                continue
            ridx, handler_q, inst_q = cands[0]
            if pools.get(handler_q) is not pool \
                    or not pool.knows(inst_q) \
                    or ridx in rs.host_fallback:
                continue
            out[name] = (ridx, inst_q)
        return (pool, out) if out else None

    def check_batch_quota_instep(self, bags: Sequence[Bag],
                                 qrows: Sequence[tuple],
                                 target: tuple):
        """One padded batch with its quota rows allocated IN the check
        trip. `qrows`: [(slot, requested name, QuotaArgs)]; `target`
        from instep_quota_target() (same snapshot). Returns
        (responses, {slot → QuotaResult}). Rows whose instance build
        fails resolve INTERNAL without the trip (quota_fused parity).
        """
        import time as _time

        from istio_tpu.runtime import monitor as _monitor
        from istio_tpu.runtime.batcher import trim_pads

        # quota-carrying batches must feed the e2e histogram + live
        # p99 window like every other serving entry — their stage
        # observations (tensorize below, h2d/device_step in the
        # dispatcher's instep branch) need matching e2e mass. Observed
        # only on SUCCESS: the batcher likewise skips errored batches,
        # so a transient device fault never flips the live p99 / SLO
        # gauges on error-path latency no request was answered with.
        from istio_tpu.runtime import forensics

        t0 = _time.perf_counter()
        forensics.RECORDER.batch_begin()
        out = self._check_batch_quota_instep_inner(bags, qrows, target)
        e2e = _time.perf_counter() - t0
        n_real = len(trim_pads(bags))
        _monitor.observe_check_e2e(e2e, n_real)
        forensics.RECORDER.note_direct(e2e, n_real)
        return out

    def _check_batch_quota_instep_inner(self, bags: Sequence[Bag],
                                        qrows: Sequence[tuple],
                                        target: tuple):
        from istio_tpu.expr.oracle import EvalError
        from istio_tpu.models.policy_engine import INTERNAL

        d = self.controller.dispatcher
        snap = d.snapshot
        pool, by_name = target
        early: dict[int, QuotaResult] = {}
        rows: list[tuple] = []
        rule_idx = np.full(len(bags), -1, np.int32)
        for slot, name, args in qrows:
            ridx, inst_q = by_name[name]
            try:
                instance = snap.instances[inst_q].build(bags[slot])
            except EvalError as exc:
                early[slot] = QuotaResult(granted_amount=0,
                                          status_code=INTERNAL,
                                          status_message=str(exc))
                continue
            except Exception as exc:
                early[slot] = QuotaResult(
                    granted_amount=0, status_code=INTERNAL,
                    status_message=f"instance build: {exc}")
                continue
            rule_idx[slot] = ridx
            rows.append((slot, inst_q, instance, args))
        # tensorize OUTSIDE the counter token: the token covers ONLY
        # stage→dispatch (the successor counters swap in as a device
        # future and the next trip chains on it), so concurrent
        # pumps' host work AND their trips overlap on the transport
        # (measured: a token held across the pull made in-step SLOWER
        # than two serialized trips)
        from istio_tpu.runtime import monitor as _monitor

        with _monitor.stage("tensorize", batch=len(bags)):
            pre = d._tensorize_for_device(bags)
        sess = pool.inline_begin(len(bags), rows,
                                 pool._clock()) if rows else None
        if sess is None:
            if rows:   # pool closed under a config swap: fall back
                for slot, _, _, args in rows:
                    early[slot] = QuotaResult(
                        granted_amount=0, status_code=14,
                        status_message="quota pool closed by config "
                                       "swap")
            return d.check(bags, pre_tensorized=pre), early
        results: dict[int, QuotaResult] = {}

        def on_pull(granted, gate) -> None:
            # fires right after the device pull, inside d.check —
            # commits (in dispatch order) before the per-row response
            # python runs
            results.update(sess.commit(np.asarray(granted),
                                       np.asarray(gate)))

        try:
            q = {"buckets": sess.buckets, "amounts": sess.amounts,
                 "be": sess.be, "mx": sess.mx, "active": sess.active,
                 "ticks": sess.ticks, "lasts": sess.lasts,
                 "rolling": sess.rolling, "rule_idx": rule_idx}
            responses = d.check(
                bags, instep=(q, sess.prev_counts, sess.dispatched,
                              on_pull),
                pre_tensorized=pre)
        except BaseException:
            sess.abort()   # no-op when on_pull already committed
            raise
        results.update(sess.early)
        results.update(early)
        return responses, results

    def shutdown(self, deadline: float | None = 5.0) -> None:
        """Ordered graceful shutdown — the lifecycle plane's runtime
        leg (COMPONENTS.md "Lifecycle & shutdown"; ordering: admission
        → pump → device → flush → join):

          1. stop admission — new checks/reports resolve a typed
             UNAVAILABLE immediately (never a hang, never a drop);
          2. drain the batchers — queued and in-flight batches run to
             completion, bounded by `deadline` seconds (leftovers past
             it still resolve: CheckBatcher.close flushes, the typed
             rejection path covers the rest);
          3. stop the batchers and flush the telemetry plane (final
             rulestats drain; the canary recorder ring is sampling
             state rebuilt from live traffic — dropped by design);
          4. close the controller — reaps prewarm threads, closes
             handlers, and closes the device quota pools (each pool's
             worker flushes pending allocations before exiting).

        Idempotent; close() is shutdown() with the default grace."""
        if getattr(self, "_shutdown_done", False):
            return
        self._shutdown_done = True
        from istio_tpu.runtime import forensics
        from istio_tpu.runtime import monitor as _monitor
        forensics.record_event("shutdown",
                               deadline_s=deadline)
        _monitor.remove_gc_hook()
        # flip every background-warm stop flag FIRST (flag-only, no
        # joins): bank prewarms poll _stopping between shapes, and
        # begin_close() stops the controller admitting new rebuilds
        # (a debounce Timer firing now becomes a no-op) and flips the
        # warm threads' flags so they wind down while the fronts drain
        self._stopping = True
        ctrl = getattr(self, "controller", None)
        if ctrl is not None:
            ctrl.begin_close()
        # stop the audit thread first: a mid-teardown evaluation would
        # read surfaces (batchers, pools) as they are being closed
        if getattr(self, "audit", None) is not None:
            self.audit.stop()
        # a still-running initial in-step prewarm must not race
        # interpreter/pool teardown (its dummy trips touch jax state):
        # flip the stop flag (polled between shapes), then reap.
        # Untimed join — the thread exits after at most the in-flight
        # compile; expiring mid-compile would abort teardown anyway.
        self._instep_prewarm_stop = True
        t = getattr(self, "_instep_prewarm_thread", None)
        if t is not None and t.is_alive():
            t.join()
        self.batcher.quiesce()
        if self._report_batcher is not None:
            self._report_batcher.quiesce()
        self.batcher.drain(deadline)
        if self._report_batcher is not None:
            self._report_batcher.drain(deadline)
        self.batcher.close()
        if self._report_batcher is not None:
            self._report_batcher.close()
            # record conservation at quiescence (the ingestion plane's
            # invariant): every record this process ever accepted must
            # by now be exported or typed-rejected — close() resolves
            # every leftover future. Non-zero in_flight here is a
            # silently-dropped record: log it loudly (counters are
            # process-global, so another still-serving RuntimeServer
            # in this process can legitimately hold records — only a
            # negative/positive residue with no other server is a bug;
            # the smoke gate asserts the exact form per scenario).
            from istio_tpu.runtime import monitor as _monitor
            cons = _monitor.report_conservation()
            if not cons["exact"]:
                import logging
                logging.getLogger("istio_tpu.runtime.server").warning(
                    "report record conservation residue at shutdown: "
                    "%s", cons)
        if self._rulestats_drainer is not None:
            self._rulestats_drainer.close()
            try:   # flush whatever the last interval left on device
                self.rulestats.drain()
            except Exception:
                pass
        # executor AFTER the batchers (no more batches can submit host
        # actions) and BEFORE the controller (handlers close last):
        # in-flight adapter calls get a bounded grace, wedged workers
        # are leaked as daemons — never waited on forever. The
        # conservation ledger must read exact at quiescence.
        if self.executor is not None:
            self.executor.close()
            from istio_tpu.runtime import monitor as _monitor
            hc = _monitor.host_action_counters()
            if not hc["exact"]:
                import logging
                logging.getLogger("istio_tpu.runtime.server").warning(
                    "host action conservation residue at shutdown: "
                    "submitted=%d resolved=%d", hc["submitted"],
                    hc["resolved"])
        self.controller.close()

    def close(self) -> None:
        self.shutdown()
