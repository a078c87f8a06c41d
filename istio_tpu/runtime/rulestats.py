"""Rule-level check telemetry: on-device accumulators, drain, export.

The decision-level observability plane (reference: Mixer's Report path
feeding telemetry adapters — prometheus/statsd/stdio — via
mixer/pkg/api/grpcServer.go:262; here the signal is harvested where it
already lives). PR 1 gave batch-level stage histograms; this module
answers *which rule* fired, denied or errored, per namespace, without
giving back the hot path: the verdict/match tensors are already on
device after every fused check step, so per-rule attribution is one
extra fold into int32 accumulator tensors that LIVE ON DEVICE across
steps (`RuleTelemetry`). A generation-tagged drain pulls deltas on a
snapshot interval — never in the batch critical path (the one
device→host sync sits behind the `# hotpath: sync-ok` pragma in
`drain`, and `scripts/hotpath_lint.py` covers this file's hot
functions) — and hands them to `RuleStatsAggregator`, which maps the
compiler's rule indices back to rule names via the snapshot, feeds the
`utils/metrics` counter families on /metrics, forwards Report-style
metric instances to registered adapter handlers, and serves the
introspect `/debug/rulestats` view (top-K hot rules, never-hit rules,
per-namespace deny rates, decision exemplars linked to RingReporter
traces).

Correctness bar: telemetry is a measurement, not an estimate — drained
counters equal an oracle recount exactly on seeded workloads
(scripts/rulestats_smoke.py, tests/test_rulestats.py). Host-fallback
rules (invisible to the device step) are counted host-side at the
overlay patch point in `Dispatcher._overlay_active`, so the totals
cover every config rule.
"""
from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable, Iterable, Mapping

import numpy as np

from istio_tpu.utils import metrics as hostmetrics
from istio_tpu.utils.log import scope

log = scope("runtime.rulestats")

OK = 0

# names of the adapter-facing Report-style instances a drain emits
INSTANCE_HITS = "rulestats.hits"
INSTANCE_DENIES = "rulestats.denies"
INSTANCE_ERRORS = "rulestats.errors"


def register_families(reg: hostmetrics.Registry) -> dict:
    """Create the rule-telemetry counter families on `reg` and
    pre-touch each with a zero so the exposition carries a zero series
    BEFORE the first drain (a dashboard must distinguish "no rule ever
    fired" from "telemetry missing"). Split out for tests that want a
    private registry."""
    fams = {
        "hits": reg.counter(
            "mixer_rule_check_hits_total",
            "check requests a rule matched (ns-visible), by rule — "
            "drained from the on-device per-rule accumulators"),
        "denies": reg.counter(
            "mixer_rule_check_denies_total",
            "check requests a rule was the winning (lowest-index) "
            "non-OK source for, by rule"),
        "errors": reg.counter(
            "mixer_rule_check_errors_total",
            "check requests whose predicate errored for a rule "
            "(ns-visible), by rule"),
        "drains": reg.counter(
            "mixer_rulestats_drains_total",
            "accumulator drains (device→host delta pulls)"),
        "drain_seconds": reg.histogram(
            "mixer_rulestats_drain_seconds",
            "drain wall time: accumulator swap + async device pull"),
    }
    for key in ("hits", "denies", "errors", "drains"):
        fams[key].inc(0.0)
    return fams


FAMILIES = register_families(hostmetrics.default_registry)


def preview_attributes(bag, limit: int = 16,
                       value_len: int = 128) -> dict:
    """Bounded attribute preview of one sampled request — THE exemplar
    rendering contract shared by /debug/rulestats and /debug/canary
    (istio_tpu/canary/differ.py): first `limit` attributes, reprs
    truncated to `value_len`, decode failures sentineled."""
    attrs: dict = {}
    try:
        for name in list(bag.names())[:limit]:
            v, ok = bag.get(name)
            if ok:
                attrs[str(name)] = repr(v)[:value_len]
    except Exception:
        attrs = {"<decode-failed>": "1"}
    return attrs


class RuleTelemetry:
    """Per-snapshot on-device rule accumulators.

    State (int32, resident on device across steps):
      hit  [S, n_rows] — requests the rule matched, per namespace slot
      deny [S, n_rows] — requests the rule won the deny for, per slot
      err  [n_rows]    — ns-visible predicate errors
    where S = len(ns_ids) + 1; the extra slot collects requests whose
    namespace is unknown to the snapshot (namespace_id() == -1).

    Two ways onto the accumulators, both dispatch only (no
    host↔device sync), both chained under one lock. `chain()` hands
    the accumulator handles to a program that computes `delta` itself
    and returns the folded accumulators: FusedPlan.packed_check's one
    program a batch. `observe()` is the same arithmetic as two programs
    of its own (jitted delta, jitted fold) behind a verdict some other
    program produced: the in-step quota and mesh paths. Padding rows
    are masked out by `real` so bucket padding never pollutes the
    counts.
    Host-fallback rules read matched=False on device; their hits and
    errors arrive through `add_host()` at the dispatcher's overlay
    patch point, into host-side numpy planes merged at drain.

    `drain()` swaps the resident zero accumulators in under the lock
    (no alloc, no sync) and pulls the OLD buffers outside it — the
    only device→host copy, generation-tagged, never on the batch
    critical path."""

    # device programs one observe() launches (_delta_fn, _fold_fn)
    OBSERVE_PROGRAMS = 2

    def __init__(self, ruleset, n_cfg: int, exemplars_per_rule: int = 4,
                 seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.n_rows = int(ruleset.rule_ns.shape[0])
        self.n_cfg = int(n_cfg)
        self.n_slots = len(ruleset.ns_ids) + 1
        self._default_ns = ruleset.ns_ids[""]
        rule_ns = np.asarray(ruleset.rule_ns, np.int32)
        # host-fallback rows read err=True on device by construction
        # (RuleSetProgram contract) — mask them out of the device err
        # fold; their real errors arrive via add_host()
        err_rows = np.ones(self.n_rows, bool)
        for ridx in ruleset.host_fallback:
            if ridx < self.n_rows:
                err_rows[ridx] = False
        self._lock = threading.Lock()
        self.generation = 0
        zeros2 = jnp.zeros((self.n_slots, self.n_rows), jnp.int32)
        # resident all-zero accumulators (device arrays are immutable):
        # what a drain swaps in, and what a program runs on when its
        # batch counts nothing (prewarm, Report traffic: no lock)
        self.zeros = (zeros2, zeros2, jnp.zeros(self.n_rows, jnp.int32))
        self._acc_hit, self._acc_deny, self._acc_err = self.zeros
        # host-side planes for host-fallback rules (overlay patch)
        self._host_hit = np.zeros((self.n_slots, self.n_rows), np.int64)
        self._host_err = np.zeros(self.n_rows, np.int64)
        # decision exemplars: per-rule reservoirs of denied/errored
        # requests (bag ref + trace/span ids), sampled host-side
        self._ex_cap = exemplars_per_rule
        self._ex: dict[int, list] = {}
        self._ex_seen: dict[int, int] = {}
        self._rng = random.Random(seed)
        # pure: (matched, err, status, deny_rule, req_ns, real) ->
        # (hit, deny, err) deltas; traced into whichever program folds
        self.delta = self._make_delta(
            rule_ns, self._default_ns, self.n_slots, err_rows)
        self._delta_fn = jax.jit(self.delta)
        self._fold_fn = jax.jit(
            lambda h, d, e, dh, dd, de: (h + dh, d + dd, e + de))

    @staticmethod
    def _make_delta(rule_ns: np.ndarray, default_ns: int, n_slots: int,
                    err_rows: np.ndarray):
        import jax
        import jax.numpy as jnp
        from jax import lax

        rns = jnp.asarray(rule_ns)
        err_rows_j = jnp.asarray(err_rows)
        n_rows = rule_ns.shape[0]
        dims = (((0,), (0,)), ((), ()))

        def delta(matched, err, status, deny_rule, req_ns, real):
            ns_ok = (rns[None, :] == default_ns) | \
                    (rns[None, :] == req_ns[:, None])
            active = matched & ns_ok & real[:, None]
            slot = jnp.where(req_ns < 0, n_slots - 1,
                             jnp.clip(req_ns, 0, n_slots - 1))
            onehot = (slot[:, None] ==
                      jnp.arange(n_slots)[None, :]).astype(jnp.int8)
            hit = lax.dot_general(onehot, active.astype(jnp.int8),
                                  dims,
                                  preferred_element_type=jnp.int32)
            deny_mask = (deny_rule[:, None] ==
                         jnp.arange(n_rows)[None, :]) & \
                        (status != OK)[:, None] & real[:, None]
            deny = lax.dot_general(onehot,
                                   deny_mask.astype(jnp.int8), dims,
                                   preferred_element_type=jnp.int32)
            err_d = jnp.sum((err & ns_ok & real[:, None] &
                             err_rows_j[None, :]).astype(jnp.int32),
                            axis=0)
            return hit, deny, err_d

        # metadata only: the profiler's device plane names the scope
        return jax.named_scope("rulestats")(delta)

    # ------------------------------------------------------------------
    # hot path (scripts/hotpath_lint.py HOT_SECTIONS cover these)
    # ------------------------------------------------------------------

    def observe(self, verdict, req_ns, real_mask) -> None:
        """Fold one check batch's per-rule counts into the device
        accumulators. `req_ns`/`real_mask` are host numpy ([B] int32 /
        bool); everything else stays on device — dispatch only, the
        fold chains onto the accumulator buffers and the drain thread
        pays the sync later."""
        deltas = self._delta_fn(verdict.matched, verdict.err,
                                verdict.status, verdict.deny_rule,
                                req_ns, real_mask)
        # the lock serializes the read-modify-write of the accumulator
        # HANDLES only (async dispatch, never a sync): concurrent
        # pipeline workers must chain their folds, not race them
        with self._lock:
            self._acc_hit, self._acc_deny, self._acc_err = \
                self._fold_fn(self._acc_hit, self._acc_deny,
                              self._acc_err, *deltas)

    def chain(self, launch):
        """Run `launch(acc_hit, acc_deny, acc_err) -> (out, (acc_hit',
        acc_deny', acc_err'))` on the current accumulator handles and
        keep what it hands back; returns `out`. `launch` dispatches the
        program that folds one batch (async, never a sync) and the lock
        is held across it: handles are read, passed and replaced as one
        step, so concurrent pumps chain their folds, none is lost or
        doubled, and a drain() swap falls before or after a batch,
        never inside it. A `launch` that raises leaves the handles as
        they were. The lock is no place to compile: a caller whose
        program may not be compiled yet runs it on `zeros` first."""
        with self._lock:
            out, (self._acc_hit, self._acc_deny, self._acc_err) = launch(
                self._acc_hit, self._acc_deny, self._acc_err)
        return out

    def add_host(self, cols, active_cols: np.ndarray,
                 err_counts: Mapping[int, int],
                 ns_slots: np.ndarray) -> None:
        """Host-side counts for host-fallback rules, from the overlay
        patch point (Dispatcher._overlay_active): `cols` rule indices,
        `active_cols` bool [B, len(cols)] (already ns-masked, padding
        already trimmed), `ns_slots` int [B] namespace slots,
        `err_counts` rule idx → predicate errors this batch. Pure
        numpy on host arrays — no device work."""
        with self._lock:
            for j, ridx in enumerate(cols):
                col = active_cols[:, j]
                if col.any():
                    np.add.at(self._host_hit[:, ridx], ns_slots[col], 1)
            for ridx, n in err_counts.items():
                self._host_err[ridx] += n

    def sample_rows(self, rows, ridxs, statuses, bags, span) -> None:
        """Reservoir-sample one batch's denied/errored requests, row
        `rows[i]` of `bags` decided by rule `ridxs[i]`, under one
        lock: keep the bag (compressed attribute bag — decoded at
        drain, never here) and the active trace span ids so the
        exemplar links straight to a RingReporter trace. Every row is
        drawn for, a uniform reservoir a rule; an entry is built, and
        the row's bag asked for, only for a row it keeps."""
        trace_id = span.get("traceId") if span else None
        span_id = span.get("id") if span else None
        now = time.time()
        with self._lock:
            for row, ridx, status in zip(rows, ridxs, statuses):
                seen = self._ex_seen.get(ridx, 0) + 1
                self._ex_seen[ridx] = seen
                bucket = self._ex.setdefault(ridx, [])
                j = len(bucket)
                if j < self._ex_cap:
                    bucket.append(None)
                else:
                    j = self._rng.randrange(seen)
                    if j >= self._ex_cap:
                        continue
                bucket[j] = {"status": status, "bag": bags[row],
                             "trace_id": trace_id, "span_id": span_id,
                             "t": now}

    def ns_slots(self, ns_ids: np.ndarray) -> np.ndarray:
        """Request ns ids → accumulator slots (unknown/-1 → last)."""
        return np.where(ns_ids < 0, self.n_slots - 1, ns_ids)

    # ------------------------------------------------------------------
    # drain boundary (the ONE deliberate device→host sync)
    # ------------------------------------------------------------------

    def drain(self) -> dict:
        """Swap zero accumulators in (no sync) and pull the old
        buffers — generation-tagged deltas since the previous drain.
        Exemplars are a sample, not a counter: returned as the current
        reservoirs (bags still encoded), not reset."""
        t0 = time.perf_counter()
        with self._lock:
            hit, deny, err = self._acc_hit, self._acc_deny, self._acc_err
            self._acc_hit, self._acc_deny, self._acc_err = self.zeros
            host_hit, self._host_hit = self._host_hit, np.zeros(
                (self.n_slots, self.n_rows), np.int64)
            host_err, self._host_err = self._host_err, np.zeros(
                self.n_rows, np.int64)
            self.generation += 1
            gen = self.generation
            exemplars = {r: list(v) for r, v in self._ex.items()}
            ex_seen = dict(self._ex_seen)
        # the drain pull: blocks THIS thread until every fold chained
        # before the swap has landed — the batch critical path already
        # moved on to the fresh buffers
        hit_np = np.asarray(hit).astype(np.int64)    # hotpath: sync-ok (drain boundary)
        deny_np = np.asarray(deny).astype(np.int64)  # hotpath: sync-ok (drain boundary)
        err_np = np.asarray(err).astype(np.int64)    # hotpath: sync-ok (drain boundary)
        hit_np += host_hit
        err_np += host_err
        wall = time.perf_counter() - t0
        return {"generation": gen, "hit": hit_np, "deny": deny_np,
                "err": err_np, "exemplars": exemplars,
                "exemplars_seen": ex_seen, "wall_s": wall}


class RuleStatsAggregator:
    """Name-keyed aggregation over drained deltas + export fan-out.

    One aggregator per RuntimeServer. `attach(dispatcher)` follows
    config swaps: the outgoing plan is drained first (no counts lost),
    then rule-index→name mapping rebinds to the new snapshot.
    Cumulative counts are keyed by qualified rule name so they survive
    revisions; `never_hit` is judged against the CURRENT snapshot's
    rules."""

    def __init__(self, top_k: int = 10, metrics: dict | None = None):
        self._lock = threading.Lock()
        self.top_k = top_k
        self._metrics = metrics if metrics is not None else FAMILIES
        self._plan = None
        self._names: list[str] = []
        self._slot_names: list[str] = []
        self.revision: int | None = None
        self.last_generation = 0
        self.drains = 0
        self.last_drain_wall_s = 0.0
        # rule name → {"hits", "denies", "errors", "ns": {ns: {...}}}
        self._cum: dict[str, dict] = {}
        self._exemplars: dict[str, list] = {}
        self._exporters: list[tuple[Any, str]] = []
        # swapped-out plans still being swept: (plan, their names,
        # drop-after timestamp) — see attach()
        self._retired: list[tuple] = []
        # sharded serving lanes (istio_tpu/sharding): bank dispatchers'
        # plans swept on every drain alongside the main plan — their
        # per-rule counts merge into the same name-keyed cumulative
        # stats (bank rule names ARE the global qualified names). See
        # attach_lanes(). Entries: (plan, names, slot_names).
        self._lanes: list[tuple] = []

    # -- wiring --

    # how long a swapped-out plan's telemetry keeps being swept by
    # subsequent drains: batches in flight on the OLD dispatcher may
    # still fold into it after the rebind (mirrors the controller's
    # orphan-handler drain grace)
    RETIRE_SWEEP_S = 3.0

    def attach(self, dispatcher) -> None:
        """Bind to a freshly published dispatcher. The OLD plan is
        drained immediately AND retired for continued sweeping: a
        batch already in flight on the old dispatcher can fold into
        the old accumulators after this rebind, so drain() keeps
        pulling retired telemetries for RETIRE_SWEEP_S before letting
        them go — a config swap never drops counts."""
        self.drain()
        snap = dispatcher.snapshot
        rs = snap.ruleset
        plan = dispatcher.fused
        with self._lock:
            old = self._plan
            if old is not None and old is not plan:
                self._retired.append(
                    (old, self._names, self._slot_names,
                     time.time() + self.RETIRE_SWEEP_S))
            has_tele = plan is not None and \
                getattr(plan, "telemetry", None) is not None
            self._plan = plan if has_tele else None
            # index→name mapping shared with the canary differ
            # (runtime/config.Snapshot.qualified_rule_names); test
            # doubles may hand bare rule lists without the method
            qn = getattr(snap, "qualified_rule_names", None)
            self._names = list(qn()) if qn is not None else [
                f"{r.namespace}/{r.name}"
                if getattr(r, "namespace", "") else r.name
                for r in snap.rules]
            by_id = {v: k for k, v in rs.ns_ids.items()}
            n_slots = len(rs.ns_ids) + 1
            self._slot_names = [
                by_id.get(i, f"ns#{i}") or "(default)"
                for i in range(n_slots - 1)] + ["(unknown)"]
            self.revision = snap.revision
            for name in self._names:
                self._cum.setdefault(
                    name, {"hits": 0, "denies": 0, "errors": 0,
                           "ns": {}})

    def attach_lanes(self, dispatchers) -> None:
        """Bind the sharded plane's bank dispatchers as additional
        drain sources (config swaps call this right after the lane
        publish). The PREVIOUS lane set is retired for continued
        sweeping exactly like attach()'s old plan — a batch in flight
        on an old bank can fold after the rebind, and a swap must
        never drop counts. The main attached plan is skipped if it
        also appears as a lane (replica-only mode's lane 0 rides the
        published dispatcher)."""
        lanes: list[tuple] = []
        seen: set[int] = set()
        with self._lock:
            main = self._plan
        for d in dispatchers:
            plan = getattr(d, "fused", None)
            if plan is None or plan is main or id(plan) in seen:
                continue
            if getattr(plan, "telemetry", None) is None:
                continue
            seen.add(id(plan))
            snap = d.snapshot
            qn = getattr(snap, "qualified_rule_names", None)
            names = list(qn()) if qn is not None else []
            rs = snap.ruleset
            by_id = {v: k for k, v in rs.ns_ids.items()}
            n_slots = len(rs.ns_ids) + 1
            slot_names = [by_id.get(i, f"ns#{i}") or "(default)"
                          for i in range(n_slots - 1)] + ["(unknown)"]
            lanes.append((plan, names, slot_names))
        with self._lock:
            for _plan, names, _slots in lanes:
                for name in names:
                    self._cum.setdefault(
                        name, {"hits": 0, "denies": 0, "errors": 0,
                               "ns": {}})
            old = self._lanes
            self._lanes = lanes
            deadline = time.time() + self.RETIRE_SWEEP_S
            live = {id(p) for p, _, _ in lanes}
            for plan, names, slots in old:
                if id(plan) not in live:
                    self._retired.append((plan, names, slots,
                                          deadline))

    def add_exporter(self, handler, template: str = "metric") -> None:
        """Register an adapter handler (prometheus/statsd/stdio/...)
        to receive Report-style metric instances on every drain."""
        with self._lock:
            self._exporters.append((handler, template))

    def reset(self) -> None:
        with self._lock:
            self._cum.clear()
            self._exemplars.clear()
            self.drains = 0
            self.last_generation = 0

    # -- drain + fold --

    def drain(self) -> dict | None:
        """Pull deltas from the attached plan's device accumulators,
        fold into the name-keyed cumulative stats, bump the /metrics
        counter families, and fan instances out to exporters. Retired
        plans (config swaps) are swept first — batches that were in
        flight across the swap fold late into the OLD accumulators.
        Returns the live plan's raw drain dict (None when no telemetry
        is attached). Called by the RuntimeServer's drain thread on
        its snapshot interval and on demand by /debug/rulestats —
        never by the serving path."""
        with self._lock:
            plan = self._plan
            names = self._names
            slot_names = self._slot_names
            now = time.time()
            retired = list(self._retired)
            self._retired = [r for r in self._retired if r[3] > now]
            lanes = list(self._lanes)
        instances: list[dict] = []
        for rplan, rnames, rslots, _deadline in retired:
            rtele = getattr(rplan, "telemetry", None)
            if rtele is None:
                continue
            try:
                instances += self._fold(rtele.drain(), rnames, rslots)
            except Exception:
                log.exception("retired-plan drain failed")
        tele = getattr(plan, "telemetry", None) if plan is not None \
            else None
        d = None
        if tele is not None:
            d = tele.drain()
            self._metrics["drains"].inc()
            self._metrics["drain_seconds"].observe(d["wall_s"])
            instances += self._fold(d, names, slot_names)
            with self._lock:
                self.last_generation = d["generation"]
                self.drains += 1
                self.last_drain_wall_s = d["wall_s"]
        # sharded serving lanes: every bank's accumulators drain into
        # the same name-keyed stats (bank names are global qualified
        # names, so counts from different banks never collide — each
        # rule lives in exactly one bank per generation, global rules
        # in every bank but each request served by exactly one)
        for lplan, lnames, lslots in lanes:
            ltele = getattr(lplan, "telemetry", None)
            if ltele is None:
                continue
            try:
                instances += self._fold(ltele.drain(), lnames, lslots)
            except Exception:
                log.exception("lane-plan drain failed")
        if d is None and not retired and not lanes:
            return None
        with self._lock:
            exporters = list(self._exporters)
        if instances:
            for handler, template in exporters:
                try:
                    handler.handle_report(template, instances)
                except Exception:
                    log.exception("rulestats exporter failed")
        return d

    def _fold(self, d: dict, names: list[str],
              slot_names: list[str]) -> list[dict]:
        """Fold one drain's deltas into the cumulative stats + counter
        families; returns the Report-style instances for exporters."""
        hit, deny, err = d["hit"], d["deny"], d["err"]
        n_cfg = min(len(names), hit.shape[1])
        hit_r = hit[:, :n_cfg].sum(axis=0)
        deny_r = deny[:, :n_cfg].sum(axis=0)
        instances: list[dict] = []
        with self._lock:
            for r in range(n_cfg):
                h, dn, e = int(hit_r[r]), int(deny_r[r]), int(err[r])
                if not (h or dn or e):
                    continue
                name = names[r]
                cum = self._cum.setdefault(
                    name, {"hits": 0, "denies": 0, "errors": 0,
                           "ns": {}})
                cum["hits"] += h
                cum["denies"] += dn
                cum["errors"] += e
                if h:
                    self._metrics["hits"].inc(h, rule=name)
                if dn:
                    self._metrics["denies"].inc(dn, rule=name)
                if e:
                    self._metrics["errors"].inc(e, rule=name)
                    instances.append({
                        "name": INSTANCE_ERRORS, "value": e,
                        "dimensions": {"rule": name}})
                for s in np.nonzero(hit[:, r] | deny[:, r])[0]:
                    ns = slot_names[s] if s < len(slot_names) \
                        else f"slot#{s}"
                    per = cum["ns"].setdefault(
                        ns, {"hits": 0, "denies": 0})
                    hs, ds = int(hit[s, r]), int(deny[s, r])
                    per["hits"] += hs
                    per["denies"] += ds
                    if hs:
                        instances.append({
                            "name": INSTANCE_HITS, "value": hs,
                            "dimensions": {"rule": name,
                                           "namespace": ns}})
                    if ds:
                        instances.append({
                            "name": INSTANCE_DENIES, "value": ds,
                            "dimensions": {"rule": name,
                                           "namespace": ns}})
            for ridx, entries in d["exemplars"].items():
                if ridx >= n_cfg:
                    continue
                self._exemplars[names[ridx]] = [
                    self._render_exemplar(e) for e in entries]
        return instances

    @staticmethod
    def _render_exemplar(e: dict) -> dict:
        """Decode a sampled request off the hot path: the compressed
        attribute bag renders to a bounded attribute preview, the
        trace/span ids pass through for /debug/traces joins."""
        return {"status": e["status"],
                "attributes": preview_attributes(e.get("bag")),
                "trace_id": e.get("trace_id"),
                "span_id": e.get("span_id"), "t": e.get("t")}

    # -- views --

    def snapshot(self, top_k: int | None = None,
                 shadowed: Iterable[str] = ()) -> dict:
        """JSON-able /debug/rulestats payload. `shadowed`: BARE rule
        names the static analyzer flagged shadowed (PR 3 findings
        carry unqualified names) — cross-checked against the never-hit
        list so a dead rule shows whether it is provably dead
        (analyzer agrees) or merely unexercised. A never-hit rule is
        flagged only when its bare name is BOTH in the set and unique
        among the current snapshot's rules: an ambiguous bare name
        (same rule name in two namespaces) must never mark a live rule
        provably dead."""
        k = top_k or self.top_k
        shadowed = set(shadowed)
        with self._lock:
            current = list(self._names)
            cum = {n: dict(v, ns={ns: dict(p)
                                  for ns, p in v["ns"].items()})
                   for n, v in self._cum.items()}
            exemplars = {n: list(v) for n, v in self._exemplars.items()}
            payload = {
                "revision": self.revision,
                "generation": self.last_generation,
                "drains": self.drains,
                "last_drain_wall_ms": round(
                    self.last_drain_wall_s * 1e3, 3),
                "rules_tracked": len(current),
            }
        ranked = sorted(
            (n for n in cum if cum[n]["hits"] or cum[n]["denies"]
             or cum[n]["errors"]),
            key=lambda n: (-cum[n]["hits"], -cum[n]["denies"], n))
        top = []
        for n in ranked[:k]:
            c = cum[n]
            deny_rate_by_ns = {
                ns: round(p["denies"] / p["hits"], 4)
                for ns, p in c["ns"].items() if p["hits"]}
            top.append({
                "rule": n, "hits": c["hits"], "denies": c["denies"],
                "errors": c["errors"],
                "deny_rate": round(c["denies"] / c["hits"], 4)
                if c["hits"] else 0.0,
                "deny_rate_by_namespace": deny_rate_by_ns,
                "by_namespace": c["ns"],
                "exemplars": exemplars.get(n, []),
            })
        never = [n for n in current
                 if not cum.get(n, {}).get("hits")]
        bare_counts: dict[str, int] = {}
        for n in current:
            bare = n.rsplit("/", 1)[-1]
            bare_counts[bare] = bare_counts.get(bare, 0) + 1
        payload["top"] = top
        never_hit = []
        for n in never:
            bare = n.rsplit("/", 1)[-1]
            never_hit.append({
                "rule": n,
                "analyzer_shadowed": bare in shadowed
                and bare_counts.get(bare) == 1})
        payload["never_hit"] = never_hit
        payload["never_hit_count"] = len(never)
        payload["exemplar_rules"] = sorted(exemplars)
        return payload

    def counts(self) -> dict:
        """{rule name: {hits, denies, errors, ns}} copy (tests, smoke
        recount comparisons)."""
        with self._lock:
            return {n: dict(v, ns={ns: dict(p)
                                   for ns, p in v["ns"].items()})
                    for n, v in self._cum.items()}


class RuleStatsDrainer:
    """Background snapshot-interval drain loop (the adapter-driven
    drain cadence). Owned by RuntimeServer; close() stops it."""

    def __init__(self, aggregator: RuleStatsAggregator,
                 interval_s: float = 0.5):
        self.aggregator = aggregator
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="rulestats-drain")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.aggregator.drain()
            except Exception:
                log.exception("rulestats drain failed")

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
