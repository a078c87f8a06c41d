"""Dispatcher — batched resolve + template/adapter fan-out.

Reference: mixer/pkg/runtime/dispatcher.go + resolver.go. Differences
by design (SURVEY.md §7 layer 4):

  * Resolution is BATCHED: one device ruleset evaluation matches a
    whole batch of requests against every rule (resolver.go's
    per-request per-rule IL loop collapses into the RuleSetProgram);
    host-fallback rules are overlaid per request.
  * Namespace targeting follows resolver.go:180 destAndNamespace — the
    identity attribute `destination.service` (svc.ns.suffix…) selects
    the rule namespace; default-namespace rules always apply.
  * The SERVING path is the fused device engine
    (models/policy_engine wired via runtime/fused): check verdicts,
    list/deny/rbac statuses, referenced bitmaps and report/quota
    activity bits come off one packed device step; only host-overlay
    actions (unfusable adapters) and host-fallback predicates run
    python per request. The generic path below (fused=None) keeps
    instance construction + adapter calls fully host-side and is the
    behavioral oracle. combineResults semantics preserved on both:
    lowest-rule-index non-OK status wins, TTLs take the min
    (dispatcher.go:322).
  * Adapter calls are panic-isolated (safeDispatch dispatcher.go:399):
    an adapter exception degrades that action to INTERNAL, never kills
    the request.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import time
from typing import Any, Mapping, Sequence

import numpy as np

from istio_tpu.adapters.sdk import (CheckResult, Handler, QuotaArgs,
                                    QuotaResult)
from istio_tpu.attribute.bag import Bag
from istio_tpu.expr.oracle import EvalError
from istio_tpu.models.policy_engine import INTERNAL, OK
from istio_tpu.runtime.apa import GeneratedBag, PodRows
from istio_tpu.runtime.config import Snapshot
from istio_tpu.runtime import monitor
from istio_tpu.templates import Variety

log = logging.getLogger("istio_tpu.runtime.dispatcher")

DEFAULT_IDENTITY_ATTR = "destination.service"


@dataclasses.dataclass
class CheckResponse:
    """Precondition result (CheckResponse.PreconditionResult).

    SHARED, DO NOT MUTATE: the fused path builds one response a verdict
    class and hands the same object to every row of the class (and
    `referenced` / `referenced_presence` are shared wider still, by
    signature). A caller that must change a field changes a copy
    (`dataclasses.replace`)."""
    status_code: int = OK
    status_message: str = ""
    valid_duration_s: float = 5.0
    valid_use_count: int = 10_000
    referenced: tuple = ()
    # item → was-present, filled by the fused path from device planes so
    # ReferencedAttributes needs no host-side bag decode (None → the
    # gRPC layer falls back to bag lookups)
    referenced_presence: dict | None = None
    # QUOTA-variety rules active for this request (fused path only) —
    # lets the served quota loop skip the per-quota re-resolve
    # (runtime/device_quota.py). None → caller must resolve.
    active_quota_rules: tuple | None = None
    # the dispatcher that produced those indices: rule indices are
    # positional within ONE snapshot, so the quota loop must read the
    # same plan even if a config swap republished mid-request
    quota_context: Any = None
    # DEVICE deny attribution: the lowest-index rule whose fused check
    # action produced the non-OK device status (-1 when the device
    # answered OK — host-overlay adapters may still set a non-OK
    # status, which stays unattributed here). The canary recorder and
    # shadow replay (istio_tpu/canary) key their per-rule diff on it.
    deny_rule: int = -1


class ClassedResponses(list):
    """A batch's CheckResponses, one a real row, as every caller
    indexes them, with the verdict classes the respond stage built
    them by: `classes[class_of[b]] is self[b]`. Rows equal in all that
    decides a response (status, TTL, use count, denying rule,
    referenced/presence signature with the active quota rules, grant)
    share one object; a row under a host action is a class of one. A
    front serialises and frames once a class instead of once a row."""
    classes: list            # the distinct CheckResponse objects
    class_of: np.ndarray     # int [n rows] → index into `classes`
    # the batch's runtime/apa.PodRows where the step holds the APA and
    # a quota instance reads what it binds (RuntimeServer.quota_bags)
    apa_pods: Any = None
    # the batch's runtime/quota_keys.KeyColumns where its plan keys a
    # quota instance by the batch's ids and a row asks for a quota
    quota_keys: Any = None


# A batch of fewer real rows than this builds its responses a row: the
# class keys (a min/max a plane and one np.unique) cost more than the
# loop they would save. Stage `respond` on the chip machine's host,
# ~6 classes a batch (PERF.md §6, PR 34): 32 rows 0.18-0.20 ms a class
# against 0.17-0.18 a row, 48 rows 0.19 against 0.24-0.26, 64 rows 0.21
# against 0.30, 1 363 rows 0.52-0.58 against 5.1-5.3. Read at few
# classes a batch: a class costs its build, so many classes in a batch
# this small would move the break-even up (no cell sends one).
RESPOND_CLASS_MIN_ROWS = 48


def _namespace_of(bag: Bag, identity_attr: str) -> str:
    """destAndNamespace (resolver.go:180): svc.ns.svc.cluster.local →
    'ns'; bare or absent destination → default namespace ''."""
    v, ok = bag.get(identity_attr)
    return namespace_of_value(v) if ok else ""


def namespace_of_value(v: Any) -> str:
    """_namespace_of for an identity value already read."""
    if not isinstance(v, str):
        return ""
    parts = v.split(".")
    return parts[1] if len(parts) >= 2 and parts[1] else ""


class Dispatcher:
    """Stateless over an immutable snapshot + built handler map; the
    controller swaps (snapshot, handlers) pairs atomically."""

    def __init__(self, snapshot: Snapshot, handlers: Mapping[str, Handler],
                 identity_attr: str = DEFAULT_IDENTITY_ATTR,
                 fused=None,
                 buckets: tuple[int, ...] = (),
                 recorder=None,
                 observe: bool = True,
                 executor=None,
                 grants=None,
                 overlap_h2d: bool = False):
        self.snapshot = snapshot
        self.handlers = dict(handlers)
        self.identity_attr = identity_attr
        # GrantPolicy (runtime/grants.py): when present, every check
        # response's valid_duration/valid_use_count is min-folded with
        # the namespace's volatility-derived grant at the respond
        # stage — the server-issued check-cache grant leg
        self.grants = grants
        self._ns_name_of: dict | None = None   # lazy rs.ns_ids inverse
        # begin the str_bytes h2d right after the C++ wire decode
        # (async device_put of the tier-narrowed plane from the pinned
        # staging buffers) so the dominant transfer overlaps the
        # host-side namespace extraction instead of serializing inside
        # the jitted call
        self.overlap_h2d = bool(overlap_h2d)
        # FusedPlan (runtime/fused.py) — when present, check() runs the
        # fused device engine and overlays only host-only actions
        self.fused = fused
        # AdapterExecutor (runtime/executor.py) — when present, the
        # fused path's host-overlay CHECK actions and quota() adapter
        # calls run on per-handler bulkhead lanes, deadline-bounded,
        # instead of inline on this thread. None (the generic path,
        # shadow replay, direct test construction) keeps the inline
        # safeDispatch loop — the behavioral oracle.
        self.executor = executor
        # canary TrafficRecorder (istio_tpu/canary/recorder.py): when
        # present, check batches tap their served decisions into the
        # sampling ring at this boundary — the same verdicts callers
        # receive, so a recorded decision is replayable evidence
        self.recorder = recorder
        # False = shadow-replay mode (istio_tpu/canary/replay.py): no
        # stage histograms, no e2e/live-p99 feeds, no rule-telemetry
        # folds, no chaos seam, no recorder tap — a canary replay must
        # not pollute the serving metrics it is judged against
        self.observe = observe
        # prewarmed serving batch shapes: device work OUTSIDE the
        # batcher (the fused report resolve) pads to these so arbitrary
        # arrival counts never compile in-band
        self.buckets = tuple(sorted(buckets))
        # any ATTRIBUTE_GENERATOR action configured? (when False the
        # server skips the per-request preprocess resolve entirely)
        self.has_apa = any(
            snapshot.actions_for(r, Variety.ATTRIBUTE_GENERATOR)
            for r in range(len(snapshot.rules)))
        self._apa: tuple | None = None      # _apa_actions' memo
        self._apa_ident: tuple | None = None  # _apa_identity's memo

    @property
    def apa_in_step(self) -> bool:
        """The fused step binds the APA's outputs itself: a Check's
        bag goes to it as the request sent it."""
        return self.fused is not None and self.fused.apa is not None

    def _handler_for(self, hc) -> Handler | None:
        """Built handler for a HandlerConfig (single home of the
        namespace-qualification rule, see config._qualify)."""
        from istio_tpu.runtime.config import _qualify
        return self.handlers.get(_qualify(hc.name, hc.namespace))

    # ------------------------------------------------------------------
    # resolution
    # ------------------------------------------------------------------

    def _grants_for_rows(self, ns_ids) -> tuple | None:
        """(grants, inverse): the (ttl_s, use_count) of each DISTINCT
        namespace in the batch and each row's index into them — one
        policy round per distinct namespace (uniform traffic: one or
        two lock acquisitions per batch). None when grants are off."""
        if self.grants is None:
            return None
        inv = self._ns_name_of
        if inv is None:
            inv = {v: k for k, v in
                   self.snapshot.ruleset.ns_ids.items()}
            self._ns_name_of = inv
        # ns_ids is the host-side id list built at tensorize time —
        # never a device buffer, so this asarray copies host memory
        uniq, inverse = np.unique(np.asarray(ns_ids),  # hotpath: sync-ok host id list
                                  return_inverse=True)
        return self.grants.grants_for(
            [inv.get(int(u), "") for u in uniq]), inverse

    def _apply_grants(self, bags: Sequence[Bag], responses) -> None:
        """Generic/oracle-path grant fold (per-bag namespace lookup —
        these paths are host-bound anyway). min() like every other
        TTL source: a grant only shortens a cache budget."""
        if self.grants is None:
            return
        for bag, resp in zip(bags, responses):
            ttl, uses = self.grants.grant(
                _namespace_of(bag, self.identity_attr))
            resp.valid_duration_s = min(resp.valid_duration_s, ttl)
            resp.valid_use_count = min(resp.valid_use_count, uses)

    def _request_ns_ids(self, bags: Sequence[Bag]) -> np.ndarray:
        return np.asarray([self.snapshot.ruleset.namespace_id(
            _namespace_of(bag, self.identity_attr)) for bag in bags],
            np.int32)

    def _ns_ids_from_batch(self, batch) -> np.ndarray:
        """destAndNamespace from the tensorized identity-attr column —
        the wire path extracts namespaces without decoding the bags."""
        rs = self.snapshot.ruleset
        slot = rs.layout.slots.get(self.identity_attr)
        n = batch.ids.shape[0]
        if slot is None:
            return np.zeros(n, np.int32)
        # hotpath: sync-ok — tensorizer output is host numpy
        ids = np.asarray(batch.ids[:, slot])      # hotpath: sync-ok
        present = np.asarray(batch.present[:, slot])  # hotpath: sync-ok
        interner = rs.interner
        out = np.zeros(n, np.int32)
        # vectorized over DISTINCT service ids — per-row python here
        # was O(B) work per batch on the batcher's only thread
        uniq, inverse = np.unique(ids, return_inverse=True)
        ns_of = np.zeros(uniq.shape[0], np.int32)
        for u, vid in enumerate(uniq):
            v = batch.value_of(int(vid), interner)
            parts = v.split(".") if isinstance(v, str) else []
            ns = parts[1] if len(parts) >= 2 and parts[1] else ""
            ns_of[u] = rs.namespace_id(ns)
        out = np.where(present, ns_of[inverse], 0).astype(np.int32)
        return out

    def _tensorize_for_device(self, bags: Sequence[Bag]):
        """(batch, ns_ids) via the C++ wire decoder when every bag
        carries wire bytes, else the python tensorizer. Where the step
        holds the APA, the batch is stamped with each row's pods
        (runtime/apa.ApaStep.stamp) and the namespaces are those its
        bound identity gives."""
        plan = self.fused
        # a taken batch (api/take.TakenRows) hands its rows over where
        # they lie in the pump's buffer; other fronts hold bytes a bag
        decode = None
        if plan.native is not None:
            spans = bags.wire_spans() \
                if hasattr(bags, "wire_spans") else None
            if spans is not None:
                decode = functools.partial(plan.native.tensorize_spans,
                                           *spans)
            else:
                wires = [getattr(bag, "wire", None) for bag in bags]
                if all(w is not None for w in wires):
                    decode = functools.partial(
                        plan.native.tensorize_wire, wires)
        if decode is not None:
            with monitor.span("tensorize.decode"):
                batch = decode()
                if plan.apa is not None:
                    batch = plan.apa.stamp(batch)
            # a batch with long rows is staged a part at a time
            # (_split_by_length), or, on the paths that do not split,
            # transferred by the launch
            if self.overlap_h2d and not self._has_long_rows(plan, batch):
                # h2d begins NOW — the transfer runs while
                # _ns_ids_from_batch does its host-side decode
                with monitor.span("tensorize.stage_put"):
                    batch = self._stage_h2d(plan, batch)
            with monitor.span("tensorize.ns_ids"):
                ns_ids = self._ns_ids_from_batch(batch)
        else:
            batch = self.snapshot.tensorizer.tensorize(bags)
            if plan.apa is not None:
                batch = plan.apa.stamp(batch)
            ns_ids = self._request_ns_ids(bags)
        if plan.apa is not None:
            ns_ids = self._apa_identity_ns(plan.apa, batch, ns_ids)
        return batch, ns_ids

    def _apa_identity(self, apa) -> tuple | None:
        """(prefix, pod has the identity output [pods], its namespace
        id [pods], the default's namespace id or None) where the step's
        APA binds the identity attribute; None where it does not."""
        if self._apa_ident is None:
            rs = self.snapshot.ruleset
            col = rs.layout.slots.get(self.identity_attr)
            found = None
            for p, c, k, dflt, has_dflt in apa.slot_writes:
                if c != col:
                    continue
                vids = apa.table[:, k]
                uniq, inv = np.unique(vids, return_inverse=True)
                ns_of = np.asarray(
                    [rs.namespace_id(namespace_of_value(
                        rs.interner.value_of(int(v)))) if v >= 0 else 0
                     for v in uniq], np.int32)
                found = (p, vids >= 0, ns_of[inv],
                         rs.namespace_id(namespace_of_value(
                             rs.interner.value_of(dflt)))
                         if has_dflt else None)
            self._apa_ident = (found,)
        return self._apa_ident[0]

    def _apa_identity_ns(self, apa, batch, ns_ids) -> np.ndarray:
        """The request namespaces where the APA binds the identity
        attribute (destAndNamespace of the bound value, as the host
        path reads it from the child bag): the step's own pod lookup,
        run on the host planes, a few array takes a batch."""
        ident = self._apa_identity(apa)
        if ident is None:
            return ns_ids
        p, has, ns_of, dflt = ident
        rows, ok = apa.host_rows(batch)
        r = rows[:, p]
        hit = (r >= 0) & has[np.maximum(r, 0)]
        miss = ns_ids if dflt is None else np.int32(dflt)
        new = np.where(hit, ns_of[np.maximum(r, 0)], miss)
        return np.where(ok, new, ns_ids).astype(np.int32)

    @staticmethod
    def _has_long_rows(plan, batch) -> bool:
        """Whether the length split has anything to cut: the plan has a
        wide program and the tensorizer kept a long row."""
        wide = batch.wide
        return bool(plan.wide_width) and wide is not None \
            and wide.count > 0

    def _split_by_length(self, plan, batch, ns_ids: np.ndarray,
                         n_real: int) -> list | None:
        """The length split: a served batch with a row whose subject
        reaches the narrow byte plane's cap is cut into parts, →
        [(rows, part batch, part ns_ids)], `rows` the part's rows as
        indices into `batch`. Rows under the cap keep the programs and
        shapes every batch had (one part, padded to its own bucket at
        its own byte tier); the long rows, sorted by their longest
        subject so a launch scans as far as its own rows ask, go
        FusedPlan.wide_bucket a part onto the wide plane, their bytes
        from the tensorizer's WideRows. None, at the cost of one
        comparison, for a batch without such a row: the path every
        batch took before."""
        if not self._has_long_rows(plan, batch):
            return None
        from istio_tpu.runtime.batcher import bucket_size

        with monitor.span("tensorize.split", on=self.observe,
                          batch=n_real):
            wide = batch.wide
            at = wide.row[:n_real]
            long_rows = np.flatnonzero(at >= 0)
            longest = wide.lens[at[long_rows]].max(axis=1)
            long_rows = long_rows[np.argsort(longest, kind="stable")]
            short_rows = np.flatnonzero(at < 0)
            buckets = self.buckets

            def take(plane, rows, size):
                out = np.zeros((size,) + plane.shape[1:], plane.dtype)
                out[:len(rows)] = plane[rows]
                return out

            def part(rows, size, str_bytes, str_lens):
                return rows, dataclasses.replace(
                    batch, wide=None, str_bytes=str_bytes,
                    str_lens=str_lens,
                    **{name: take(np.asarray(getattr(batch, name)),  # hotpath: sync-ok host planes
                                  rows, size)
                       for name in ("ids", "present", "map_present",
                                    "hash_ids")}), take(ns_ids, rows, size)

            parts = []
            if len(short_rows):
                size = bucket_size(len(short_rows), buckets) \
                    if buckets else len(short_rows)
                lens = take(batch.str_lens, short_rows, size)
                # cut at the part's own byte tier (narrow_batch then
                # finds nothing left to slice)
                width = plan._serve_width(dataclasses.replace(
                    batch, str_lens=lens))
                parts.append(part(
                    short_rows, size,
                    take(batch.str_bytes[:, :, :width], short_rows, size),
                    lens))
            step = plan.wide_bucket(buckets) if buckets else len(long_rows)
            for lo in range(0, len(long_rows), step):
                rows = long_rows[lo:lo + step]
                size = step if buckets else len(rows)
                parts.append(part(rows, size,
                                  take(wide.data, at[rows], size),
                                  take(wide.lens, at[rows], size)))
            if self.overlap_h2d:
                # every part's byte plane in ONE put: a put hands the
                # interpreter lock away, and taking it back from the
                # other pump costs more than the copy (PERF.md §6, PR 35)
                import jax
                with monitor.span("tensorize.stage_put", on=self.observe):
                    staged = jax.device_put(
                        [cut.str_bytes for _, cut, _ in parts])
                parts = [(rows, dataclasses.replace(cut, str_bytes=put), ns)
                         for (rows, cut, ns), put in zip(parts, staged)]
            return parts

    @staticmethod
    def _join_parts(plan, batch, parts: list, outs: list, n_real: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """The parts' packed arrays → (one packed array [., n_real] in
        the rows' own order, the rows left undecided). Everything
        after it (fold, respond, tags, exemplars, rule telemetry's host
        half) reads that array as it reads an unsplit batch's. Row 4
        holds the batch's evaluation errors again: a narrow part's
        count, a wide part's per-row counts (_base_packer), but for
        the UNDECIDED rows: those that saturate the wide plane too and
        had a visible rule err. Whether that err is truncation's or an
        evaluation error the host finds out, and counts
        (_decide_on_host)."""
        packed = np.empty((outs[0].shape[0], n_real), np.int32)
        wide = batch.wide
        n_err = 0
        undecided = []
        for (rows, cut, _), out in zip(parts, outs):
            k = len(rows)
            packed[:, rows] = out[:, :k]
            width = int(cut.str_bytes.shape[2])
            if width != plan.wide_width:
                n_err += int(out[4, 0])
                continue
            flag = out[4, :k]
            left = (flag < 0) & \
                (wide.lens[wide.row[rows]] >= width).any(axis=1)
            n_err += int(-(flag[(flag < 0) & ~left] + 1).sum())
            undecided.append(rows[left])
        packed[4] = n_err
        return packed, np.concatenate(undecided)

    def _decide_on_host(self, plan, batch, b: int, bag: Bag,
                        ns_id: int) -> tuple[CheckResponse, int]:
        """→ (the host oracle's response for row `b` of `batch`, its
        evaluation errors). Only the rules that can match the row are
        evaluated (RuleSetProgram.host_candidates: its host's blocks,
        not its namespace's rules), each by the snapshot oracle's own
        program; the response is the generic path's (_check_one: every
        action on the host), so it carries no quota activity bits, as
        an oracle-bridged response carries none."""
        rs = self.snapshot.ruleset
        n_cfg = len(self.snapshot.rules)
        seen = (rs.ns_ids[""], ns_id)
        oracle = self._oracle()
        active, errs = [], 0
        candidates = rs.host_candidates(batch, b)
        if plan.apa is not None:
            # the row's bag as the step saw it; a guard on a bound
            # column reads the request, not the step
            bag = self._with_apa([bag])[0]
            if rs.guards[0] in plan.apa.written_cols:
                candidates = range(rs.n_rules)
        for ridx in candidates:
            if ridx >= n_cfg or rs.rule_ns[ridx] not in seen:
                continue
            try:
                if oracle.evaluate(ridx, bag):
                    active.append(ridx)
            except Exception:
                errs += 1
        resp = self._check_one(bag, active, (), attribute=True,
                               referenced=plan.pred_attrs_for_ns(ns_id))
        self._apply_grants([bag], [resp])
        return resp, errs

    @staticmethod
    def _stage_h2d(plan, batch):
        """Overlapped h2d from the pinned staging: narrow the byte
        plane to its serve tier FIRST (so the staged shape is exactly
        the compiled shape), then start the async device_put. The
        returned batch's str_bytes is a committed device array —
        packed_check's own narrow/transfer become no-ops for it. A
        staging error is a device-path failure like any other: it
        propagates to ResilientChecker, where it is retried, counted
        and (breaker open) served by the oracle."""
        import dataclasses as _dc

        import jax
        nb = plan.narrow_batch(batch)
        return _dc.replace(nb, str_bytes=jax.device_put(nb.str_bytes))

    def _overlay_active(self, packed: np.ndarray, bags: Sequence[Bag],
                        ns_ids: np.ndarray, observe: bool = False
                        ) -> tuple[np.ndarray, dict]:
        """Decode the packed step's bitpacked overlay plane →
        (ns-masked active bits [len(bags), n_overlay_cols], rule idx →
        column position). Host-fallback rules' bits are oracle-patched;
        device + host resolve errors are accounted. `bags`/`ns_ids`
        must already be trimmed of padding rows. `observe`: feed
        host-fallback hits/errors into the rule-telemetry plane — set
        only by the CHECK path (the device accumulators can't see
        fallback rules, so their counts patch in here, exactly where
        their verdicts do)."""
        plan, rs = self.fused, self.snapshot.ruleset
        n_err = int(packed[4, 0]) if packed.shape[1] else 0
        if n_err and self.observe:   # replay mode: no counter feeds
            monitor.RESOLVE_ERRORS.inc(n_err)
        cols = plan.overlay_cols
        if not len(cols):
            return np.zeros((len(bags), 0), bool), {}
        from istio_tpu.runtime.fused import unpack_word_rows
        n_words = plan.n_ref_words
        n_ov_words = plan.n_overlay_words
        n_real = len(bags)
        active_sub = unpack_word_rows(
            packed[5 + n_words:5 + n_words + n_ov_words, :n_real],
            len(cols))
        col_pos = {int(r): i for i, r in enumerate(cols)}
        rns = rs.rule_ns[cols]
        ns_ok_sub = (rns[None, :] == rs.ns_ids[""]) | \
                    (rns[None, :] == ns_ids[:, None])
        host_errs = 0
        fb_cols: list[int] = []
        fb_pos: list[int] = []
        err_by_rule: dict[int, int] = {}
        for ridx in rs.host_fallback:
            pos = col_pos.get(ridx)
            if pos is None:   # rbac pseudo-rule row: no overlay col
                continue
            fb_cols.append(ridx)
            fb_pos.append(pos)
            vis_errs = 0
            # ONLY ns-visible (bag, rule) pairs are oracle-evaluated:
            # the ns mask below zeroes invisible bits regardless, so a
            # slow fallback predicate (attribute pulls, extern calls)
            # must never run for traffic that can never see its rule —
            # and the generic path's error accounting is (err & ns_ok),
            # so skipping keeps RESOLVE_ERRORS oracle-identical (it
            # over-counted invisible errors before)
            for b in np.nonzero(ns_ok_sub[:, pos])[0]:
                m, _, e = rs.host_eval(ridx, bags[b])
                active_sub[b, pos] = m
                if e:
                    vis_errs += 1
            if vis_errs:
                err_by_rule[ridx] = vis_errs
                host_errs += vis_errs
        if host_errs and self.observe:
            monitor.RESOLVE_ERRORS.inc(host_errs)
        active_sub &= ns_ok_sub
        tele = plan.telemetry
        if observe and tele is not None and (fb_cols or err_by_rule):
            tele.add_host(fb_cols, active_sub[:, fb_pos],
                          err_by_rule, tele.ns_slots(ns_ids))
        return active_sub, col_pos

    def _overlay_fallback(self, matched: np.ndarray, err: np.ndarray,
                          ns_ids: np.ndarray, bags: Sequence[Bag]
                          ) -> tuple[np.ndarray, np.ndarray]:
        """Patch host-fallback rules' verdicts into the device output and
        account namespace-visible errors; returns (active, ns_ok),
        clipped to config rules (ruleset rows past len(snapshot.rules)
        are rbac pseudo-rules — no actions behind them, and their errs
        are adapter-level, not resolve-level)."""
        rs = self.snapshot.ruleset
        n_cfg = len(self.snapshot.rules)
        for ridx in rs.host_fallback:
            if ridx >= n_cfg:
                continue
            for b, bag in enumerate(bags):
                m, _, e = rs.host_eval(ridx, bag)
                matched[b, ridx] = m
                err[b, ridx] = e
        matched = matched[:, :n_cfg]
        err = err[:, :n_cfg]
        # hotpath: sync-ok — generic path's designated ns-mask pull
        ns_ok = np.asarray(rs.namespace_mask(ns_ids))[:, :n_cfg]  # hotpath: sync-ok
        n_err = int((err & ns_ok).sum())   # hotpath: sync-ok (host numpy)
        if n_err:
            monitor.RESOLVE_ERRORS.inc(n_err)
        return matched & ns_ok, ns_ok

    def _resolve(self, bags: Sequence[Bag], observe: bool = False
                 ) -> tuple[list[list[int]], list[list[int]]]:
        """Batched rule matching → per-bag (active, namespace-visible)
        rule index lists. One device step for the whole batch; fallback
        + namespace masking applied host-side (cheap: bool arrays).
        `observe`: feed the CHECK stage histograms — only the check
        path sets it; report/quota/APA resolves share this code but
        must not pollute the Check() decomposition."""
        snap = self.snapshot
        if snap.ruleset.n_rules == 0:   # device arrays are padded to ≥1
            empty: list[list[int]] = [[] for _ in bags]
            return empty, [[] for _ in bags]
        with monitor.resolve_timer():
            with monitor.stage("tensorize", on=observe,
                               batch=len(bags)):
                batch = snap.tensorizer.tensorize(bags)
            with monitor.stage("device_step", on=observe):
                if observe:
                    # chaos seam at the generic path's device boundary
                    # (check traffic only — observe gates out report/
                    # quota/APA resolves), mirroring packed_check's
                    from istio_tpu.runtime.resilience import CHAOS
                    CHAOS.device_step()
                matched, _, err = snap.ruleset(batch)
                # hotpath: sync-ok — the generic path's designated pull
                matched = np.array(matched)    # hotpath: sync-ok
                err = np.array(err)            # hotpath: sync-ok
        ns_ids = self._request_ns_ids(bags)
        active, ns_ok = self._overlay_fallback(matched, err, ns_ids, bags)
        return ([list(np.nonzero(active[b])[0]) for b in range(len(bags))],
                [list(np.nonzero(ns_ok[b])[0]) for b in range(len(bags))])

    # ------------------------------------------------------------------
    # varieties
    # ------------------------------------------------------------------

    def check(self, bags: Sequence[Bag], instep: Any = None,
              pre_tensorized: Any = None,
              deadline: float | None = None) -> list[CheckResponse]:
        """`instep`: optional (q_arrays, counts, on_dispatch, on_pull)
        from an in-step quota session (device_quota.
        InlineQuotaSession) — the quota alloc rides the check
        program's trip; `on_dispatch(new_counts)` fires the moment
        the program is in flight (the session swaps the pool onto the
        device future and releases its token, letting the next trip
        chain on-device) and `on_pull(granted, gate)` right after the
        pull, before any per-row response python. `pre_tensorized`:
        (batch, ns_ids) computed by the caller (outside the token);
        must correspond to `bags` exactly. Both require the fused
        path. `deadline`: the batch's min remaining absolute
        perf_counter instant (threaded from the batcher) — host
        adapter actions inherit it via the executor plane; None =
        unbounded (plus any configured per-action timeout)."""
        if self.fused is not None:
            return self._check_fused(bags, instep=instep,
                                     pre_tensorized=pre_tensorized,
                                     deadline=deadline)
        actives, visibles = self._resolve(bags, observe=self.observe)
        with monitor.stage("respond", on=self.observe):
            out = []
            for bag, rule_idxs, vis in zip(bags, actives, visibles):
                out.append(self._check_one(bag, rule_idxs, vis))
            self._apply_grants(bags, out)
        # NO recorder tap here: the generic path's statuses include
        # host-adapter results the shadow replay (empty handlers,
        # device surface only) can never reproduce — a corpus recorded
        # on a non-fused server would diff as permanently divergent
        # against an identical config. Canary recording is fused-only,
        # like the replay itself.
        return out

    def _check_fused(self, bags: Sequence[Bag], instep: Any = None,
                     pre_tensorized: Any = None,
                     deadline: float | None = None
                     ) -> list[CheckResponse]:
        """Fused serving path: ONE device step computes rule matching +
        denier/list verdicts + TTLs for the whole batch; the host loop
        below only touches rules with non-fusable actions (and rules
        whose predicate fell back to the host oracle). Status merge is
        lowest-rule-index-wins on both sides, so host results from a
        lower rule index override the device candidate and vice versa —
        the two paths provably pick the same rule's status."""
        from istio_tpu.runtime.batcher import trim_pads

        snap, plan = self.snapshot, self.fused
        # real (non-padding) prefix length, known BEFORE the device
        # call: the telemetry fold masks padding rows on device, and
        # every host-side pass below runs on the real prefix only
        n_real = len(trim_pads(bags))
        observe = self.observe
        if observe and plan.apa is not None:
            monitor.note_apa_rows("step", n_real)
        # serve.device (= h2d + device_step) and serve.overlay (= fold
        # + respond) exist for the zipkin tracer alone: with no
        # reporter the two grouping spans are off altogether
        grouped = observe and monitor.zipkin_on()
        bridged = False
        with (monitor.resolve_timer() if observe
              else contextlib.nullcontext()):
            parts = undecided = None
            if pre_tensorized is not None:
                batch, ns_ids = pre_tensorized
            else:
                # C++ wire→tensor decode when possible: no
                # per-request python work
                with monitor.stage("tensorize", on=observe,
                                   batch=len(bags)):
                    batch, ns_ids = self._tensorize_for_device(bags)
                    if instep is None:
                        parts = self._split_by_length(plan, batch,
                                                      ns_ids, n_real)
            # swap-warm oracle bridge: while a background warm is
            # still compiling this shape's program (a config swap
            # deferred the shapes live traffic was NOT serving), the
            # batch serves through the CPU oracle — the new snapshot's
            # semantics apply immediately and no request pays the
            # in-band XLA trace. Serving path only (shadow replay
            # keeps the device surface; the in-step quota path has no
            # oracle equivalent and compiles through). Bridged
            # responses carry no device activity bits, so a quota
            # riding one falls back to the host adapter path.
            if observe and instep is None and any(
                    plan.swap_warm_pending(cut) for cut in
                    ([batch] if parts is None else
                     [cut for _, cut, _ in parts])):
                bridged = True
            else:
                # ONE device→host pull for the whole verdict: every
                # extra pull is another sync (chip_smoke.py prints
                # device_sync_ms), and plane-by-plane conversion was
                # six of them per batch
                with monitor.span("device", on=grouped):
                    if instep is not None:
                        q_arrays, counts, on_dispatch, on_pull = instep
                        with monitor.stage("h2d"):
                            packed_dev, new_counts = \
                                plan.packed_check_instep(
                                    batch, ns_ids, q_arrays, counts,
                                    n_real=n_real)
                            # the program is IN FLIGHT: on_dispatch
                            # swaps the pool onto the device-future
                            # counters and drops the token, so the
                            # next trip chains on-device while this
                            # one's pull is still outstanding
                            on_dispatch(new_counts)
                        with monitor.stage("device_step"):
                            packed = np.asarray(packed_dev)   # the pull — hotpath: sync-ok
                        # granted/gate are the LAST two rows;
                        # everything the overlay decode reads sits
                        # before them
                        on_pull(packed[-2], packed[-1] != 0)
                    elif parts is not None:
                        packed, undecided = self._join_parts(
                            plan, batch, parts, plan.packed_check_parts(
                                [(cut, cut_ns, len(rows))
                                 for rows, cut, cut_ns in parts],
                                observe=observe), n_real)
                    else:
                        packed = plan.packed_check(batch, ns_ids,
                                                   observe=observe,
                                                   n_real=n_real)
        if bridged:
            return self.check_host_oracle(bags)
        with monitor.span("overlay", on=grouped, batch=n_real):
            out = self._fold_respond(snap, plan, packed, batch,
                                     bags[:n_real], ns_ids[:n_real],
                                     observe, deadline, undecided)
        if plan.apa is not None and plan.apa.quota_bindings:
            out.apa_pods = PodRows(plan.apa, *plan.apa.host_rows(batch))
        # a taken batch the shim decoded, with a row that asks a quota
        if plan.quota_keys is not None and \
                getattr(bags, "asks_quota", None) is not None and \
                bags.asks_quota() and bags.wire_spans() is not None:
            out.quota_keys = plan.quota_keys.columns(batch, n_real,
                                                     out.apa_pods)
        return out

    def _fold_respond(self, snap, plan, packed: np.ndarray, batch,
                      bags: Sequence[Bag], ns_ids: np.ndarray,
                      observe: bool, deadline: float | None,
                      undecided: np.ndarray | None = None
                      ) -> ClassedResponses:
        """The host half of the fused check after the pull: stage
        `fold` (packed-plane decode: overlay bits, host-action
        submits, referenced/presence signature dedup) then stage
        `respond` (one CheckResponse a verdict class). `bags`/`ns_ids`
        are the real prefix — bucket-padding rows carry no caller
        (the batcher appends PadBags at the tail and zips results
        against real requests), and at small arrival rates a
        512-bucket batch is mostly padding: per-row python here is
        the serving CPU budget. `undecided`: the rows a length split
        left to the host (_join_parts); `fold` gives them the oracle's
        verdict (span fold.undecided), in `packed` too, so what reads
        the pulled planes (decided-by counts, exemplars, the canary
        tap) reads the verdict that was served."""
        from istio_tpu.runtime.fused import (
            class_int_rows, dedup_bit_rows, unpack_word_rows)
        from istio_tpu.utils import tracing

        tr = tracing.get_tracer()
        n_real = len(bags)
        status = packed[0]
        dur = packed[1].view(np.float32)
        uses = packed[2]
        deny_rule = packed[3]
        rs = snap.ruleset
        ex = self.executor
        host_pending: dict[int, list] | None = None
        # Any exception from here to the claims must not leak
        # submitted-but-unclaimed actions: the conservation ledger
        # (submitted == resolved) is a smoke/bench gate, and a
        # ResilientChecker retry of this batch would re-submit
        # every action while the first generation dangled.
        try:
            with monitor.stage("fold", on=observe):
                # referenced-attribute item bits (rows 5..5+W): the
                # device computed predicate + instance attr uses per
                # request; the host just decodes set bits into names
                n_words = plan.n_ref_words
                if n_words:
                    ref_bits = unpack_word_rows(
                        packed[5:5 + n_words, :n_real],
                        len(plan.item_names))

                # Only plan.overlay_cols of the [B, R] matched plane
                # are ever inspected host-side (the rows after the ref
                # bits); converting the full plane (16MB/batch at
                # B=2048, R=10k) was the original serving bottleneck.
                # Namespace masking for the subset happens in numpy;
                # host-fallback rules are oracle-evaluated into their
                # subset positions (_overlay_active, shared with the
                # fused report path).
                active_sub, col_pos = self._overlay_active(
                    packed, bags, ns_ids, observe=observe)
                held: dict[int, CheckResponse] = {}
                if undecided is not None and len(undecided):
                    with monitor.span("fold.undecided", on=observe,
                                      batch=len(undecided)):
                        host_errs = 0
                        for b in undecided.tolist():
                            held[b], errs = self._decide_on_host(
                                plan, batch, b, bags[b], int(ns_ids[b]))
                            host_errs += errs
                            status[b] = held[b].status_code
                            dur[b] = held[b].valid_duration_s
                            uses[b] = held[b].valid_use_count
                            deny_rule[b] = held[b].deny_rule \
                                if held[b].status_code != OK \
                                else np.iinfo(np.int32).max
                        # the host ran their every action: none is
                        # left to overlay
                        active_sub[undecided] = False
                    if observe:
                        if host_errs:
                            monitor.RESOLVE_ERRORS.inc(host_errs)
                        wide = batch.wide
                        monitor.note_undecided_rows(
                            list(snap.ruleset.layout.byte_slots),
                            np.argmax(wide.lens[wide.row[undecided]]
                                      >= wide.data.shape[2],
                                      axis=1).tolist())
                # hotpath: sync-ok x2 — tensorizer planes are host numpy
                present_np = np.asarray(   # hotpath: sync-ok
                    batch.present)[:n_real]
                map_present_np = np.asarray(   # hotpath: sync-ok
                    batch.map_present)[:n_real]
                lay = rs.layout

                ha = plan.host_rule_idx
                ha_pos = np.asarray([col_pos[int(r)] for r in ha],
                                    np.int64)
                qa_rules = sorted({qa[0] for qa in plan.quota_actions})
                qa_pos = [col_pos[r] for r in qa_rules]
                # rows with a host action active: their response
                # reads the bag and the executor's results
                host_rows = active_sub[:, ha_pos].any(axis=1)
                if observe:
                    monitor.note_check_decided(plan.rows_by_section(
                        deny_rule[:n_real], host_rows))

                # adapter-executor plane (runtime/executor.py): submit
                # every host action NOW, so adapter calls run on their
                # handler bulkhead lanes WHILE the fold below decodes
                # the referenced/presence planes — the response loop
                # then claims results in rule order, bounded by the
                # request deadline. One list per row under a host
                # action (only their bags are asked for), entries (rule
                # idx, HostAction | final CheckResult) in exactly the
                # order the inline loop would have executed them, so
                # lowest-rule-index-wins merging is byte-identical.
                if ex is not None and len(ha):
                    from istio_tpu.runtime.config import _qualify
                    from istio_tpu.runtime.executor import check_fallback
                    host_pending = {}
                    for b in np.flatnonzero(host_rows).tolist():
                        bag = bags[b]
                        row: list = []
                        for ridx in ha[active_sub[b, ha_pos]]:
                            ridx = int(ridx)
                            for hc, template, inst_names in \
                                    plan.host_actions[ridx]:
                                handler = self._handler_for(hc)
                                if handler is None:
                                    continue
                                hq = _qualify(hc.name, hc.namespace)
                                for iname in inst_names:
                                    try:
                                        instance = snap.instances[
                                            iname].build(bag)
                                    except EvalError as exc:
                                        # instance build stays on this
                                        # thread (_safe_check parity:
                                        # EvalError → INTERNAL, counted
                                        # as a dispatch error)
                                        monitor.DISPATCH_ERRORS.inc()
                                        row.append((ridx, CheckResult(
                                            status_code=INTERNAL,
                                            status_message=str(exc))))
                                        continue
                                    row.append((ridx, ex.submit(
                                        hq,
                                        self._bound_check(
                                            handler, template, instance),
                                        check_fallback)))
                        host_pending[b] = row

                # Referenced/presence construction deduplicated across
                # the batch: uniform traffic produces a handful of
                # distinct (referenced bits, presence bits) signatures,
                # and building the name tuples + presence dicts per ROW
                # was milliseconds of python per request — seconds per
                # 2048-batch, single-threaded in the batcher worker.
                # Shared objects are read-only by contract (the gRPC
                # layer only serializes them).
                ref_of = sig_of = None
                if n_words:
                    # NOT np.unique over rows (`axis=0`) of the unpacked
                    # bytes: it sorts ~60-field records field by field
                    # under the GIL, ~20 ms a 1,300-row batch.
                    with monitor.span("fold.signature", on=observe,
                                      batch=n_real) as keyed:
                        first, sig_of = dedup_bit_rows(
                            (ref_bits, present_np, map_present_np,
                             active_sub))
                        keyed.tag(distinct=len(first))
                    if observe:
                        monitor.FOLD_SIGNATURE_CLASSES.inc(len(first))
                    names = plan.item_names
                    shared: list[tuple[tuple, dict]] = []
                    for b in first:
                        referenced = {
                            names[j] for j in np.nonzero(ref_bits[b])[0]}
                        act_row = active_sub[b]
                        for ridx, extra in \
                                plan.unmapped_instance_attrs.items():
                            if act_row[col_pos[ridx]]:
                                referenced |= extra
                        pres_row = present_np[b]
                        mp_row = map_present_np[b]
                        presence: dict = {}
                        for item in referenced:
                            if isinstance(item, tuple):
                                col = lay.derived_slots.get(item)
                                if col is not None:
                                    presence[item] = bool(pres_row[col])
                            else:
                                col = lay.slots.get(item)
                                if col is not None:
                                    presence[item] = bool(pres_row[col])
                                else:
                                    mcol = lay.map_slots.get(item)
                                    if mcol is not None:
                                        presence[item] = \
                                            bool(mp_row[mcol])
                        shared.append(
                            (tuple(sorted(referenced, key=str)),
                             presence))
                    ref_of = [shared[i] for i in sig_of]
                elif plan.unmapped_instance_attrs:
                    # no layout items at all, but some rules still
                    # carry instance attrs — merge them per row from
                    # the overlaid activity bits (presence is
                    # unknowable without a layout)
                    ref_of = []
                    for b in range(n_real):
                        referenced: set = set()
                        for ridx, extra in \
                                plan.unmapped_instance_attrs.items():
                            if active_sub[b, col_pos[ridx]]:
                                referenced |= extra
                        ref_of.append(
                            (tuple(sorted(referenced, key=str)), {}))
            with monitor.stage("respond", on=observe):
                # decision exemplars: denied/errored rows
                # reservoir-sample into the telemetry plane (host-side,
                # post-fold, from the already-decoded verdict) with the
                # batch's active span so a /debug/rulestats entry links
                # to its RingReporter trace; the canary recorder shares
                # the span so its samples join traces
                tele = plan.telemetry if observe else None
                tele_span = tr._current() if tele is not None \
                    or self.recorder is not None else None
                # server-issued check-cache grants: one (ttl, uses) pair
                # per distinct namespace, min-folded into every response
                # below (allow AND deny — a delta that flips a cached
                # DENY must revoke it too). The flight-recorder tape gets
                # the grant decision as its own stage (a post-revocation
                # policy stampede must be attributable).
                with monitor.span("grant", tap=True, on=observe and
                                  self.grants is not None):
                    grant_of = self._grants_for_rows(ns_ids)
                denied = status[:n_real] != OK

                def respond_row(b: int) -> CheckResponse:
                    """Row b's response: the device verdict merged
                    with the row's host actions, lowest rule index
                    first. The one copy of the merge rules: a verdict
                    class takes its response from its first row."""
                    if b in held:
                        return held[b]
                    resp = CheckResponse()
                    resp.valid_duration_s = min(resp.valid_duration_s,
                                                float(dur[b]))
                    resp.valid_use_count = min(resp.valid_use_count,
                                               int(uses[b]))
                    dev_rule = int(deny_rule[b])
                    dev_applied = False
                    host_active = ha[active_sub[b, ha_pos]] \
                        if host_rows[b] else ()
                    pend = host_pending.get(b) \
                        if host_pending is not None else None
                    pi = 0
                    for ridx in host_active:
                        ridx = int(ridx)
                        # ties at ridx == dev_rule follow the rule's
                        # config action order: if its first CHECK action
                        # is fused, the device result applies before the
                        # host actions
                        if not dev_applied and (
                                ridx > dev_rule or
                                (ridx == dev_rule and
                                 dev_rule in plan.fused_first_rules)):
                            self._apply_device_status(
                                resp, plan, dev_rule, int(status[b]))
                            dev_applied = True
                        if pend is not None:
                            # executor path: CLAIM this rule's
                            # pre-submitted results (same order the
                            # submit pass appended them), each wait
                            # bounded by the batch deadline — an
                            # unresolved action folds as its fail-policy
                            # verdict, never a held batch
                            while pi < len(pend) \
                                    and pend[pi][0] == ridx:
                                item = pend[pi][1]
                                pi += 1
                                result = item \
                                    if isinstance(item, CheckResult) \
                                    else ex.resolve(item, deadline)
                                self._combine(resp, result)
                            continue
                        for hc, template, inst_names in \
                                plan.host_actions[ridx]:
                            handler = self._handler_for(hc)
                            if handler is None:
                                continue
                            for iname in inst_names:
                                ib = snap.instances[iname]
                                result = self._safe_check(
                                    handler, template, ib, bags[b])
                                self._combine(resp, result)
                    if not dev_applied:
                        self._apply_device_status(resp, plan, dev_rule,
                                                  int(status[b]))
                    if status[b] != OK:
                        resp.deny_rule = dev_rule
                    # referenced/presence: precomputed per unique
                    # signature
                    if ref_of is not None:
                        resp.referenced, resp.referenced_presence = \
                            ref_of[b]
                    if qa_rules:
                        resp.active_quota_rules = tuple(
                            r for r, p in zip(qa_rules, qa_pos)
                            if active_sub[b, p])
                        resp.quota_context = self
                    else:
                        resp.active_quota_rules = ()
                    if grant_of is not None:
                        grants, grant_class = grant_of
                        g_ttl, g_uses = grants[grant_class[b]]
                        resp.valid_duration_s = min(
                            resp.valid_duration_s, g_ttl)
                        resp.valid_use_count = min(
                            resp.valid_use_count, g_uses)
                    return resp

                if n_real < RESPOND_CLASS_MIN_ROWS:
                    # too few rows to repay the keys: a response a row
                    first = class_of = np.arange(n_real)
                else:
                    # One response a VERDICT CLASS: rows equal in every
                    # plane a response is built from share one object.
                    # A row under a host action reads its bag and its
                    # executor items, so it is a class of its own; the
                    # signature class holds the row's active_sub bits,
                    # the quota rules' among them.
                    columns = [
                        status[:n_real], packed[1, :n_real],
                        uses[:n_real],
                        np.where(denied, deny_rule[:n_real], -1),
                        sig_of if sig_of is not None else
                        dedup_bit_rows((active_sub,))[1]]
                    if grant_of is not None:
                        columns.append(grant_of[1])
                    own = host_rows
                    if held:    # a host-decided row: its own object
                        own = host_rows.copy()
                        own[undecided] = True
                    if own.any():
                        columns.append(np.where(
                            own, np.arange(1, n_real + 1), 0))
                    first, class_of = class_int_rows(columns)
                classes = [respond_row(b) for b in first.tolist()]
                out = ClassedResponses(
                    map(classes.__getitem__, class_of.tolist())
                    if len(classes) < n_real else classes)
                out.classes, out.class_of = classes, class_of
                if observe:
                    alone = np.count_nonzero(np.bincount(class_of) == 1) \
                        if len(first) < n_real else n_real
                    monitor.note_respond_classes(
                        len(classes), n_real - alone, alone)
                if tele is not None:
                    rows = np.flatnonzero(denied)
                    if len(rows):
                        tele.sample_rows(
                            rows.tolist(), deny_rule[rows].tolist(),
                            status[rows].tolist(), bags, tele_span)
            if self.recorder is not None:
                # canary tap: bags/out are already padding-trimmed; one
                # stride check per batch, bounded appends for sampled rows
                # (istio_tpu/canary/recorder.py — off the device path).
                # The DEVICE planes are recorded, not the merged response:
                # the shadow replay compares device-decidable decisions
                # (host adapters never fire in shadow)
                self.recorder.tap(bags, out, snap, self.identity_attr,
                                  tele_span,
                                  device=(status, dur, uses, deny_rule))
            return out
        except BaseException:
            if host_pending is not None:
                for _row in host_pending.values():
                    for _ridx, _item in _row:
                        if not isinstance(_item, CheckResult):
                            ex.abandon(_item)
            raise

    @staticmethod
    def _apply_device_status(resp: CheckResponse, plan, dev_rule: int,
                             dev_status: int) -> None:
        """Merge the device verdict like one more adapter result."""
        if dev_status == OK:
            return
        if resp.status_code == OK:
            resp.status_code = dev_status
            resp.status_message = plan.message_for(dev_rule, dev_status)
        else:
            resp.status_message = (resp.status_message + "; " +
                                   plan.message_for(dev_rule, dev_status)
                                   ).strip("; ")

    def check_host_oracle(self, bags: Sequence[Bag],
                          deadline: float | None = None
                          ) -> list[CheckResponse]:
        """Graceful-degradation check path: resolve every rule on the
        CPU via the whole-snapshot oracle (compiler/ruleset.py
        SnapshotOracle) and run the generic host adapter loop — NO
        device step anywhere, so a tripped circuit breaker
        (runtime/resilience.py) can keep answering correctly while the
        device is down. Deliberately does not feed the stage
        decomposition: fallback latency is not serving latency, and
        attributing it to device_step/tensorize would corrupt the
        decomposition the SLO gauges are judged against (the e2e
        histogram still covers these requests via the batcher)."""
        from istio_tpu.runtime.batcher import trim_pads

        bags = self._with_apa(trim_pads(bags))
        oracle = self._oracle()
        out: list[CheckResponse] = []
        n_err = 0
        for bag in bags:
            ns = _namespace_of(bag, self.identity_attr)
            active, visible, errs = oracle.resolve(bag, ns)
            n_err += errs
            out.append(self._check_one(bag, active, visible))
        self._apply_grants(bags, out)
        if n_err:
            monitor.RESOLVE_ERRORS.inc(n_err)
        return out

    def _oracle(self):
        """Lazily-built whole-snapshot oracle, cached per dispatcher
        (per snapshot: a config swap publishes a fresh Dispatcher).
        Seeded with the ruleset's host-fallback programs so those
        rules never recompile. Only CONFIG rules participate — ruleset
        rows past len(snapshot.rules) are rbac pseudo-rules whose
        actions live on their owning config rule."""
        cached = getattr(self, "_snapshot_oracle", None)
        if cached is None:
            from istio_tpu.compiler.ruleset import SnapshotOracle
            rs = self.snapshot.ruleset
            n_cfg = len(self.snapshot.rules)
            cached = SnapshotOracle(
                rs.rules[:n_cfg], self.snapshot.finder,
                seed={r: p for r, p in rs.host_fallback.items()
                      if r < n_cfg})
            self._snapshot_oracle = cached
        return cached

    def _check_one(self, bag: Bag, rule_idxs: list[int],
                   visible: list[int], referenced=(),
                   attribute: bool = False) -> CheckResponse:
        """`referenced`: the visible rules' attribute uses where the
        caller holds them already (FusedPlan.pred_attrs_for_ns).
        `attribute`: name the rule whose action gave the response its
        status in `deny_rule`, as the fused path does."""
        snap = self.snapshot
        resp = CheckResponse()
        # ReferencedAttributes: every namespace-visible rule's predicate
        # was EVALUATED for this request (protoBag.go:117 tracking →
        # compile-time bitmaps, SURVEY.md §2.2); matched rules add their
        # instances' attribute uses below.
        referenced = set(referenced)
        for ridx in visible:
            referenced |= snap.ruleset.attr_names[ridx]
        for ridx in rule_idxs:
            for hc, template, inst_names in snap.actions_for(
                    ridx, Variety.CHECK):
                handler = self._handler_for(hc)
                if handler is None:
                    continue
                for iname in inst_names:
                    ib = snap.instances[iname]
                    referenced |= ib.referenced_attrs
                    result = self._safe_check(handler, template, ib, bag)
                    self._combine(resp, result)
                    if attribute and resp.deny_rule < 0 \
                            and resp.status_code != OK:
                        resp.deny_rule = int(ridx)
        resp.referenced = tuple(sorted(referenced, key=str))
        return resp

    @staticmethod
    def _bound_check(handler: Handler, template: str,
                     instance) -> Any:
        """Zero-arg adapter call for the executor plane — the worker
        side of _safe_check's dispatch leg (same counter accounting;
        exceptions resolve via the executor's retry + safeDispatch
        INTERNAL path, runtime/executor.py)."""
        def call():
            # DISPATCH_ERRORS for a failing action is counted ONCE in
            # check_fallback's error branch (the resolve-side single
            # accounting home) — counting per attempt here would
            # double-bill retried calls relative to the inline path
            with monitor.dispatch_timer():
                return handler.handle_check(template, instance)
        return call

    def _safe_check(self, handler: Handler, template: str, ib,
                    bag: Bag) -> CheckResult:
        with monitor.dispatch_timer():
            try:
                instance = ib.build(bag)
            except EvalError as exc:
                monitor.DISPATCH_ERRORS.inc()
                return CheckResult(status_code=INTERNAL,
                                   status_message=str(exc))
            try:
                return handler.handle_check(template, instance)
            except Exception as exc:   # safeDispatch (dispatcher.go:399)
                monitor.DISPATCH_ERRORS.inc()
                log.exception("adapter check failed")
                return CheckResult(status_code=INTERNAL,
                                   status_message=f"adapter panic: {exc}")

    @staticmethod
    def _combine(resp: CheckResponse, r: CheckResult) -> None:
        """combineResults (dispatcher.go:322): worst status, min TTLs."""
        if not r.ok and resp.status_code == OK:
            resp.status_code = r.status_code
            resp.status_message = r.status_message
        elif not r.ok:
            resp.status_message = \
                f"{resp.status_message}; {r.status_message}".strip("; ")
        resp.valid_duration_s = min(resp.valid_duration_s,
                                    r.valid_duration_s)
        resp.valid_use_count = min(resp.valid_use_count,
                                   r.valid_use_count)

    def report(self, bags: Sequence[Bag]) -> None:
        from istio_tpu.runtime.batcher import trim_pads
        from istio_tpu.runtime.config import _qualify

        # defensive vs padded callers (BatchCheck-style fronts hand
        # bucket-shaped batches): padding rows carry no caller and
        # must not fire empty-match report rules
        bags = trim_pads(bags)
        if not bags:
            return
        fctx = None
        if self.fused is not None:
            if not self.fused.report_rules:
                return      # no REPORT rules configured: nothing to do
            # rows already contain ONLY active report-rule indices;
            # fctx carries device-built instance fields (VERDICT r4
            # item 3 — per-record expr eval off the host)
            actives, fctx = self._report_active_fused(bags)
        else:
            actives, _ = self._resolve(bags)
        rl = self.fused.report_lowering if self.fused is not None \
            else None
        observe = self.observe
        # adapter_dispatch accumulates ONLY handle_report wall time
        # (the documented stage semantics): host instance builds for
        # unlowerable instances run in this loop too and must not be
        # blamed on exporters — that ambiguity is what the
        # per-exporter accounting exists to remove
        adapter_s = 0.0
        tmpl_records: dict[str, int] = {}
        for b, (bag, rule_idxs) in enumerate(zip(bags, actives)):
            for ridx in rule_idxs:
                for hc, template, inst_names in self.snapshot.actions_for(
                        ridx, Variety.REPORT):
                    handler = self._handler_for(hc)
                    if handler is None:
                        continue
                    instances = []
                    for iname in inst_names:
                        if fctx is not None and iname in rl.specs:
                            inst = fctx.materialize(iname, b)
                            if inst is None:
                                # device-invalid field: the EvalError
                                # abort, same accounting as the host
                                monitor.DISPATCH_ERRORS.inc()
                                log.warning("instance %s: field "
                                            "evaluation failed", iname)
                            else:
                                instances.append(inst)
                            continue
                        if self.apa_in_step:
                            # a host-built instance reads what the
                            # step's APA bound, as the rule did
                            bag = self._with_apa([bag])[0]
                        try:
                            instances.append(
                                self.snapshot.instances[iname].build(bag))
                        except EvalError as exc:
                            monitor.DISPATCH_ERRORS.inc()
                            log.warning("instance %s: %s", iname, exc)
                    if instances:
                        t_h = time.perf_counter()
                        failed = False
                        with monitor.dispatch_timer():
                            try:
                                handler.handle_report(template, instances)
                            except Exception:
                                failed = True
                                monitor.DISPATCH_ERRORS.inc()
                                log.exception("adapter report failed")
                        adapter_s += time.perf_counter() - t_h
                        if observe:
                            # per-exporter delivery/drop/lag gauges
                            # (adapter-export backpressure accounting
                            # — a slow or throwing exporter must be
                            # attributable from /debug/report)
                            monitor.note_adapter_export(
                                _qualify(hc.name, hc.namespace),
                                template, len(instances),
                                time.perf_counter() - t_h,
                                error=failed)
                            if not failed:
                                tmpl_records[template] = \
                                    tmpl_records.get(template, 0) + \
                                    len(instances)
        if observe:
            if adapter_s > 0 or tmpl_records:
                monitor.observe_report_stage("adapter_dispatch",
                                             adapter_s)
            for template, n in tmpl_records.items():
                monitor.REPORT_TEMPLATE_RECORDS.inc(n,
                                                    template=template)

    def _report_active_fused(self, bags: Sequence[Bag]
                             ) -> tuple[list[list[int]], Any]:
        """Per-bag ACTIVE REPORT-rule indices via the fused packed
        step: one device pull of the bitpacked overlay plane instead of
        the full [B, R] matched plane + host ns-masking (the generic
        _resolve path pulls the whole [B, R] plane per RPC, ~20 MB at
        10k rules and B=2048). Shares the check path's tensorize and
        overlay decode (incl. fallback patching, ns masking and
        resolve-error accounting). Record counts pad to the prewarmed
        serving bucket shapes, and oversize batches run in
        largest-bucket CHUNKS — arbitrary (client-controlled) report
        sizes must never compile a fresh XLA program in-band (the
        variable-shape pathology device_quota.py documents).

        When the snapshot's report instances lowered
        (plan.report_lowering), the SAME pull additionally carries
        every instance-field value/valid plane (packed_report); the
        returned ReportFieldCtx materializes finished instances so
        report() skips InstanceBuilder.build entirely for them."""
        from istio_tpu.runtime.batcher import pad_to_bucket
        from istio_tpu.runtime.report_lower import ReportFieldCtx

        plan = self.fused
        rl = plan.report_lowering
        fctx = ReportFieldCtx(rl, self.snapshot.ruleset.interner) \
            if rl is not None else None
        # field rows live after the head + ref-bit + overlay words
        # (FusedPlan.packed_report row layout)
        base = 5 + plan.n_ref_words + plan.n_overlay_words
        rcols = None
        cap = self.buckets[-1] if self.buckets else len(bags) or 1
        out: list[list[int]] = []
        observe = self.observe
        for lo in range(0, len(bags), cap):
            chunk = bags[lo:lo + cap]
            padded = pad_to_bucket(chunk, self.buckets) \
                if self.buckets else chunk
            with monitor.resolve_timer():
                t_tz = time.perf_counter()
                batch, ns_ids = self._tensorize_for_device(padded)
                t_dev = time.perf_counter()
                packed = plan.packed_report(batch, ns_ids) \
                    if rl is not None \
                    else plan.packed_check(batch, ns_ids,
                                           observe=False)
                t_done = time.perf_counter()
                if observe:
                    # report-pipeline stages, per chunk (the report
                    # analog of tensorize/h2d+device_step — the
                    # packed_report call carries dispatch AND pull)
                    monitor.observe_report_stage("tensorize",
                                                 t_dev - t_tz)
                    monitor.observe_report_stage("device_field_eval",
                                                 t_done - t_dev)
            active_sub, col_pos = self._overlay_active(
                packed, chunk,
                np.asarray(ns_ids)[:len(chunk)])  # hotpath: sync-ok (host ids)
            if rcols is None:
                rcols = [(ridx, col_pos[ridx])
                         for ridx in sorted(plan.report_rules)
                         if ridx in col_pos]
            t_dec = time.perf_counter()
            if fctx is not None:
                # skip the unique-id decode for chunks with no active
                # report rule anywhere — their planes are never read
                any_active = bool(rcols) and bool(   # hotpath: sync-ok
                    active_sub[:, [p for _, p in rcols]].any())
                fctx.add_chunk(packed, base, len(chunk), batch,
                               decode=any_active)
                if observe:
                    monitor.observe_report_stage(
                        "intern_decode",
                        time.perf_counter() - t_dec)
            out.extend(
                [ridx for ridx, pos in rcols if active_sub[b, pos]]
                for b in range(len(chunk)))
        if fctx is not None:
            fctx.seal()
        return out, fctx

    def quota(self, bag: Bag, quota_name: str,
              args: QuotaArgs,
              deadline: float | None = None) -> QuotaResult:
        """Dispatches to at most ONE handler (dispatcher.go:242-260).
        With an executor attached the adapter call runs on its handler
        lane (bulkheaded, deadline-bounded — the shared-quota backend
        may be a genuinely remote side effect); inline otherwise."""
        actives = self._resolve([bag])[0][0]
        for ridx in actives:
            for hc, template, inst_names in self.snapshot.actions_for(
                    ridx, Variety.QUOTA):
                for iname in inst_names:
                    if iname.split(".")[0] != quota_name and \
                            iname != quota_name:
                        continue
                    handler = self._handler_for(hc)
                    if handler is None:
                        continue
                    try:
                        instance = self.snapshot.instances[iname].build(bag)
                    except EvalError as exc:
                        monitor.DISPATCH_ERRORS.inc()
                        return QuotaResult(granted_amount=0,
                                           status_code=INTERNAL,
                                           status_message=str(exc))
                    except Exception as exc:
                        # safeDispatch parity: a malformed attribute
                        # value must degrade to a typed INTERNAL
                        # denial, never fail the whole RPC untyped
                        monitor.DISPATCH_ERRORS.inc()
                        log.exception("quota instance build failed")
                        return QuotaResult(granted_amount=0,
                                           status_code=INTERNAL,
                                           status_message=str(exc))
                    ex = self.executor
                    if ex is not None:
                        from istio_tpu.runtime.config import _qualify
                        from istio_tpu.runtime.executor import \
                            quota_fallback
                        amount = args.quota_amount
                        act = ex.submit(
                            _qualify(hc.name, hc.namespace),
                            self._bound_quota(handler, template,
                                              instance, args),
                            lambda policy, reason, _a=amount:
                                quota_fallback(policy, reason, _a))
                        return ex.resolve(act, deadline)
                    try:
                        with monitor.dispatch_timer():
                            return handler.handle_quota(template, instance,
                                                        args)
                    except Exception as exc:
                        monitor.DISPATCH_ERRORS.inc()
                        log.exception("adapter quota failed")
                        return QuotaResult(granted_amount=0,
                                           status_code=INTERNAL,
                                           status_message=str(exc))
        # no matching quota rule: grant freely (reference returns empty)
        return QuotaResult(granted_amount=args.quota_amount)

    @staticmethod
    def _bound_quota(handler: Handler, template: str, instance,
                     args: QuotaArgs) -> Any:
        def call():
            with monitor.dispatch_timer():
                return handler.handle_quota(template, instance, args)
        return call

    def preprocess(self, bag: Bag) -> Bag:
        """APA phase (dispatcher.go:285) for one request."""
        return self.preprocess_batch([bag])[0]

    def preprocess_batch(self, bags: Sequence[Bag]) -> Sequence[Bag]:
        """APA phase (dispatcher.go:285) over a batch: run the
        ATTRIBUTE_GENERATOR actions and bind their outputs into a child
        bag a row (runtime/apa.GeneratedBag). Where the step binds them
        itself (`apa_in_step`), or there is no APA, the batch comes
        back as it is. The host path resolves the APA rules once for
        the batch (not at all where each is mesh-wide and matches
        everything) and counts its rows."""
        if not self.has_apa or self.apa_in_step:
            return bags
        actives = self._apa_rules_active(
            bags, lambda bs: [set(a) for a in self._resolve(bs)[0]])
        out = [self._generate(bag, act) for bag, act in zip(bags, actives)]
        if self.observe:
            monitor.note_apa_rows("host", len(out))
        return out

    def _with_apa(self, bags: Sequence[Bag]) -> list[Bag]:
        """`bags` with the APA's outputs bound where they are not yet:
        on the host, the rules by the whole-snapshot oracle, so no
        device step runs (check_host_oracle, a row the host decides)."""
        if not self.has_apa or all(isinstance(bag, GeneratedBag)
                                   for bag in bags):
            return list(bags)
        oracle = self._oracle()

        def resolve(raw: Sequence[Bag]) -> list[set]:
            out = []
            for bag in raw:
                ns = _namespace_of(bag, self.identity_attr)
                act = set()
                for ridx in self._apa_actions()[1]:
                    rule_ns = oracle.rules[ridx].namespace
                    if rule_ns and rule_ns != ns:
                        continue
                    try:
                        if oracle.evaluate(ridx, bag):
                            act.add(ridx)
                    except Exception:
                        monitor.RESOLVE_ERRORS.inc()
                out.append(act)
            return out

        raw = [b for b in bags if not isinstance(b, GeneratedBag)]
        made = iter(self._generate(bag, act) for bag, act in
                    zip(raw, self._apa_rules_active(raw, resolve)))
        out = [bag if isinstance(bag, GeneratedBag) else next(made)
               for bag in bags]
        if self.observe:
            monitor.note_apa_rows("host", len(raw))
        return out

    def _apa_actions(self) -> tuple[list, list]:
        """([(rule, handler cfg, template, instance names)] of every
        ATTRIBUTE_GENERATOR action in rule order, [the rules among them
        that some request may not activate]): a rule in the default
        namespace with no match is active for all."""
        if self._apa is None:
            snap = self.snapshot
            acts = [(r, hc, template, names)
                    for r in range(len(snap.rules))
                    for hc, template, names in snap.actions_for(
                        r, Variety.ATTRIBUTE_GENERATOR)]
            rs = snap.ruleset
            cond = sorted({r for r, *_ in acts
                           if snap.rules[r].match.strip() not in ("", "true")
                           or int(rs.rule_ns[r]) != rs.ns_ids[""]})
            self._apa = (acts, cond)
        return self._apa

    def _apa_rules_active(self, bags: Sequence[Bag], resolve) -> list:
        """Each bag's active APA rules: `resolve(bags)` → active rule
        sets, asked only where some APA rule is conditional."""
        acts, cond = self._apa_actions()
        always = {r for r, *_ in acts} - set(cond)
        if not cond:
            return [always] * len(bags)
        return [always | (a & set(cond)) for a in resolve(bags)]

    def _generate(self, bag: Bag, active: set) -> GeneratedBag:
        """One request's APA on the host: each active action's instance
        built on `bag`, its adapter asked, the outputs bound."""
        from istio_tpu.runtime.apa import bind, parse_bindings

        child = GeneratedBag(parent=bag)
        for ridx, hc, template, inst_names in self._apa_actions()[0]:
            if ridx not in active:
                continue
            handler = self._handler_for(hc)
            if handler is None:
                continue
            for iname in inst_names:
                ib = self.snapshot.instances[iname]
                try:
                    instance = ib.build(bag)
                    outputs = handler.generate_attributes(template,
                                                          instance)
                except EvalError as exc:
                    monitor.DISPATCH_ERRORS.inc()
                    log.warning("APA %s: %s", iname, exc)
                    continue
                except Exception:
                    monitor.DISPATCH_ERRORS.inc()
                    log.exception("APA adapter failed")
                    continue
                bindings = getattr(ib, "attribute_bindings", None)
                if bindings:
                    parsed = getattr(ib, "_parsed_bindings", None)
                    if parsed is None:
                        parsed = parse_bindings(bindings,
                                                self.snapshot.finder)
                        ib._parsed_bindings = parsed
                    bind(child, parsed, outputs, bag)
                else:
                    for key, value in outputs.items():
                        child.set(key.replace("_", "."), value)
        return child
