"""Fused serving plan — wire the PolicyEngine into the check path.

The reference server assembles the same runtime it benchmarks
(mixer/pkg/server/server.go:92); this module is that assembly step for
the TPU build: given a validated Snapshot, extract every CHECK action
the fused device step can absorb (denier → DenySpec, id-exact string
lists → ListEntrySpec) and build one PolicyEngine per snapshot —
REUSING the snapshot's compiled RuleSetProgram, so a config swap pays
rule compilation once. Everything that cannot lower (rbac/opa/apikey
handlers, regex/CIDR/case-insensitive lists, refreshable list
providers, rules whose predicate fell back to the host oracle) is
collected into `host_actions` for the dispatcher to overlay per
request.

Quota IS on the served device path: QUOTA-variety actions are wired
into `quota_actions`, the check step's activity bits say which quota
rules matched each request (no re-resolve), and allocations ride the
per-handler device counter pools in runtime/device_quota.py — a host
dedup-replay cache in front (memquota.go:259 buildWithDedup
semantics), batched device scatter-add allocation behind it. The host
memquota adapter remains the fallback for non-memquota quota handlers
and the generic (non-fused) dispatch path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Mapping

import numpy as np

from istio_tpu.models.policy_engine import (DenySpec, INTERNAL,
                                            ListEntrySpec, PolicyEngine,
                                            OK, PERMISSION_DENIED,
                                            RbacSpec)
from istio_tpu.runtime import monitor
from istio_tpu.runtime.config import Snapshot
from istio_tpu.templates import Variety
from istio_tpu.utils.log import scope

log = scope("runtime.fused")

_FUSABLE_LIST_TYPES = ("STRINGS", "REGEX", "IP_ADDRESSES")

# latency-tier byte-plane width: batches whose every string fits this
# many bytes serve through a str_bytes plane sliced to it (see
# FusedPlan.narrow_batch) — the worst-case max_str_len plane is paid
# only by batches that actually carry long strings
STR_TIER_MIN = 32


# The bucket of the wide program (FusedPlan.wide_bucket): rows with a
# subject at the narrow cap are served this many a launch, as many
# launches as a batch's long rows need, through ONE more step shape a
# snapshot. Sorted by their longest subject first, so a launch's scan
# runs as long as its own rows ask.
WIDE_BUCKET = 256


_BY = {name: i for i, name in enumerate(monitor.CHECK_DECIDED_BY)}


def str_tiers(layout, interner=None) -> tuple[int, ...]:
    """Byte-plane length tiers for a snapshot: (STR_TIER_MIN, L) when
    the layout carries real byte slots wider than the small tier, else
    the single full width. Each tier is one extra jit trace per bucket
    (prewarmed like buckets are), bought back on every easy batch: the
    H2D bytes and every full-width byte op (prefix/suffix/exact
    compares, lex_cmp) shrink L/STR_TIER_MIN-fold.

    `interner`: the snapshot's InternTable, consulted AFTER every
    program compiled (its max_byte_const_len is grow-only). A tier
    below the longest compiled byte CONSTANT is unsound — narrowing
    slices constant rows, and a constant longer than the tier loses
    real tail bytes (e.g. the subject of `"long...".endsWith(attr)`),
    flipping verdicts the runtime str_lens check cannot catch — so the
    small tier only exists when every constant fits it."""
    L = layout.max_str_len
    min_safe = STR_TIER_MIN
    if interner is not None:
        min_safe = max(min_safe,
                       int(getattr(interner, "max_byte_const_len", 0)))
    if layout.n_byte_slots and L > min_safe:
        return (min_safe, L)
    return (L,)


def pack_bool_rows(flags, n_words: int):
    """[B, n_words*32] bool → int32 word rows [n_words, B]: THE wire
    convention for every bitpacked plane of the packed pull (ref bits,
    overlay bits, report-field valid bits) — little-endian bit order
    within each 32-bit word, transposed so words stack as rows. Device
    side; `unpack_word_rows` is the host inverse."""
    import jax.numpy as jnp
    from jax import lax

    b = flags.shape[0]
    bit_w = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    words = jnp.sum(flags.reshape(b, n_words, 32).astype(jnp.uint32)
                    * bit_w[None, None, :], axis=2)
    return lax.bitcast_convert_type(words, jnp.int32).T


def unpack_word_rows(rows: np.ndarray, n_bits: int) -> np.ndarray:
    """Host inverse of pack_bool_rows: int32 word rows [W, B] (a slice
    of the packed pull) → bool [B, n_bits]."""
    return np.unpackbits(
        np.ascontiguousarray(rows.T).view(np.uint8), axis=1,
        bitorder="little")[:, :n_bits].astype(bool)


def dedup_bit_rows(planes) -> tuple[np.ndarray, np.ndarray]:
    """Exact dedup of the rows of bool planes [B, w_i] laid side by
    side (any w_i may be 0) → (first [U]: one representative row per
    distinct row, inverse [B]: each row's class, so that row b equals
    row first[inverse[b]] in every plane). Rows are bit-packed into
    uint64 words, ceil(Σw / 64) a row, and sorted as integers: the key
    is an injective image of the row, nothing is hashed."""
    n = planes[0].shape[0]
    width = sum(p.shape[1] for p in planes)
    bits = np.zeros((n, max(64, -(-width // 64) * 64)), bool)
    at = 0
    for p in planes:
        bits[:, at:at + p.shape[1]] = p
        at += p.shape[1]
    words = np.packbits(bits, axis=1).view(np.uint64)
    order = np.lexsort(words.T)
    ranked = words[order]
    new = np.ones(n, bool)
    new[1:] = (ranked[1:] != ranked[:-1]).any(axis=1)
    inverse = np.empty(n, np.intp)
    inverse[order] = np.cumsum(new) - 1
    return order[new], inverse


def class_int_rows(columns) -> tuple[np.ndarray, np.ndarray]:
    """Exact classes of the rows of integer columns [B] laid side by
    side → (first [U]: one representative row a class, inverse [B]:
    each row's class), as dedup_bit_rows gives them for bit planes.
    Each column becomes one digit of a mixed-radix int64 key (its
    offset from the column's least value where the span is small, else
    its 1-D np.unique rank; a constant column is no digit at all) and
    one 1-D np.unique over the key names the classes: injective, so
    nothing is hashed, and never np.unique over rows (`axis=0`)."""
    n = len(columns[0])
    key, radix = None, 1
    for col in columns:
        lo, hi = int(col.min()), int(col.max())   # hotpath: sync-ok host planes
        if lo == hi:
            continue
        span = hi - lo + 1
        if span <= 1 << 20:
            digit = col.astype(np.int64) - lo
        else:
            uniq, digit = np.unique(col, return_inverse=True)
            span = len(uniq)
        if radix * span >= 1 << 62:
            uniq, key = np.unique(key, return_inverse=True)
            radix = len(uniq)
        key = digit if key is None else key * span + digit
        radix *= span
    if key is None:
        return np.zeros(1, np.intp), np.zeros(n, np.intp)
    _, first, inverse = np.unique(key, return_index=True,
                                  return_inverse=True)
    return first, inverse


@dataclasses.dataclass
class FusedPlan:
    """Per-snapshot serving plan: device engine + host overlay map."""
    engine: PolicyEngine
    # rule idx → CHECK actions the device cannot absorb (same tuples as
    # Snapshot.actions_for); host-fallback rules carry ALL their actions
    host_actions: dict[int, list]
    host_rule_idx: np.ndarray          # sorted keys of host_actions
    # per rule: attrs referenced by its CHECK instances (generic-path
    # ReferencedAttributes parity: active rules add instance attr uses)
    instance_attrs: list[frozenset]
    deny_info: dict[int, tuple[int, str]]   # rule → (code, message)
    list_rules: frozenset
    # rules whose rbac action is fused (device pseudo-rule NFA,
    # compiler/rbac_lower.py) — for status messages + diagnostics
    rbac_rules: frozenset = frozenset()
    # rule idx → index into monitor.CHECK_DECIDED_BY of the section
    # whose verdict a deny_rule of that index is (a rule with several
    # fused actions: the step's own tie order, deny → list → rbac); one
    # entry more than rule rows, `ok`, for the rows no rule denied
    decided_section: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(1, np.int8))
    # why list actions stayed host-side, e.g. "CASE_INSENSITIVE_STRINGS",
    # "provider-refreshed", "REGEX:unsupported-pattern" (bench
    # enumeration of the unfusable envelope)
    unfused_list_kinds: tuple = ()
    # rules carrying REPORT-variety actions: their activity bits ride
    # overlay_cols so dispatcher.report reads ONE bitpacked pull
    # instead of the full [B, R] matched plane (r4)
    report_rules: frozenset = frozenset()
    # QUOTA-variety wiring for the served quota loop
    # (grpcServer.go:188-230): [(rule idx, handler qname, instance
    # qname, accepted quota names)] in rule order. The rules' activity
    # bits ride overlay_cols so the gRPC quota loop never re-resolves
    # (runtime/device_quota.py).
    quota_actions: tuple = ()
    # C++ wire→tensor decoder (istio_tpu/native); None when the
    # toolchain is unavailable — python Tensorizer serves instead
    native: Any = None
    # rules whose FIRST check action is fused — device status wins ties
    # against host-overlay actions of the same rule (config action order)
    fused_first_rules: frozenset = frozenset()
    # the only rule columns the host ever inspects per request: rules
    # with host-overlay actions, host-fallback predicates, or non-empty
    # instance attribute sets. The dispatcher converts JUST these
    # columns of the [B, R] matched plane — at 10k rules the full-plane
    # copy was the serving bottleneck.
    overlay_cols: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    fused_deny: int = 0
    fused_lists: int = 0
    # referenced-attribute items: item j < n_columns is the column's
    # slot/derived attr, item n_columns + m is map slot m's attr name.
    # The device computes the FULL per-request referenced bitmap
    # (predicate attrs of ns-visible rules + instance attrs of active
    # rules) and ships it bitpacked — at 10k rules the host-side
    # per-request set unions and the [B, R] overlay pull (~5 MB per
    # batch) were the serving bottleneck.
    item_names: list = dataclasses.field(default_factory=list)
    inst_mask: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 0), np.int8))
    pred_map_mask: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0, 0), np.int8))
    # rule → instance attrs with no layout item (rare); such rules stay
    # in overlay_cols and their names merge host-side
    unmapped_instance_attrs: dict = dataclasses.field(default_factory=dict)
    _ns_pred_cache: dict = dataclasses.field(default_factory=dict)
    # the dp×mp mesh build_fused_plan sharded the engine step over, or
    # None: what packed_check selects its launch on (see there)
    mesh: Any = None
    # jit of _base_step: THE program of a served Check batch off a mesh
    _step: Any = None
    # jit of _base_step(wide=True), `step_wide`: the program of the
    # rows a length split sends to the wide byte plane
    _step_wide: Any = None
    _packer: Any = None
    # compiled REPORT instance-field programs (runtime/report_lower.py)
    # — None when no report instance lowered; the dispatcher then keeps
    # the host InstanceBuilder.build for every instance
    report_lowering: Any = None
    # on-device per-rule hit/deny/err accumulators + exemplar
    # reservoirs (runtime/rulestats.RuleTelemetry) — None when rule
    # telemetry is disabled (ServerArgs.rule_telemetry=False). Folded
    # by packed_check (inside its one program) and packed_check_instep
    # on check batches only; drained off the hot path by the aggregator.
    telemetry: Any = None
    _report_packer: Any = None
    _instep_packer: Any = None
    # byte-plane length tiers (str_tiers(layout)): serving batches whose
    # strings all fit the small tier compile/serve at the sliced shape
    str_tiers: tuple = ()
    # observed-check tier usage: byte-plane width actually served →
    # batch count (GIL-atomic int bumps; /debug/roofline judges the
    # live device_step p50 against the dominant width, not the
    # worst-case max_str_len plane)
    _tier_served: dict = dataclasses.field(default_factory=dict)
    # observed-check (bucket rows, byte width) → batch count: the
    # shapes live traffic actually serves. A config swap warms THESE
    # synchronously pre-swap (swap latency scales with what traffic
    # uses, not the full bucket × tier product) and defers the rest to
    # a post-swap background warm.
    _shape_served: dict = dataclasses.field(default_factory=dict)
    # (bucket rows, byte width) pairs whose serving programs are
    # compiled (prewarm dummies and organic trips both register)
    _warmed_shapes: set = dataclasses.field(default_factory=set)
    # a background warm is still filling _warmed_shapes: batches at
    # missing shapes bridge to the host oracle (Dispatcher._check_fused)
    # instead of tracing in-band
    _warm_pending: bool = False
    # completed prewarm_instep (buckets, counts-shape) combinations
    _instep_warmed: set = dataclasses.field(default_factory=set)

    @property
    def n_ref_words(self) -> int:
        return (len(self.item_names) + 31) // 32

    def narrow_batch(self, batch):
        """Latency-tier bucket specialization (byte-plane axis): when
        every string in the batch fits the small tier, slice str_bytes
        to it so the engine step + packer run (and prewarm) a tighter
        XLA shape instead of riding the max_str_len worst case.
        Verdict-identical by construction: sliced lanes are zero
        padding past every row's length, and the truncation contract
        compares str_lens against layout.max_str_len — which narrowing
        never changes (a row truncated at ingest has len == max_str_len
        and keeps the full-width shape). Host-side numpy only."""
        w = self._serve_width(batch)   # single home of tier routing
        if not isinstance(batch.str_bytes, np.ndarray) \
                or w >= int(batch.str_bytes.shape[2]):
            return batch
        return dataclasses.replace(
            batch,
            str_bytes=np.ascontiguousarray(batch.str_bytes[:, :, :w]))

    @property
    def n_overlay_words(self) -> int:
        return (len(self.overlay_cols) + 31) // 32

    @property
    def wide_width(self) -> int:
        """Width of the byte plane `step_wide` serves long rows at; 0
        where the snapshot has no such program (no byte slot to
        truncate, or a mesh: its step is shard_engine_check's jit)."""
        return 0 if self.mesh is not None \
            else self.engine.ruleset.layout.wide_str_len

    @staticmethod
    def wide_bucket(buckets) -> int:
        """Rows a launch of the wide program: WIDE_BUCKET, or the
        largest serving bucket where that is smaller."""
        return min(WIDE_BUCKET, max(buckets))

    def packed_check(self, batch, ns_ids, observe: bool = True,
                     n_real: int | None = None) -> np.ndarray:
        """The engine step + device-side packing into ONE int32 array
        [5 + W + C, B] pulled with a single host↔device sync (W =
        n_ref_words, C = len(overlay_cols)). Pulling plane-by-plane
        costs one sync per plane (chip_smoke.py prints
        device_sync_ms), and the unpacked referenced/overlay planes
        are megabytes of D2H per batch.

        Rows: 0 status, 1 valid_duration_s (f32 bits), 2
        valid_use_count, 3 deny_rule, 4 err_count (broadcast),
        5..5+W referenced-item bits (little-endian within each int32),
        then matched[:, overlay_cols] BITPACKED the same way (raw,
        ns-unmasked) — a 1k-column overlay plane shipped as int32 was
        8 MB/batch of D2H.

        `n_real`: count of non-padding rows (the leading prefix);
        rows past it are bucket padding the rule-telemetry fold must
        ignore. None = every row is real.

        Off a mesh the step, the rule-telemetry delta, its fold into
        the resident accumulators and the packer are ONE jitted
        program (_base_step): a launch costs ~0.6–2 ms of host wall
        whatever it computes, and the verdict's planes never leave
        the program. Under a mesh the engine step is
        shard_engine_check's own jit, so the same closures are
        launched apart behind it (_launch_apart)."""
        return self.packed_check_parts([(batch, ns_ids, n_real)],
                                       observe)[0]

    def packed_check_parts(self, parts, observe: bool = True) -> list:
        """packed_check for a served batch in one part or, split by
        subject length (Dispatcher._split_by_length), several: `parts`
        holds (batch, ns_ids, n_real) each, a part on the wide byte
        plane served by `step_wide`. Every part is launched before any
        is pulled, under ONE stage `h2d` (span dispatch.step) and ONE
        stage `device_step`, so the stages tile a pump's cycle as they
        do for a batch of one program; → the parts' packed arrays, in
        order. A wide part's row 4 is per row (_base_packer)."""
        staged = []
        by_width: dict = {}     # byte-plane width → rows served at it
        for batch, ns_ids, n_real in parts:
            batch = self.narrow_batch(batch)   # latency-tier byte plane
            ns_arr = np.asarray(ns_ids)    # hotpath: sync-ok (host ids)
            b = ns_arr.shape[0]
            if observe:
                w = int(batch.str_bytes.shape[2])
                self._tier_served[w] = self._tier_served.get(w, 0) + 1
                key = (int(batch.ids.shape[0]), w)
                self._shape_served[key] = \
                    self._shape_served.get(key, 0) + 1
                by_width[w] = by_width.get(w, 0) + \
                    (b if n_real is None else n_real)
            # rows the rule telemetry counts. `observe=False` (prewarm
            # dummy batches — a compile would dwarf every real
            # observation — and the fused report fallback, which is
            # REPORT traffic) runs the same executable with none:
            # prewarm compiles exactly what is served, and counts
            # nothing. Only check trips feed the Check() decomposition
            # either.
            counted = np.int32((b if n_real is None else n_real)
                               if observe else 0)
            staged.append((batch, ns_arr, counted))
        if observe:
            monitor.note_rows_by_width(by_width)
            # fault-injection seam at the device boundary (chaos suite
            # + scripts/chaos_smoke.py): an injected exception here
            # unwinds exactly like a real device-step failure. Gated
            # on observe so prewarm dummy trips and the fused report
            # fallback never trip the breaker.
            from istio_tpu.runtime.resilience import CHAOS
            CHAOS.device_step()
        # stage `h2d` = the async launch with the implicit transfer
        # of every jit argument; stage `device_step` = the blocking
        # pull (waits the programs out, then D2H).
        with monitor.stage("h2d", on=observe):
            if self.mesh is not None:
                devs = [self._launch_apart(batch, ns_arr, counted, observe)
                        for batch, ns_arr, counted in staged]
            else:
                with monitor.span("dispatch.step", on=observe):
                    devs = [self._launch_step(batch, ns_arr, counted)
                            for batch, ns_arr, counted in staged]
                if observe:
                    monitor.note_device_programs("check", len(devs))
        with monitor.stage("device_step", on=observe):
            # the host<->device sync, one a program — hotpath: sync-ok
            outs = [np.asarray(dev) for dev in devs]   # hotpath: sync-ok
            # these (bucket, width) shapes' programs are compiled now —
            # the swap-warm oracle bridge stops routing them away
            for batch, _, _ in staged:
                self._warmed_shapes.add((int(batch.ids.shape[0]),
                                         int(batch.str_bytes.shape[2])))
        return outs

    def _launch_step(self, batch, ns_arr, counted):
        """Launch the one program of a Check batch (async) and return
        the packed verdict's device handle. With a telemetry plane a
        batch that counts rides the accumulator handles through the
        program under RuleTelemetry's lock (chain): per-rule counts
        stay exact under concurrent pumps and a drain's swap."""
        import jax

        if int(batch.str_bytes.shape[2]) == self.wide_width:
            if self._step_wide is None:
                self._step_wide = jax.jit(self._base_step(wide=True))
            step = self._step_wide
        else:
            if self._step is None:
                self._step = jax.jit(self._base_step())
            step = self._step
        eng, tele = self.engine, self.telemetry
        if tele is None:
            return step(eng.params, batch, ns_arr, eng.quota_counts,
                        counted)
        shape = (int(batch.ids.shape[0]), int(batch.str_bytes.shape[2]))
        if not counted or shape not in self._warmed_shapes:
            # a trip that counts nothing (prewarm, Report traffic)
            # runs on the resident zeros and leaves the live
            # accumulators and their lock alone; so does the first
            # trip at a shape, ahead of its counted one: a compile
            # under that lock would stop the other pump and the drain
            dev, _ = step(eng.params, batch, ns_arr, eng.quota_counts,
                          np.int32(0), *tele.zeros)
            if not counted:
                return dev
        return tele.chain(
            lambda *accs: step(eng.params, batch, ns_arr,
                               eng.quota_counts, counted, *accs))

    def _launch_apart(self, batch, ns_arr, counted, observe: bool):
        """packed_check under a mesh: the sharded engine step, the
        rule-telemetry delta + fold and the packer as four launches,
        each under its own dispatch.* span."""
        import jax

        if self._packer is None:
            self._packer = jax.jit(self._base_packer())
        with monitor.span("dispatch.step", on=observe):
            verdict = self.engine.check(batch, ns_arr)
        programs = 2                       # the step and the packer
        if observe and self.telemetry is not None:
            with monitor.span("dispatch.rulestats"):
                self.telemetry.observe(
                    verdict, ns_arr, np.arange(ns_arr.shape[0]) < counted)
            programs += self.telemetry.OBSERVE_PROGRAMS
        with monitor.span("dispatch.pack", on=observe):
            dev = self._packer(verdict, ns_arr)
        if observe:
            monitor.note_device_programs("check", programs)
        return dev

    def _base_step(self, wide: bool = False):
        """The step(params, batch, req_ns, quota_counts, n_real[,
        acc_hit, acc_deny, acc_err]) closure _launch_step jits: the
        engine's raw step, RuleTelemetry's pure delta folded into the
        accumulators passed through, and _base_packer's pack, composed
        in one trace. `n_real` is a traced scalar: rows at or past it
        count nothing (0 for prewarm dummies and Report traffic: one
        executable a shape). Without a telemetry plane the program is
        step + pack and returns `packed` alone.

        It is NAMED `step`: the profiler then calls it `jit_step`,
        which is what the benchmark's device readers look for
        (benchmark/scopes.py STEP_MODULE), and the named scopes of
        the three closures (match … combine, rulestats, pack) ride
        inside it. The served engine carries no device quota
        (build_fused_plan passes quotas=()), so the counts the raw
        step returns are the dummy it was handed.

        `wide`: the same trace over the wide byte plane, NAMED
        `step_wide` (the profiler's `jit_step_wide`: the readers that
        look for `jit_step` do not count it) with the same scopes
        inside, its packer's row 4 per row. The byte predicates take
        their truncation cap from the plane they are traced on
        (tensor_expr._cap) and their scans stop at the launch's longest
        subject (a while_loop on max(lens)), so nothing else differs."""
        import jax
        import jax.numpy as jnp

        raw_step = self.engine.raw_step
        pack = self._base_packer(row_errs=wide)
        delta = None if self.telemetry is None else self.telemetry.delta

        def step(params, batch, req_ns, quota_counts, n_real, *accs):
            verdict, _counts = raw_step(params, batch, req_ns,
                                        quota_counts)
            packed = pack(verdict, req_ns)
            if delta is None:
                return packed
            real = jnp.arange(req_ns.shape[0]) < n_real
            deltas = delta(verdict.matched, verdict.err, verdict.status,
                           verdict.deny_rule, req_ns, real)
            with jax.named_scope("rulestats"):
                return packed, tuple(a + d for a, d in zip(accs, deltas))

        if wide:
            step.__name__ = step.__qualname__ = "step_wide"
        return step

    def _base_packer(self, row_errs: bool = False):
        """The pack(verdict, req_ns) closure shared by packed_check and
        packed_report (which appends report-field planes).

        `row_errs` (the wide program): row 4 says of each row what the
        broadcast count says of the batch. 0 where no rule visible to
        the row erred, else -(n + 1), n the row's share of err_count
        (config rules; an rbac pseudo-rule's err makes the row
        negative and counts nothing). The host reads it beside what
        it knows already, which rows saturate the plane: such a row
        with an err is undecided, any other err is an evaluation
        error as upstream means it (Dispatcher._join_parts)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from istio_tpu.ops.bytes_ops import pack_bits, unpack_bits
        rs = self.engine.ruleset
        cols = jnp.asarray(self.overlay_cols, jnp.int32)
        rule_ns = jnp.asarray(rs.rule_ns)
        default_ns = rs.ns_ids[""]
        # instance/predicate-map literal masks ride bit-packed and
        # unpack to int8 on device per step (pack_bits discipline —
        # one bit of information per cell, 1/8 the resident int8 bytes)
        inst_bits = jnp.asarray(pack_bits(self.inst_mask))
        pred_map_bits = jnp.asarray(pack_bits(self.pred_map_mask))
        n_items = len(self.item_names)
        n_words = self.n_ref_words
        n_cols = rs.layout.n_columns
        n_maps_used = self.pred_map_mask.shape[1]
        dims = (((1,), (0,)), ((), ()))
        n_counted = self.engine.count_rules

        def errs_by_row(verdict, req_ns):
            seen = verdict.err & ((rule_ns[None, :] == default_ns) |
                                  (rule_ns[None, :] == req_ns[:, None]))
            counted = seen if n_counted is None else \
                seen & (jnp.arange(seen.shape[1]) < n_counted)[None, :]
            n = jnp.sum(counted.astype(jnp.int32), axis=1)
            return jnp.where(jnp.any(seen, axis=1), -(n + 1), 0)

        def pack(verdict, req_ns):
            b = verdict.status.shape[0]
            dur_bits = lax.bitcast_convert_type(
                verdict.valid_duration_s, jnp.int32)
            head = jnp.stack([
                verdict.status, dur_bits, verdict.valid_use_count,
                verdict.deny_rule,
                errs_by_row(verdict, req_ns) if row_errs else
                jnp.broadcast_to(verdict.err_count.astype(jnp.int32),
                                 (b,))])
            parts = [head]
            if n_items:
                ns_ok = (rule_ns[None, :] == default_ns) | \
                        (rule_ns[None, :] == req_ns[:, None])
                active = verdict.matched & ns_ok
                items = jnp.zeros((b, n_words * 32), bool)
                # predicate columns: the engine already ns-masks
                # (referenced is [B, max(n_cols, 1)] — slice off
                # the 0-column placeholder when the layout is empty)
                items = items.at[:, :n_cols].set(
                    verdict.referenced[:, :n_cols])
                if n_maps_used:
                    pred_map_j = unpack_bits(
                        pred_map_bits, n_maps_used).astype(jnp.int8)
                    pred_maps = lax.dot_general(
                        ns_ok.astype(jnp.int8), pred_map_j, dims,
                        preferred_element_type=jnp.int32) > 0
                    items = items.at[
                        :, n_cols:n_cols + n_maps_used].set(
                            items[:, n_cols:n_cols + n_maps_used]
                            | pred_maps)
                inst_mask_j = unpack_bits(
                    inst_bits, n_items).astype(jnp.int8)
                inst = lax.dot_general(
                    active.astype(jnp.int8), inst_mask_j, dims,
                    preferred_element_type=jnp.int32) > 0
                items = items.at[:, :n_items].set(
                    items[:, :n_items] | inst)
                parts.append(pack_bool_rows(items, n_words))
            if cols.size:
                ov = jnp.take(verdict.matched, cols, axis=1)
                n_ov_words = (cols.shape[0] + 31) // 32
                ov_pad = jnp.zeros((b, n_ov_words * 32), bool)
                ov_pad = ov_pad.at[:, :cols.shape[0]].set(ov)
                parts.append(pack_bool_rows(ov_pad, n_ov_words))
            return jnp.concatenate(parts, axis=0) \
                if len(parts) > 1 else head

        # metadata only: the profiler's device plane names the scope
        return jax.named_scope("pack")(pack)

    def packed_report(self, batch, ns_ids,
                      observe: bool = True) -> np.ndarray:
        """packed_check's rows PLUS the report instance-field planes in
        the SAME single device pull (VERDICT r4 item 3 — one RTT per
        report batch, never one per plane): after the overlay words
        come F int32 value rows (intern ids; 0/1 for BOOL fields) and
        ceil(F/32) bitpacked field-valid words, F =
        report_lowering.n_fields. Falls back to packed_check when no
        instance lowered. `observe=False` for prewarm dummy trips —
        they must not feed the served-shape set below."""
        if self.report_lowering is None or \
                self.report_lowering.n_fields == 0:
            # zero field programs (e.g. reportnothing-only): the check
            # rows alone serve; ReportFieldCtx slices empty planes.
            # observe=False: this is REPORT traffic — it must not feed
            # the Check() stage decomposition. Narrow ONCE and pass
            # the narrowed batch down (packed_check's own narrow then
            # early-returns — no second byte-plane copy).
            batch = self.narrow_batch(batch)
            if observe:
                key = (int(batch.ids.shape[0]),
                       int(batch.str_bytes.shape[2]))
                self._shape_served[key] = \
                    self._shape_served.get(key, 0) + 1
            return self.packed_check(batch, ns_ids, observe=False)
        import jax

        batch = self.narrow_batch(batch)   # latency-tier byte plane
        # report traffic feeds the served-shape set too: the pre-swap
        # warm must cover the shapes the report coalescer serves (its
        # field program compiles per shape like the check program),
        # or the first post-swap report trip pays an in-band trace —
        # there is no oracle bridge on the report path
        if observe:
            key = (int(batch.ids.shape[0]),
                   int(batch.str_bytes.shape[2]))
            self._shape_served[key] = \
                self._shape_served.get(key, 0) + 1
        ns_arr = np.asarray(ns_ids)        # hotpath: sync-ok (host ids)
        if self.mesh is not None:
            if self._report_packer is None:
                self._report_packer = jax.jit(self._base_report_packer())
            dev = self._report_packer(self.engine.check(batch, ns_arr),
                                      ns_arr, batch)
        else:
            # the field planes read the batch alone, so the Check
            # program's packed rows ARE the head: launch it counting
            # nothing (REPORT traffic) and append the fields to its
            # handle. No second step-bearing program a shape to
            # compile, keep resident and prewarm.
            if self._report_packer is None:
                self._report_packer = jax.jit(self._base_report_fields())
            dev = self._report_packer(
                self._launch_step(batch, ns_arr, np.int32(0)), batch)
        return np.asarray(dev)             # hotpath: sync-ok (the pull)

    def _base_report_fields(self):
        """The fields(packed, batch) closure: packed_check's rows with
        the report field planes appended."""
        import jax.numpy as jnp

        rl = self.report_lowering
        n_f = rl.n_fields
        n_w = rl.n_valid_words

        def fields(head, fbatch):
            vals, valid = rl.field_planes(fbatch)
            b = vals.shape[1]
            vpad = jnp.zeros((b, n_w * 32), bool)
            vpad = vpad.at[:, :n_f].set(valid.T)
            return jnp.concatenate(
                [head, vals, pack_bool_rows(vpad, n_w)], axis=0)

        return fields

    def _base_report_packer(self):
        """The packr(verdict, req_ns, batch) closure packed_report jits
        under a mesh, behind the sharded step: _base_packer's rows plus
        the report field planes."""
        pack = self._base_packer()
        fields = self._base_report_fields()

        def packr(verdict, req_ns, fbatch):
            return fields(pack(verdict, req_ns), fbatch)

        return packr

    def packed_check_instep(self, batch, ns_ids, q: Mapping[str, Any],
                            counts,
                            n_real: int | None = None) -> tuple[Any, Any]:
        """packed_check's rows PLUS an IN-STEP quota allocation in the
        SAME device program — the quota-carrying batch pays ONE trip
        instead of check-trip + pool-flush-trip serialized on the
        transport.

        Narrowed to the batch's byte tier like packed_check.

        `q` carries the staged per-row alloc arrays from
        device_quota.InlineQuotaSession (buckets/amounts/be/mx/active/
        ticks/lasts/rolling, plus rule_idx — the ruleset row whose
        ns-masked matched bit gates the alloc by zeroing its amount;
        the roll runs for every staged row). `counts` is the pool's
        counter buffer; returns DEVICE handles (packed, new_counts) —
        packed's last TWO rows are granted and gate once pulled."""
        import jax

        batch = self.narrow_batch(batch)   # latency-tier byte plane
        if n_real is None or n_real > 0:   # prewarm dummies pass 0
            w = int(batch.str_bytes.shape[2])
            self._tier_served[w] = self._tier_served.get(w, 0) + 1
            key = (int(batch.ids.shape[0]), w)
            self._shape_served[key] = self._shape_served.get(key, 0) + 1
        if self._instep_packer is None:
            import jax.numpy as jnp
            from istio_tpu.models.quota_alloc import \
                make_rolling_alloc_step
            pack = self._base_packer()
            rs = self.engine.ruleset
            rule_ns = jnp.asarray(rs.rule_ns)
            default_ns = rs.ns_ids[""]
            n_buckets, k_ticks = counts.shape
            # the general contended-mixed kernel unconditionally: the
            # fast/unit variants are host-selected shape optimizations
            # the merged program cannot branch on
            seg = make_rolling_alloc_step(int(n_buckets), int(k_ticks),
                                          jit=False)[3]

            def packq(verdict, req_ns, cnt, buckets, amounts, be, mx,
                      active, ticks, lasts, rolling, rule_idx):
                head = pack(verdict, req_ns)
                rows = jnp.arange(buckets.shape[0])
                safe_rule = jnp.clip(rule_idx, 0,
                                     rule_ns.shape[0] - 1)
                rn = rule_ns[safe_rule]
                ns_ok = (rn == default_ns) | (rn == req_ns)
                # the reference's quota loop runs ONLY on successful
                # checks (grpcServer.go:188) — a denied row must not
                # consume. Device status IS the final status here:
                # instep_quota_target refuses snapshots with host
                # overlay actions or host-fallback predicates. The
                # gate zeroes AMOUNTS (consume nothing) while the ROLL
                # runs for every STAGED row — the session's optimistic
                # host tick bookkeeping depends on rolls being
                # unconditional (chained-trip staging).
                gate = active & (rule_idx >= 0) & ns_ok & \
                    (verdict.status == 0) & \
                    verdict.matched[rows, safe_rule]
                amt = jnp.where(gate, amounts, 0)
                granted, new_cnt = seg(cnt, buckets, amt, be, mx,
                                       active, ticks, lasts, rolling)
                extra = jnp.stack([granted.astype(jnp.int32),
                                   gate.astype(jnp.int32)])
                return jnp.concatenate([head, extra], axis=0), new_cnt

            self._instep_packer = jax.jit(packq)
        # the caller (Dispatcher._check_fused) holds stage `h2d` over
        # this whole call; the dispatch.* spans split it as in
        # packed_check. Prewarm dummies (n_real=0) observe nothing.
        served = n_real is None or n_real > 0
        with monitor.span("dispatch.step", on=served):
            verdict = self.engine.check(batch, ns_ids)
        ns_arr = np.asarray(ns_ids)        # hotpath: sync-ok (host ids)
        if self.telemetry is not None:
            # in-step quota batches ARE check traffic — same per-rule
            # fold as packed_check (prewarm_instep passes n_real=0 so
            # its dummy trips fold all-masked, counting nothing)
            with monitor.span("dispatch.rulestats", on=served):
                b = ns_arr.shape[0]
                real = np.arange(b) < (b if n_real is None else n_real)
                self.telemetry.observe(verdict, ns_arr, real)
        # DEVICE handles, not host arrays: the caller swaps the pool
        # onto new_counts at dispatch (the next trip chains on-device)
        # and pulls `packed` with the counter token already released
        with monitor.span("dispatch.pack", on=served):
            out = self._instep_packer(
                verdict,
                ns_arr,
                counts,
                q["buckets"], q["amounts"], q["be"], q["mx"],
                q["active"], q["ticks"], q["lasts"], q["rolling"],
                q["rule_idx"])
        if served:
            monitor.note_device_programs(
                "instep", 2 if self.telemetry is None
                else 2 + self.telemetry.OBSERVE_PROGRAMS)
        self._warmed_shapes.add((int(batch.ids.shape[0]),
                                 int(batch.str_bytes.shape[2])))
        return out

    def pred_attrs_for_ns(self, ns_id: int) -> frozenset:
        """Union of predicate attr uses over rules visible to ns_id —
        every visible rule's predicate is evaluated for the request
        (protoBag.go:117 tracking → compile-time bitmaps)."""
        cached = self._ns_pred_cache.get(ns_id)
        if cached is not None:
            return cached
        rs = self.engine.ruleset
        default = rs.ns_ids[""]
        out: set = set()
        for ridx in range(rs.n_rules):
            if rs.rule_ns[ridx] == default or rs.rule_ns[ridx] == ns_id:
                out |= rs.attr_names[ridx]
        frozen = frozenset(out)
        self._ns_pred_cache[ns_id] = frozen
        return frozen

    def cache_stats(self) -> dict:
        """Compiled-program cache occupancy per jitted serving program
        (one entry per warmed bucket shape) — the /debug/cache
        payload's compile-cache half. A serving bucket missing here
        means the next batch at that shape pays an in-band XLA
        trace."""
        out: dict[str, Any] = {}
        for name in ("_step", "_step_wide", "_packer", "_report_packer",
                     "_instep_packer"):
            f = getattr(self, name, None)
            if f is None:
                continue
            size = getattr(f, "_cache_size", None)
            out[name.lstrip("_") + "_entries"] = \
                int(size()) if callable(size) else None
        out["ns_pred_cache_entries"] = len(self._ns_pred_cache)
        return out

    def prewarm(self, buckets, should_stop=None, backoff=None) -> None:
        """Trace/compile the engine step for every serving batch shape.

        Called by the controller BEFORE the atomic dispatcher swap
        (SURVEY hard-part #5; resolver refcount-swap semantics,
        mixer/pkg/runtime/resolver.go:240-247): the old snapshot keeps
        serving while the new one's jit cache fills, so no request pays
        multi-second trace time in-band after a config change.

        `should_stop`: zero-arg callable polled between shapes — the
        controller's BACKGROUND initial prewarm passes its shutdown
        flag so a closing server never leaves a daemon thread compiling
        into interpreter teardown (C++ abort on exit). `backoff`: see
        warm_shapes."""
        self.warm_shapes(self.all_warm_shapes(buckets),
                         should_stop=should_stop, backoff=backoff)

    def all_warm_shapes(self, buckets) -> list:
        """Every (bucket rows, byte tier) pair narrow_batch can route
        a served batch to — the full shape product prewarm compiles."""
        lay = self.engine.ruleset.layout
        tiers = sorted(set(self.str_tiers or (lay.max_str_len,)))
        pairs = [(b, t) for b in sorted(set(buckets)) for t in tiers]
        if self.wide_width and pairs:
            # the one shape of the wide program
            pairs.append((self.wide_bucket(buckets), self.wide_width))
        return pairs

    def served_shapes(self) -> set:
        """(bucket rows, byte width) pairs live traffic has actually
        served through this plan — the pre-swap warm priority set."""
        return set(self._shape_served)

    def map_served_shapes(self, buckets, served) -> list:
        """Old plan's observed (bucket, width) pairs mapped onto THIS
        plan's warmable (bucket, tier) pairs (width → smallest tier
        that holds it). Empty/unmappable `served` returns the full
        product — the conservative first-swap behavior."""
        pairs = self.all_warm_shapes(buckets)
        if not served:
            return pairs
        lay = self.engine.ruleset.layout
        tiers = sorted(set(self.str_tiers or (lay.max_str_len,)))
        bset = set(buckets)
        out: list = []
        for b, w in sorted(served):
            if w == self.wide_width and w:   # the wide program's shape
                pair = (self.wide_bucket(buckets), w)
            elif b not in bset:
                continue
            else:
                pair = (b, next((t for t in tiers if t >= w), tiers[-1]))
            if pair not in out:
                out.append(pair)
        return out or pairs

    def warm_shapes(self, pairs, should_stop=None,
                    backoff=None) -> None:
        """Compile the SERVING entry (packed_check's program(s), plus
        packed_report's field program when report instances lowered)
        for each (bucket, byte-tier) pair. `backoff` is
        called between shapes: the config-swap path passes a
        serving-latency yield (controller._serving_backoff) so a
        loaded single core keeps serving while this thread traces
        jaxprs — the warm yields to traffic, never the reverse."""
        from istio_tpu.runtime import forensics
        for b, tier in pairs:
            if should_stop is not None and should_stop():
                return
            # mesh event timeline: prewarm start/end per shape — the
            # compile whose GIL hold a swap-window p99 spike blames
            forensics.record_event("prewarm", shape=f"{b}x{tier}",
                                   phase="start")
            t_w0 = time.perf_counter()
            batch = self._dummy_batch(b, tier)
            self.packed_check(batch, np.zeros(b, np.int32),
                              observe=False)
            if self.report_lowering is not None and \
                    self.report_rules:
                self.packed_report(batch, np.zeros(b, np.int32),
                                   observe=False)
            forensics.record_event(
                "prewarm", shape=f"{b}x{tier}", phase="end",
                wall_ms=round((time.perf_counter() - t_w0) * 1e3, 1))
            if backoff is not None:
                backoff()

    def begin_warm(self) -> None:
        """A warm phase is running (or queued) for this plan: serving
        batches at not-yet-compiled shapes bridge to the host oracle
        (Dispatcher._check_fused) instead of tracing in-band. Pair
        with end_warm() in a finally — a plan left warm-pending would
        oracle-serve its missing shapes forever."""
        self._warm_pending = True

    def end_warm(self) -> None:
        self._warm_pending = False

    def swap_warm_pending(self, batch) -> bool:
        """True while a warm is still filling this batch's (bucket,
        byte-tier) program slot — the dispatcher then serves the batch
        through the CPU oracle: the new snapshot's semantics apply
        immediately and no request pays the in-band XLA trace."""
        if not self._warm_pending:
            return False
        b = int(batch.ids.shape[0])
        return (b, self._serve_width(batch)) not in self._warmed_shapes

    def _serve_width(self, batch) -> int:
        """The byte-plane width this batch serves at — THE tier-routing
        decision (narrow_batch slices to it, swap_warm_pending keys on
        it; one implementation so the two can never drift). Host numpy
        only."""
        w = int(batch.str_bytes.shape[2])
        tiers = self.str_tiers
        if w == self.wide_width or len(tiers) < 2 \
                or not isinstance(batch.str_bytes, np.ndarray) \
                or not isinstance(batch.str_lens, np.ndarray):
            return w
        t = tiers[0]
        if w <= t or not batch.str_lens.size:
            return w
        m = int(batch.str_lens.max())   # hotpath: sync-ok (host numpy)
        return t if m <= t else w

    def _dummy_batch(self, b: int, tier: int):
        """Dummy AttributeBatch routed to exactly one byte-plane tier
        of bucket size `b`. The dummy MUST flatten to the same pytree
        treedef as served batches (hash_ids included) — a treedef
        mismatch compiles a cache entry serving never hits, silently
        un-doing the prewarm."""
        from istio_tpu.compiler.layout import AttributeBatch

        lay = self.engine.ruleset.layout
        tiers = sorted(set(self.str_tiers or (lay.max_str_len,)))
        # lens pinned AT the tier so narrow_batch routes the dummy to
        # exactly this tier's compiled shape (0 → small tier;
        # max_str_len → the full-width worst case)
        lens = 0 if tier == min(tiers) else tier
        return AttributeBatch(
            ids=np.zeros((b, lay.n_columns), np.int32),
            present=np.zeros((b, lay.n_columns), bool),
            map_present=np.zeros((b, max(lay.n_maps, 1)), bool),
            # the wide tier is a plane of its own width
            str_bytes=np.zeros((b, max(lay.n_byte_slots, 1),
                                max(lay.max_str_len, tier)), np.uint8),
            str_lens=np.full((b, max(lay.n_byte_slots, 1)),
                             lens, np.int32),
            hash_ids=np.zeros((b, lay.n_columns), np.int32))

    def _prewarm_batches(self, b: int) -> list:
        """Dummy AttributeBatches covering every byte-plane tier for
        bucket size `b` (prewarm_instep's shape walk)."""
        lay = self.engine.ruleset.layout
        tiers = sorted(set(self.str_tiers or (lay.max_str_len,)))
        return [self._dummy_batch(b, tier) for tier in tiers]

    def prewarm_instep(self, buckets, counts, should_stop=None) -> None:
        """Compile the in-step quota program for every serving bucket
        (ServerArgs.quota_in_step fronts call this before taking
        traffic — a first-quota-batch compile mid-serve stalls every
        row behind it; RuntimeServer wires it on every publish).
        `counts` only supplies the counter SHAPE; the dummy trips
        never touch the pool's live buffer. `should_stop` is polled
        between shapes like prewarm's — a closing server must be able
        to stop a background warm before interpreter teardown.

        Completed (buckets, counts-shape) combinations are memoized:
        the post-publish backstop re-invokes this after the pre-swap
        hook already warmed, and re-executing every bucket × tier
        dummy trip would contend with live traffic for the device."""
        import jax.numpy as jnp

        key = (tuple(sorted(set(buckets))), tuple(counts.shape))
        if key in self._instep_warmed:
            return
        zero_counts = jnp.zeros_like(counts)
        for b in sorted(set(buckets)):
            for batch in self._prewarm_batches(b):
                if should_stop is not None and should_stop():
                    return
                q = {"buckets": np.zeros(b, np.int32),
                     "amounts": np.zeros(b, np.int32),
                     "be": np.zeros(b, bool),
                     "mx": np.zeros(b, np.int32),
                     "active": np.zeros(b, bool),
                     "ticks": np.zeros(b, np.int32),
                     "lasts": np.zeros(b, np.int32),
                     "rolling": np.zeros(b, bool),
                     "rule_idx": np.full(b, -1, np.int32)}
                packed, _cnt = self.packed_check_instep(
                    batch, np.zeros(b, np.int32), q, zero_counts,
                    n_real=0)   # dummies must not feed rule telemetry
                np.asarray(packed)   # force compile + execute
        # only a COMPLETED warm counts (not stopped)
        self._instep_warmed.add(key)

    def rows_by_section(self, deny_rule: np.ndarray,
                        host_active: np.ndarray) -> np.ndarray:
        """One batch's rows per monitor.CHECK_DECIDED_BY entry, from
        the pulled deny_rule plane [B] and whether a host-overlay
        action was active on each row [B]. A row the device left OK
        carries INT32_MAX, which reads the lookup's last entry: `ok`,
        or `host` when host adapters then had the word."""
        by = self.decided_section[np.minimum(
            deny_rule, len(self.decided_section) - 1)]
        by = np.where((by == _BY["ok"]) & host_active, _BY["host"], by)
        return np.bincount(by, minlength=len(_BY))

    def message_for(self, rule_idx: int, status: int) -> str:
        """Best-effort status message for a device-produced denial."""
        info = self.deny_info.get(rule_idx)
        if info is not None and info[0] == status:
            return info[1]
        if rule_idx in self.rbac_rules:
            if status == PERMISSION_DENIED:
                return "RBAC: permission denied"   # rbac.go:241
            if status == INTERNAL:
                return "authorization instance evaluation failed"
        if rule_idx in self.list_rules:
            if status == INTERNAL:
                # absent/malformed value: the host path's EvalError /
                # adapter-panic shape, not a membership rejection
                return "list instance evaluation failed"
            name = self.engine.ruleset.rules[rule_idx].name
            return f"rejected by list check (rule {name})"
        return "denied by policy"


def build_fused_plan(snapshot: Snapshot,
                     mesh=None,
                     rule_telemetry: bool = True) -> FusedPlan | None:
    """Extract fusable CHECK actions and build the snapshot's engine.

    `mesh` (jax.sharding.Mesh, dp×mp) re-jits the engine step under the
    multi-chip serving layout (parallel/mesh.py shard_engine_check):
    requests shard over dp, rule rows over mp, one psum on the verdict
    fold — the SAME serving path, scaled across chips.

    `rule_telemetry` wires per-rule hit/deny/err accumulators
    (runtime/rulestats.RuleTelemetry) into the packed check step."""
    rs = snapshot.ruleset
    if rs.n_rules == 0:
        return None
    layout = rs.layout

    deny_by_rule: dict[int, DenySpec] = {}
    deny_info: dict[int, tuple[int, str]] = {}
    lists: list[ListEntrySpec] = []
    list_rules: set[int] = set()
    unfused_kinds: set[str] = set()
    rbacs: list[RbacSpec] = []
    rbac_rules: set[int] = set()
    host_actions: dict[int, list] = {}
    instance_attrs: list[frozenset] = []
    # ruleset rows beyond the config rules are rbac pseudo-rules — they
    # carry no actions and never appear in overlays or host fallbacks
    n_real = len(snapshot.rules)

    def add_host(ridx: int, action) -> None:
        host_actions.setdefault(ridx, []).append(action)

    fused_first: set[int] = set()
    for ridx in range(n_real):
        attrs: set = set()
        for pos, action in enumerate(
                snapshot.actions_for(ridx, Variety.CHECK)):
            hc, template, inst_names = action
            for iname in inst_names:
                attrs |= snapshot.instances[iname].referenced_attrs
            if ridx in rs.host_fallback:
                # device matched==False for fallback rules; their fused
                # contributions would be inert — run everything on host
                add_host(ridx, action)
                continue
            if hc.adapter == "rbac" and template == "authorization":
                from istio_tpu.runtime.config import _qualify
                handler_ref = _qualify(hc.name, hc.namespace)
                fused_insts, host_insts = [], []
                for iname in inst_names:
                    g = snapshot.rbac_groups.get((handler_ref, iname))
                    if g is not None and g.lowered:
                        fused_insts.append((iname, g))
                    else:
                        host_insts.append(iname)
                if fused_insts and pos == 0 and not host_insts:
                    fused_first.add(ridx)
                for iname, g in fused_insts:
                    rbacs.append(RbacSpec(
                        rule=ridx, allow_rows=g.allow_rows,
                        guard_row=g.guard_row,
                        valid_duration_s=float(
                            hc.params.get("caching_ttl_s", 60.0))))
                    rbac_rules.add(ridx)
                if host_insts:
                    add_host(ridx, (hc, template, host_insts))
                continue
            if hc.adapter == "denier":
                if pos == 0:
                    fused_first.add(ridx)
                code = int(hc.params.get("status_code", PERMISSION_DENIED))
                msg = str(hc.params.get("status_message", "denied"))
                dur = float(hc.params.get("valid_duration_s", 5.0))
                uses = int(hc.params.get("valid_use_count", 10_000))
                prev = deny_by_rule.get(ridx)
                if prev is None:
                    deny_by_rule[ridx] = DenySpec(
                        rule=ridx, status=code, valid_duration_s=dur,
                        valid_use_count=uses)
                    deny_info[ridx] = (code, msg)
                else:   # merged denier actions: first status, min TTLs
                    deny_by_rule[ridx] = DenySpec(
                        rule=ridx, status=prev.status,
                        valid_duration_s=min(prev.valid_duration_s, dur),
                        valid_use_count=min(prev.valid_use_count, uses))
                continue
            if hc.adapter == "list" and template == "listentry":
                fused, host = _split_list_instances(
                    snapshot, hc, inst_names, layout, unfused_kinds)
                if pos == 0 and fused and not host:
                    fused_first.add(ridx)
                for iname, value_attr in fused:
                    lists.append(ListEntrySpec(
                        rule=ridx, value_attr=value_attr,
                        entries=list(hc.params.get("overrides", ())),
                        blacklist=bool(hc.params.get("blacklist", False)),
                        valid_duration_s=float(
                            hc.params.get("caching_ttl_s", 300.0)),
                        valid_use_count=int(
                            hc.params.get("caching_use_count", 10_000)),
                        entry_type=str(hc.params.get("entry_type",
                                                     "STRINGS"))))
                    list_rules.add(ridx)
                if host:
                    add_host(ridx, (hc, template, host))
                continue
            add_host(ridx, action)
        instance_attrs.append(frozenset(attrs))

    # QUOTA-variety actions: recorded (in rule order) so the served
    # quota loop can reuse the check step's activity bits instead of
    # re-resolving (dispatcher.quota dispatches to at most ONE handler,
    # matching by instance name — dispatcher.go:242-260)
    quota_actions: list = []
    quota_rules: set[int] = set()
    for ridx in range(n_real):
        for hc, template, inst_names in snapshot.actions_for(
                ridx, Variety.QUOTA):
            from istio_tpu.runtime.config import _qualify
            for iname in inst_names:
                names = frozenset({iname, iname.split(".")[0]})
                quota_actions.append(
                    (ridx, _qualify(hc.name, hc.namespace), iname,
                     names))
                quota_rules.add(ridx)

    engine = PolicyEngine(ruleset=rs, finder=snapshot.finder,
                          deny=list(deny_by_rule.values()), lists=lists,
                          quotas=(), rbacs=rbacs, jit=True,
                          count_rules=n_real)
    if mesh is not None:
        from istio_tpu.parallel.mesh import shard_engine_check
        engine._step = shard_engine_check(mesh, engine)
    native = None
    try:
        from istio_tpu.native.tensorizer import NativeTensorizer
        native = NativeTensorizer(rs.layout, rs.interner)
    except Exception as exc:   # toolchain missing → python tensorize
        log.warning("native tensorizer unavailable, serving with the "
                    "python wire decoder: %s", exc)
    monitor.note_dfa_banks(rs.geometry.get("dfa_banks", ()))
    log.info("fused plan: %d deny rules, %d lists, %d rbac actions "
             "(%d pseudo-rules), %d host-overlay rules, native=%s",
             len(deny_by_rule), len(lists), len(rbacs),
             rs.n_rules - n_real, len(host_actions), native is not None)

    # referenced-attribute item space: every layout column (slot or
    # derived) plus every map slot. Instance attrs that map to an item
    # flow through the device bitmap; the rare unmappable ones keep
    # their rule in the host overlay.
    n_cols, n_maps = layout.n_columns, layout.n_maps
    item_names: list = [None] * (n_cols + n_maps)
    item_of: dict = {}
    for name, col in layout.slots.items():
        item_names[col] = name
        item_of[name] = col
    for pair, col in layout.derived_slots.items():
        item_names[col] = pair
        item_of[pair] = col
    for name, mcol in layout.map_slots.items():
        item_names[n_cols + mcol] = name
        item_of[name] = n_cols + mcol
    n_items = len(item_names)
    n_rows = int(rs.rule_ns.shape[0])   # incl. mp-sharding padding
    inst_mask = np.zeros((n_rows, n_items), np.int8)
    unmapped: dict[int, frozenset] = {}
    for ridx, attrs in enumerate(instance_attrs):
        if ridx in rs.host_fallback:
            # the device never knows whether a host-fallback rule
            # matched — its instance attrs merge host-side from the
            # oracle-overlaid activity bits
            if attrs:
                unmapped[ridx] = attrs
            continue
        missing = []
        for item in attrs:
            idx = item_of.get(item)
            if idx is None:
                missing.append(item)
            else:
                inst_mask[ridx, idx] = 1
        if missing:
            unmapped[ridx] = frozenset(missing)
    # predicate MAP-name uses (e.g. `ar["k"]` references "ar" too) —
    # the engine's referenced plane covers columns only
    pred_map_mask = np.zeros((n_rows, max(n_maps, 1)), np.int8)
    for ridx in range(rs.n_rules):
        for item in rs.attr_names[ridx]:
            if isinstance(item, str) and item in layout.map_slots:
                pred_map_mask[ridx, layout.map_slots[item]] = 1

    report_rules = {ridx for ridx in range(n_real)
                    if snapshot.actions_for(ridx, Variety.REPORT)}
    report_lowering = None
    if report_rules:
        try:
            from istio_tpu.runtime.report_lower import \
                build_report_lowering
            report_lowering = build_report_lowering(snapshot)
        except Exception:
            log.exception("report lowering failed; report instances "
                          "build on host")
    real_fallback = {r for r in rs.host_fallback if r < n_real}
    overlay = set(host_actions) | real_fallback | set(unmapped) \
        | quota_rules | report_rules
    telemetry = None
    if rule_telemetry:
        try:
            from istio_tpu.runtime.rulestats import RuleTelemetry
            telemetry = RuleTelemetry(rs, n_real)
        except Exception:
            log.exception("rule telemetry unavailable; serving "
                          "without per-rule accumulators")
    decided_section = np.full(n_rows + 1, _BY["deny"], np.int8)
    decided_section[-1] = _BY["ok"]
    for section, members in (("rbac", rbac_rules), ("list", list_rules),
                             ("deny", deny_info)):
        decided_section[np.asarray(sorted(members), np.intp)] = \
            _BY[section]
    return FusedPlan(engine=engine, native=native, mesh=mesh,
                     telemetry=telemetry,
                     decided_section=decided_section,
                     # AFTER every compile above (engine, report
                     # lowering): the interner's constant-length max
                     # is grow-only and now complete for this snapshot
                     str_tiers=str_tiers(layout, rs.interner),
                     host_actions=host_actions,
                     host_rule_idx=np.asarray(sorted(host_actions),
                                              np.int64),
                     instance_attrs=instance_attrs,
                     deny_info=deny_info,
                     list_rules=frozenset(list_rules),
                     rbac_rules=frozenset(rbac_rules),
                     quota_actions=tuple(quota_actions),
                     fused_first_rules=frozenset(fused_first),
                     overlay_cols=np.asarray(sorted(overlay), np.int64),
                     fused_deny=len(deny_by_rule), fused_lists=len(lists),
                     item_names=item_names,
                     inst_mask=inst_mask,
                     pred_map_mask=pred_map_mask[:, :n_maps]
                     if n_maps else np.zeros((n_rows, 0), np.int8),
                     unmapped_instance_attrs=unmapped,
                     unfused_list_kinds=tuple(sorted(unfused_kinds)),
                     report_rules=frozenset(report_rules),
                     report_lowering=report_lowering)


def _split_list_instances(snapshot: Snapshot, hc, inst_names, layout,
                          unfused_kinds: set | None = None
                          ) -> tuple[list, list]:
    """(fused [(iname, value_attr)], host [iname]) for a list action.

    Fusable entry types (each with its own device lowering in
    models/policy_engine.py ListEntrySpec):
      STRINGS       — static overrides, exact case-sensitive match
      REGEX         — every pattern inside the DFA-compilable subset
                      (ops/regex_dfa); value needs a byte slot
      IP_ADDRESSES  — every entry a parseable CIDR/address; value must
                      be an IP_ADDRESS/BYTES-typed attribute
                      (string-rendered IPs keep host semantics) with a
                      byte slot
    CASE_INSENSITIVE_STRINGS and refreshable providers keep list.go's
    host semantics (mixer/adapter/list/list.go:115-247); `unfused_kinds`
    collects why an action stayed host-side (bench enumeration,
    VERDICT r3 item 3)."""
    p: Mapping[str, Any] = hc.params
    et = p.get("entry_type", "STRINGS")

    def reject(reason: str) -> tuple[list, list]:
        if unfused_kinds is not None:
            unfused_kinds.add(reason)
        return [], list(inst_names)

    if et not in _FUSABLE_LIST_TYPES:
        return reject(et)
    if p.get("provider") is not None or p.get("provider_url"):
        return reject("provider-refreshed")
    entries = p.get("overrides", ())
    if et == "STRINGS":
        if not all(isinstance(e, str) for e in entries):
            return reject("STRINGS:non-string-entries")
    elif et == "REGEX":
        from istio_tpu.ops.regex_dfa import compile_regex
        try:
            for e in entries:
                compile_regex(str(e))
        except Exception:
            return reject("REGEX:unsupported-pattern")
    elif et == "IP_ADDRESSES":
        import ipaddress
        try:
            for e in entries:
                ipaddress.ip_network(str(e), strict=False)
        except ValueError:
            return reject("IP_ADDRESSES:bad-cidr")
    from istio_tpu.attribute.types import ValueType
    fused, host = [], []
    for iname in inst_names:
        ref = snapshot.instances[iname].value_attr_ref()
        slot_ok = ref is not None and (
            ref in layout.derived_slots if isinstance(ref, tuple)
            else ref in layout.slots)
        if et in ("REGEX", "IP_ADDRESSES"):
            slot_ok = slot_ok and ref in layout.byte_slots
        if et == "IP_ADDRESSES":
            # the device compares RAW IP BYTES against binary CIDR
            # prefixes — only IP_ADDRESS-typed attrs carry those.
            # Map-derived (tuple) refs are utf-8 TEXT ("10.1.2.3");
            # fusing them would compare text bytes against binary
            # prefixes and flip verdicts — host parses instead.
            if isinstance(ref, tuple) or \
                    layout.manifest.get(ref) != ValueType.IP_ADDRESS:
                slot_ok = False
        elif not isinstance(ref, tuple) and \
                layout.manifest.get(ref) == ValueType.IP_ADDRESS:
            # STRINGS/REGEX over an IP-typed value: the host adapter
            # normalizes the bytes to a textual IP before matching
            # (list_adapter.handle_check); the device id scan interns
            # bytes and strings under different tags and the byte plane
            # carries binary — no lowering matches, keep host
            slot_ok = False
        if slot_ok:
            fused.append((iname, ref))
        else:
            host.append(iname)
            if unfused_kinds is not None:
                unfused_kinds.add(f"{et}:value-not-lowerable")
    return fused, host
