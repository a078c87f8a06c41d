"""PolicyEngine — the fused batched Check()/Quota() device step.

Reference call stack being replaced (SURVEY.md §3.1, per request,
sequential): grpcServer.Check → dispatcher.Resolve (IL-interpret every
rule's match predicate, resolver.go:202-238) → per-action template
ProcessCheck (IL-interpret every instance field) → adapter Handle*
(denier.go, list.go:68, memquota.go:107) → combineResults
(dispatcher.go:322 — AND statuses, min TTLs).

Here the WHOLE pipeline for a batch of B requests is one XLA program:

    ruleset match          atom eval + index gathers  [B, R] 3-valued
    × namespace mask       broadcast compare          [B, R]
    deny actions           masked min-reduce          [B]
    listentry membership   gather + equality scan     [B, n_lists]
    quota alloc            scatter-add on counters    [B] (device state)
    referenced attrs       one more int8 matmul       [B, n_cols]
    combine                AND of statuses, min TTLs  CheckVerdict

Adapter semantics fused on device:
  * denier (mixer/adapter/denier): per-rule fixed status + TTLs.
  * list   (mixer/adapter/list): whitelist/blacklist membership of one
    expression value, lowered per entry type (ListEntrySpec): exact
    STRINGS as an interned-id equality scan over a padded
    [n_lists, max_entries] matrix, static REGEX entries as packed
    per-byte-slot DFA banks, IP_ADDRESSES as CIDR prefix compares in
    v6-mapped space. Case-insensitive and provider-refreshed lists
    keep list.go's host semantics via the runtime overlay.
  * memquota (mixer/adapter/memquota): token-bucket-style windowed
    counters resident on device; a batch allocates with a scatter-add
    and reads back grants (best-effort per replica, exactly like the
    reference's per-replica memquota).

Rules whose predicate cannot lower run host-side via the ruleset
program's oracle fallback; the runtime overlays their verdicts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import (AttributeBatch, InternTable, Tensorizer)
from istio_tpu.compiler.ruleset import Rule, RuleSetProgram, compile_ruleset
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.ops import bytes_ops
from istio_tpu.utils.log import scope

log = scope("models.policy_engine")

# istio.mixer.v1 / google.rpc status codes used on the check path.
OK = 0
NOT_FOUND = 5
PERMISSION_DENIED = 7
RESOURCE_EXHAUSTED = 8
INTERNAL = 13
UNAVAILABLE = 14
_BIG = np.float32(3.4e38)
# adapter CheckResult defaults (adapters/sdk.py) — INTERNAL results
# min these into the TTL fold, host-_combine parity
DEFAULT_DUR = np.float32(5.0)
DEFAULT_USES = np.int32(10_000)


# occurrence rank within key groups — single-sourced with the rolling
# quota kernels (models/quota_alloc.batch_rank)
from istio_tpu.models.quota_alloc import batch_rank as _batch_rank  # noqa: E402


@dataclasses.dataclass(frozen=True)
class DenySpec:
    """denier adapter wiring for one rule (denier.go params)."""
    rule: int                      # rule index in the ruleset
    status: int = PERMISSION_DENIED
    valid_duration_s: float = 5.0
    valid_use_count: int = 10000


@dataclasses.dataclass(frozen=True)
class ListEntrySpec:
    """list adapter wiring for one rule (listentry template +
    mixer/adapter/list): check `value_attr`'s membership in a fixed
    list. Three device lowerings by entry_type (list.go ListEntryType):

      STRINGS       — interned-id equality scan (exact match)
      REGEX         — packed byte-DFA bank over the value's byte slot
                      (Go regexp search semantics, ops/regex_dfa);
                      truncated values with no definitive prefix hit
                      mark the rule's err bit (the byte-predicate
                      truncation contract) and suppress the deny
      IP_ADDRESSES  — CIDR prefix compare over the value's IP bytes in
                      v6-mapped space, with v4/v6 version matching
                      (host parity: list_adapter._member)

    CASE_INSENSITIVE_STRINGS and provider-refreshed lists stay host-
    side (runtime/fused.py enumerates them as unfusable)."""
    rule: int
    value_attr: str                # attribute (or (map,key)) whose value is checked
    entries: Sequence[Any]         # list payload per entry_type
    blacklist: bool = False       # True: member → deny; False: non-member → deny
    valid_duration_s: float = 5.0
    valid_use_count: int = 10000
    entry_type: str = "STRINGS"


@dataclasses.dataclass(frozen=True)
class RbacSpec:
    """rbac adapter wiring for one rule (mixer/adapter/rbac rbac.go:181
    HandleAuthorization): the policy's (binding, subject, role-rule)
    triples were lowered to pseudo-rule rows (compiler/rbac_lower.py);
    the request is allowed iff ANY `allow_rows` row matched. The
    `guard_row` tracks host instance-evaluation errors: when it is not
    definitely-true, the host path would have failed the instance build
    with INTERNAL (dispatcher _safe_check), so the device reports the
    same."""
    rule: int
    allow_rows: tuple[int, ...]
    guard_row: int = -1            # -1: instance can never error
    valid_duration_s: float = 60.0  # handler caching_ttl_s


@dataclasses.dataclass(frozen=True)
class QuotaSpec:
    """memquota wiring for one rule: fixed-window rate limit keyed by an
    attribute's interned id (memquota.go rolling window simplified to
    fixed windows device-side; dedup stays in the runtime layer)."""
    rule: int
    key_attr: str
    max_amount: int = 100
    n_buckets: int = 4096          # hash space for keys


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CheckVerdict:
    """Batched check result (adapter.CheckResult semantics, check.go:28)."""
    status: Any            # int32 [B] — google.rpc code
    valid_duration_s: Any  # float32 [B]
    valid_use_count: Any   # int32 [B]
    referenced: Any        # bool [B, n_columns] attribute-use bitmap
    matched: Any           # bool [B, R] (diagnostics + host overlay)
    err: Any               # bool [B, R]
    deny_rule: Any         # int32 [B] — lowest rule idx that produced a
    #                        non-OK status; INT32_MAX when status is OK.
    #                        The serving overlay merges host adapter
    #                        results against this in rule order.
    err_count: Any         # int32 [] — total namespace-visible predicate
    #                        errors in the batch (monitoring; lets the
    #                        host skip converting the full err plane)

    def tree_flatten(self):
        return ((self.status, self.valid_duration_s, self.valid_use_count,
                 self.referenced, self.matched, self.err, self.deny_rule,
                 self.err_count), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class PolicyEngine:
    """Compiled fused policy step for one config snapshot.

    Construction compiles the ruleset + action tensors; `check(batch,
    ns_ids)` runs the fused device program. Quota state lives in
    `self.quota_counts` (donated through each step).
    """

    def __init__(self, rules: Sequence[Rule] | None = None,
                 finder: AttributeDescriptorFinder | None = None,
                 deny: Sequence[DenySpec] = (),
                 lists: Sequence[ListEntrySpec] = (),
                 quotas: Sequence[QuotaSpec] = (),
                 rbacs: Sequence[RbacSpec] = (),
                 interner: InternTable | None = None,
                 max_str_len: int | None = None,
                 jit: bool = True,
                 ruleset: RuleSetProgram | None = None,
                 count_rules: int | None = None):
        if ruleset is None:
            assert rules is not None and finder is not None
            # REGEX/CIDR lists match value BYTES — their value attrs
            # need byte (and, for map reads, derived) layout slots
            # (the snapshot builder does the same, runtime/config.py)
            lsrcs = [l.value_attr for l in lists
                     if l.entry_type in ("REGEX", "IP_ADDRESSES")]
            ruleset = compile_ruleset(
                rules, finder, interner=interner, max_str_len=max_str_len,
                jit=False,
                extra_derived_keys=[r for r in lsrcs
                                    if isinstance(r, tuple)],
                extra_byte_sources=sorted(set(lsrcs), key=str))
        self.ruleset = ruleset
        self.finder = finder
        lay = self.ruleset.layout
        interner = self.ruleset.interner
        # rule-axis width INCLUDING mp-sharding padding (ruleset
        # rule_pad) — every per-rule tensor and the matched/err planes
        # share it; rs.n_rules counts real rules only
        R = int(self.ruleset.rule_ns.shape[0])
        # err accounting covers only real config rules: pseudo-rule rows
        # (rbac lowering) err routinely on requests missing instance
        # attrs, which maps to adapter-level INTERNAL, not a predicate
        # resolve error (host parity: RESOLVE_ERRORS vs DISPATCH_ERRORS)
        self.count_rules = count_rules
        if count_rules is None or count_rules >= R:
            err_rule_mask = None
        else:
            err_rule_mask = np.zeros(R, bool)
            err_rule_mask[:count_rules] = True

        # --- denier tensors ---
        deny_mask = np.zeros(R, bool)
        deny_status = np.full(R, OK, np.int32)
        deny_dur = np.full(R, _BIG, np.float32)
        deny_uses = np.full(R, np.iinfo(np.int32).max, np.int32)
        for d in deny:
            deny_mask[d.rule] = True
            deny_status[d.rule] = d.status
            deny_dur[d.rule] = d.valid_duration_s
            deny_uses[d.rule] = d.valid_use_count

        # --- list tensors ---
        n_lists = len(lists)
        max_entries = max((len(l.entries) for l in lists
                           if l.entry_type == "STRINGS"), default=1) or 1
        list_ids = np.zeros((max(n_lists, 1), max_entries), np.int64)
        list_rule = np.zeros(max(n_lists, 1), np.int32)
        list_slot = np.zeros(max(n_lists, 1), np.int32)
        list_black = np.zeros(max(n_lists, 1), bool)
        list_code = np.full(max(n_lists, 1), PERMISSION_DENIED, np.int32)
        list_dur = np.full(max(n_lists, 1), _BIG, np.float32)
        list_uses = np.full(max(n_lists, 1), np.iinfo(np.int32).max, np.int32)
        for i, l in enumerate(lists):
            if l.entry_type == "STRINGS":
                ids = [interner.intern(e) for e in l.entries]
                list_ids[i, :len(ids)] = ids
                # pad with ID_INVALID: a present slot's id is never 0
                # (constants ≥ 1, ephemerals ≤ -1), and absent slots are
                # masked by `present`, so padding can never match
                list_ids[i, len(ids):] = 0
            # REGEX/IP rows keep all-zero id entries (member False from
            # the id scan; their member columns are overwritten by the
            # byte-level paths below)
            list_rule[i] = l.rule
            list_slot[i] = self._slot_for(l.value_attr)
            list_black[i] = l.blacklist
            # host-path parity (adapters/list_adapter.py): blacklist hit
            # → PERMISSION_DENIED, whitelist miss → NOT_FOUND
            list_code[i] = PERMISSION_DENIED if l.blacklist else NOT_FOUND
            list_dur[i] = l.valid_duration_s
            list_uses[i] = l.valid_use_count
        rx_banks = self._build_regex_banks(lists)
        cidr_bank = self._build_cidr_bank(lists)

        # --- rbac tensors ---
        n_rbac = len(rbacs)
        k_allow = max((len(r.allow_rows) for r in rbacs), default=1) or 1
        # indices into m_ext = [matched | FALSE col | TRUE col]:
        # padding of allow rows points at FALSE (OR identity), a missing
        # guard points at TRUE (instance can never error)
        FALSE_COL = R
        TRUE_COL = R + 1
        rb_rule = np.zeros(max(n_rbac, 1), np.int32)
        rb_dur = np.full(max(n_rbac, 1), _BIG, np.float32)
        rb_guard = np.full(max(n_rbac, 1), TRUE_COL, np.int32)
        rb_allow = np.full((max(n_rbac, 1), k_allow), FALSE_COL,
                           np.int32)
        for i, r in enumerate(rbacs):
            rb_rule[i] = r.rule
            rb_dur[i] = r.valid_duration_s
            if r.guard_row >= 0:
                rb_guard[i] = r.guard_row
            for s, row in enumerate(r.allow_rows):
                rb_allow[i, s] = row

        # --- quota tensors ---
        n_quotas = len(quotas)
        q_rule = np.zeros(max(n_quotas, 1), np.int32)
        q_slot = np.zeros(max(n_quotas, 1), np.int32)
        q_max = np.zeros(max(n_quotas, 1), np.int32)
        q_nb = np.ones(max(n_quotas, 1), np.int32)
        n_buckets = max((q.n_buckets for q in quotas), default=1)
        if n_quotas * n_buckets >= np.iinfo(np.int32).max:
            raise ValueError(
                f"quota hash space too large: {n_quotas} quotas × "
                f"{n_buckets} buckets must stay below 2^31-1 (int32 "
                "composite sort keys)")
        self._quota_slots = frozenset(
            self._slot_for(q.key_attr) for q in quotas)
        for i, q in enumerate(quotas):
            q_rule[i] = q.rule
            q_slot[i] = self._slot_for(q.key_attr)
            q_max[i] = q.max_amount
            q_nb[i] = q.n_buckets   # per-quota hash space (counter rows
            #                         are padded to the widest quota)
        self.quota_counts = jnp.zeros((max(n_quotas, 1), n_buckets),
                                      jnp.int32)
        self._has_quota = n_quotas > 0

        ruleset_run = self.ruleset.fn   # fn(ruleset_params, batch)
        # referenced-attr literal mask rides as BIT LANES (pack_bits)
        # and unpacks to int8 on device once per step — the [R, C]
        # int8 mask at 50k rules was MBs of resident weight for one
        # bit of information per cell
        from istio_tpu.ops.bytes_ops import pack_bits
        n_attr_cols = int(self.ruleset.attr_mask.shape[1])
        attr_mask_bits = jnp.asarray(pack_bits(self.ruleset.attr_mask))
        rule_ns = jnp.asarray(self.ruleset.rule_ns)
        default_ns = self.ruleset.ns_ids[""]
        deny_mask_j = jnp.asarray(deny_mask)
        deny_status_j = jnp.asarray(deny_status)
        deny_dur_j = jnp.asarray(deny_dur)
        deny_uses_j = jnp.asarray(deny_uses)
        has_lists = n_lists > 0
        max_len = self.ruleset.layout.max_str_len
        list_ids_j = jnp.asarray(list_ids)
        list_rule_j = jnp.asarray(list_rule)
        list_slot_j = jnp.asarray(list_slot)
        list_black_j = jnp.asarray(list_black)
        list_code_j = jnp.asarray(list_code)
        list_dur_j = jnp.asarray(list_dur)
        list_uses_j = jnp.asarray(list_uses)
        q_rule_j = jnp.asarray(q_rule)
        q_slot_j = jnp.asarray(q_slot)
        q_max_j = jnp.asarray(q_max)
        q_nb_j = jnp.asarray(q_nb)
        has_rbac = n_rbac > 0
        rb_rule_j = jnp.asarray(rb_rule)
        rb_dur_j = jnp.asarray(rb_dur)
        rb_guard_j = jnp.asarray(rb_guard)
        rb_allow_j = jnp.asarray(rb_allow)
        err_rule_mask_j = None if err_rule_mask is None \
            else jnp.asarray(err_rule_mask)
        dims = (((1,), (0,)), ((), ()))

        # Value-carrying bank tensors ride in PARAMS (traced
        # arguments), never as closure constants: intern ids and
        # config values (status codes, TTLs, list membership ids,
        # quota limits, per-rule namespaces) change under config
        # deltas without changing any shape, and baking them into the
        # HLO would change the compiled program's identity — defeating
        # jax's jit cache across swaps and the persistent compilation
        # cache across restarts (compiler/cache.py: a constant-only
        # config edit must keep every HLO bit-identical). Only
        # structure-bearing banks (packed regex DFAs, CIDR tables)
        # stay closure-bound — editing those changes shapes, which is
        # a legitimate recompile.
        pe_params = {
            "pe_rule_ns": rule_ns,
            "pe_attr_mask_bits": attr_mask_bits,
            "pe_deny_mask": deny_mask_j,
            "pe_deny_status": deny_status_j,
            "pe_deny_dur": deny_dur_j,
            "pe_deny_uses": deny_uses_j,
            "pe_list_ids": list_ids_j,
            "pe_list_rule": list_rule_j,
            "pe_list_slot": list_slot_j,
            "pe_list_black": list_black_j,
            "pe_list_code": list_code_j,
            "pe_list_dur": list_dur_j,
            "pe_list_uses": list_uses_j,
            "pe_q_rule": q_rule_j,
            "pe_q_slot": q_slot_j,
            "pe_q_max": q_max_j,
            "pe_q_nb": q_nb_j,
            "pe_rb_rule": rb_rule_j,
            "pe_rb_dur": rb_dur_j,
            "pe_rb_guard": rb_guard_j,
            "pe_rb_allow": rb_allow_j,
        }
        if err_rule_mask_j is not None:
            pe_params["pe_err_rule_mask"] = err_rule_mask_j

        def step(params: Any, batch: AttributeBatch, req_ns: Any,
                 quota_counts: Any):
            rule_ns = params["pe_rule_ns"]
            attr_mask_bits = params["pe_attr_mask_bits"]
            deny_mask_j = params["pe_deny_mask"]
            deny_status_j = params["pe_deny_status"]
            deny_dur_j = params["pe_deny_dur"]
            deny_uses_j = params["pe_deny_uses"]
            list_ids_j = params["pe_list_ids"]
            list_rule_j = params["pe_list_rule"]
            list_slot_j = params["pe_list_slot"]
            list_black_j = params["pe_list_black"]
            list_code_j = params["pe_list_code"]
            list_dur_j = params["pe_list_dur"]
            list_uses_j = params["pe_list_uses"]
            q_rule_j = params["pe_q_rule"]
            q_slot_j = params["pe_q_slot"]
            q_max_j = params["pe_q_max"]
            q_nb_j = params["pe_q_nb"]
            rb_rule_j = params["pe_rb_rule"]
            rb_dur_j = params["pe_rb_dur"]
            rb_guard_j = params["pe_rb_guard"]
            rb_allow_j = params["pe_rb_allow"]
            err_rule_mask_j = params.get("pe_err_rule_mask")
            b = batch.ids.shape[0]
            # jax.named_scope: metadata only — the profiler's device
            # plane names each op's scope, so a trace can group the
            # step's device time by section
            with jax.named_scope("match"):
                matched, not_matched, err = ruleset_run(params, batch)
            ns_ok = (rule_ns[None, :] == default_ns) | \
                    (rule_ns[None, :] == req_ns[:, None])
            active = matched & ns_ok                      # [B, R]

            # Status combining is LOWEST-RULE-INDEX-WINS, the same
            # deterministic rule as the host dispatcher (_combine keeps
            # the first non-OK result, and the host iterates rules in
            # ascending index order). google.rpc codes are not
            # severity-ordered, so a max() over codes would diverge from
            # the host path on multi-deny requests. Ties within one rule
            # resolve deny → list → quota. TTLs take the min over every
            # ACTIVE fused rule (dispatcher.go:322 semantics).
            BIGI = jnp.iinfo(jnp.int32).max
            rule_idx = jnp.arange(active.shape[1], dtype=jnp.int32)

            with jax.named_scope("deny"):
                dmask = active & deny_mask_j[None, :]
                d_key = jnp.where(dmask, rule_idx[None, :], BIGI)
                d_arg = jnp.argmin(d_key, axis=1)
                cand_rule = jnp.min(d_key, axis=1)
                cand_status = deny_status_j[d_arg]
                dur = jnp.min(
                    jnp.where(dmask, deny_dur_j[None, :], _BIG), axis=1)
                uses = jnp.min(jnp.where(dmask, deny_uses_j[None, :],
                                         np.iinfo(np.int32).max), axis=1)

            if has_lists:
                with jax.named_scope("lists"):
                    sym = batch.ids[:, list_slot_j]           # [B, L]
                    sym_ok = batch.present[:, list_slot_j]
                    member = jnp.any(
                        sym[:, :, None] == list_ids_j[None, :, :], axis=2)
                    # und exists ONLY when regex banks do: the err
                    # scatter-max below is a [B, R]-operand scatter, and
                    # running it with an identically-False mask faulted
                    # the TPU at 50k rules (r4 regression; XLA kernel
                    # fault) while buying nothing
                    und = jnp.zeros_like(member) if rx_banks else None
                    for bank in rx_banks:
                        # one packed DFA scan per value byte slot answers
                        # every REGEX list over that subject. MXU one-hot
                        # formulations win at EVERY batch size (profiled
                        # r4/r5: the per-step [B, N] gather is latency-
                        # bound regardless of B — it alone held the B=64
                        # latency tier over the 1ms budget)
                        s_data = batch.str_bytes[:, bank["bslot"]]
                        s_lens = batch.str_lens[:, bank["bslot"]]
                        if bank["packed"] is not None:
                            m = bytes_ops.dfa_match_many_onehot(
                                s_data, s_lens, bank["packed"])
                        elif bank["packed_blk"] is not None:
                            m = bytes_ops.dfa_match_many_onehot_blocked(
                                s_data, s_lens, bank["packed_blk"])
                        else:
                            m = bytes_ops.dfa_match_many(
                                s_data, s_lens, bank["trans"],
                                bank["accept"])
                        m8 = m.astype(jnp.int8)
                        hit = lax.dot_general(
                            m8, bank["M"], dims,
                            preferred_element_type=jnp.int32) > 0
                        dec = lax.dot_general(
                            m8, bank["M_def"], dims,
                            preferred_element_type=jnp.int32) > 0
                        # truncation contract (= byte predicates): a $-free
                        # prefix hit is definitive; anything else on a
                        # truncated value is undecidable → err the rule's
                        # row, suppress the deny (fail-open, counted)
                        # (the wide plane's rows are whole up to its
                        # width: tensor_expr._cap)
                        trunc = (s_lens >= max(
                            max_len, s_data.shape[1]))[:, None]
                        member = member.at[:, bank["pos"]].set(
                            jnp.where(trunc, dec, hit))
                        und = und.at[:, bank["pos"]].set(trunc & ~dec)
                    bad = None        # present-but-unusable values
                    if cidr_bank is not None:
                        vb = batch.str_bytes[:, cidr_bank["bslots"], :16]
                        vl = batch.str_lens[:, cidr_bank["bslots"]]
                        mapped = jnp.zeros_like(vb)
                        mapped = mapped.at[:, :, 10:12].set(255)
                        mapped = mapped.at[:, :, 12:16].set(vb[:, :, 0:4])
                        is4 = vl == 4
                        v6m_pre = jnp.concatenate(
                            [jnp.zeros(10, jnp.uint8),
                             jnp.full(2, 255, jnp.uint8)])
                        val_mapped = jnp.all(
                            vb[:, :, :12] == v6m_pre[None, None, :], axis=2)
                        v = jnp.where(is4[:, :, None], mapped, vb)
                        val_ok = is4 | (vl == 16)
                        val_v4 = is4 | ((vl == 16) & val_mapped)
                        hit_e = jnp.all(
                            (v[:, :, None, :] & cidr_bank["mask"][None]) ==
                            cidr_bank["prefix"][None], axis=3)
                        hit_e &= cidr_bank["valid"][None]
                        hit_e &= (val_v4[:, :, None] ==
                                  cidr_bank["ent_v4"][None])
                        member = member.at[:, cidr_bank["pos"]].set(
                            jnp.any(hit_e, axis=2) & val_ok)
                        # malformed present IP bytes (length not 4/16):
                        # the host adapter raises before membership →
                        # INTERNAL (handle_check's bytes normalization)
                        bad = jnp.zeros_like(member).at[
                            :, cidr_bank["pos"]].set(~val_ok)
                    # host parity for unusable values: an ACTIVE list rule
                    # whose value is absent (instance build EvalError) or
                    # malformed takes the _safe_check INTERNAL path — the
                    # device must not silently fail open
                    l_rule_act = active[:, list_rule_j]
                    l_internal = l_rule_act & ~sym_ok
                    l_eval = l_rule_act & sym_ok
                    if bad is not None:
                        l_internal |= l_rule_act & sym_ok & bad
                        l_eval &= ~bad
                    if und is not None:
                        l_eval &= ~und
                        err = err.at[:, list_rule_j].max(und)
                    l_hit = l_internal | (
                        l_eval & (member == list_black_j[None, :]))
                    l_key = jnp.where(l_hit, list_rule_j[None, :], BIGI)
                    l_arg = jnp.argmin(l_key, axis=1)
                    l_rule = jnp.min(l_key, axis=1)
                    winner_internal = jnp.take_along_axis(
                        l_internal, l_arg[:, None], axis=1)[:, 0]
                    take_l = l_rule < cand_rule     # strict: deny wins ties
                    cand_status = jnp.where(
                        take_l,
                        jnp.where(winner_internal, INTERNAL,
                                  list_code_j[l_arg]),
                        cand_status)
                    cand_rule = jnp.minimum(cand_rule, l_rule)
                    dur = jnp.minimum(dur, jnp.min(
                        jnp.where(l_eval, list_dur_j[None, :], _BIG), axis=1))
                    uses = jnp.minimum(uses, jnp.min(
                        jnp.where(l_eval, list_uses_j[None, :],
                                  np.iinfo(np.int32).max), axis=1))
                    # an INTERNAL result carries the CheckResult DEFAULTS
                    # into the TTL min (host _combine parity)
                    any_internal = jnp.any(l_internal, axis=1)
                    dur = jnp.where(any_internal,
                                    jnp.minimum(dur, DEFAULT_DUR), dur)
                    uses = jnp.where(any_internal,
                                     jnp.minimum(uses, DEFAULT_USES), uses)

            if has_rbac:
                with jax.named_scope("rbac"):
                    # allowed iff ANY lowered (binding, subject, role-rule)
                    # pseudo-rule matched; guard row not definitely-true →
                    # the host instance build would have errored → INTERNAL
                    # (rbac.go:181 + dispatcher _safe_check parity)
                    m_ext = jnp.concatenate(
                        [matched, jnp.zeros((b, 1), bool),
                         jnp.ones((b, 1), bool)], axis=1)
                    allow = jnp.any(m_ext[:, rb_allow_j], axis=2)
                    guard_ok = m_ext[:, rb_guard_j]
                    r_active = active[:, rb_rule_j]
                    r_deny = r_active & guard_ok & ~allow
                    r_bad = r_deny | (r_active & ~guard_ok)
                    rb_key = jnp.where(r_bad, rb_rule_j[None, :], BIGI)
                    rb_arg = jnp.argmin(rb_key, axis=1)
                    rb_rule_min = jnp.min(rb_key, axis=1)
                    rb_status = jnp.where(
                        jnp.take_along_axis(r_deny, rb_arg[:, None],
                                            axis=1)[:, 0],
                        PERMISSION_DENIED, INTERNAL)
                    take_rb = rb_rule_min < cand_rule   # deny/list win ties
                    cand_status = jnp.where(take_rb, rb_status, cand_status)
                    cand_rule = jnp.minimum(cand_rule, rb_rule_min)
                    # the handler returns caching_ttl on allow AND deny
                    # verdicts alike; on INTERNAL the host CheckResult
                    # carries only defaults (no-op under min) — skip it
                    dur = jnp.minimum(dur, jnp.min(
                        jnp.where(r_active & guard_ok, rb_dur_j[None, :],
                                  _BIG), axis=1))
            with jax.named_scope("combine"):
                status = jnp.where(cand_rule < BIGI, cand_status, OK)

            if self._has_quota:
                with jax.named_scope("quota"):
                    # bucket = stable content hash mod hash space; fixed
                    # window. Uses hash_ids, not ids: ephemeral ids vary
                    # with encounter order while the counter window
                    # persists across batches. Quota is dispatched only
                    # when the precondition check passed
                    # (grpcServer.go:188-230 runs the quota loop after a
                    # successful Check) — denied requests must not consume
                    # tokens.
                    key = batch.hash_ids[:, q_slot_j]         # [B, Q]
                    key_ok = batch.present[:, q_slot_j]
                    q_active = active[:, q_rule_j] & key_ok & \
                        (status == OK)[:, None]               # [B, Q]
                    bucket = (key % q_nb_j[None, :]).astype(jnp.int32)
                    # sequential-within-batch grant: request i granted iff
                    # prior_count + its rank among same-bucket active peers
                    # < max. One flattened stable sort over [Q·B] composite
                    # keys ranks every quota at once (the naive [B, B, Q]
                    # pairwise compare cost 8ms/step at B=2048).
                    # composite int32 keys; the inactive sentinel INT32_MAX
                    # sorts past every real key (constructor bounds
                    # n_quotas·n_buckets < INT32_MAX — jnp has no int64
                    # without x64 mode)
                    n_q = quota_counts.shape[0]
                    qoff = jnp.arange(n_q, dtype=jnp.int32)[None, :] * \
                        quota_counts.shape[1]
                    ckey = jnp.where(q_active, bucket + qoff,
                                     jnp.iinfo(jnp.int32).max)
                    if b <= 256:
                        # latency tier: the flattened sort costs ~0.2ms of
                        # fixed latency; a strict-lower-triangle pairwise
                        # count is B²·Q trivial compares at small static B
                        eq = ckey[None, :, :] == ckey[:, None, :]  # [B,B,Q]
                        lower = (jnp.arange(b)[None, :] <
                                 jnp.arange(b)[:, None])[:, :, None]
                        rank = jnp.sum(eq & lower, axis=1,
                                       dtype=jnp.int32)            # [B, Q]
                    else:
                        rank = _batch_rank(
                            ckey.T.reshape(-1)).reshape(n_q, b).T
                    prior_per_req = quota_counts[
                        jnp.arange(n_q)[None, :], bucket]            # [B, Q]
                    granted = q_active & (
                        prior_per_req + rank < q_max_j[None, :])
                    over = q_active & ~granted
                    # quota only runs where status is still OK (q_active
                    # gating above), so a RESOURCE_EXHAUSTED here is always
                    # the lowest-index non-OK source for that request
                    any_over = jnp.any(over, axis=1)
                    status = jnp.where(any_over, RESOURCE_EXHAUSTED, status)
                    cand_rule = jnp.where(
                        any_over,
                        jnp.min(jnp.where(over, q_rule_j[None, :], BIGI),
                                axis=1),
                        cand_rule)
                    # commit grants: scatter-add per (quota, bucket)
                    flat = bucket + jnp.arange(bucket.shape[1])[None, :] * \
                        quota_counts.shape[1]
                    add = jnp.zeros(quota_counts.size, jnp.int32).at[
                        flat.reshape(-1)].add(
                            granted.astype(jnp.int32).reshape(-1))
                    quota_counts = quota_counts + add.reshape(
                        quota_counts.shape)

            with jax.named_scope("combine"):
                attr_mask = bytes_ops.unpack_bits(
                    attr_mask_bits, n_attr_cols).astype(jnp.int8)
                referenced = lax.dot_general(
                    ns_ok.astype(jnp.int8), attr_mask, dims,
                    preferred_element_type=jnp.int32) > 0
                verdict = CheckVerdict(status=status.astype(jnp.int32),
                                       valid_duration_s=dur,
                                       valid_use_count=uses,
                                       referenced=referenced,
                                       matched=matched, err=err,
                                       deny_rule=jnp.where(
                                           status == OK, BIGI, cand_rule),
                                       err_count=jnp.sum(
                                           ((err & ns_ok) if err_rule_mask_j
                                            is None else
                                            (err & ns_ok &
                                             err_rule_mask_j[None, :]))
                                           .astype(jnp.int32)))
            return verdict, quota_counts

        # ---- compiled-shape geometry for the roofline accounting
        # layer (compiler/roofline.py): every entry derives from the
        # ACTUAL device tensors built above, never hand constants
        def _banks_geom() -> list:
            out = []
            for bank in rx_banks:
                g = {"m_bytes": int(bank["M"].nbytes)
                     + int(bank["M_def"].nbytes),
                     "n_lists": int(bank["M"].shape[1])}
                if bank["packed"] is not None:
                    p = bank["packed"]
                    g.update(kind="dense", s_tot=int(p["n_states"]),
                             n_cls=int(p["n_classes"]),
                             step_bytes=int(p["step_bits"].nbytes),
                             n_pats=int(p["accept"].shape[1]))
                elif bank["packed_blk"] is not None:
                    p = bank["packed_blk"]
                    g.update(kind="blocked",
                             s_max=int(p["n_states_max"]),
                             n_cls=int(p["n_classes"]),
                             step_bytes=int(p["step_bits"].nbytes),
                             n_pats=int(p["n_pats"]))
                else:
                    g.update(kind="gather",
                             step_bytes=int(bank["trans"].nbytes),
                             n_pats=int(bank["trans"].shape[0]),
                             s_max=int(bank["trans"].shape[1]))
                out.append(g)
            return out

        self.geometry = {
            "n_rows": R,
            "n_deny": len(deny),
            "deny_bytes": int(deny_mask_j.nbytes + deny_status_j.nbytes
                              + deny_dur_j.nbytes + deny_uses_j.nbytes),
            "n_lists": n_lists,
            "list_max_entries": int(list_ids.shape[1]),
            "list_table_bytes": int(list_ids_j.nbytes)
            if has_lists else 0,
            "rx_banks": _banks_geom(),
            "cidr_entries": 0 if cidr_bank is None else
            int(cidr_bank["prefix"].shape[0]
                * cidr_bank["prefix"].shape[1]),
            "cidr_bytes": 0 if cidr_bank is None else
            int(cidr_bank["prefix"].nbytes + cidr_bank["mask"].nbytes),
            "n_rbac": n_rbac,
            "rbac_k_allow": k_allow,
            "n_quotas": n_quotas,
            "quota_buckets": int(n_buckets),
            "attr_mask_bits_bytes": int(attr_mask_bits.nbytes),
            "n_attr_cols": n_attr_cols,
        }

        self.raw_step = step   # unjitted: for entry()/sharded wrappers
        # ruleset index tensors + the engine bank tensors above — one
        # argument pytree every step entry (jit, sharded, bench)
        # passes through; parallel/mesh.param_shardings replicates
        # unknown keys, so the pe_* banks need no policy entry there
        self.params = {**self.ruleset.params, **pe_params}
        # donate the quota buffer only when quota state actually
        # threads through the step: donation invalidates the input
        # buffer, which breaks concurrent (pipelined) batches that all
        # read the same dummy counts array
        donate = (3,) if self._has_quota else ()
        self._step = jax.jit(step, donate_argnums=donate) if jit else step

    def _slot_for(self, attr: Any) -> int:
        lay = self.ruleset.layout
        if isinstance(attr, tuple):
            if attr not in lay.derived_slots:
                raise ValueError(f"no derived slot for {attr}; reference it "
                                 "in a rule or add it to derived_keys")
            return lay.derived_slots[attr]
        return lay.slot_of(attr)

    def _byte_slot_for(self, l: ListEntrySpec) -> int:
        bslot = self.ruleset.layout.byte_slots.get(l.value_attr)
        if bslot is None:
            raise ValueError(
                f"{l.entry_type} list value {l.value_attr!r} has no byte "
                "slot; pass it via compile_ruleset(extra_byte_sources=...)")
        return bslot

    def _build_regex_banks(self, lists: Sequence[ListEntrySpec]) -> list:
        """REGEX lists grouped by value byte slot → one packed DFA bank
        per slot; patterns deduplicated within a bank (1,000 rules
        sharing one handler share ONE DFA, not 1,000). Raises
        UnsupportedRegex for patterns outside the DFA subset — callers
        (runtime/fused.py) gate fusability on that."""
        from istio_tpu.ops.regex_dfa import (compile_regex,
                                             pack_dfas_tiered)

        groups: dict[int, dict] = {}
        for i, l in enumerate(lists):
            if l.entry_type != "REGEX":
                continue
            bslot = self._byte_slot_for(l)
            g = groups.setdefault(bslot, {"pat_idx": {}, "dfas": [],
                                          "dollar": [], "lists": []})
            idxs = []
            for e in l.entries:
                e = str(e)
                j = g["pat_idx"].get(e)
                if j is None:
                    j = len(g["dfas"])
                    g["pat_idx"][e] = j
                    g["dfas"].append(compile_regex(e))
                    g["dollar"].append("$" in e)
                idxs.append(j)
            g["lists"].append((i, idxs))
        banks = []
        for bslot in sorted(groups):
            g = groups[bslot]
            tiers = pack_dfas_tiered(g["dfas"])
            dollar = np.asarray(g["dollar"], bool)
            # [n_pats, n_lists_in_bank] membership, transposed for
            # dot_general; M_def keeps only $-free patterns (whose
            # prefix hits are definitive on truncated values)
            m = np.zeros((len(g["dfas"]), len(g["lists"])), np.int8)
            for r, (_, idxs) in enumerate(g["lists"]):
                m[idxs, r] = 1
            banks.append({
                "bslot": bslot,
                "trans": None if tiers["trans"] is None
                else jnp.asarray(tiers["trans"]),
                "accept": None if tiers["accept"] is None
                else jnp.asarray(tiers["accept"]),
                "packed": tiers["packed"],
                "packed_blk": tiers["packed_blk"],
                "M": jnp.asarray(m),
                "M_def": jnp.asarray(m * (~dollar[:, None])),
                "pos": jnp.asarray([i for i, _ in g["lists"]],
                                   jnp.int32),
            })
        return banks

    def _build_cidr_bank(self, lists: Sequence[ListEntrySpec]):
        """IP_ADDRESSES lists → per-entry (prefix, mask) byte planes in
        v6-mapped space. v4 nets map to ::ffff:0:0/96+len; membership
        additionally requires the value's v4/v6 version to equal the
        entry's (ipaddress `addr in net` is version-strict — host
        parity with list_adapter._member)."""
        import ipaddress

        items = [(i, l) for i, l in enumerate(lists)
                 if l.entry_type == "IP_ADDRESSES"]
        if not items:
            return None
        n_c = len(items)
        e_max = max((len(l.entries) for _, l in items), default=1) or 1
        prefix = np.zeros((n_c, e_max, 16), np.uint8)
        mask = np.zeros((n_c, e_max, 16), np.uint8)
        valid = np.zeros((n_c, e_max), bool)
        ent_v4 = np.zeros((n_c, e_max), bool)
        bslots = np.zeros(n_c, np.int32)
        pos = np.zeros(n_c, np.int32)
        for r, (i, l) in enumerate(items):
            bslots[r] = self._byte_slot_for(l)
            pos[r] = i
            for e_i, e in enumerate(l.entries):
                net = ipaddress.ip_network(str(e), strict=False)
                if net.version == 4:
                    plen = net.prefixlen + 96
                    addr = (b"\x00" * 10 + b"\xff\xff" +
                            net.network_address.packed)
                    ent_v4[r, e_i] = True
                else:
                    plen = net.prefixlen
                    addr = net.network_address.packed
                m_int = (((1 << plen) - 1) << (128 - plen)) if plen else 0
                mbytes = m_int.to_bytes(16, "big")
                prefix[r, e_i] = np.frombuffer(
                    bytes(a & mm for a, mm in zip(addr, mbytes)),
                    np.uint8)
                mask[r, e_i] = np.frombuffer(mbytes, np.uint8)
                valid[r, e_i] = True
        return {"prefix": jnp.asarray(prefix), "mask": jnp.asarray(mask),
                "valid": jnp.asarray(valid),
                "ent_v4": jnp.asarray(ent_v4),
                "bslots": jnp.asarray(bslots),
                "pos": jnp.asarray(pos)}

    # ------------------------------------------------------------------
    def check(self, batch: AttributeBatch, req_ns: Any) -> CheckVerdict:
        """NOTE: with device quotas this is a read-modify-write on
        quota_counts and must not run concurrently; the quota-free
        serving engine (runtime/fused.py) is safe under the batcher's
        pipelined workers."""
        verdict, counts = self._step(self.params, batch, req_ns,
                                     self.quota_counts)
        if self._has_quota:
            self.quota_counts = counts
        return verdict

    def reset_quota(self) -> None:
        """New quota window (the runtime calls this on a timer —
        memquota's window roll)."""
        self.quota_counts = jnp.zeros_like(self.quota_counts)

    @property
    def tensorizer(self) -> Tensorizer:
        # hash exactly the quota key slots — the only consumers of the
        # stable-hash plane (hashing every cell costs ~10× the
        # tensorize itself in Python)
        return Tensorizer(self.ruleset.layout, self.ruleset.interner,
                          hash_slots=self._quota_slots)
