"""Tensor expression compiler: AST → batched masked evaluation under jit.

This replaces the reference's IL compiler + stack-VM interpreter hot loop
(mixer/pkg/il/compiler + interpreter/interpreterRun.go:70 — O(rules)
sequential per request) with data-parallel evaluation: ONE traced program
evaluates an expression for a whole batch of requests at once.

Short-circuit + 3-valued-presence semantics are compiled into masked
boolean algebra (SURVEY.md §7 layer 3b: "no short-circuit — evaluate
everything, mask errors, reduce"). Every node lowers to a triple

    (val, ok, err)   each [B]

where `ok` means "produced a value" and `err` means "hard runtime error".
Absence (fallback-able) is `~ok & ~err`. The exact masking rules mirror
the oracle (istio_tpu/expr/oracle.py), which mirrors the IL codegen:

  eff_err(x)  = x.err | ~x.ok          # hard context turns absence → error
  LAND(a,b):   err = ea | (~ea & a.val & eb)        ; val = a.val & b.val
  LOR(a,b):    err = ea | (~ea & ~a.val & eb)       ; val = a.val | b.val
  OR(a,b):     val = a.ok ? a.val : b.val
               ok  = a.ok | (~a.err & b.ok)
               err = a.err | (~a.ok & ~a.err & b.err)
  EQ/NEQ, externs: err = OR of eff_err(operand)

A suppressed operand's garbage value can never leak: `a.val & b.val` is
False whenever the suppressing side is False, and `|` dually.

Because the language has no ordering/arithmetic (func.go:39-72), all
non-boolean values are interned int32 ids (see layout.py) and EQ is id
comparison; ip()/timestamp() normalization happens at intern time. String
byte-level predicates lower to ops/bytes_ops (+ regex_dfa).

Expressions the device path cannot lower — dynamic-key INDEX, non-constant
match/regex patterns, ip()/timestamp() over runtime strings, unsupported
regex constructs — raise HostFallback at compile time and are routed to
the oracle by the runtime dispatcher.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import (AttributeBatch, BatchLayout,
                                       ID_TRUE, InternTable,
                                       ORDER_KEY_TYPES, order_key_bytes)
from istio_tpu.expr.checker import (AttributeDescriptorFinder, DEFAULT_FUNCS,
                                    eval_type)
from istio_tpu.expr.exprs import Expression, FunctionCall
from istio_tpu.expr.externs import ExternError, extern_ip, extern_timestamp
from istio_tpu.expr.parser import parse
from istio_tpu.ops import bytes_ops
from istio_tpu.ops.regex_dfa import UnsupportedRegex, compile_regex

V = ValueType
_BYTE_PREDS = ("match", "matches", "startsWith", "endsWith")
_CMP_FUNCS = ("LSS", "LEQ", "GTR", "GEQ")


class HostFallback(Exception):
    """Expression cannot run on device; evaluate with the oracle."""


@dataclasses.dataclass
class TVal:
    val: Any   # bool[B] for BOOL nodes, int32[B] ids otherwise
    ok: Any    # bool[B]
    err: Any   # bool[B]


@dataclasses.dataclass
class BVal:
    """Byte-string view of a subtree (subject of a byte predicate)."""
    data: Any  # uint8[B, L]
    lens: Any  # int32[B]
    ok: Any
    err: Any


def _eff_err(t: TVal) -> Any:
    return t.err | ~t.ok


# ---------------------------------------------------------------------------
# Requirement collection (pre-pass)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Requirements:
    """What the layout must provide for a set of expressions."""
    derived_keys: set[tuple[str, str]] = dataclasses.field(default_factory=set)
    byte_sources: set[Any] = dataclasses.field(default_factory=set)
    # (extern name, operand key) → operand AST: runtime ip()/
    # timestamp() conversions the tensorizer runs at ingest
    extern_sources: dict[tuple[str, str], Any] = \
        dataclasses.field(default_factory=dict)
    # constant `matches` pattern → the DFA collecting compiled to
    # prove it lowers (None: the device subset cannot hold it), kept
    # for the ruleset's DFA groups: a pattern is compiled once
    dfas: dict[str, Any] = dataclasses.field(default_factory=dict)

    def merge(self, other: "Requirements") -> None:
        self.derived_keys |= other.derived_keys
        self.byte_sources |= other.byte_sources
        self.extern_sources.update(other.extern_sources)
        self.dfas.update(other.dfas)


def _extern_operand_ok(e: Expression) -> bool:
    """Shapes the tensorizer's ingest oracle may evaluate: constants,
    variables, constant-key INDEX, and `|` fallbacks over those."""
    if e.const_ is not None or e.var is not None:
        return True
    f = e.fn
    if f is None:
        return False
    if f.name == "INDEX":
        return (f.args[0].var is not None
                and f.args[1].const_ is not None)
    if f.name == "OR":
        return all(_extern_operand_ok(a) for a in f.args)
    return False


def collect_requirements(ast: Expression, finder: AttributeDescriptorFinder,
                         reqs: Requirements | None = None) -> Requirements:
    """Walk the AST collecting derived-slot and byte-slot needs; raises
    HostFallback for shapes the device path cannot express."""
    if reqs is None:
        reqs = Requirements()
    _collect(ast, finder, reqs, as_bytes=False)
    return reqs


def _collect(e: Expression, finder: AttributeDescriptorFinder,
             reqs: Requirements, as_bytes: bool) -> None:
    if e.const_ is not None:
        return
    if e.var is not None:
        vt = finder.get_attribute(e.var.name)
        if vt is None:
            raise HostFallback(f"unknown attribute {e.var.name}")
        if as_bytes:
            reqs.byte_sources.add(e.var.name)
        return
    f = e.fn
    assert f is not None
    if f.name == "INDEX":
        tgt = f.args[0]
        if tgt.var is not None:
            map_vars = [tgt.var.name]
        elif (tgt.fn is not None and tgt.fn.name == "OR"
              and all(a.var is not None for a in tgt.fn.args)
              and not as_bytes):
            # (mapA | mapB)[key]: both maps' derived slots + presence
            map_vars = [a.var.name for a in tgt.fn.args]
        else:
            raise HostFallback("INDEX over non-variable map")
        if f.args[1].const_ is None:
            raise HostFallback("dynamic string-map key")
        key = f.args[1].const_.value
        if not isinstance(key, str):
            raise HostFallback("non-string map key")
        for m in map_vars:
            if finder.get_attribute(m) != ValueType.STRING_MAP:
                raise HostFallback(f"INDEX over non-map {m}")
            reqs.derived_keys.add((m, key))
        if as_bytes:
            reqs.byte_sources.add((map_vars[0], key))
        return
    if f.name == "OR":
        _collect(f.args[0], finder, reqs, as_bytes)
        _collect(f.args[1], finder, reqs, as_bytes)
        return
    if f.name in _BYTE_PREDS:
        if f.name == "match":
            subject, pattern = f.args[0], f.args[1]
        elif f.name == "matches":
            subject, pattern = f.args[0], f.target
        else:  # startsWith / endsWith
            subject, pattern = f.target, f.args[0]
        if pattern is None or pattern.const_ is None or \
                not isinstance(pattern.const_.value, str):
            if f.name == "matches":
                # runtime regex compilation has no device analog
                raise HostFallback("non-constant pattern for matches")
            # dynamic prefix/suffix/glob: BOTH sides ride byte planes
            # (bytes_ops.dyn_*_match)
            _collect(pattern, finder, reqs, as_bytes=True)
            _collect(subject, finder, reqs, as_bytes=True)
            return
        if f.name == "matches":
            try:
                reqs.dfas[pattern.const_.value] = compile_regex(
                    pattern.const_.value)
            except UnsupportedRegex as exc:
                reqs.dfas[pattern.const_.value] = None
                import re as _re
                try:
                    _re.compile(pattern.const_.value)
                except _re.error:
                    # invalid pattern: the oracle errors on EVERY
                    # evaluation → lowers to a constant-error atom,
                    # no requirements needed
                    return
                raise HostFallback(str(exc))
        _collect(subject, finder, reqs, as_bytes=True)
        return
    if f.name in ("ip", "timestamp"):
        arg = f.args[0]
        if arg.const_ is None:
            # runtime conversion: the TENSORIZER runs it at ingest into
            # an extern column (layout.extern_slots) — string parsing
            # has no device form, so it happens at the edge, once per
            # request, not per rule
            if not _extern_operand_ok(arg):
                raise HostFallback(
                    f"{f.name}() over an un-ingestable operand")
            _collect(arg, finder, reqs, as_bytes=False)
            reqs.extern_sources[(f.name, str(arg))] = arg
        return
    if f.name in _CMP_FUNCS:
        # ordered comparisons ride the byte planes: strings as utf-8,
        # numerics as 8-byte order keys (layout.order_key_bytes) —
        # keys of DIFFERENT types are not mutually comparable, so only
        # same-type pairs lower. INT64-vs-DOUBLE is a real comparison
        # on the oracle (python int<float) → host fallback; every
        # other mixed/unorderable pair makes the oracle raise on EVERY
        # evaluation → a constant-error atom, no requirements needed.
        ta, tb = (eval_type(a, finder, DEFAULT_FUNCS) for a in f.args)
        if ta != tb:
            if {ta, tb} <= {V.INT64, V.DOUBLE}:
                raise HostFallback("mixed numeric comparison")
            return   # oracle type error every row
        if ta != V.STRING and ta not in ORDER_KEY_TYPES:
            return   # unorderable (BOOL/IP/BYTES): oracle error
        for a in f.args:
            _collect(a, finder, reqs, as_bytes=True)
        return
    if f.name in ("EQ", "NEQ", "LAND", "LOR"):
        for a in f.args:
            _collect(a, finder, reqs, as_bytes=False)
        return
    raise HostFallback(f"unsupported function on device: {f.name}")


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------

class _Ctx:
    def __init__(self, layout: BatchLayout, interner: InternTable,
                 finder: AttributeDescriptorFinder):
        self.layout = layout
        self.interner = interner
        self.finder = finder

    def type_of(self, e: Expression) -> ValueType:
        return eval_type(e, self.finder, DEFAULT_FUNCS)


NodeFn = Callable[[AttributeBatch], TVal]
ByteFn = Callable[[AttributeBatch], BVal]


def _const_tval(value: Any, vtype: ValueType, ctx: _Ctx) -> NodeFn:
    if vtype == V.BOOL:
        v = bool(value)

        def fn(batch: AttributeBatch) -> TVal:
            b = batch.ids.shape[0]
            return TVal(jnp.full(b, v, bool), jnp.ones(b, bool),
                        jnp.zeros(b, bool))
        return fn
    cid = ctx.interner.intern(value)

    def fn(batch: AttributeBatch) -> TVal:
        b = batch.ids.shape[0]
        return TVal(jnp.full(b, cid, jnp.int32), jnp.ones(b, bool),
                    jnp.zeros(b, bool))
    return fn


def _error_tval() -> NodeFn:
    def fn(batch: AttributeBatch) -> TVal:
        b = batch.ids.shape[0]
        return TVal(jnp.zeros(b, jnp.int32), jnp.zeros(b, bool),
                    jnp.ones(b, bool))
    return fn


def _compile_node(e: Expression, ctx: _Ctx) -> NodeFn:
    if e.const_ is not None:
        return _const_tval(e.const_.value, e.const_.vtype, ctx)

    if e.var is not None:
        vt = ctx.finder.get_attribute(e.var.name)
        if vt is None:
            raise HostFallback(f"unknown attribute {e.var.name}")
        if vt == V.STRING_MAP:
            raise HostFallback("bare string-map variable on device")
        col = ctx.layout.slot_of(e.var.name)
        is_bool = vt == V.BOOL

        def fn(batch: AttributeBatch) -> TVal:
            ids = batch.ids[:, col]
            ok = batch.present[:, col]
            val = (ids == ID_TRUE) if is_bool else ids
            return TVal(val, ok, jnp.zeros_like(ok))
        return fn

    f = e.fn
    assert f is not None
    name = f.name

    if name == "INDEX":
        key = f.args[1].const_.value
        tgt = f.args[0]
        if tgt.var is not None:
            col = ctx.layout.derived_slot_of(tgt.var.name, key)

            def fn(batch: AttributeBatch) -> TVal:
                ok = batch.present[:, col]
                return TVal(batch.ids[:, col], ok, jnp.zeros_like(ok))
            return fn
        # (mapA | mapB)[key] — _collect validated the OR-of-vars shape:
        # soft map fallback selects by MAP presence, then the chosen
        # map's derived slot supplies value/presence (oracle: `|` soft
        # mode over map variables, then the usual INDEX encoding)
        m1 = tgt.fn.args[0].var.name
        m2 = tgt.fn.args[1].var.name
        c1 = ctx.layout.derived_slot_of(m1, key)
        c2 = ctx.layout.derived_slot_of(m2, key)
        mp1 = ctx.layout.map_slots[m1]
        mp2 = ctx.layout.map_slots[m2]

        def fn(batch: AttributeBatch) -> TVal:
            sel = batch.map_present[:, mp1]
            val = jnp.where(sel, batch.ids[:, c1], batch.ids[:, c2])
            ok = jnp.where(sel, batch.present[:, c1],
                           batch.map_present[:, mp2]
                           & batch.present[:, c2])
            return TVal(val, ok, jnp.zeros_like(ok))
        return fn

    if name == "OR":
        fa = _compile_node(f.args[0], ctx)
        fb = _compile_node(f.args[1], ctx)

        def fn(batch: AttributeBatch) -> TVal:
            a, b = fa(batch), fb(batch)
            val = jnp.where(a.ok, a.val, b.val)
            ok = a.ok | (~a.err & b.ok)
            err = a.err | (~a.ok & ~a.err & b.err)
            return TVal(val, ok, err)
        return fn

    if name in ("EQ", "NEQ"):
        fa = _compile_node(f.args[0], ctx)
        fb = _compile_node(f.args[1], ctx)
        negate = name == "NEQ"

        def fn(batch: AttributeBatch) -> TVal:
            a, b = fa(batch), fb(batch)
            cmp = a.val == b.val
            if negate:
                cmp = ~cmp
            ee = _eff_err(a) | _eff_err(b)
            return TVal(cmp, ~ee, ee)
        return fn

    if name == "LAND":
        fa = _compile_node(f.args[0], ctx)
        fb = _compile_node(f.args[1], ctx)

        def fn(batch: AttributeBatch) -> TVal:
            a, b = fa(batch), fb(batch)
            ea, eb = _eff_err(a), _eff_err(b)
            err = ea | (~ea & a.val & eb)
            val = a.val & b.val & ~err
            return TVal(val, ~err, err)
        return fn

    if name == "LOR":
        fa = _compile_node(f.args[0], ctx)
        fb = _compile_node(f.args[1], ctx)

        def fn(batch: AttributeBatch) -> TVal:
            a, b = fa(batch), fb(batch)
            ea, eb = _eff_err(a), _eff_err(b)
            err = ea | (~ea & ~a.val & eb)
            val = ((a.val & ~ea) | (b.val & ~eb)) & ~err
            return TVal(val, ~err, err)
        return fn

    if name in _BYTE_PREDS:
        return _compile_byte_pred(f, ctx)

    if name in _CMP_FUNCS:
        return _compile_cmp(f, ctx)

    if name in ("ip", "timestamp"):
        arg = f.args[0]
        if arg.const_ is None:
            # ingest-converted extern column (layout.extern_slots):
            # ID_INVALID marks a conversion/lookup error
            col = ctx.layout.extern_slots.get((name, str(arg)))
            if col is None:
                raise HostFallback(
                    f"{name}() operand missing an extern slot")

            def fn(batch: AttributeBatch) -> TVal:
                ids = batch.ids[:, col]
                pres = batch.present[:, col]
                err = pres & (ids == 0)
                ok = pres & ~err
                return TVal(ids, ok, err)
            return fn
        raw = arg.const_.value
        try:
            value = (extern_ip(raw) if name == "ip"
                     else extern_timestamp(raw))
        except ExternError:
            return _error_tval()  # runtime-error constant, oracle parity
        return _const_tval(value, V.IP_ADDRESS if name == "ip"
                           else V.TIMESTAMP, ctx)

    raise HostFallback(f"unsupported function on device: {name}")


def _cap(max_len: int, data) -> int:
    """The length at which a row of the byte plane `data` [B, W] may be
    truncated: the layout's max_str_len on the planes every batch
    carries (a tier below it only drops padding), the plane's own width
    on the wide plane (layout.WIDE_STR_LEN), whose rows are kept whole
    up to it. A Python int at trace time."""
    return max(max_len, int(data.shape[1]))


def _compile_cmp(f: FunctionCall, ctx: _Ctx) -> NodeFn:
    """Ordered comparison (expr LSS/LEQ/GTR/GEQ, reference func.go's
    ordered intrinsics) over the byte planes.

    Strings compare as raw utf-8 (Go string order); numerics compare by
    their 8-byte order keys (layout.order_key_bytes) — both reduce to
    one lex_cmp. NaN operands arrive as present-but-EMPTY numeric rows
    and read False under every comparison (IEEE semantics, oracle
    parity). String rows at the byte-slot cap may be truncated, making
    the comparison undecidable → err (_compile_byte_pred says what the
    serving paths make of it)."""
    name = f.name
    ta = ctx.type_of(f.args[0])
    tb = ctx.type_of(f.args[1])
    if ta != tb:
        if {ta, tb} <= {V.INT64, V.DOUBLE}:
            raise HostFallback("mixed numeric comparison")
        return _error_tval()   # oracle type error on every row
    if ta != V.STRING and ta not in ORDER_KEY_TYPES:
        # the oracle raises "unordered operand" on every evaluation
        return _error_tval()
    numeric = ta in ORDER_KEY_TYPES
    fa = _compile_bytes(f.args[0], ctx)
    fb = _compile_bytes(f.args[1], ctx)
    max_len = ctx.layout.max_str_len

    def fn(batch: AttributeBatch) -> TVal:
        a, b = fa(batch), fb(batch)
        ee = (a.err | ~a.ok) | (b.err | ~b.ok)
        c = bytes_ops.lex_cmp(a.data, a.lens, b.data, b.lens)
        if name == "LSS":
            val = c < 0
        elif name == "LEQ":
            val = c <= 0
        elif name == "GTR":
            val = c > 0
        else:
            val = c >= 0
        if numeric:
            # NaN marker (empty key): all four comparisons read False,
            # never err. Malformed-payload marker (1-byte key,
            # layout.ORDER_KEY_ERROR): the oracle raises per row → err
            nan = (a.ok & (a.lens == 0)) | (b.ok & (b.lens == 0))
            bad = (a.ok & (a.lens == 1)) | (b.ok & (b.lens == 1))
            ee = ee | bad
            val = val & ~nan
        else:
            # either side possibly truncated → order undecidable
            cap = _cap(max_len, a.data)
            ee = ee | (a.ok & (a.lens >= cap)) \
                    | (b.ok & (b.lens >= cap))
        val = val & ~ee
        return TVal(val, ~ee, ee)
    return fn


def _compile_byte_pred(f: FunctionCall, ctx: _Ctx) -> NodeFn:
    """Byte predicates with truncation safety.

    Strings of max_str_len bytes or more land truncated in the byte
    plane (layout.py). Per predicate:
      * prefix checks (startsWith, `x*` globs, exact globs shorter
        than the cap) only read the head — always decidable;
      * suffix/tail checks (endsWith, `*x` globs, cap-length exact
        globs) are undecidable on a possibly-truncated row → the rule
        errs on the row;
      * unanchored regex: a hit inside the stored prefix proves a hit
        in the full string, so only a MISS on a truncated row is
        undecidable; a `$`-anchored regex could falsely anchor at the
        truncation point, so every truncated row is undecidable.
    What becomes of such an err. The fused Check path (the one every
    deployment with a fused plan serves through) never shows a row a
    plane that truncates it while a wider one is to be had: a row with
    a subject at the cap is served by the wide program
    (Dispatcher._split_by_length, FusedPlan's `step_wide`), where the
    cap is that plane's width (_cap), and a row that saturates the wide
    plane too and has a rule err is given the host oracle's verdict
    (Dispatcher._decide_on_host, counted in
    mixer_check_undecided_rows_total). The generic path
    (Dispatcher._resolve), the fused report resolve, the in-step quota
    program and a mesh keep upstream's meaning of an evaluation error
    for it: the rule is skipped on the row and RESOLVE_ERRORS counts
    it, so there a deny rule that reads a subject past the cap does
    not fire.
    A pattern longer than the cap can't be represented on device at
    all → HostFallback at compile time.
    """
    max_len = ctx.layout.max_str_len
    if f.name == "match":
        pattern_ast = f.args[1]
    elif f.name == "matches":
        pattern_ast = f.target
    else:
        pattern_ast = f.args[0]
    if pattern_ast.const_ is None and f.name != "matches":
        return _compile_dyn_byte_pred(f, ctx)
    # "safe": truncation can't change the result; "miss": only a False
    # on a truncated row is unreliable; "all": every truncated row is
    if f.name == "match":
        subject_ast, pattern = f.args[0], f.args[1].const_.value
        if len(pattern.encode("utf-8")) > max_len:
            raise HostFallback("glob pattern exceeds byte-slot width")
        op = partial(bytes_ops.glob_match, pattern=pattern)
        if pattern.endswith("*"):
            trunc = "safe"                      # prefix glob
        elif pattern.startswith("*"):
            trunc = "all"                       # suffix glob
        else:
            # exact: safe unless the stored prefix could equal the
            # pattern while the real string continues past the cap
            trunc = "safe" if len(pattern.encode()) < max_len else "all"
    elif f.name == "matches":
        subject_ast, pattern = f.args[0], f.target.const_.value
        try:
            dfa = compile_regex(pattern)
        except UnsupportedRegex:
            import re as _re
            try:
                _re.compile(pattern)
            except _re.error:
                return _error_tval()   # invalid pattern: always errors
            raise
        trans = jnp.asarray(dfa.transitions)
        accept = jnp.asarray(dfa.accept)
        op = lambda data, lens: bytes_ops.dfa_match(data, lens, trans, accept)
        trunc = "all" if "$" in pattern else "miss"
    elif f.name == "startsWith":
        subject_ast, pattern = f.target, f.args[0].const_.value
        if len(pattern.encode("utf-8")) > max_len:
            raise HostFallback("prefix exceeds byte-slot width")
        op = lambda data, lens: bytes_ops.prefix_match(data, lens,
                                                       pattern.encode())
        trunc = "safe"
    else:  # endsWith
        subject_ast, pattern = f.target, f.args[0].const_.value
        op = lambda data, lens: bytes_ops.suffix_match(data, lens,
                                                       pattern.encode())
        trunc = "all"

    fsub = _compile_bytes(subject_ast, ctx)

    def fn(batch: AttributeBatch) -> TVal:
        s = fsub(batch)
        ee = s.err | ~s.ok
        val = op(s.data, s.lens) & ~ee
        if trunc != "safe":
            maybe_truncated = s.ok & (s.lens >= _cap(max_len, s.data))
            undecidable = maybe_truncated if trunc == "all" \
                else (maybe_truncated & ~val)
            ee = ee | undecidable
            val = val & ~ee
        return TVal(val, ~ee, ee)
    return fn


def _bank_scan(tiers: dict) -> tuple[str, Callable, tuple]:
    """(tier, scan(s: BVal) → bool [B, n], its resident arrays) of a
    bank every row scans whole (regex_dfa.pack_dfas_tiered: the
    one-hot tiers, or the flat gather)."""
    # the MXU formulations win at EVERY serving batch size (profiled
    # r4 at B=256: 0.055 ms vs 0.279 ms for the flat gather — the
    # per-step [B, N] gather is latency-bound on TPU regardless of B)
    for tier, key, op in (
            ("onehot", "packed", bytes_ops.dfa_match_many_onehot),
            ("onehot-blocked", "packed_blk",
             bytes_ops.dfa_match_many_onehot_blocked)):
        p = tiers[key]
        if p is not None:
            return tier, (lambda s: op(s.data, s.lens, p)), \
                (p["step_bits"], p["cls"], p["accept"])
    trans_j, accept_j = jnp.asarray(tiers["trans"]), \
        jnp.asarray(tiers["accept"])
    return "gather", (lambda s: bytes_ops.dfa_match_many(
        s.data, s.lens, trans_j, accept_j)), (trans_j, accept_j)


def compile_dfa_group(subject_ast: Expression, patterns: list[str],
                      dfas: list, ctx: "_Ctx", guard=None,
                      prefix: str = "") -> Callable:
    """ALL constant-pattern `matches` atoms over ONE subject, evaluated
    in a single packed scan (ops/bytes_ops.dfa_match_many*).

    Per-atom DFA scans are latency-bound: each of the L scan steps is a
    tiny [B] gather, so k separate atoms cost k·L sequential steps
    (~40 ms for the 1k-route table, VERDICT r2 weak #3). Packing turns
    that into ONE L-step scan with [B, k] gathers — the batched-NFA
    shape SURVEY §7 hard-part 1 calls for.

    `guard` = (slot column, [intern ids per pattern]) where the ruleset
    shows one: every conjunction holding pattern i also holds
    `column == id` for one of pattern i's ids, so on a row whose column
    reads another id the pattern's column is read by no conjunction
    that can still hold; () marks a pattern this column does not
    guard. A bank past both one-hot tiers then scans each row's own
    candidates (regex_dfa.pack_dfas_tiered's `cand`, tier
    "candidates") and writes a guarded-out column False; the unguarded patterns are a
    bank of their own that every row scans. The candidate bank's
    arrays ride the step's arguments (`fn.params`, keys under
    `prefix`), not the program's constants.

    Returns fn(batch, params) → (val [B, k], ee [B, k]) with exactly
    _compile_byte_pred's semantics per column: subject absence/error
    masks the row; truncated rows are fully undecidable for $-anchored
    patterns and miss-undecidable otherwise. Column c is pattern
    `fn.order[c]` (a split bank returns its candidates first).
    `fn.banks` says what was built, a record a bank: subject, tier,
    automata, resident bytes, automata scanned a row."""
    from istio_tpu.ops.regex_dfa import pack_dfas_tiered

    max_len = ctx.layout.max_str_len
    fsub = _compile_bytes(subject_ast, ctx)
    guard_of = gvals = None
    if guard is not None:
        gvals = np.unique(np.asarray(
            [i for held in guard[1] for i in held], np.int32))
        guard_of = [np.searchsorted(gvals, held) for held in guard[1]]
    # tier selection shared with the engine's list banks
    # (regex_dfa.pack_dfas_tiered)
    tiers = pack_dfas_tiered(dfas, guard_of)
    cand = tiers["cand"]

    def bank(tier: str, automata: int, resident, scanned: int) -> dict:
        return {"subject": str(subject_ast), "tier": tier,
                "automata": automata,
                "bytes": sum(int(a.nbytes) for a in resident),
                "candidates": scanned}

    own: dict = {}
    if cand is None:
        order = list(range(len(dfas)))
        tier, whole, resident = _bank_scan(tiers)
        banks = [bank(tier, len(dfas), resident, len(dfas))]
    else:
        rest = cand["rest"]
        order = cand["members"] + (rest["members"] if rest else [])
        n = len(cand["members"])
        s_max, width = cand["n_states_max"], cand["width"]
        sel = np.zeros((cand["k"], n), np.float32)
        sel[cand["slot"], np.arange(n)] = 1.0
        own = {"local": cand["local"], "accept": cand["accept"],
               "class_of": cand["class_of"], "cand": cand["cand"],
               "gvals": gvals, "sel": jnp.asarray(sel, jnp.bfloat16)}
        own = {prefix + k: jnp.asarray(v) for k, v in own.items()}
        banks = [bank("candidates", n, own.values(), cand["k"])]
        if rest:
            tier, others, resident = _bank_scan(rest)
            banks.append(bank(tier, len(rest["members"]), resident,
                              len(rest["members"])))
    trunc_all = jnp.asarray(np.array(["$" in patterns[i] for i in order]))

    def candidates(batch: AttributeBatch, s: BVal, params) -> Any:
        """[B, n] acceptance from a scan of each row's own automata."""
        n_values = gvals.shape[0]
        ids = batch.ids[:, guard[0]]
        vals = params[prefix + "gvals"]
        at = jnp.minimum(jnp.searchsorted(vals, ids, method="compare_all"),
                         n_values - 1)
        # row -> its guard value's index; n_values: names no value
        gi = jnp.where(batch.present[:, guard[0]] & (vals[at] == ids),
                       at, n_values)
        mine = params[prefix + "cand"][gi]                       # [B, K]
        acc = bytes_ops.dfa_match_candidates(
            s.data, s.lens, mine, params[prefix + "local"],
            params[prefix + "accept"], params[prefix + "class_of"],
            s_max, width)
        # column j reads its slot, and holds where the row's candidate
        # there is j itself and accepted: under another value the slot
        # is another automaton's. The automaton is named by two
        # base-256 digits, each exact in bf16; n, the dead automaton,
        # is no column's
        named = jnp.where(acc, mine, n)
        col = jnp.arange(n, dtype=jnp.int32)
        out = True
        for digit in (lambda x: x >> 8, lambda x: x & 255):
            at_slot = jnp.dot(digit(named).astype(jnp.bfloat16),
                              params[prefix + "sel"])
            out = out & (at_slot == digit(col).astype(jnp.bfloat16)[None, :])
        return out

    def fn(batch: AttributeBatch, params=None):
        s = fsub(batch)
        if cand is None:
            m = whole(s)
        else:
            m = candidates(batch, s, params)
            if rest:
                m = jnp.concatenate([m, others(s)], axis=1)
        ee = (s.err | ~s.ok)[:, None] & jnp.ones_like(m)
        val = m & ~ee
        maybe = (s.ok & (s.lens >= _cap(max_len, s.data)))[:, None]
        undecidable = jnp.where(trunc_all[None, :], maybe, maybe & ~val)
        ee = ee | undecidable
        val = val & ~ee
        return val, ee

    fn.params, fn.order, fn.banks = own, order, banks
    return fn


def compile_prefix_group(subject_ast: Expression, prefixes: list[str],
                         ctx: "_Ctx") -> Callable:
    """ALL constant-prefix `startsWith` atoms over ONE subject, in one
    compare per byte position (ops/bytes_ops.prefix_match_many).

    A mesh's ServiceRoles name their services by prefix (`svc7.*`), so
    a snapshot holds a distinct constant per role over one subject:
    1 000 atoms each traced, lowered and compiled as its own slice,
    compare and reduction were 59 000 StableHLO lines a step shape and
    most of a five-minute cold start (PERF.md §6, PR 29).

    Returns fn(batch) → (val [B, k], ee [B, k]) with exactly
    _compile_byte_pred's semantics per column: subject absence/error
    masks the row; a prefix check reads the head only, so truncation
    never makes it undecidable."""
    fsub = _compile_bytes(subject_ast, ctx)
    raw = [p.encode("utf-8") for p in prefixes]

    def fn(batch: AttributeBatch):
        s = fsub(batch)
        m = bytes_ops.prefix_match_many(s.data, s.lens, raw)
        ee = (s.err | ~s.ok)[:, None] & jnp.ones_like(m)
        return m & ~ee, ee
    return fn


def _compile_dyn_byte_pred(f: FunctionCall, ctx: _Ctx) -> NodeFn:
    """Byte predicates whose PATTERN is itself a runtime string
    (`as.startsWith(as2)`, `match(as, as2)`): both operands ride byte
    planes and bytes_ops.dyn_*_match compares them row-wise.

    Truncation: the subject's stored prefix decides a prefix check iff
    the pattern fits under the cap; suffix/exact/glob verdicts on a
    possibly-truncated subject, and any possibly-truncated pattern,
    are undecidable → err (host oracle takes the row)."""
    max_len = ctx.layout.max_str_len
    if f.name == "match":
        subject_ast, pattern_ast = f.args[0], f.args[1]
        op, trunc_subject = bytes_ops.dyn_glob_match, "all"
    elif f.name == "startsWith":
        subject_ast, pattern_ast = f.target, f.args[0]
        op, trunc_subject = bytes_ops.dyn_prefix_match, "safe"
    else:   # endsWith
        subject_ast, pattern_ast = f.target, f.args[0]
        op, trunc_subject = bytes_ops.dyn_suffix_match, "all"
    fsub = _compile_bytes(subject_ast, ctx)
    fpat = _compile_bytes(pattern_ast, ctx)

    def fn(batch: AttributeBatch) -> TVal:
        s, p = fsub(batch), fpat(batch)
        ee = (s.err | ~s.ok) | (p.err | ~p.ok)
        val = op(s.data, s.lens, p.data, p.lens)
        cap = _cap(max_len, s.data)
        undecidable = p.ok & (p.lens >= cap)
        if trunc_subject == "all":
            undecidable = undecidable | (s.ok & (s.lens >= cap))
        ee = ee | undecidable
        val = val & ~ee
        return TVal(val, ~ee, ee)
    return fn


def _compile_bytes(e: Expression, ctx: _Ctx) -> ByteFn:
    """Compile a STRING-typed subtree to its byte-tensor view."""
    lay = ctx.layout
    if e.const_ is not None:
        if e.const_.vtype in ORDER_KEY_TYPES:
            raw = order_key_bytes(e.const_.value, e.const_.vtype)
        else:
            raw = str(e.const_.value).encode("utf-8")[:lay.max_str_len]
        row = np.zeros(lay.max_str_len, dtype=np.uint8)
        if raw:
            row[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        n = len(raw)
        # the latency-tier gate (fused.str_tiers) reads this back: a
        # batch plane must never narrow below the longest constant —
        # slicing a constant row loses REAL tail bytes (a >tier
        # constant subject of endsWith would silently flip verdicts;
        # the runtime str_lens check cannot see compile-time rows)
        ctx.interner.note_byte_const(n)

        def fn(batch: AttributeBatch) -> BVal:
            b = batch.ids.shape[0]
            # constant rows follow the BATCH plane's width, which a
            # narrowed latency-tier batch (fused.narrow_batch) slices
            # below max_str_len. Sound because str_tiers gates every
            # tier to >= the longest compiled constant (note_byte_const
            # above): row[:w] only ever drops zero padding, and `n`
            # keeps the TRUE length for the tiebreaks.
            # The wide plane (layout.WIDE_STR_LEN) pads the row out.
            w = batch.str_bytes.shape[2]
            at_w = row[:w] if w <= row.shape[0] else np.concatenate(
                [row, np.zeros(w - row.shape[0], np.uint8)])
            return BVal(jnp.broadcast_to(jnp.asarray(at_w), (b, w)),
                        jnp.full(b, n, jnp.int32),
                        jnp.ones(b, bool), jnp.zeros(b, bool))
        return fn

    if e.var is not None:
        bcol = lay.byte_slots[e.var.name]
        col = lay.slot_of(e.var.name)

        def fn(batch: AttributeBatch) -> BVal:
            ok = batch.present[:, col]
            return BVal(batch.str_bytes[:, bcol, :], batch.str_lens[:, bcol],
                        ok, jnp.zeros_like(ok))
        return fn

    f = e.fn
    assert f is not None
    if f.name == "INDEX":
        pair = (f.args[0].var.name, f.args[1].const_.value)
        bcol = lay.byte_slots[pair]
        col = lay.derived_slot_of(*pair)

        def fn(batch: AttributeBatch) -> BVal:
            ok = batch.present[:, col]
            return BVal(batch.str_bytes[:, bcol, :], batch.str_lens[:, bcol],
                        ok, jnp.zeros_like(ok))
        return fn

    if f.name == "OR":
        fa = _compile_bytes(f.args[0], ctx)
        fb = _compile_bytes(f.args[1], ctx)

        def fn(batch: AttributeBatch) -> BVal:
            a, b = fa(batch), fb(batch)
            sel = a.ok[:, None]
            data = jnp.where(sel, a.data, b.data)
            lens = jnp.where(a.ok, a.lens, b.lens)
            ok = a.ok | (~a.err & b.ok)
            err = a.err | (~a.ok & ~a.err & b.err)
            return BVal(data, lens, ok, err)
        return fn

    raise HostFallback(f"cannot view {f.name}(...) as bytes on device")


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TensorProgram:
    """A compiled expression: fn(batch) → (val [B], valid [B]).

    For BOOL expressions val is bool; otherwise val holds intern ids that
    `decode_value` maps back to Python values. `valid` is False exactly
    where the oracle would raise an evaluation error.
    """
    text: str
    result_type: ValueType
    fn: Callable[[AttributeBatch], tuple[Any, Any]]
    layout: BatchLayout
    interner: InternTable

    def __call__(self, batch: AttributeBatch) -> tuple[Any, Any]:
        return self.fn(batch)

    def decode_value(self, raw: Any, batch: AttributeBatch | None = None
                     ) -> Any:
        if self.result_type == V.BOOL:
            return bool(raw)
        vid = int(raw)
        if batch is not None:
            return batch.value_of(vid, self.interner)
        return self.interner.value_of(vid)


def compile_expression(text: str, finder: AttributeDescriptorFinder,
                       layout: BatchLayout,
                       interner: InternTable, jit: bool = True) -> TensorProgram:
    """Parse + type check + lower to a jitted batched evaluator.

    Raises HostFallback when the expression needs the oracle, and
    TypeError_/ParseError exactly like the oracle path."""
    ast = parse(text)
    rtype = eval_type(ast, finder, DEFAULT_FUNCS)
    ctx = _Ctx(layout, interner, finder)
    node = _compile_node(ast, ctx)

    def run(batch: AttributeBatch) -> tuple[Any, Any]:
        t = node(batch)
        return t.val, t.ok & ~t.err

    return TensorProgram(text=text, result_type=rtype,
                         fn=jax.jit(run) if jit else run,
                         layout=layout, interner=interner)


def compile_field(ast: Expression, finder: AttributeDescriptorFinder,
                  layout: BatchLayout, interner: InternTable
                  ) -> tuple[NodeFn, ValueType]:
    """Lower ONE already-parsed instance-field expression to an
    UNJITTED batched node (REPORT instance construction,
    runtime/report_lower.py — the reference evaluates these through
    the same IL hot loop as predicates, template.gen.go ProcessReport).
    The caller stacks many field nodes into a single device program
    alongside the packed check step. Raises HostFallback exactly like
    compile_expression; the returned TVal follows the same masked
    algebra (`ok & ~err` marks rows where the oracle would NOT raise).
    """
    rtype = eval_type(ast, finder, DEFAULT_FUNCS)
    ctx = _Ctx(layout, interner, finder)
    return _compile_node(ast, ctx), rtype
