"""Ruleset compiler: N match predicates → one batched tensor program.

This is the batched replacement for the reference resolver's per-request
loop (mixer/pkg/runtime/resolver.go:202-238 filterActions — which calls
the IL interpreter once per rule per request, 100-600ns each per
bench.baseline). Here a whole config snapshot compiles ONCE into device
tensors and every request batch is matched against ALL rules in one
fused XLA program:

    atoms:   evaluate every unique primitive predicate once per request
             → m[B, A] "definitely true", n[B, A] "definitely false"
    conj:    lit = [m ‖ n ‖ TRUE];  sat[B, n_conj] = AND over each
             conjunction's padded literal indices (gather + all)
    rules:   matched = OR over each rule's M-conjunction indices;
             not_matched likewise over N; err = ~matched & ~not_matched

The conj/rule stages are padded index gathers + reductions rather than
one-hot [2A, n_conj] / [n_conj, R] matmuls: conjunctions average only a
few literals, so the dense matmul burns ~1000× the useful FLOPs
(measured 23ms vs ~1ms per 2048×10k-rule step on v5e). The index
tensors ride HBM bandwidth and shard over a rule axis ("mp") for
VMEM-bound snapshots (istio_tpu/parallel/mesh.py).

Exactness: each predicate's AST is decomposed over its top-level
LAND/LOR skeleton into a pair of monotone DNFs over per-atom literals
{m_a, n_a}, where m_a = val∧¬err ("definitely true") and
n_a = ¬val∧¬err ("definitely false"):

    M(atom)      = {{m_a}}                 N(atom)      = {{n_a}}
    M(a && b)    = M(a)∧M(b)               N(a && b)    = N(a) ∨ (M(a)∧N(b))
    M(a || b)    = M(a) ∨ (N(a)∧M(b))      N(a || b)    = N(a)∧N(b)

These recurrences are provably equivalent to the short-circuit +
error-propagation semantics of the oracle (istio_tpu/expr/oracle.py,
mirroring IL generateLand/generateLor compiler.go:373/:354): e.g. a
short-circuited `false && err` is N(a)∧anything ⇒ not-matched, while
`true && err` is neither M nor N ⇒ error. The conformance tests
(tests/test_ruleset.py) check every corpus predicate against the oracle.

Atoms are deduplicated ACROSS rules (10k istio rules share a few hundred
distinct predicates in practice) and evaluated in three tiers:
  1. a vectorized gather-compare for EQ/NEQ(slot, const) — covers the
     overwhelming majority of real istio match clauses;
  2. a vectorized slot-vs-slot compare;
  3. per-atom compiled closures from tensor_expr for everything else
     (byte predicates, `|` fallback chains, nested EQ of booleans).

Rules whose predicate cannot lower (dynamic patterns, DNF blowup past
`dnf_cap`) are marked host-fallback and carry an OracleProgram; the
runtime dispatcher overlays their verdicts on the device result.

ReferencedAttributes (protoBag.go:117 semantics) become compile-time
per-rule attribute bitmaps (SURVEY.md §2.2 translation note).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import (AttributeBatch, BatchLayout,
                                       ID_FALSE, ID_TRUE, InternTable,
                                       build_layout)
from istio_tpu.compiler import tensor_expr
from istio_tpu.compiler.tensor_expr import (HostFallback, Requirements,
                                            collect_requirements)
from istio_tpu.expr.checker import (AttributeDescriptorFinder, DEFAULT_FUNCS,
                                    TypeError_, eval_type)
from istio_tpu.expr.exprs import Expression, const_expr
from istio_tpu.expr.externs import ExternError, extern_ip, extern_timestamp
from istio_tpu.expr.oracle import OracleProgram
from istio_tpu.expr.parser import parse

V = ValueType

# A literal is (atom_index, kind): kind 'm' = definitely-true,
# 'n' = definitely-false. A conjunction is a frozenset of literals; a DNF
# a set of conjunctions.
Literal = tuple[int, str]
Conj = frozenset
Dnf = set

DEFAULT_DNF_CAP = 128

# Rows the conjunction index tensors (eqc_*, lit_idx) round up to. The
# TPU compiler unrolls the [B, F, L] gather-compare when F is not a
# multiple of 16: compiled for a described v5e at B=2048, F=24001 took
# 120 s and 58 MB of code, F=24064 6 s and 2.5 MB (tests/
# test_tpu_compile.py keeps the guard). One lane-width keeps every
# snapshot on the fast side and lets rule edits that stay inside a
# 128-row step reuse the compiled program.
CONJ_ALIGN = 128


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


class DnfBlowup(HostFallback):
    """Predicate's DNF exceeded dnf_cap conjunctions."""


def _contradicts(c: Conj) -> bool:
    idxs = {}
    for idx, kind in c:
        prev = idxs.get(idx)
        if prev is not None and prev != kind:
            return True
        idxs[idx] = kind
    return False


def _dnf_and(a: Dnf, b: Dnf, cap: int) -> Dnf:
    out: Dnf = set()
    for x in a:
        for y in b:
            c = x | y
            if not _contradicts(c):
                out.add(c)
    if len(out) > cap:
        raise DnfBlowup(f"DNF exceeded {cap} conjunctions")
    return _prune(out)


def _prune(d: Dnf) -> Dnf:
    """Drop subsumed conjunctions (c2 ⊇ c1 is redundant)."""
    by_size = sorted(d, key=len)
    kept: list[Conj] = []
    for c in by_size:
        if not any(k <= c for k in kept):
            kept.append(c)
    return set(kept)


@dataclasses.dataclass
class Rule:
    """A policy rule's match clause (reference: the `match:` field of a
    mixer rule, config.proto; resolver.go:34 Rule)."""
    name: str
    match: str = ""          # empty = always matches (resolver.go:219)
    namespace: str = ""
    # pre-built predicate AST (synthesized pseudo-rules, e.g. the rbac
    # lowering compiler/rbac_lower.py) — used instead of parsing `match`
    ast: Expression | None = None


def _rule_ast(rule: Rule) -> Expression:
    if rule.ast is not None:
        return rule.ast
    return parse(rule.match.strip() or "true")


def _rule_oracle(rule: Rule,
                 finder: AttributeDescriptorFinder) -> OracleProgram:
    if rule.ast is not None:
        return OracleProgram.from_ast(rule.ast, finder)
    return OracleProgram(rule.match.strip() or "true", finder)


@dataclasses.dataclass
class _AtomTable:
    """Deduplicated primitive predicates across all rules. Append-only
    with O(added) rollback: mark() before a speculative decompose,
    revert(mark) drops only the atoms added since — copying the whole
    table per rule made snapshot compile quadratic in rule count."""
    asts: list[Expression] = dataclasses.field(default_factory=list)
    by_key: dict[str, int] = dataclasses.field(default_factory=dict)
    _keys: list[str] = dataclasses.field(default_factory=list)

    def index_of(self, e: Expression) -> int:
        key = str(e)
        idx = self.by_key.get(key)
        if idx is None:
            idx = len(self.asts)
            self.by_key[key] = idx
            self.asts.append(e)
            self._keys.append(key)
        return idx

    def mark(self) -> int:
        return len(self.asts)

    def revert(self, mark: int) -> None:
        for key in self._keys[mark:]:
            del self.by_key[key]
        del self._keys[mark:]
        del self.asts[mark:]


def _decompose(e: Expression, atoms: _AtomTable, cap: int) -> tuple[Dnf, Dnf]:
    """→ (M, N): DNFs for definitely-matched / definitely-not-matched."""
    if e.const_ is not None and e.const_.vtype == V.BOOL:
        if e.const_.value:
            return ({frozenset()}, set())
        return (set(), {frozenset()})
    if e.fn is not None and e.fn.name in ("LAND", "LOR"):
        name = e.fn.name
        args = e.fn.args
        m, n = _decompose(args[0], atoms, cap)
        for arg in args[1:]:
            ma, na = _decompose(arg, atoms, cap)
            if name == "LAND":
                m, n = _dnf_and(m, ma, cap), _prune(n | _dnf_and(m, na, cap))
            else:
                m, n = _prune(m | _dnf_and(n, ma, cap)), _dnf_and(n, na, cap)
        return m, n
    idx = atoms.index_of(e)
    return ({frozenset([(idx, "m")])}, {frozenset([(idx, "n")])})


def _fold_time_const(e: Expression) -> Any | None:
    """Fold ip("c")/timestamp("c") over a constant into a value;
    None if not that shape. ExternError propagates (oracle parity: the
    atom then always errors — handled by the general path)."""
    f = e.fn
    if f is None or f.name not in ("ip", "timestamp"):
        return None
    if not f.args or f.args[0].const_ is None:
        return None
    raw = f.args[0].const_.value
    return extern_ip(raw) if f.name == "ip" else extern_timestamp(raw)


@dataclasses.dataclass
class _SlotRef:
    col: int


def _slot_ref(e: Expression, layout: BatchLayout,
              finder: AttributeDescriptorFinder) -> _SlotRef | None:
    """Variable or INDEX(map, const-key) → its scalar/derived column."""
    if e.var is not None:
        vt = finder.get_attribute(e.var.name)
        if vt is None or vt == V.STRING_MAP:
            return None
        return _SlotRef(layout.slot_of(e.var.name))
    f = e.fn
    if (f is not None and f.name == "INDEX" and f.args[0].var is not None
            and f.args[1].const_ is not None
            and isinstance(f.args[1].const_.value, str)):
        pair = (f.args[0].var.name, f.args[1].const_.value)
        if pair in layout.derived_slots:
            return _SlotRef(layout.derived_slots[pair])
    return None


def _const_id(e: Expression, interner: InternTable) -> int | None:
    """Constant operand (or foldable ip()/timestamp()) → intern id."""
    if e.const_ is not None:
        v = e.const_.value
        if isinstance(v, bool):
            return ID_TRUE if v else ID_FALSE
        return interner.intern(v)
    try:
        folded = _fold_time_const(e)
    except ExternError:
        return None
    if folded is None:
        return None
    return interner.intern(folded)


def _build_dfa_span(on: bool):
    """Host time of regex -> DFA -> packed bank inside the rule
    compile: the span `build.dfa`, off where the snapshot holds no
    constant-pattern regex."""
    from istio_tpu.runtime import monitor   # lazy: runtime imports us

    return monitor.span("build.dfa", on=bool(on))


def _dfa_guards(per_rule, dfa_atoms: set, eq_info: Mapping) -> dict:
    """atom -> {column: {intern ids}}: the columns on which EVERY
    conjunction holding the atom (either polarity) also asserts an
    id-equality, and the ids asserted there, one a holder (a pattern
    two hosts share reads two). Where the row's column reads none of
    them, no conjunction that reads the atom can hold, so the atom's
    value there reaches no verdict. Go's `&&` short-circuits the same
    way: behind a false `destination.service == X` the regex is never
    evaluated."""
    guards: dict[int, dict[int, set]] = {}
    for mn in per_rule:
        for conj in (mn[0] | mn[1]) if mn else ():
            held = [a for a, _ in conj if a in dfa_atoms]
            if not held:
                continue
            # "column == id": an EQ atom definitely true, or a NEQ
            # atom definitely false
            asserts = dict(eq_info[a][:2] for a, kind in conj
                           if a in eq_info and eq_info[a][2] == (kind == "n"))
            for a in held:
                if a not in guards:
                    guards[a] = {col: {cid} for col, cid in asserts.items()}
                    continue
                mine = guards[a]
                for col in [c for c in mine if c not in asserts]:
                    del mine[col]
                for col in mine:
                    mine[col].add(asserts[col])
    return guards


def _bank_guard(atoms: Sequence[int], guards: Mapping
                ) -> tuple[int, list[tuple]] | None:
    """(column, [ids per atom]): the id-equality column that guards
    the most atoms of the group (then the one that leaves a row the
    fewest candidates), each atom with the ids under which some
    conjunction reads it, () where this column does not guard it. None
    if no column guards any atom."""
    best = None
    for col in sorted({c for a in atoms for c in guards.get(a, ())}):
        ids = [tuple(sorted(guards.get(a, {}).get(col, ()))) for a in atoms]
        most = collections.Counter(i for held in ids for i in held)
        key = (-sum(map(bool, ids)), max(most.values()))
        if best is None or key < best[0]:
            best = (key, col, ids)
    return None if best is None else best[1:]


def _rule_guards(per_rule, eq_info: Mapping) -> tuple:
    """(column, {intern id: [rule idx]}, [rule idx]): the id-equality
    column that guards the most rules, the rules it guards by the ids
    under which they can match, and the rules it does not guard. A
    rule is guarded on a column where EVERY conjunction of its M-DNF
    asserts `column == id` there (an EQ atom definitely true, a NEQ
    atom definitely false): on a row whose column reads another id
    the rule cannot match. What lets the host decide a row by its
    host's ten blocks and not its namespace's six hundred
    (RuleSetProgram.host_candidates). (None, {}, all rules) where no
    column guards a rule."""
    held: list[dict | None] = []
    for mn in per_rule:
        cols: dict[int, set] | None = None
        for conj in (mn[0] if mn else ()):
            asserts = dict(eq_info[a][:2] for a, kind in conj
                           if a in eq_info and eq_info[a][2] == (kind == "n"))
            if cols is None:
                cols = {c: {cid} for c, cid in asserts.items()}
            else:
                cols = {c: ids | {asserts[c]} for c, ids in cols.items()
                        if c in asserts}
        held.append(cols)
    guarded = collections.Counter(c for cols in held for c in cols or ())
    if not guarded:
        return None, {}, list(range(len(per_rule)))
    col = min(guarded, key=lambda c: (-guarded[c], c))
    by_id: dict[int, list] = {}
    free = []
    for ridx, cols in enumerate(held):
        if cols and col in cols:
            for cid in sorted(cols[col]):
                by_id.setdefault(cid, []).append(ridx)
        else:
            free.append(ridx)
    return col, by_id, free


@dataclasses.dataclass
class RuleSetProgram:
    """The compiled snapshot. `fn(batch)` → (matched, not_matched, err)
    each bool[B, n_rows], where n_rows = n_rules rounded up to
    `rule_pad` (mp-sharding padding; pad rows read False/True/False and
    belong to an unmatchable namespace — size consumers off
    rule_ns.shape[0], NOT n_rules). Host-fallback rules read
    False/False/True on device; overlay with `host_eval`."""
    rules: list[Rule]
    layout: BatchLayout
    interner: InternTable
    fn: Callable[..., tuple[Any, Any, Any]]   # fn(params, batch)
    params: Mapping[str, Any]   # device index tensors (lit_idx/conj_*_idx)
    n_atoms: int
    n_conjs: int
    host_fallback: dict[int, OracleProgram]   # rule idx → oracle
    fallback_reason: dict[int, str]
    attr_mask: np.ndarray                     # bool [n_rows, n_columns]
    attr_names: list[set]                     # per REAL rule (n_rules)
    rule_ns: np.ndarray                       # int32 [n_rows]
    ns_ids: dict[str, int]
    # ---- debugging surface (compiler/disasm.py — the il/text +
    #      Stepper role). Retained source structure, not device state:
    atom_asts: list[Any] = dataclasses.field(default_factory=list)
    atom_tier: dict[int, str] = dataclasses.field(default_factory=dict)
    per_rule_dnf: list[Any] = dataclasses.field(default_factory=list)
    # ---- compiled-shape geometry (atom tier counts, conjunction split,
    #      padded index widths) — the roofline accounting layer
    #      (compiler/roofline.py) derives per-step bytes/op counts from
    #      THESE shapes, never from hand constants
    geometry: dict = dataclasses.field(default_factory=dict)
    # _rule_guards' (column, {id: rules}, unguarded rules)
    guards: tuple = (None, {}, ())

    @property
    def n_rules(self) -> int:
        return len(self.rules)

    def host_candidates(self, batch: AttributeBatch, row: int) -> list:
        """The rules that can match row `row` of a host batch, in rule
        order: those the guard column does not guard and those it
        guards under the id the row reads there. Every rule where the
        row lacks the column: a guard then errs and does not miss."""
        col, by_id, free = self.guards
        if col is None or not batch.present[row, col]:
            return list(range(self.n_rules))
        return sorted(free + by_id.get(int(batch.ids[row, col]), []))

    def __call__(self, batch: AttributeBatch) -> tuple[Any, Any, Any]:
        return self.fn(self.params, batch)

    def namespace_id(self, ns: str) -> int:
        """Id for a request namespace; unknown namespaces match only
        default-namespace ('') rules."""
        return self.ns_ids.get(ns, -1)

    def namespace_mask(self, req_ns_ids: Any) -> Any:
        """bool[B, n_rules]: rule visible to the request's namespace —
        default-namespace rules apply to everyone (resolver.go:110
        default + destination-namespace rule lists)."""
        rns = jnp.asarray(self.rule_ns)
        req = jnp.asarray(req_ns_ids)
        return (rns[None, :] == self.ns_ids[""]) | (rns[None, :] == req[:, None])

    def host_eval(self, rule_idx: int, bag) -> tuple[bool, bool, bool]:
        """(matched, not_matched, err) for one host-fallback rule."""
        prog = self.host_fallback[rule_idx]
        try:
            v = bool(prog.evaluate(bag))
            return v, not v, False
        except Exception:
            return False, False, True


def fused_check_status(snapshot, plan, ridx: int, bag) -> int:
    """The status the FUSED device lowering of rule `ridx`'s check
    actions produces for `bag`, re-derived host-side from the
    snapshot's action metadata: denier codes via plan.deny_info,
    STRINGS-list membership with the blacklist→PERMISSION_DENIED /
    whitelist-miss→NOT_FOUND / absent→INTERNAL codes of
    models/policy_engine. THE shared decision-status derivation —
    next to SnapshotOracle because both are the host-side semantic
    truth device paths are judged against: the rulestats smoke gate's
    oracle recount (scripts/rulestats_smoke.py) and the config
    canary's exemplar confirmation (istio_tpu/canary/differ.py) both
    import it, so the two verification surfaces can never silently
    disagree."""
    from istio_tpu.templates import Variety

    info = plan.deny_info.get(ridx) if plan is not None else None
    if info is not None:
        return info[0]
    if plan is not None and ridx in plan.list_rules:
        for hc, _template, inst_names in snapshot.actions_for(
                ridx, Variety.CHECK):
            if hc.adapter != "list":
                continue
            entries = set(map(str, hc.params.get("overrides", ())))
            blacklist = bool(hc.params.get("blacklist", False))
            for iname in inst_names:
                ref = snapshot.instances[iname].value_attr_ref()
                if isinstance(ref, tuple):
                    c, ok = bag.get(ref[0])
                    v = c.get(ref[1]) if ok and \
                        isinstance(c, Mapping) else None
                    ok = v is not None
                else:
                    v, ok = bag.get(ref)
                if not ok or not isinstance(v, str):
                    return 13            # INTERNAL: absent value
                member = v in entries
                if member and blacklist:
                    return 7             # PERMISSION_DENIED
                if not member and not blacklist:
                    return 5             # NOT_FOUND
    return 0


class SnapshotOracle:
    """Whole-snapshot CPU oracle executor — the graceful-degradation
    resolve path the device circuit breaker falls back to
    (runtime/resilience.py).

    Per-rule OracleProgram evaluation with the same namespace-targeting
    semantics as the device RuleSetProgram (default-namespace rules
    apply to everyone; rules in other namespaces only to requests
    addressed there). Correctness over speed by design: every rule runs
    interpreted python per request, which is exactly the conformance
    oracle the compiler tests pin the device programs against — so a
    tripped breaker degrades latency, never answers.

    Oracle programs compile lazily per rule (a breaker trip must not
    pay a whole-snapshot compile before answering its first batch) and
    are seeded with the ruleset's existing host-fallback programs.
    Thread-safe: fallback batches run concurrently on the batcher's
    worker pool."""

    def __init__(self, rules: Sequence[Rule],
                 finder: AttributeDescriptorFinder,
                 seed: Mapping[int, OracleProgram] | None = None):
        self.rules = list(rules)
        self.finder = finder
        self._progs: dict[int, OracleProgram] = dict(seed or {})
        self._lock = threading.Lock()

    def _prog(self, ridx: int) -> OracleProgram:
        prog = self._progs.get(ridx)
        if prog is None:
            prog = _rule_oracle(self.rules[ridx], self.finder)
            with self._lock:
                self._progs.setdefault(ridx, prog)
        return prog

    def evaluate(self, ridx: int, bag) -> bool:
        """Rule `ridx`'s predicate on one request; raises where its
        evaluation does."""
        return bool(self._prog(ridx).evaluate(bag))

    def resolve(self, bag, request_ns: str
                ) -> tuple[list[int], list[int], int]:
        """→ (active rule idxs, namespace-visible rule idxs, n_errors)
        for one request — the per-bag shape Dispatcher._check_one
        consumes. A predicate that raises counts as not-matched plus
        one resolve error (host_eval parity)."""
        active: list[int] = []
        visible: list[int] = []
        errs = 0
        for ridx, rule in enumerate(self.rules):
            if rule.namespace and rule.namespace != request_ns:
                continue
            visible.append(ridx)
            try:
                matched = bool(self._prog(ridx).evaluate(bag))
            except Exception:
                errs += 1
                continue
            if matched:
                active.append(ridx)
        return active, visible, errs


def compile_ruleset(rules: Sequence[Rule], finder: AttributeDescriptorFinder,
                    *, interner: InternTable | None = None,
                    max_str_len: int | None = None,
                    dnf_cap: int = DEFAULT_DNF_CAP,
                    jit: bool = True,
                    extra_derived_keys: Sequence[tuple[str, str]] = (),
                    extra_byte_sources: Sequence[Any] = (),
                    extra_extern_sources: Sequence[tuple[str, str, Any]] = (),
                    rule_pad: int = 1,
                    decomp_cache=None
                    ) -> RuleSetProgram:
    """Compile a rule snapshot. Never raises for individual bad rules —
    un-lowerable predicates fall back to the oracle; predicates that do
    not even type-check to BOOL raise TypeError_ (config validation's
    job, store/validator.go analog).

    `extra_derived_keys` adds (map, key) columns consumers outside the
    predicates need — e.g. listentry instances the fused engine turns
    into id-membership scans (runtime/fused.py). `extra_byte_sources`
    likewise adds byte slots (attr name or (map, key)) for consumers
    that match VALUE BYTES rather than interned ids — REGEX/CIDR list
    entries lowered to device DFA/prefix scans. `extra_extern_sources`
    adds ip()/timestamp() ingest columns the same way (REPORT instance
    field expressions lowered by runtime/report_lower.py).

    `rule_pad` rounds the RULE-AXIS arrays (conj index matrices,
    rule_ns, attr_mask — and therefore the matched/err planes) up to a
    multiple, so the axis can shard evenly over an mp mesh dimension
    (parallel/mesh.py). Pad rows are definitely-not-matched, never
    error, and belong to an unmatchable namespace; `n_rules` still
    counts real rules only.

    `decomp_cache` (compiler/cache.DecompCache) memoizes the parse +
    DNF decomposition per match string ACROSS compiles: a config delta
    re-presents almost every predicate unchanged, and parse+decompose
    dominate the host-side compile at fleet scale. Replay re-interns
    the cached atom ASTs into this compile's _AtomTable (cross-rule
    dedup preserved) and skips eval_type — entries only exist for
    rules that already validated under the same manifest digest (the
    cache clears itself when the finder or dnf_cap changes)."""
    from istio_tpu.compiler.cache import DecompEntry

    interner = interner or InternTable()
    atoms = _AtomTable()
    per_rule: list[tuple[Dnf, Dnf] | None] = []   # None = host fallback
    host_fallback: dict[int, OracleProgram] = {}
    fallback_reason: dict[int, str] = {}
    parsed: list[Expression] = []

    if decomp_cache is not None:
        decomp_cache.begin(finder, dnf_cap)
    for ridx, rule in enumerate(rules):
        # synthesized pseudo-rules (pre-built ast, e.g. rbac lowering)
        # bypass the cache: they never parse, and keying them would
        # need an ast rendering that costs what it saves
        ckey = rule.match if rule.ast is None else None
        ent = decomp_cache.get(ckey) \
            if decomp_cache is not None and ckey is not None else None
        if ent is not None:
            parsed.append(ent.ast)
            if ent.is_fallback:
                per_rule.append(None)
                host_fallback[ridx] = ent.oracle
                fallback_reason[ridx] = ent.reason
            else:
                idxs = [atoms.index_of(a) for a in ent.atom_asts]
                per_rule.append((
                    {frozenset((idxs[p], k) for p, k in conj)
                     for conj in ent.m},
                    {frozenset((idxs[p], k) for p, k in conj)
                     for conj in ent.n}))
            continue
        ast = _rule_ast(rule)
        rtype = eval_type(ast, finder, DEFAULT_FUNCS)
        if rtype != V.BOOL:
            raise TypeError_(
                f"rule {rule.name}: match must be BOOL, got {rtype.name}")
        parsed.append(ast)
        try:
            mark = atoms.mark()
            mn = _decompose(ast, atoms, dnf_cap)
            per_rule.append(mn)
            if decomp_cache is not None and ckey is not None:
                used = sorted({i for conj in (mn[0] | mn[1])
                               for i, _ in conj})
                pos = {i: p for p, i in enumerate(used)}
                decomp_cache.put(ckey, DecompEntry(
                    ast=ast,
                    atom_asts=tuple(atoms.asts[i] for i in used),
                    m=tuple(tuple(sorted((pos[i], k) for i, k in conj))
                            for conj in mn[0]),
                    n=tuple(tuple(sorted((pos[i], k) for i, k in conj))
                            for conj in mn[1])))
        except HostFallback as exc:
            atoms.revert(mark)              # undo partial atom adds
            per_rule.append(None)
            oracle = _rule_oracle(rule, finder)
            host_fallback[ridx] = oracle
            fallback_reason[ridx] = str(exc)
            if decomp_cache is not None and ckey is not None:
                decomp_cache.put(ckey, DecompEntry(
                    ast=ast, oracle=oracle, reason=str(exc)))

    # Requirements for every device atom; atoms that cannot lower demote
    # every rule that references them to host fallback.
    reqs = Requirements()
    bad_atoms: set[int] = set()

    def collect(aidxs) -> None:
        for aidx in aidxs:
            try:
                r = Requirements()
                collect_requirements(atoms.asts[aidx], finder, r)
            except HostFallback:
                bad_atoms.add(aidx)
                continue
            reqs.merge(r)

    # the regex atoms first, on their own: collecting compiles each
    # constant pattern to its DFA (kept in reqs.dfas), which is most
    # of a route table's host compile and the first part of build.dfa
    regex_atoms = [aidx for aidx, ast in enumerate(atoms.asts)
                   if ast.fn is not None and ast.fn.name == "matches"]
    with _build_dfa_span(regex_atoms):
        collect(regex_atoms)
    held = set(regex_atoms)
    collect(aidx for aidx in range(len(atoms.asts)) if aidx not in held)
    if bad_atoms:
        for ridx, mn in enumerate(per_rule):
            if mn is None:
                continue
            used = {i for conj in (mn[0] | mn[1]) for i, _ in conj}
            if used & bad_atoms:
                per_rule[ridx] = None
                host_fallback[ridx] = _rule_oracle(rules[ridx], finder)
                fallback_reason[ridx] = "atom not lowerable"

    manifest = {n: finder.get_attribute(n) for n in finder.names()}
    kwargs = {} if max_str_len is None else {"max_str_len": max_str_len}
    ext = dict(reqs.extern_sources)
    for n, k, east in extra_extern_sources:
        ext.setdefault((n, k), east)
    layout = build_layout(
        manifest,
        sorted(set(reqs.derived_keys) | set(extra_derived_keys)),
        sorted(set(reqs.byte_sources) | set(extra_byte_sources), key=str),
        extern_sources=[(n, k, ast) for (n, k), ast
                        in ext.items()], **kwargs)

    # ---- classify atoms into vectorizable tiers ----
    # An atom can still refuse to lower here (e.g. STRING_MAP equality
    # has no device view even though its requirements collected fine);
    # demote every rule using it to host fallback and reclassify.
    ctx = tensor_expr._Ctx(layout, interner, finder)
    while True:
        live_atoms = sorted({i for mn in per_rule if mn
                             for conj in (mn[0] | mn[1]) for i, _ in conj})
        eq_cols: list[int] = []; eq_cids: list[int] = []
        eq_neg: list[bool] = []
        eq_atom_idx: list[int] = []
        ss_a: list[int] = []; ss_b: list[int] = []; ss_neg: list[bool] = []
        ss_atom_idx: list[int] = []
        # constant-pattern regex atoms grouped by subject: one packed
        # multi-DFA scan per subject instead of one scan per atom
        # (tensor_expr.compile_dfa_group)
        dfa_groups: dict[str, dict] = {}
        # constant-prefix startsWith atoms grouped by subject likewise
        # (tensor_expr.compile_prefix_group)
        prefix_groups: dict[str, dict] = {}
        gen_fns: list[Callable] = []
        gen_atom_idx: list[int] = []
        unlowerable: set[int] = set()

        for aidx in live_atoms:
            ast = atoms.asts[aidx]
            done = False
            f = ast.fn
            if ast.var is not None \
                    and finder.get_attribute(ast.var.name) == V.BOOL:
                eq_cols.append(layout.slot_of(ast.var.name))
                eq_cids.append(ID_TRUE); eq_neg.append(False)
                eq_atom_idx.append(aidx); done = True
            elif f is not None and f.name in ("EQ", "NEQ") \
                    and len(f.args) == 2:
                neg = f.name == "NEQ"
                for x, y in ((f.args[0], f.args[1]),
                             (f.args[1], f.args[0])):
                    sref = _slot_ref(x, layout, finder)
                    if sref is None:
                        continue
                    cid = _const_id(y, interner)
                    if cid is not None:
                        eq_cols.append(sref.col); eq_cids.append(cid)
                        eq_neg.append(neg); eq_atom_idx.append(aidx)
                        done = True
                        break
                if not done:
                    ra = _slot_ref(f.args[0], layout, finder)
                    rb = _slot_ref(f.args[1], layout, finder)
                    if ra is not None and rb is not None:
                        ss_a.append(ra.col); ss_b.append(rb.col)
                        ss_neg.append(neg); ss_atom_idx.append(aidx)
                        done = True
            if not done and f is not None and f.name == "matches" \
                    and f.target is not None \
                    and f.target.const_ is not None:
                pattern = f.target.const_.value
                dfa = reqs.dfas.get(pattern)
                try:
                    # probe the subject NOW so an un-viewable subject
                    # falls through to the generic path's fallback
                    tensor_expr._compile_bytes(f.args[0], ctx)
                except Exception:
                    dfa = None
                if dfa is not None:
                    g = dfa_groups.setdefault(
                        str(f.args[0]),
                        {"subject": f.args[0], "atoms": [],
                         "patterns": [], "dfas": []})
                    g["atoms"].append(aidx)
                    g["patterns"].append(pattern)
                    g["dfas"].append(dfa)
                    done = True
            if not done and f is not None and f.name == "startsWith" \
                    and f.target is not None \
                    and f.args[0].const_ is not None:
                prefix = f.args[0].const_.value
                try:
                    # probe as above; a prefix past the byte-slot cap
                    # is the generic path's HostFallback
                    tensor_expr._compile_bytes(f.target, ctx)
                    fits = len(prefix.encode("utf-8")) \
                        <= layout.max_str_len
                except Exception:
                    fits = False
                if fits:
                    g = prefix_groups.setdefault(
                        str(f.target), {"subject": f.target,
                                        "atoms": [], "prefixes": []})
                    g["atoms"].append(aidx)
                    g["prefixes"].append(prefix)
                    done = True
            if not done:
                try:
                    gen_fns.append(tensor_expr._compile_node(ast, ctx))
                except HostFallback:
                    unlowerable.add(aidx)   # keep scanning: one pass
                    continue                # collects every bad atom
                gen_atom_idx.append(aidx)

        if not unlowerable:
            break
        for ridx, mn in enumerate(per_rule):
            if mn is None:
                continue
            used = {i for conj in (mn[0] | mn[1]) for i, _ in conj}
            if used & unlowerable:
                per_rule[ridx] = None
                host_fallback[ridx] = _rule_oracle(rules[ridx], finder)
                fallback_reason[ridx] = "atom not lowerable"

    eq_info = {aidx: (eq_cols[i], eq_cids[i], eq_neg[i])
               for i, aidx in enumerate(eq_atom_idx)}
    # a bank past both one-hot tiers scans each row's candidate
    # automata where an id-equality guards its atoms, and the few atoms
    # read unguarded as a bank of their own
    # (tensor_expr.compile_dfa_group); its arrays join the params
    with _build_dfa_span(dfa_groups):
        guards = _dfa_guards(
            per_rule, {a for g in dfa_groups.values() for a in g["atoms"]},
            eq_info)
        dfa_group_fns = [tensor_expr.compile_dfa_group(
            g["subject"], g["patterns"], g["dfas"], ctx,
            guard=_bank_guard(g["atoms"], guards), prefix=f"dfa{gi}_")
            for gi, g in enumerate(dfa_groups.values())]
    # a group's columns in the order its function returns them
    dfa_atom_idx = [g["atoms"][i]
                    for g, gfn in zip(dfa_groups.values(), dfa_group_fns)
                    for i in gfn.order]
    # the prefix groups ride beside the dfa groups: both return
    # (val [B, k], ee [B, k])
    prefix_group_fns = [tensor_expr.compile_prefix_group(
        g["subject"], g["prefixes"], ctx) for g in prefix_groups.values()]
    prefix_atom_idx = [a for g in prefix_groups.values()
                       for a in g["atoms"]]

    n_atoms = len(atoms.asts)
    ss_a_a = np.asarray(ss_a, np.int32)
    ss_b_a = np.asarray(ss_b, np.int32)
    ss_neg_a = np.asarray(ss_neg, bool)

    # ---- conjunction + rule matrices ----
    conj_list: list[Conj] = []
    conj_key: dict[Conj, int] = {}
    rule_m_cols: list[list[int]] = []
    rule_n_cols: list[list[int]] = []
    for mn in per_rule:
        if mn is None:
            rule_m_cols.append([]); rule_n_cols.append([])
            continue
        cols_mn = []
        for dnf in mn:
            cols = []
            for conj in dnf:
                j = conj_key.get(conj)
                if j is None:
                    j = len(conj_list)
                    conj_key[conj] = j
                    conj_list.append(conj)
                cols.append(j)
            cols_mn.append(cols)
        rule_m_cols.append(cols_mn[0]); rule_n_cols.append(cols_mn[1])

    n_conjs = len(conj_list)
    n_rules = len(rules)
    # rule-axis padding for even mp sharding (see docstring)
    n_rows = max(_round_up(max(n_rules, 1), rule_pad), 1)

    # ---- fused gather–compare fast path ----
    # Conjunctions whose EVERY literal is a tier-1 EQ/NEQ(slot, const)
    # atom skip the two-stage evaluation (atom planes → literal
    # gather): their sat column gathers the slot ids/present bits
    # DIRECTLY and compares against the interned constants in the same
    # pass — one fused gather-compare over the slot tensor instead of
    # materializing the m/n literal planes and re-gathering them.
    # Literal truth for an EQ atom: m = cmp∧present, n = ¬cmp∧present,
    # so a (atom, kind) literal is ((ids==cid) ^ neg ^ (kind=='n')) ∧
    # present, and padding lanes read True (AND identity). EQ atoms
    # dominate real istio configs, so most snapshots evaluate entirely
    # here and the legacy literal-gather stage compiles away.
    # Conjunction columns permute fused-first; the rule-stage index
    # matrices are remapped through the permutation.
    fused_j = [j for j, conj in enumerate(conj_list)
               if all(aidx in eq_info for aidx, _ in conj)]
    fused_set = set(fused_j)
    legacy_j = [j for j in range(n_conjs) if j not in fused_set]
    n_fused = len(fused_j)
    n_legacy = n_conjs - n_fused
    new_of_old = np.zeros(max(n_conjs, 1), np.int32)
    for newj, oldj in enumerate(fused_j + legacy_j):
        new_of_old[oldj] = newj
    conj_list = [conj_list[j] for j in fused_j + legacy_j]
    rule_m_cols = [[int(new_of_old[j]) for j in cols]
                   for cols in rule_m_cols]
    rule_n_cols = [[int(new_of_old[j]) for j in cols]
                   for cols in rule_n_cols]
    # the legacy block only exists for conjunctions it still owns (or
    # as the placeholder column of an empty ruleset)
    use_legacy = n_legacy > 0 or n_fused == 0

    l_max_f = max((len(conj_list[j]) for j in range(n_fused)),
                  default=1) or 1
    l_max = max((len(conj_list[j]) for j in range(n_fused, n_conjs)),
                default=1) or 1
    k_max = max((max(len(m), len(n)) for m, n in
                 ((rule_m_cols[r], rule_n_cols[r]) for r in range(n_rules))),
                default=1) or 1

    # Conjunction-axis alignment (CONJ_ALIGN): the index tensors round
    # their conjunction rows up; pad rows read True (all-pad lanes /
    # the LIT_TRUE sentinel), no rule indexes them, and run() slices
    # the sat block back to its real width, so column numbering and
    # verdicts are untouched.
    f_rows = _round_up(max(n_fused, 1), CONJ_ALIGN)
    eqc_col = np.zeros((f_rows, l_max_f), np.int32)
    eqc_cid = np.zeros((f_rows, l_max_f), np.int32)
    eqc_xor = np.zeros((f_rows, l_max_f), bool)
    eqc_pad = np.ones((f_rows, l_max_f), bool)
    for j in range(n_fused):
        for s, (aidx, kind) in enumerate(sorted(conj_list[j])):
            col, cid, neg = eq_info[aidx]
            eqc_col[j, s] = col
            eqc_cid[j, s] = cid
            eqc_xor[j, s] = bool(neg) ^ (kind == "n")
            eqc_pad[j, s] = False

    # The legacy m/n planes carry ONLY the EQ atoms some legacy
    # conjunction still references — an EQ atom every referencing
    # conjunction of which went fused would be gathered/compared into
    # lanes no lit_idx row ever reads (XLA cannot DCE them: lit_idx is
    # a traced param, not a constant). ss/dfa/gen atoms are legacy by
    # construction (any conjunction holding one is non-fusable).
    legacy_atom_set = {aidx for conj in conj_list[n_fused:]
                       for aidx, _ in conj}
    eq_keep = [i for i, aidx in enumerate(eq_atom_idx)
               if aidx in legacy_atom_set]
    eq_live_idx = [eq_atom_idx[i] for i in eq_keep]
    order = eq_live_idx + ss_atom_idx + dfa_atom_idx \
        + prefix_atom_idx + gen_atom_idx
    n_live = max(len(order), 1)   # width of the m/n literal blocks
    # inverse permutation: position of atom i in the concatenated output
    pos_of = np.full(max(n_atoms, 1), 0, dtype=np.int32)
    for pos, aidx in enumerate(order):
        pos_of[aidx] = pos
    eq_cols_a = np.asarray([eq_cols[i] for i in eq_keep], np.int32)
    eq_cids_a = np.asarray([eq_cids[i] for i in eq_keep], np.int32)
    eq_neg_a = np.asarray([eq_neg[i] for i in eq_keep], bool)

    # Sparse (gather) formulation. Conjunctions average only a few
    # literals and rules a few conjunctions, so dense [2A, n_conj] /
    # [n_conj, R] one-hot matmuls waste ~1000× the FLOPs (measured
    # 23ms/step at 10k rules on v5e); padded index gathers + AND/OR
    # reductions are pure HBM-bandwidth ops (<2ms). Sentinel columns:
    # literal index 2·n_live is always-TRUE (AND identity), conjunction
    # index n_conjs is always-FALSE (OR identity).
    LIT_TRUE = 2 * n_live
    CONJ_FALSE = max(n_conjs, 1)   # sat has max(n_conjs,1) real columns
    CONJ_TRUE = CONJ_FALSE + 1     # pad rows: definitely-not-matched
    # legacy literal gather rows: only the conjunctions the fused
    # gather-compare path above did NOT absorb (an all-EQ snapshot
    # compiles no literal gather at all)
    n_legacy_cols = max(n_legacy, 1)
    lit_idx = np.full((_round_up(n_legacy_cols, CONJ_ALIGN), l_max),
                      LIT_TRUE, np.int32)
    for jj, conj in enumerate(conj_list[n_fused:]):
        for s, (aidx, kind) in enumerate(sorted(conj)):
            lit_idx[jj, s] = pos_of[aidx] + (0 if kind == "m" else n_live)
    conj_m_idx = np.full((n_rows, k_max), CONJ_FALSE, np.int32)
    conj_n_idx = np.full((n_rows, k_max), CONJ_FALSE, np.int32)
    # padding rows read not_matched=True (never "err"): their N gather
    # points at the always-TRUE sentinel column
    conj_n_idx[n_rules:, 0] = CONJ_TRUE
    for ridx in range(n_rules):
        for s, j in enumerate(rule_m_cols[ridx]):
            conj_m_idx[ridx, s] = j
        for s, j in enumerate(rule_n_cols[ridx]):
            conj_n_idx[ridx, s] = j

    # Index tensors are ARGUMENTS, not closure constants: 10k-rule
    # snapshots would otherwise embed MBs of literals in the HLO (the
    # serialized program must stay small for remote compilation).
    params = {"lit_idx": jnp.asarray(lit_idx),
              "conj_m_idx": jnp.asarray(conj_m_idx),
              "conj_n_idx": jnp.asarray(conj_n_idx),
              "eqc_col": jnp.asarray(eqc_col),
              "eqc_cid": jnp.asarray(eqc_cid),
              "eqc_xor": jnp.asarray(eqc_xor),
              "eqc_pad": jnp.asarray(eqc_pad)}
    for gfn in dfa_group_fns:
        params.update(gfn.params)

    def run(params: Mapping[str, Any],
            batch: AttributeBatch) -> tuple[Any, Any, Any]:
        b = batch.ids.shape[0]
        sat_parts = []
        if n_fused:
            # fused gather-compare: one pass over the slot tensor
            # computes every all-EQ conjunction's sat bit — no literal
            # planes, no second gather
            iv = batch.ids[:, params["eqc_col"]]        # [B, F, Lf]
            pv = batch.present[:, params["eqc_col"]]
            hit = ((iv == params["eqc_cid"][None]) ^
                   params["eqc_xor"][None]) & pv
            sat_parts.append(jnp.all(hit | params["eqc_pad"][None],
                                     axis=2)[:, :n_fused])
        if use_legacy:
            parts_m, parts_n = [], []
            if eq_cols_a.size:
                ids = batch.ids[:, eq_cols_a]
                pres = batch.present[:, eq_cols_a]
                cmp = (ids == eq_cids_a[None, :]) ^ eq_neg_a[None, :]
                parts_m.append(cmp & pres)
                parts_n.append(~cmp & pres)
            if ss_a_a.size:
                pres = batch.present[:, ss_a_a] & batch.present[:, ss_b_a]
                cmp = (batch.ids[:, ss_a_a] == batch.ids[:, ss_b_a]) \
                    ^ ss_neg_a[None, :]
                parts_m.append(cmp & pres)
                parts_n.append(~cmp & pres)
            for gfn in dfa_group_fns:
                # metadata only: the trace's device operations carry
                # the scope path (benchmark/scopes.py)
                with jax.named_scope("dfa"):
                    gval, gee = gfn(batch, params)
                parts_m.append(gval)           # already masked by ~ee
                parts_n.append(~gval & ~gee)
            for gfn in prefix_group_fns:
                gval, gee = gfn(batch)
                parts_m.append(gval)
                parts_n.append(~gval & ~gee)
            for fn in gen_fns:
                t = fn(batch)
                ee = t.err | ~t.ok
                parts_m.append((t.val & ~ee)[:, None])
                parts_n.append((~t.val & ~ee)[:, None])
            if parts_m:
                m_all = jnp.concatenate(parts_m, axis=1)
                n_all = jnp.concatenate(parts_n, axis=1)
            else:
                m_all = jnp.zeros((b, 1), bool)
                n_all = jnp.zeros((b, 1), bool)
            # lit[:, LIT_TRUE] is the AND-identity sentinel
            lit = jnp.concatenate(
                [m_all, n_all, jnp.ones((b, 1), bool)], axis=1)
            sat_parts.append(
                jnp.all(lit[:, params["lit_idx"]],
                        axis=2)[:, :n_legacy_cols])
        sat = sat_parts[0] if len(sat_parts) == 1 \
            else jnp.concatenate(sat_parts, axis=1)   # [B, n_conjs]
        # sat[:, CONJ_FALSE] is the OR-identity sentinel;
        # sat[:, CONJ_TRUE] the always-true column rule-axis padding
        # points its N gather at
        sat_ext = jnp.concatenate(
            [sat, jnp.zeros((b, 1), bool), jnp.ones((b, 1), bool)],
            axis=1)
        matched = jnp.any(sat_ext[:, params["conj_m_idx"]], axis=2)
        not_matched = jnp.any(sat_ext[:, params["conj_n_idx"]], axis=2)
        # empty-M rules (incl. host fallback): matched stays False; the
        # err bit below correctly reads True only for device rules whose
        # DNF pair is inconclusive on this input.
        err = ~matched & ~not_matched
        return matched, not_matched, err

    # ---- per-rule attribute bitmaps (compile-time ReferencedAttributes) ----
    attr_mask = np.zeros((n_rows, max(layout.n_columns, 1)), bool)
    attr_names: list[set] = []
    for ridx in range(n_rules):
        names: set = set()
        _collect_attr_names(parsed[ridx], finder, names)
        attr_names.append(names)
        for item in names:
            if isinstance(item, tuple):
                if item in layout.derived_slots:
                    attr_mask[ridx, layout.derived_slots[item]] = True
            elif item in layout.slots:
                attr_mask[ridx, layout.slots[item]] = True

    ns_ids: dict[str, int] = {"": 0}
    # pad rows carry an unmatchable namespace (ids are ≥ 0, unknown
    # request namespaces are -1) so they are invisible everywhere
    rule_ns = np.full(n_rows, -7, np.int32)
    if n_rules == 0:
        rule_ns[:] = 0   # placeholder row of an empty ruleset
    for ridx, rule in enumerate(rules):
        ns = rule.namespace
        if ns not in ns_ids:
            ns_ids[ns] = len(ns_ids)
        rule_ns[ridx] = ns_ids[ns]

    atom_tier = {aidx: "id-eq" for aidx in eq_atom_idx}
    atom_tier.update({aidx: "slot-eq" for aidx in ss_atom_idx})
    atom_tier.update({aidx: "dfa-pack" for aidx in dfa_atom_idx})
    atom_tier.update({aidx: "prefix-pack" for aidx in prefix_atom_idx})
    atom_tier.update({aidx: "tensor" for aidx in gen_atom_idx})

    geometry = {
        # EQ atoms the LEGACY stage materializes planes for (fused-only
        # EQ atoms are excluded above) — the roofline model sizes the
        # legacy stage from this; the total is n_eq_atoms_total
        "n_eq_atoms": len(eq_keep),
        "n_eq_atoms_total": len(eq_atom_idx),
        "n_ss_atoms": len(ss_atom_idx),
        "n_dfa_atoms": len(dfa_atom_idx),
        "n_prefix_atoms": len(prefix_atom_idx),
        "n_gen_atoms": len(gen_atom_idx),
        "n_dfa_groups": len(dfa_groups),
        "n_prefix_groups": len(prefix_groups),
        # per DFA bank (one a group, two where a group splits):
        # subject, tier, automata, resident bytes, automata scanned a
        # row (tensor_expr.compile_dfa_group)
        "dfa_banks": [b for gfn in dfa_group_fns for b in gfn.banks],
        "n_live": n_live,
        "n_conjs": n_conjs,
        "n_fused_conjs": n_fused,
        "n_legacy_conjs": n_legacy,
        "use_legacy": use_legacy,
        "l_max_fused": int(eqc_col.shape[1]) if n_fused else 0,
        "l_max_legacy": int(lit_idx.shape[1]) if use_legacy else 0,
        "k_max": k_max,
        "n_rows": n_rows,
    }

    return RuleSetProgram(
        rules=list(rules), layout=layout, interner=interner,
        fn=jax.jit(run) if jit else run, params=params,
        n_atoms=n_atoms, n_conjs=n_conjs,
        host_fallback=host_fallback, fallback_reason=fallback_reason,
        attr_mask=attr_mask, attr_names=attr_names,
        rule_ns=rule_ns, ns_ids=ns_ids,
        atom_asts=list(atoms.asts), atom_tier=atom_tier,
        per_rule_dnf=list(per_rule), geometry=geometry,
        guards=_rule_guards(per_rule, eq_info))


def _collect_attr_names(e: Expression, finder: AttributeDescriptorFinder,
                        out: set) -> None:
    if e.var is not None:
        out.add(e.var.name)
        return
    f = e.fn
    if f is None:
        return
    if (f.name == "INDEX" and f.args[0].var is not None
            and f.args[1].const_ is not None):
        out.add(f.args[0].var.name)
        out.add((f.args[0].var.name, f.args[1].const_.value))
        return
    if f.target is not None:
        _collect_attr_names(f.target, finder, out)
    for a in f.args:
        _collect_attr_names(a, finder, out)
