"""Regex → byte-level DFA compiler for device-side `matches()`.

The reference evaluates RE2 regexes on the host per call
(mixer/pkg/il/runtime/externs.go:118 `matches`). On TPU we compile each
pattern ONCE (host side, config time) into a dense uint8-alphabet DFA
transition table; evaluation is then a fixed-length `lax.scan` of gathers
(or a Pallas one-hot matmul) over the padded subject bytes — thousands of
subjects × patterns per device step.

Supported syntax (the subset real mesh configs use): literals, `.`,
character classes `[a-z]`/`[^...]` with escapes, groups `(...)`,
alternation `|`, repetition `* + ? {m} {m,} {m,n}`, anchors `^`/`$` at the
pattern edges, escapes `\\d \\D \\w \\W \\s \\S` and escaped
metacharacters. Unsupported constructs (backreferences, lookaround,
non-greedy — irrelevant for acceptance — inner anchors, unicode classes)
raise UnsupportedRegex; callers fall back to the host oracle.

Semantics target: Go regexp.MatchString — UNANCHORED search. Patterns are
compiled as `.*(pattern)` and acceptance is monitored at every prefix
length, so `search` semantics come out of a single end-state check per
step.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# the bit-lane codec lives with its on-device inverse (unpack_bits);
# re-exported here because the packed one-hot step banks below are its
# heaviest producer
from istio_tpu.ops.bytes_ops import pack_bits

ALPHABET = 256


class UnsupportedRegex(ValueError):
    pass


# ---------------------------------------------------------------------------
# Pattern AST
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Node:
    kind: str                      # lit/class/any/cat/alt/star/plus/opt/rep/empty
    chars: frozenset[int] | None = None
    children: tuple["_Node", ...] = ()
    lo: int = 0
    hi: int = 0


_CLASS_ESCAPES = {
    "d": frozenset(range(0x30, 0x3A)),
    "w": frozenset(list(range(0x30, 0x3A)) + list(range(0x41, 0x5B)) +
                   list(range(0x61, 0x7B)) + [0x5F]),
    "s": frozenset([0x20, 0x09, 0x0A, 0x0D, 0x0B, 0x0C]),
}
_META = set(".*+?()[]{}|^$\\")


def _negate(s: frozenset[int]) -> frozenset[int]:
    return frozenset(range(ALPHABET)) - s


class _RegexParser:
    def __init__(self, pattern: str):
        self.p = pattern
        self.i = 0

    def peek(self) -> str | None:
        return self.p[self.i] if self.i < len(self.p) else None

    def next(self) -> str:
        c = self.p[self.i]
        self.i += 1
        return c

    def parse(self) -> tuple[_Node, bool, bool]:
        """Returns (ast, anchored_start, anchored_end)."""
        anchored_start = False
        anchored_end = False
        if self.peek() == "^":
            self.next()
            anchored_start = True
        node = self.alternation()
        # trailing $ is consumed inside alternation handling; detect flag
        if self.i < len(self.p):
            raise UnsupportedRegex(f"trailing junk in pattern: {self.p[self.i:]!r}")
        if node.kind == "cat" and node.children and \
                node.children[-1].kind == "end_anchor":
            node = _Node("cat", children=node.children[:-1])
            anchored_end = True
        elif node.kind == "end_anchor":
            node = _Node("empty")
            anchored_end = True
        return node, anchored_start, anchored_end

    def alternation(self) -> _Node:
        branches = [self.concat()]
        while self.peek() == "|":
            self.next()
            branches.append(self.concat())
        if len(branches) == 1:
            return branches[0]
        if any(b.kind == "end_anchor" or
               (b.kind == "cat" and any(c.kind == "end_anchor"
                                        for c in b.children))
               for b in branches):
            raise UnsupportedRegex("anchor inside alternation")
        return _Node("alt", children=tuple(branches))

    def concat(self) -> _Node:
        parts: list[_Node] = []
        while True:
            c = self.peek()
            if c is None or c in "|)":
                break
            parts.append(self.repeat())
        if not parts:
            return _Node("empty")
        for p in parts[:-1]:
            if p.kind == "end_anchor":
                raise UnsupportedRegex("$ not at pattern end")
        if len(parts) == 1:
            return parts[0]
        return _Node("cat", children=tuple(parts))

    def repeat(self) -> _Node:
        atom = self.atom()
        while True:
            c = self.peek()
            if c == "*":
                self.next()
                atom = _Node("star", children=(atom,))
            elif c == "+":
                self.next()
                atom = _Node("plus", children=(atom,))
            elif c == "?":
                self.next()
                atom = _Node("opt", children=(atom,))
            elif c == "{":
                atom = self.bounded(atom)
            else:
                if self.peek() == "?":  # non-greedy suffix like *? — greedy
                    self.next()         # equivalence holds for acceptance
                    continue
                return atom

    def bounded(self, atom: _Node) -> _Node:
        self.next()  # consume {
        spec = ""
        while self.peek() is not None and self.peek() != "}":
            spec += self.next()
        if self.peek() != "}":
            raise UnsupportedRegex("unterminated {}")
        self.next()
        parts = spec.split(",")
        try:
            if len(parts) == 1:
                lo = hi = int(parts[0])
            elif parts[1] == "":
                lo, hi = int(parts[0]), -1
            else:
                lo, hi = int(parts[0]), int(parts[1])
        except ValueError:
            raise UnsupportedRegex(f"bad repetition {{{spec}}}")
        if hi != -1 and (hi < lo or hi > 64):
            raise UnsupportedRegex(f"repetition bound too large {{{spec}}}")
        return _Node("rep", children=(atom,), lo=lo, hi=hi)

    def atom(self) -> _Node:
        c = self.next()
        if c == "(":
            if self.peek() == "?":
                self.next()
                if self.peek() == ":":
                    self.next()          # (?: non-capturing — fine
                else:
                    raise UnsupportedRegex("(?...) construct")
            node = self.alternation()
            if self.peek() != ")":
                raise UnsupportedRegex("unbalanced paren")
            self.next()
            return node
        if c == "[":
            return self.char_class()
        if c == ".":
            return _Node("any")
        if c == "$":
            return _Node("end_anchor")
        if c == "^":
            raise UnsupportedRegex("^ not at pattern start")
        if c == "\\":
            return self.escape()
        if c in "*+?{":
            raise UnsupportedRegex(f"dangling {c!r}")
        if ord(c) > 255:
            raise UnsupportedRegex("non-byte character")
        return _Node("lit", chars=frozenset([ord(c)]))

    def escape(self) -> _Node:
        if self.peek() is None:
            raise UnsupportedRegex("trailing backslash")
        c = self.next()
        if c in _CLASS_ESCAPES:
            return _Node("class", chars=_CLASS_ESCAPES[c])
        if c.upper() in _CLASS_ESCAPES and c.isupper():
            return _Node("class", chars=_negate(_CLASS_ESCAPES[c.lower()]))
        if c == "n":
            return _Node("lit", chars=frozenset([10]))
        if c == "t":
            return _Node("lit", chars=frozenset([9]))
        if c == "r":
            return _Node("lit", chars=frozenset([13]))
        if c in _META or not c.isalnum():
            return _Node("lit", chars=frozenset([ord(c)]))
        if c.upper() == "B":
            raise UnsupportedRegex("word boundary")
        raise UnsupportedRegex(f"escape \\{c}")

    def char_class(self) -> _Node:
        negated = False
        if self.peek() == "^":
            self.next()
            negated = True
        chars: set[int] = set()
        first = True
        while True:
            c = self.peek()
            if c is None:
                raise UnsupportedRegex("unterminated character class")
            if c == "]" and not first:
                self.next()
                break
            first = False
            c = self.next()
            if c == "\\":
                nxt = self.next()
                if nxt in _CLASS_ESCAPES:
                    chars |= _CLASS_ESCAPES[nxt]
                    continue
                if nxt.upper() in _CLASS_ESCAPES and nxt.isupper():
                    chars |= _negate(_CLASS_ESCAPES[nxt.lower()])
                    continue
                lo_ch = {"n": 10, "t": 9, "r": 13}.get(nxt, ord(nxt))
            else:
                lo_ch = ord(c)
            if self.peek() == "-" and self.i + 1 < len(self.p) and \
                    self.p[self.i + 1] != "]":
                self.next()
                hi_c = self.next()
                if hi_c == "\\":
                    hi_c = self.next()
                chars |= set(range(lo_ch, ord(hi_c) + 1))
            else:
                chars.add(lo_ch)
        if any(ch > 255 for ch in chars):
            raise UnsupportedRegex("non-byte character in class")
        return _Node("class",
                     chars=_negate(frozenset(chars)) if negated
                     else frozenset(chars))


# ---------------------------------------------------------------------------
# Thompson NFA
# ---------------------------------------------------------------------------

class _NFA:
    def __init__(self) -> None:
        self.eps: list[list[int]] = []
        self.trans: list[list[tuple[frozenset[int], int]]] = []

    def new_state(self) -> int:
        self.eps.append([])
        self.trans.append([])
        return len(self.eps) - 1

    def add_eps(self, a: int, b: int) -> None:
        self.eps[a].append(b)

    def add_trans(self, a: int, chars: frozenset[int], b: int) -> None:
        self.trans[a].append((chars, b))


_ANY = frozenset(range(ALPHABET))


def _build(nfa: _NFA, node: _Node) -> tuple[int, int]:
    """Thompson construction: returns (start, accept)."""
    s, t = nfa.new_state(), nfa.new_state()
    k = node.kind
    if k == "empty":
        nfa.add_eps(s, t)
    elif k in ("lit", "class"):
        nfa.add_trans(s, node.chars, t)
    elif k == "any":
        nfa.add_trans(s, _ANY, t)
    elif k == "cat":
        prev = s
        for child in node.children:
            cs, ct = _build(nfa, child)
            nfa.add_eps(prev, cs)
            prev = ct
        nfa.add_eps(prev, t)
    elif k == "alt":
        for child in node.children:
            cs, ct = _build(nfa, child)
            nfa.add_eps(s, cs)
            nfa.add_eps(ct, t)
    elif k == "star":
        cs, ct = _build(nfa, node.children[0])
        nfa.add_eps(s, cs)
        nfa.add_eps(s, t)
        nfa.add_eps(ct, cs)
        nfa.add_eps(ct, t)
    elif k == "plus":
        cs, ct = _build(nfa, node.children[0])
        nfa.add_eps(s, cs)
        nfa.add_eps(ct, cs)
        nfa.add_eps(ct, t)
    elif k == "opt":
        cs, ct = _build(nfa, node.children[0])
        nfa.add_eps(s, cs)
        nfa.add_eps(ct, t)
        nfa.add_eps(s, t)
    elif k == "rep":
        prev = s
        for _ in range(node.lo):
            cs, ct = _build(nfa, node.children[0])
            nfa.add_eps(prev, cs)
            prev = ct
        if node.hi == -1:  # {m,}
            cs, ct = _build(nfa, node.children[0])
            nfa.add_eps(prev, cs)
            nfa.add_eps(ct, cs)
            nfa.add_eps(ct, t)
            nfa.add_eps(prev, t)
        else:
            for _ in range(node.hi - node.lo):
                cs, ct = _build(nfa, node.children[0])
                nfa.add_eps(prev, cs)
                nfa.add_eps(prev, t)
                prev = ct
            nfa.add_eps(prev, t)
    elif k == "end_anchor":
        raise UnsupportedRegex("$ in unsupported position")
    else:  # pragma: no cover
        raise UnsupportedRegex(f"internal: node {k}")
    return s, t


# ---------------------------------------------------------------------------
# Subset construction → dense DFA
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DFA:
    """Dense byte DFA. transitions[state, byte] → state;
    accept[state] → bool. State 0 is the start state.

    For unanchored (search) semantics, acceptance is sticky: accepting
    states only transition to accepting states, so checking acceptance
    after consuming all `len` bytes is equivalent to checking at every
    prefix. This keeps the device step to a single scan with one final
    accept gather."""
    transitions: np.ndarray  # int32 [n_states, 256]
    accept: np.ndarray       # bool  [n_states]
    pattern: str

    @property
    def n_states(self) -> int:
        return int(self.transitions.shape[0])


_MAX_DFA_STATES = 2048


def compile_regex(pattern: str) -> DFA:
    """Compile to a dense search-semantics DFA (Go regexp.MatchString
    equivalence for the supported subset)."""
    ast, anchored_start, anchored_end = _RegexParser(pattern).parse()

    # search semantics: allow any prefix unless ^-anchored
    if not anchored_start:
        ast = _Node("cat", children=(_Node("star", children=(_Node("any"),)),
                                     ast))
    # unless $-anchored, allow any suffix — combined with sticky accept
    if not anchored_end:
        ast = _Node("cat", children=(ast,
                                     _Node("star", children=(_Node("any"),))))

    nfa = _NFA()
    start, accept = _build(nfa, ast)

    def eps_closure(states: frozenset[int]) -> frozenset[int]:
        stack = list(states)
        seen = set(states)
        while stack:
            s = stack.pop()
            for t in nfa.eps[s]:
                if t not in seen:
                    seen.add(t)
                    stack.append(t)
        return frozenset(seen)

    # Byte classes of THIS pattern: bytes no transition's character
    # set tells apart move every state alike, so a state is expanded
    # once a class (a route regex has ~30) and not once a byte.
    # Classes are numbered by their smallest byte, so states are
    # discovered, and numbered, in the order a byte-by-byte walk finds
    # them.
    set_ids: dict[frozenset[int], int] = {}
    for outs in nfa.trans:
        for chars, _ in outs:
            set_ids.setdefault(chars, len(set_ids))
    # (a last all-False row: a pattern with no transition has one class)
    member = np.zeros((len(set_ids) + 1, ALPHABET), bool)
    for chars, k in set_ids.items():
        member[k, list(chars)] = True
    _, reps, class_of = np.unique(member, axis=1, return_index=True,
                                  return_inverse=True)
    by_first = np.argsort(reps)
    reps = reps[by_first]
    rank = np.empty(len(reps), np.int64)
    rank[by_first] = np.arange(len(reps))
    class_of = rank[class_of.reshape(-1)]
    covers = [np.flatnonzero(member[k, reps]).tolist()
              for k in range(len(set_ids))]
    moves = [[(covers[set_ids[chars]], t) for chars, t in outs]
             for outs in nfa.trans]

    start_set = eps_closure(frozenset([start]))
    dfa_ids: dict[frozenset[int], int] = {start_set: 0}
    worklist = [start_set]
    rows: list[np.ndarray] = []
    accepts: list[bool] = []

    while worklist:
        cur = worklist.pop()
        cur_id = dfa_ids[cur]
        while len(rows) <= cur_id:
            rows.append(np.zeros(ALPHABET, dtype=np.int32))
            accepts.append(False)
        accepts[cur_id] = accept in cur

        # group target NFA states by byte class
        by_class: list[set[int]] = [set() for _ in reps]
        for s in cur:
            for classes, t in moves[s]:
                for c in classes:
                    by_class[c].add(t)
        row = np.zeros(len(reps), dtype=np.int32)
        closure_cache: dict[frozenset[int], int] = {}
        for c, targets in enumerate(by_class):
            tgt = frozenset(targets)
            if tgt in closure_cache:
                row[c] = closure_cache[tgt]
                continue
            # sticky accept for search semantics: an unanchored end's
            # suffix .* already keeps acceptance
            nxt = eps_closure(tgt) if tgt else frozenset()
            tid = dfa_ids.get(nxt)
            if tid is None:
                tid = len(dfa_ids)
                if tid >= _MAX_DFA_STATES:
                    raise UnsupportedRegex(
                        f"DFA for {pattern!r} exceeds {_MAX_DFA_STATES} states")
                dfa_ids[nxt] = tid
                worklist.append(nxt)
            row[c] = tid
            closure_cache[tgt] = tid
        rows[cur_id] = row[class_of]

    while len(rows) < len(dfa_ids):
        rows.append(np.zeros(ALPHABET, dtype=np.int32))
        accepts.append(False)
    # fill states discovered but not yet expanded (empty set sink)
    for st, sid in dfa_ids.items():
        if sid < len(accepts):
            accepts[sid] = accept in st

    return DFA(transitions=np.stack(rows), accept=np.array(accepts, bool),
               pattern=pattern)


def dfa_matches_host(dfa: DFA, subject: bytes) -> bool:
    """Host-side DFA run (oracle for the device kernel)."""
    state = 0
    for b in subject:
        state = int(dfa.transitions[state, b])
    return bool(dfa.accept[state])


def pack_dfas(dfas: list[DFA]) -> tuple[np.ndarray, np.ndarray]:
    """Stack several DFAs into one padded transition bank for the
    vectorized device step: returns (trans [n, S_max, 256] int32,
    accept [n, S_max] bool)."""
    smax = max(d.n_states for d in dfas)
    trans = np.zeros((len(dfas), smax, ALPHABET), dtype=np.int32)
    accept = np.zeros((len(dfas), smax), dtype=bool)
    for i, d in enumerate(dfas):
        trans[i, :d.n_states] = d.transitions
        accept[i, :d.n_states] = d.accept
    return trans, accept


def pack_dfas_classes(dfas: list[DFA]) -> dict:
    """CHEAP phase of the one-hot packing: renumber all automata into
    one global state space and compute the bank-wide byte EQUIVALENCE
    CLASSES (bytes with identical transition columns across every
    state). O(S·256) numpy work — callers size-gate on
    n_states/n_classes BEFORE paying for the step matrix
    (pack_dfas_onehot). Nothing here is quadratic in the bank: a
    10 000-automaton bank comes through (250k states)."""
    offs = np.cumsum([0] + [d.n_states for d in dfas])
    s_tot = int(offs[-1])
    gt = np.zeros((s_tot, ALPHABET), np.int32)
    for i, d in enumerate(dfas):
        gt[offs[i]:offs[i + 1]] = d.transitions + offs[i]
    # Equal columns, found by a 64-bit mix of each column and then
    # proved equal: sorting 256 columns of 250k states apiece
    # (np.unique over the whole table) was 15 s of a 10 000-automaton
    # bank's build. Classes keep the numbering that sort gave them
    # (the lexicographic order of the distinct columns).
    weights = np.random.default_rng(0x5eed).integers(
        1, 1 << 63, s_tot, dtype=np.uint64) | np.uint64(1)
    mix = (gt.astype(np.uint64) * weights[:, None]).sum(axis=0)
    _, first, group = np.unique(mix, return_index=True,
                                return_inverse=True)
    distinct = gt[:, first]
    if (gt == distinct[:, group]).all():
        _, order = np.unique(distinct, axis=1, return_inverse=True)
        class_of = order.reshape(-1)[group]
    else:   # two columns mixed alike: sort them all
        _, class_of = np.unique(gt, axis=1, return_inverse=True)
        class_of = class_of.reshape(-1)
    n_cls = int(class_of.max()) + 1
    rep = np.zeros(n_cls, np.int64)   # a representative byte per class
    for byte in range(ALPHABET - 1, -1, -1):
        rep[class_of[byte]] = byte
    return {"gt": gt, "class_of": class_of, "rep": rep,
            "starts": offs[:-1].astype(np.int32),
            "n_states": s_tot, "n_classes": n_cls}


def pack_dfas_onehot(dfas: list[DFA],
                     classes: dict | None = None) -> dict:
    """Pack several DFAs for the MXU (one-hot matmul) device kernel
    (bytes_ops.dfa_match_many_onehot).

    Returns {"step_bits": [S·C, ceil(S/32)] BIT-PACKED one-hot
    transition matrix (row s·C+c → one-hot of next state; pack_bits
    lanes, unpacked to bf16 on device once per kernel invocation —
    bytes_ops.unpack_bits), "cls": [256, C] one-hot byte→class matrix,
    "starts": [N] int32 global start states, "accept": [S, N] pattern
    acceptance matrix}. The step matrix is O(S²·C) one-hot entries —
    bit lanes keep the resident bank at 1/32 of the f32 formulation's
    bytes; size-gate via pack_dfas_classes first."""
    k = classes if classes is not None else pack_dfas_classes(dfas)
    s_tot, n_cls = k["n_states"], k["n_classes"]
    gt, class_of, rep = k["gt"], k["class_of"], k["rep"]
    step = np.zeros((s_tot * n_cls, s_tot), bool)
    rows = (np.arange(s_tot)[:, None] * n_cls
            + np.arange(n_cls)[None, :]).reshape(-1)
    cols = gt[:, rep].reshape(-1)          # [S, C] next states
    step[rows, cols] = True
    cls = np.zeros((ALPHABET, n_cls), np.float32)
    cls[np.arange(ALPHABET), class_of] = 1.0
    accept = np.zeros((s_tot, len(dfas)), np.float32)
    for i, (d, lo) in enumerate(zip(dfas, k["starts"])):
        accept[lo:lo + d.n_states, i] = d.accept
    return {"step_bits": pack_bits(step), "cls": cls,
            "starts": k["starts"], "accept": accept,
            "n_states": s_tot, "n_classes": n_cls}


def pack_dfas_onehot_blocked(dfas: list[DFA],
                             classes: dict | None = None) -> dict:
    """BLOCK-DIAGONAL one-hot packing: per-pattern step matrices padded
    to the widest automaton, for bytes_ops.dfa_match_many_onehot_blocked
    (a batched matmul over the pattern axis).

    The dense pack_dfas_onehot matrix is O((Σsᵢ)²·C) — quadratic in the
    BANK, so a 23-glob bank blows the size gate and used to fall back
    to the latency-bound gather scan. Blocks are O(N·s_max²·C): states
    never cross patterns, so the dense matrix was block-diagonal
    anyway — this stores only the blocks.

    Returns {"step_bits": [N, s_max·C, ceil(s_max/32)] bit-packed
    blocks (pack_bits lanes, device-unpacked once per invocation),
    "cls": [256, C], "accept": [N, s_max] (acceptance of pattern i's
    own states), "n_states_max", "n_classes", "n_pats"}; pattern i
    starts in its local state 0 (compile_regex numbers the start
    state 0)."""
    k = classes if classes is not None else pack_dfas_classes(dfas)
    n = len(dfas)
    n_cls = int(k["n_classes"])
    class_of, rep = k["class_of"], k["rep"]
    s_max = max(d.n_states for d in dfas)
    step = np.zeros((n, s_max * n_cls, s_max), bool)
    accept = np.zeros((n, s_max), np.float32)
    for i, d in enumerate(dfas):
        s_i = d.n_states
        rows = (np.arange(s_i)[:, None] * n_cls
                + np.arange(n_cls)[None, :]).reshape(-1)
        cols = d.transitions[:, rep].reshape(-1)
        step[i, rows, cols] = True
        accept[i, :s_i] = d.accept
        # padding states self-loop dead (all-zero rows: a one-hot that
        # reaches them vanishes — they are unreachable from state 0)
    cls = np.zeros((ALPHABET, n_cls), np.float32)
    cls[np.arange(ALPHABET), class_of] = 1.0
    return {"step_bits": pack_bits(step), "cls": cls, "accept": accept,
            "n_states_max": s_max, "n_classes": n_cls, "n_pats": n}


# Table cells one row's candidates may hold (K·s_max·width): the
# candidate scan lays every row's own tables out beside it, so a bank
# of wide automata would cost the batch what it saves the scan
CANDIDATE_CELLS = 1 << 15
# Automata a candidate bank may hold: the scatter back to the bank's
# columns names an automaton by two base-256 digits (bf16 holds an
# integer up to 256 exactly; compile_dfa_group)
CANDIDATE_AUTOMATA = 1 << 16


def pack_dfas_candidates(dfas: list[DFA], classes: dict,
                         guard_of: list) -> dict | None:
    """CANDIDATE packing for bytes_ops.dfa_match_candidates: one small
    table per automaton, plus, per guard value, the automata that
    value guards. `guard_of[i]`, values in [0, G), names the values of
    the bank's guard (an id-equality every conjunction holding
    automaton i asserts, compiler/ruleset.py) under which automaton i
    can change a verdict: one where one host holds the pattern,
    several where hosts share it. A row scans its own guard value's K
    automata and not the bank's N.

    A table is indexed by state and byte CLASS, not byte (a
    10 000-automaton route table has ~30 classes: an eighth of a
    256-column table), its width rounded up to a power of two, and
    holds the automaton's LOCAL next state, one byte a cell where the
    widest automaton allows. `classes` may be those of a larger bank
    (a finer partition serves). Automaton N is the dead one every
    padding candidate runs: it stays in state 0 and never accepts.

    An automaton sits at ONE slot under every value that holds it
    (shared automata are placed first, each at the lowest slot free
    under all its values), so the scatter back to the bank's columns
    is one [K, N] selection whatever the row's value.

    Returns {"local": int8|int32 [N+1, s_max·width], "accept": bool
    [(N+1)·s_max], "class_of": int32 [256], "cand": int32 [G+1, K]
    automaton indices padded with N (row G: a row whose guard names no
    value), "slot": int32 [N], "n_states_max", "width", "k"}; None
    when K tables are more than CANDIDATE_CELLS a row, or the bank
    more than CANDIDATE_AUTOMATA."""
    n = len(dfas)
    s_max = max(d.n_states for d in dfas)
    rep = classes["rep"]
    width = 1 << max(len(rep) - 1, 0).bit_length()
    n_values = 1 + max(v for held in guard_of for v in held)
    taken: list[set] = [set() for _ in range(n_values)]
    slot = np.empty(n, np.int32)
    for i in sorted(range(n), key=lambda i: -len(guard_of[i])):
        at = 0
        while any(at in taken[v] for v in guard_of[i]):
            at += 1
        slot[i] = at
        for v in guard_of[i]:
            taken[v].add(at)
    k = int(slot.max()) + 1
    if k * s_max * width > CANDIDATE_CELLS or n >= CANDIDATE_AUTOMATA:
        return None
    # unwritten cells (padding states and classes, the dead automaton)
    # are never reached, or lead to state 0 of an automaton that
    # accepts nothing
    local = np.zeros((n + 1, s_max, width),
                     np.int8 if s_max <= 127 else np.int32)
    accept = np.zeros((n + 1, s_max), bool)
    cand = np.full((n_values + 1, k), n, np.int32)
    for i, d in enumerate(dfas):
        local[i, :d.n_states, :len(rep)] = d.transitions[:, rep]
        accept[i, :d.n_states] = d.accept
        cand[list(guard_of[i]), slot[i]] = i
    return {"local": local.reshape(n + 1, -1),
            "accept": accept.reshape(-1),
            "class_of": classes["class_of"].astype(np.int32),
            "cand": cand, "slot": slot, "n_states_max": s_max,
            "width": width, "k": k}


def pack_dfas_tiered(dfas: "list[DFA]", guard_of=None) -> dict:
    """One home for the engine-wide DFA bank strategy (used by both
    tensor_expr.compile_dfa_group and the policy engine's list banks):
    dense one-hot MXU matmul (small banks), BLOCK-DIAGONAL one-hot
    (banks of many small automata — O(N·s_max²·C) per step where dense
    is quadratic in the whole bank), and past both either a scan of
    each row's CANDIDATE automata, where the caller can name guard
    values for automata (`guard_of`: per automaton the values that
    guard it, () for one read unguarded; pack_dfas_candidates: a route
    table's 10 000 patterns, ten a host), or the flat-gather scan of
    the whole bank (pathological single automata too big for either
    one-hot tier). The MXU formulations win at EVERY batch size — the
    per-step [B, N] gather is latency-bound on TPU — so flat tables
    are built ONLY when nothing else is feasible (they would otherwise
    be dead device weight).

    → {"packed", "packed_blk", "cand", "trans", "accept", "classes"}
    with exactly one of packed / packed_blk / cand / (trans, accept)
    non-None. Under `cand` the bank is SPLIT: it packs the guarded
    automata (cand["members"]: their indices in `dfas`) and `rest`,
    None where every automaton is guarded, is this function's own
    result for the others (rest["members"]), a bank every row scans:
    a mesh-wide pattern beside a route table must not take the table
    off the candidate scan.
    """
    classes = pack_dfas_classes(dfas)
    s_max = max(d.n_states for d in dfas)
    dense_ok = (classes["n_states"] ** 2 * classes["n_classes"]
                <= 4_000_000)
    blocked_ok = (len(dfas) * s_max ** 2 * classes["n_classes"]
                  <= 8_000_000)
    out = {"packed": None, "packed_blk": None, "cand": None,
           "trans": None, "accept": None, "classes": classes}
    if dense_ok:
        out["packed"] = pack_dfas_onehot(dfas, classes)
        return out
    if blocked_ok:
        out["packed_blk"] = pack_dfas_onehot_blocked(dfas, classes)
        return out
    held = [i for i, g in enumerate(guard_of or ()) if len(g)]
    cand = pack_dfas_candidates(
        [dfas[i] for i in held], classes,
        [guard_of[i] for i in held]) if held else None
    if cand is None:
        out["trans"], out["accept"] = pack_dfas(dfas)
        return out
    rest = sorted(set(range(len(dfas))) - set(held))
    out["cand"] = {**cand, "members": held, "rest": {
        "members": rest, **pack_dfas_tiered([dfas[i] for i in rest])}
        if rest else None}
    return out
