"""Shared route automaton: route-rule matches → the policy ruleset
tensors (BASELINE.json: "Pilot's route compiler emits the same NFA for
VirtualService/RouteRule header+URI match so L7 routing and policy
share one compiled automaton").

Every (service, route-rule) pair lowers its match block to ONE
predicate in the SAME expression language the policy engine compiles
(exact → EQ, prefix → startsWith, regex → matches, header presence →
`|` fallback probe), then the whole mesh's route table becomes a
RuleSetProgram. Batched route selection = one device step:

    matched [B, R]  →  choice[b] = highest-precedence matched rule
                       (argmax over precedence-ordered weights)

`select()` returns per-request route indices; index n_rules means "no
rule matched → default route". The host-side `select_host()` applies
identical semantics sequentially and is the conformance oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Mapping, Sequence

import numpy as np

from istio_tpu.attribute.bag import Bag, bag_from_mapping
from istio_tpu.attribute.types import ValueType
from istio_tpu.compiler.layout import Tensorizer
from istio_tpu.compiler.ruleset import Rule, compile_ruleset
from istio_tpu.expr.checker import AttributeDescriptorFinder
from istio_tpu.pilot.model import Config, Service

V = ValueType

# vocabulary of the route-match automaton
ROUTE_MANIFEST: dict[str, ValueType] = {
    "destination.service": V.STRING,
    "request.path": V.STRING,
    "request.method": V.STRING,
    "request.scheme": V.STRING,
    "request.host": V.STRING,
    "request.headers": V.STRING_MAP,
    "source.service": V.STRING,
}
ROUTE_FINDER = AttributeDescriptorFinder(ROUTE_MANIFEST)

_ABSENT = "\x00absent\x00"


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _header_ref(name: str) -> str:
    """Pseudo-headers map to first-class attributes (header.go:27
    translates :path/:method the same way)."""
    specials = {"uri": "request.path", ":path": "request.path",
                ":method": "request.method", "method": "request.method",
                ":authority": "request.host", "authority": "request.host",
                "scheme": "request.scheme", ":scheme": "request.scheme"}
    if name in specials:
        return specials[name]
    return f"request.headers[{_quote(name)}]"


def match_to_predicate(hostname: str, match: Mapping[str, Any] | None,
                       source: str | None = None) -> str:
    """Route-rule match block → one boolean expression."""
    parts = [f"destination.service == {_quote(hostname)}"]
    if source:
        parts.append(f"source.service == {_quote(source)}")
    headers = {}
    if match:
        headers = match.get("request", {}).get("headers", {}) \
            if "request" in match else match.get("headers", {}) or {}
    for name, cond in sorted(headers.items()):
        ref = _header_ref(name)
        is_map = ref.startswith("request.headers[")
        probe = f"({ref} | {_quote(_ABSENT)})" if is_map else ref
        if not cond or cond == {"presence": True}:
            parts.append(f"{probe} != {_quote(_ABSENT)}")
        elif "exact" in cond:
            parts.append(f"{probe} == {_quote(cond['exact'])}")
        elif "prefix" in cond:
            parts.append(f"{probe}.startsWith({_quote(cond['prefix'])})")
        elif "regex" in cond:
            # Envoy route regexes are FULL match; `matches` is an
            # unanchored search (Go regexp.MatchString parity), so
            # _anchor forces full-match semantics — wrapping `^(pat)$`
            # for unanchored/alternation patterns, passing
            # already-anchored pipe-free patterns through bare so they
            # keep lowering to the device DFA (which rejects nested
            # inner anchors). NOTE: the RECEIVER of .matches() is the
            # PATTERN (see testing/corpus.py).
            parts.append(f"{_quote(_anchor(cond['regex']))}"
                         f".matches({probe})")
    return " && ".join(parts)


def _anchor(pattern: str) -> str:
    """Force full-match semantics. A pattern that is already anchored
    on both ends AND safe to use bare (no top-level alternation that
    the anchors wouldn't distribute over) stays as-is — wrapping it
    would nest anchors inside the group, which the device DFA compiler
    rejects (regex_dfa: no inner anchors) and needlessly sends the
    rule to the host oracle."""
    if (pattern.startswith("^") and pattern.endswith("$")
            and not pattern.endswith("\\$") and "|" not in pattern):
        return pattern
    return f"^({pattern})$"


def _winner(matched, weight, default):
    """Shared selection tail (THE precedence rule — keep single-sourced
    across the dense/compact device kernels): highest weight among
    matched rows wins; nothing matched → default."""
    import jax.numpy as jnp
    scores = matched * weight[None, :]
    best = jnp.argmax(scores, axis=1)
    hit = jnp.max(scores, axis=1) > 0
    return jnp.where(hit, best, default)


@dataclasses.dataclass
class RouteEntry:
    rule: Config
    service: Service
    predicate: str
    precedence: int


class RouteTable:
    """The whole mesh's route rules as one device program."""

    def __init__(self, services: Sequence[Service],
                 rules_by_host: Mapping[str, Sequence[Config]],
                 max_str_len: int = 256):
        self.entries: list[RouteEntry] = []
        host_of = {s.hostname: s for s in services}
        for hostname in sorted(rules_by_host):
            service = host_of.get(hostname)
            if service is None:
                continue
            for rule in rules_by_host[hostname]:
                src = rule.spec.get("match", {}).get("source")
                pred = match_to_predicate(hostname,
                                          rule.spec.get("match"), src)
                self.entries.append(RouteEntry(
                    rule=rule, service=service, predicate=pred,
                    precedence=int(rule.spec.get("precedence", 0))))
        rules = [Rule(name=f"route{i}", match=e.predicate)
                 for i, e in enumerate(self.entries)]
        self.program = compile_ruleset(rules, ROUTE_FINDER,
                                       max_str_len=max_str_len)
        self.tensorizer = Tensorizer(self.program.layout,
                                     self.program.interner)
        # selection weights: precedence first, then config order
        # (route_rules sorting, route.go) — higher weight wins
        n = len(self.entries)
        order = sorted(range(n),
                       key=lambda i: (-self.entries[i].precedence, i))
        self._weight = np.zeros(max(n, 1), np.int64)
        for rank, idx in enumerate(order):
            self._weight[idx] = n - rank          # best rank → largest
        self.default_index = n

    # -- device path --

    def select(self, requests: Sequence[Mapping[str, Any] | Bag]
               ) -> np.ndarray:
        """One device step: per-request winning route index
        (default_index when nothing matches)."""
        bags = [r if isinstance(r, Bag) else bag_from_mapping(dict(r))
                for r in requests]
        if not self.entries:
            return np.full(len(bags), self.default_index, np.int64)
        batch = self.tensorizer.tensorize(bags)
        if not self.program.host_fallback:
            # argmax on device: pulling the [B, R] matched plane costs
            # R/64 times the bytes of the [B] winner indices (megabytes
            # per batch at 10k routes behind a high-RTT transport)
            return np.asarray(self._select_on_device(
                self.program.params, batch), dtype=np.int64)
        matched, _, _ = self.program(batch)
        matched = np.array(matched)
        for ridx in self.program.host_fallback:
            for b, bag in enumerate(bags):
                matched[b, ridx] = self.program.host_eval(ridx, bag)[0]
        scores = matched * self._weight[None, :]
        best = scores.argmax(axis=1)
        hit = scores.max(axis=1) > 0
        return np.where(hit, best, self.default_index)

    @functools.cached_property
    def native(self):
        """C++ wire→tensor decoder for the route layout (None when the
        native toolchain is unavailable)."""
        try:
            from istio_tpu.native.tensorizer import NativeTensorizer
            return NativeTensorizer(self.program.layout,
                                    self.program.interner)
        except Exception as exc:
            # select_wire silently serving the python fallback forever
            # would read as an unexplained throughput collapse
            import logging
            logging.getLogger("istio_tpu.pilot.route_nfa").warning(
                "native tensorizer unavailable, route wire path "
                "serving with the python decoder: %s", exc)
            return None

    def select_wire(self, wires: Sequence[bytes], block: bool = True):
        """Winning route per wire-encoded CompressedAttributes record —
        the sidecar-facing fast path: C++ decode + ONE device program
        (match + precedence argmax), no per-request python.

        block=False returns the un-synchronized device array so callers
        can pipeline batches (XLA queues the steps; one sync drains
        them all — the throughput shape behind a high-RTT transport).
        Falls back to the python path when the native shim is absent or
        host-fallback rules exist (those need per-row oracle evals)."""
        if not self.entries:
            return np.full(len(wires), self.default_index, np.int64)
        if self.native is None or self.program.host_fallback:
            from istio_tpu.api.wire import LazyWireBag
            return self.select([LazyWireBag(w) for w in wires])
        batch = self.native.tensorize_wire(wires)
        # COMPACT byte-plane transfer: str_bytes is [B, nbyte, L] but
        # real subjects (paths, hosts) are ~20 bytes — shipping the
        # dense plane is ~10× the payload the host→device transfer
        # has to carry (the route tier's bottleneck when last
        # profiled, before PR 1). Ship the ragged bytes + offsets and
        # expand with one device gather instead.
        sb = np.asarray(batch.str_bytes)
        lens = np.asarray(batch.str_lens)
        L = sb.shape[2]
        mask = np.arange(L)[None, None, :] < lens[:, :, None]
        flat = sb[mask]
        total = flat.shape[0]
        cap = max(1024, 1 << int(total).bit_length())  # stable shapes
        if cap > sb.size:     # pathological: dense is smaller
            out = self._select_on_device(self.program.params, batch)
            return np.asarray(out).astype(np.int64) if block else out
        flat_p = np.zeros(cap, np.uint8)
        flat_p[:total] = flat
        # presence bitpacked, starts recomputed on device from lens,
        # lens as int16 — every byte shipped is wall-clock here
        pres_p = np.packbits(np.asarray(batch.present), axis=1,
                             bitorder="little")
        out = self._select_on_device_compact(
            self.program.params, batch.ids, pres_p,
            batch.map_present, flat_p, lens.astype(np.int16))
        return np.asarray(out).astype(np.int64) if block else out

    @functools.cached_property
    def _select_on_device(self):
        import jax
        import jax.numpy as jnp
        weight = jnp.asarray(self._weight)
        default = self.default_index
        raw = self.program.fn          # fn(params, batch)

        def run(params, batch):
            matched, _, _ = raw(params, batch)
            return _winner(matched, weight, default)

        return jax.jit(run)

    @functools.cached_property
    def _select_on_device_compact(self):
        """select with the byte plane shipped RAGGED (flat bytes +
        per-slot offsets) and re-densified by one device gather — the
        H2D payload shrinks ~10× vs the dense [B, nbyte, L] plane (the
        transfer, not the step, bounds route throughput behind a
        high-RTT/low-bandwidth device link)."""
        import jax
        import jax.numpy as jnp

        from istio_tpu.compiler.layout import AttributeBatch

        weight = jnp.asarray(self._weight)
        default = self.default_index
        raw = self.program.fn
        L = self.program.layout.max_str_len
        n_cols = self.program.layout.n_columns

        def run(params, ids, pres_packed, map_present, flat, lens16):
            lens = lens16.astype(jnp.int32)
            b, nbyte = lens.shape
            flat_lens = lens.reshape(-1)
            starts = (jnp.cumsum(flat_lens) - flat_lens).reshape(
                b, nbyte)
            idx = starts[:, :, None] + jnp.arange(L)[None, None, :]
            sb = flat[jnp.clip(idx, 0, flat.shape[0] - 1)]
            sb = jnp.where(
                jnp.arange(L)[None, None, :] < lens[:, :, None], sb, 0)
            bits = ((pres_packed[:, :, None] >>
                     jnp.arange(8, dtype=jnp.uint8)) & 1) > 0
            present = bits.reshape(b, -1)[:, :n_cols]
            batch = AttributeBatch(
                ids=ids, present=present, map_present=map_present,
                str_bytes=sb, str_lens=lens,
                hash_ids=jnp.zeros_like(ids))   # routes never hash
            matched, _, _ = raw(params, batch)
            return _winner(matched, weight, default)

        return jax.jit(run)

    # -- host oracle --

    def select_host(self, request: Mapping[str, Any]) -> int:
        best, best_w = self.default_index, 0
        for i, entry in enumerate(self.entries):
            if self._matches_host(entry, request) and \
                    self._weight[i] > best_w:
                best, best_w = i, int(self._weight[i])
        return best

    @staticmethod
    def _matches_host(entry: RouteEntry,
                      request: Mapping[str, Any]) -> bool:
        if request.get("destination.service") != entry.service.hostname:
            return False
        spec = entry.rule.spec
        src = spec.get("match", {}).get("source")
        if src and request.get("source.service") != src:
            return False
        headers = {}
        if spec.get("match"):
            m = spec["match"]
            headers = m.get("request", {}).get("headers", {}) \
                if "request" in m else m.get("headers", {}) or {}
        for name, cond in headers.items():
            ref = _header_ref(name)
            if ref.startswith("request.headers["):
                value = (request.get("request.headers") or {}).get(name)
            else:
                value = request.get(ref)
            if not cond or cond == {"presence": True}:
                if value is None:
                    return False
            elif "exact" in cond:
                if value != cond["exact"]:
                    return False
            elif "prefix" in cond:
                if value is None or not str(value).startswith(
                        cond["prefix"]):
                    return False
            elif "regex" in cond:
                # mirror the device predicate EXACTLY: unanchored
                # search of the ^(pat)$ wrapper (same engine semantics
                # incl. the $-before-trailing-newline subtlety)
                if value is None or re.search(_anchor(cond["regex"]),
                                              str(value)) is None:
                    return False
        return True

    def route_for(self, index: int) -> RouteEntry | None:
        if 0 <= index < len(self.entries):
            return self.entries[index]
        return None


class RouteScopeProgram:
    """Source-admission half of the mesh's route-rule match blocks as
    ONE compiled program — the per-node part of config generation.

    Per-sidecar RDS generation filters each destination's route rules
    by the polling node's source identity (`match.source`,
    route.go buildVirtualHost / model._match_source). The reference
    re-evaluates that filter per node per rule on the host; here every
    source-constrained (host, rule) pair lowers its constraint to one
    `source.service == "..."` predicate in the SAME expression
    language / ruleset tensors the route NFA and policy engine compile
    (BASELINE's shared-automaton doctrine), so admission for ALL
    pending node groups is one batched device step:

        admits [B, C]  →  row b: does node-group b's source satisfy
                          constrained pair c?

    Unconstrained rules admit every source by construction and never
    enter the program; a node with no source identity admits
    everything (the `_match_source` None-source semantics) and skips
    the device plane entirely. Header/URI match halves are NOT
    evaluated here — they become envoy match JSON in the generated
    config (the data plane evaluates them per request; RouteTable
    evaluates them per request on-device for the policy tie-in).

    `digest` content-addresses the constraint set (host, rule index,
    source) so snapshots carry the compiled program across
    generations whenever no source constraint moved (PR 10 doctrine).
    Compilation is lazy — building a snapshot whose digest matches the
    previous generation never compiles.
    """

    def __init__(self, rules_by_host: Mapping[str, Sequence[Any]]):
        from istio_tpu.compiler.cache import stable_digest

        self._constrained: list[tuple[str, int]] = []
        self._sources: list[str] = []
        for host in sorted(rules_by_host):
            for i, rule in enumerate(rules_by_host[host]):
                src = (rule.spec.get("match") or {}).get("source")
                if src:
                    self._constrained.append((host, i))
                    self._sources.append(str(src))
        self._slot = {pair: j for j, pair in
                      enumerate(self._constrained)}
        self.n_constrained = len(self._constrained)
        self.digest = stable_digest(
            [(h, i, s) for (h, i), s in zip(self._constrained,
                                            self._sources)])

    @functools.cached_property
    def _program(self):
        """Lazy compile: (program, tensorizer) over the constraint
        predicates; None when nothing in the mesh is
        source-constrained."""
        if not self._constrained:
            return None
        rules = [Rule(name=f"scope{j}",
                      match=f"source.service == {_quote(src)}")
                 for j, src in enumerate(self._sources)]
        program = compile_ruleset(rules, ROUTE_FINDER, max_str_len=256)
        return program, Tensorizer(program.layout, program.interner)

    def admit_rows(self, sources: Sequence[str | None]) -> list:
        """One device step for a batch of node-group source
        identities → per-row admission maps. Row value None means
        'admit everything' (no identity, or no constrained rules).
        The batch pads to a power of two so churn storms reuse a few
        compiled shapes instead of one per pending-set size."""
        if self._program is None or not sources:
            return [None] * len(sources)
        program, tensorizer = self._program
        n = len(sources)
        cap = 1 << max(n - 1, 0).bit_length() if n > 1 else 1
        padded = [s or "" for s in sources] + [""] * (cap - n)
        bags = [bag_from_mapping({"source.service": s}) for s in padded]
        batch = tensorizer.tensorize(bags)
        matched, _, _ = program(batch)
        m = np.asarray(matched) > 0    # hotpath: sync-ok — THE designated admission-plane pull (one per batched generation)
        for ridx in program.host_fallback:   # defensive: EQ never falls back
            for b in range(n):
                m[b, ridx] = program.host_eval(ridx, bags[b])[0]
        rows = []
        for b, s in enumerate(sources):
            if s is None:
                rows.append(None)
            else:
                rows.append({pair: bool(m[b, j]) for j, pair in
                             enumerate(self._constrained)})
        return rows

    def admits(self, row, host: str, rule_index: int) -> bool:
        """Does the admission row (one admit_rows element) include
        `rules_by_host[host][rule_index]`? Unconstrained rules and
        identity-less rows always admit."""
        if row is None:
            return True
        pair = (host, rule_index)
        if pair not in self._slot:
            return True
        return row[pair]
