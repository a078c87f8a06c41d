"""A quota row's memquota key from ids the batch already holds.

A quota instance is a function of the attributes it reads
(templates/framework.InstanceBuilder.build), so where the batch holds
ids that decide every attribute it reads, rows with equal ids build
equal instances and so equal keys (adapters/memquota._key). A plan
keeps one memo, (instance, the row's ids) → key: the first row of a key
in the plan's life builds the instance on the bag the per-row path
would build it on, and every later row of that key finds it there, with
no bag and no build. The memo lives and dies with its plan: a publish
builds a new plan, and a row is keyed by the plan that served its Check
(CheckResponse.quota_context).

Two sources of ids:

  * the batch's columns: a STRING scalar with a layout column, or
    `m["k"]` of a string map whose (m, k) has a derived column (the
    map's presence joins the key), as the C++ shim interned them, one id
    a distinct value; none a column the step's APA writes (the host
    planes hold what the request sent, not what the APA binds there);
  * the pod rows the stamp found (runtime/apa.ApaStep.host_rows,
    PodRows), for an attribute the step's APA binds for a quota
    (ApaStep.quota_bindings) from `$out.<side>_<output>` with a default
    that reads no attribute: what `bind` sets there is a function of
    the side's pod row alone (-1, no pod: the default), and the pod
    table is the plan's, so a row means one pod for the plan's life.
    Such an instance's ident holds the pod row of each side its reads
    reach; a row whose instance builds nothing (PodRows.ok false) keeps
    the per-row path, and its miss builds on the bag the stamp's pod
    rows bind for that row alone (RuntimeServer.quota_bags).

Which instances, decided once at plan build (build_quota_keys): every
attribute reference in the instance's expressions is keyed by one of
the two; no APA runs on the host. Any other shape (a binding whose
default reads the request, or whose output names no side) keeps the
per-row path. Which rows, at run time (KeyColumns.keys): a batch the
shim decoded (Dispatcher._check_fused gathers the columns only then),
with its pod rows where an instance reads a binding, and a present id
whose value is a `str`: a value of another type on the wire keeps the
per-row path. An id >= 0 is an interned constant's, fixed for the
plan's life; a negative id is the shim's runtime value, reassigned when
the shim flushes its side table, so the value itself is the key there.
A build that raises is not memoised: the per-row path builds the row
again and answers its error as it always has.

What the ids cannot see: a record that sends a scalar attribute's name
under its string-map field (the shim leaves the column absent, the
python bag holds a dict), as on the Check path itself (PERF.md §7)."""
from __future__ import annotations

from itertools import repeat
from types import MappingProxyType
from typing import Any, Mapping

import numpy as np

from istio_tpu.adapters.memquota import _key as dims_key
from istio_tpu.attribute.types import ValueType
from istio_tpu.runtime.apa import STEP_PREFIXES, _constant
from istio_tpu.runtime.apa import _reads as _attrs
from istio_tpu.templates import Variety

# an absent slot's code: no id the shim hands out
_ABSENT = int(np.iinfo(np.int32).min)

# memo entries a plan keeps before it starts over: above the distinct
# keys of any deployment the benchmark holds (~1 400 to ~4 800 a
# snapshot) and half the keyspace of a device pool (DEFAULT_BUCKETS)
MEMO_CAP = 1 << 16


def _reads(e, layout, out: list,
           sides: Mapping = MappingProxyType({})) -> bool:
    """Append to `out` what each attribute reference of expression `e`
    reads: (column, map column or None), or the side (an int) that
    `sides` gives a bound attribute; False where one is keyed by
    neither (a bound attribute whose side is None among them)."""
    if e.var is not None:
        name = e.var.name
        if name in sides:
            if sides[name] is None:
                return False
            out.append(sides[name])
            return True
        col = layout.slots.get(name)
        if col is None or layout.manifest.get(name) != ValueType.STRING:
            return False
        out.append((col, None))
        return True
    f = e.fn
    if f is None:
        return True                     # a constant
    if f.name == "INDEX" and f.args[0].var is not None \
            and f.args[0].var.name not in sides \
            and f.args[1].const_ is not None:
        name = f.args[0].var.name
        col = layout.derived_slots.get((name, f.args[1].const_.value))
        mcol = layout.map_slots.get(name)
        if col is None or mcol is None:
            return False
        out.append((col, mcol))
        return True
    operands = list(f.args) if f.target is None else [f.target, *f.args]
    return all(_reads(a, layout, out, sides) for a in operands)


def _expressions(tree: Mapping):
    """Every expression of an InstanceBuilder.expr_tree()."""
    for value in tree.values():
        if isinstance(value, Mapping):
            yield from _expressions(value)
        else:
            yield value


def _sides(bindings) -> dict:
    """attribute → the STEP_PREFIXES index of the side whose pod row
    decides what `bindings` (ApaStep.quota_bindings) set there: an
    output `$out.<side>_<output>` with a default that reads no
    attribute and evaluates; None where the request's bag can decide it
    (no default, one that reads the request, an output of no side)."""
    out: dict = {}
    for b in bindings:
        if b.key is None:               # binds nothing
            continue
        side = b.key.partition("_")[0]
        keyed = side in STEP_PREFIXES and b.default is not None \
            and not _attrs(b.default.ast) and _constant(b.default)[0]
        out[b.attr] = STEP_PREFIXES.index(side) if keyed else None
    return out


def build_quota_keys(snapshot, quota_actions, apa) -> "QuotaKeys | None":
    """The plan's QuotaKeys, or None where no quota instance of it is
    keyed by its batches' ids. `apa`: the ApaStep the plan's step
    holds, or None."""
    if not quota_actions:
        return None
    if apa is None:
        if any(snapshot.actions_for(r, Variety.ATTRIBUTE_GENERATOR)
               for r in range(len(snapshot.rules))):
            return None                 # the host binds every row's bag
        written, bound_maps = frozenset(), frozenset()
    else:
        written = apa.written_cols
        bound_maps = frozenset(w[1] for w in apa.map_writes)
    sides = _sides(apa.quota_bindings) if apa is not None else {}
    layout = snapshot.ruleset.layout
    reads: dict[str, list] = {}
    for _ridx, _hq, iname, _names in quota_actions:
        if iname in reads:
            continue
        out: list = []
        if all(_reads(e, layout, out, sides) for e in
               _expressions(snapshot.instances[iname].expr_tree())) \
                and not any(r[0] in written or r[1] in bound_maps
                            for r in out if type(r) is tuple):
            reads[iname] = out
    if not reads:
        return None
    return QuotaKeys(snapshot.instances, snapshot.ruleset.interner, reads)


class QuotaKeys:
    """A plan's quota instances whose key its batches' ids decide, and
    the memo of their keys. Two pumps share it: a get or a set of the
    memo is one dict operation, and two rows that miss one key at once
    both build it and store the same key."""

    def __init__(self, instances: Mapping[str, Any], interner,
                 reads: Mapping[str, list]):
        self.instances = instances
        self._interner = interner
        cols = sorted({r[0] for rs in reads.values() for r in rs
                       if type(r) is tuple})
        mcols = sorted({r[1] for rs in reads.values() for r in rs
                        if type(r) is tuple and r[1] is not None})
        self.cols = np.asarray(cols, np.intp)
        self.mcols = np.asarray(mcols, np.intp)
        # instance → (its positions in a gathered row: its columns'
        # codes, then its maps' presence; how many are columns)
        self.at: dict[str, tuple[list[int], int]] = {}
        # instance → the sides whose pod rows join its ident, in order
        self.sides: dict[str, list[int]] = {}
        for iname, rs in reads.items():
            pairs = [r for r in rs if type(r) is tuple]
            mine = [cols.index(c) for c in dict.fromkeys(c for c, _ in pairs)]
            maps = [len(cols) + mcols.index(m) for m in
                    dict.fromkeys(m for _, m in pairs if m is not None)]
            self.at[iname] = (mine + maps, len(mine))
            self.sides[iname] = sorted({r for r in rs if type(r) is int})
        self.memo: dict[tuple, str] = {}

    def columns(self, batch, n: int, pods=None) -> "KeyColumns":
        """The key columns of the first `n` rows of a batch the shim
        decoded, copied out of its staging planes: a code a column (the
        id, or _ABSENT), then each map's presence (0 / 1). `pods`: the
        batch's runtime/apa.PodRows, or None."""
        codes = np.where(np.asarray(batch.present)[:n, self.cols],
                         np.asarray(batch.ids)[:n, self.cols],
                         _ABSENT).astype(np.int32)
        if len(self.mcols):
            codes = np.concatenate(
                [codes, np.asarray(batch.map_present)[:n, self.mcols]
                 .astype(np.int32)], axis=1)
        return KeyColumns(self, codes, batch.ephemeral_values, pods)

    def exact(self, ident: tuple, n_cols: int) -> bool:
        """Whether each interned id among the first `n_cols` codes of
        `ident` (after the instance's name) is a string's."""
        return all(type(self._interner.value_of(c)) is str
                   for c in ident[1:1 + n_cols]
                   if type(c) is int and c >= 0)


class KeyColumns:
    """One batch's key columns (QuotaKeys.columns) and pod rows, read an
    instance at a time by the native front's quota group."""

    __slots__ = ("_keys", "_codes", "_ephemeral", "_pods")

    def __init__(self, keys: QuotaKeys, codes: np.ndarray, ephemeral,
                 pods=None):
        self._keys = keys
        self._codes = codes
        self._ephemeral = ephemeral
        self._pods = pods

    def _idents(self, iname: str, at: list[int]) -> list:
        """Instance `iname`'s memo ident a row: its name, its codes (a
        runtime value as the value itself), then its sides' pod rows;
        None for a row the ids do not key (a runtime value that is no
        string, a row whose pods bind nothing, every row of a batch
        without pod rows where it reads a binding)."""
        n = len(self._codes)
        parts: list = [repeat(iname, n)]
        off: list = []                  # rows the ids do not key
        ephemeral = self._ephemeral
        for j in at:
            col = self._codes[:, j]
            values = col.tolist()
            for row in np.flatnonzero((col > _ABSENT) & (col < 0)).tolist():
                value = values[row] = ephemeral[-1 - values[row]]
                if type(value) is not str:
                    off.append(row)
            parts.append(values)
        sides = self._keys.sides[iname]
        if sides:
            if self._pods is None:
                return [None] * n
            parts += [self._pods.rows[:n, side].tolist() for side in sides]
            off += np.flatnonzero(~self._pods.ok[:n]).tolist()
        out = list(zip(*parts))
        for row in off:
            out[row] = None
        return out

    def keys(self, iname: str, rows: list[int],
             bag_of) -> tuple[list, int]:
        """The memquota key of instance `iname` for each row of `rows`,
        None where the row takes the per-row path (an instance the ids
        do not key, a value that is no string, a row whose pods bind
        nothing, a build that raised); and how many of them were built
        here. A miss builds the instance on `bag_of(row)`, the bag the
        per-row path builds on, and a later row of its ident finds it in
        the memo."""
        keys = self._keys
        at = keys.at.get(iname)
        if at is None:
            return [None] * len(rows), 0
        idents = self._idents(iname, at[0])
        memo = keys.memo
        found = [memo.get(idents[row]) for row in rows]
        built = 0
        if None in found:
            for i, row in enumerate(rows):
                ident = idents[row]
                if found[i] is not None or ident is None:
                    continue
                key = memo.get(ident)
                if key is None and keys.exact(ident, at[1]):
                    key = self._build(iname, ident, bag_of(row))
                    built += key is not None
                found[i] = key
        return found, built

    def _build(self, iname: str, ident: tuple, bag) -> str | None:
        """Instance `iname`'s key built on `bag`, stored under `ident`;
        None where the build raised."""
        keys = self._keys
        try:
            key = dims_key(keys.instances[iname].build(bag))
        except Exception:   # answered by the per-row path, as before
            return None
        if len(keys.memo) >= MEMO_CAP:
            keys.memo.clear()
        keys.memo[ident] = key
        return key
