"""A quota row's memquota key from the batch's interned ids.

A quota instance is a function of the attributes it reads
(templates/framework.InstanceBuilder.build), so where each attribute it
reads is a string the C++ shim puts in a layout column, as one id a
distinct value, rows with equal ids build equal instances and so equal
keys (adapters/memquota._key). A plan keeps one memo, (instance, the
row's ids) → key: the first row of a key in the plan's life builds the
instance on its own bag, as the per-row path does, and every later row
of that key finds it there, with no bag and no build. The memo lives
and dies with its plan: a publish builds a new plan, and a row is keyed
by the plan that served its Check (CheckResponse.quota_context).

Which instances, decided once at plan build (build_quota_keys): every
attribute reference in the instance's expressions is a STRING scalar
with a layout column, or `m["k"]` of a string map whose (m, k) has a
derived column (the map's presence joins the key); none is a column
the step's APA writes (the host planes hold what the request sent, not
what the APA binds there), no quota instance reads what the APA binds
(ApaStep.quota_bindings: those rows are built on bags bound from the
stamp's pod rows), and no APA runs on the host. Which rows, at run
time (KeyColumns.key): a batch the shim decoded (Dispatcher.
_check_fused gathers the columns only then), and a present id whose
value is a `str`: a value of another type on the wire keeps the
per-row path. An id >= 0 is an interned constant's, fixed for the
plan's life; a negative id is the shim's runtime value, reassigned when
the shim flushes its side table, so the value itself is the key there.
A build that raises is not memoised: the per-row path builds the row
again and answers its error as it always has.

What the ids cannot see: a record that sends a scalar attribute's name
under its string-map field (the shim leaves the column absent, the
python bag holds a dict), as on the Check path itself (PERF.md §7)."""
from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from istio_tpu.adapters.memquota import _key as dims_key
from istio_tpu.attribute.types import ValueType
from istio_tpu.templates import Variety

# an absent slot's code: no id the shim hands out
_ABSENT = int(np.iinfo(np.int32).min)

# memo entries a plan keeps before it starts over: above the distinct
# keys of any deployment the benchmark holds (~1 400 to ~4 800 a
# snapshot) and half the keyspace of a device pool (DEFAULT_BUCKETS)
MEMO_CAP = 1 << 16


def _reads(e, layout, out: list) -> bool:
    """Append to `out` the (column, map column or None) each attribute
    reference of expression `e` reads; False where one is not keyed
    exactly by a column."""
    if e.var is not None:
        col = layout.slots.get(e.var.name)
        if col is None or \
                layout.manifest.get(e.var.name) != ValueType.STRING:
            return False
        out.append((col, None))
        return True
    f = e.fn
    if f is None:
        return True                     # a constant
    if f.name == "INDEX" and f.args[0].var is not None \
            and f.args[1].const_ is not None:
        name = f.args[0].var.name
        col = layout.derived_slots.get((name, f.args[1].const_.value))
        mcol = layout.map_slots.get(name)
        if col is None or mcol is None:
            return False
        out.append((col, mcol))
        return True
    operands = list(f.args) if f.target is None else [f.target, *f.args]
    return all(_reads(a, layout, out) for a in operands)


def _expressions(tree: Mapping):
    """Every expression of an InstanceBuilder.expr_tree()."""
    for value in tree.values():
        if isinstance(value, Mapping):
            yield from _expressions(value)
        else:
            yield value


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
    elif apa.quota_bindings:
        return None
    else:
        written = apa.written_cols
        bound_maps = frozenset(w[1] for w in apa.map_writes)
    layout = snapshot.ruleset.layout
    reads: dict[str, list] = {}
    for _ridx, _hq, iname, _names in quota_actions:
        if iname in reads:
            continue
        out: list = []
        if all(_reads(e, layout, out) for e in
               _expressions(snapshot.instances[iname].expr_tree())) \
                and not any(c in written or m in bound_maps
                            for c, m in out):
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
        cols = sorted({c for r in reads.values() for c, _ in r})
        mcols = sorted({m for r in reads.values() for _, m in r
                        if m is not None})
        self.cols = np.asarray(cols, np.intp)
        self.mcols = np.asarray(mcols, np.intp)
        # instance → (its positions in a gathered row: its columns'
        # codes, then its maps' presence; how many are columns)
        self.at: dict[str, tuple[list[int], int]] = {}
        for iname, r in reads.items():
            mine = [cols.index(c) for c in dict.fromkeys(c for c, _ in r)]
            maps = [len(cols) + mcols.index(m) for m in
                    dict.fromkeys(m for _, m in r if m is not None)]
            self.at[iname] = (mine + maps, len(mine))
        self.memo: dict[tuple, str] = {}

    def columns(self, batch, n: int) -> "KeyColumns":
        """The key columns of the first `n` rows of a batch the shim
        decoded, copied out of its staging planes: a code a column (the
        id, or _ABSENT), then each map's presence (0 / 1)."""
        codes = np.where(np.asarray(batch.present)[:n, self.cols],
                         np.asarray(batch.ids)[:n, self.cols],
                         _ABSENT).astype(np.int32)
        if len(self.mcols):
            codes = np.concatenate(
                [codes, np.asarray(batch.map_present)[:n, self.mcols]
                 .astype(np.int32)], axis=1)
        return KeyColumns(self, codes, batch.ephemeral_values)

    def exact(self, ident: tuple, n_cols: int) -> bool:
        """Whether each interned id among the first `n_cols` codes of
        `ident` (after the instance's name) is a string's."""
        return all(type(self._interner.value_of(c)) is str
                   for c in ident[1:1 + n_cols]
                   if type(c) is int and c >= 0)


class KeyColumns:
    """One batch's key columns (QuotaKeys.columns), read a row at a time
    by the native front's quota group."""

    __slots__ = ("_keys", "_codes", "_ephemeral", "_rows")

    def __init__(self, keys: QuotaKeys, codes: np.ndarray, ephemeral):
        self._keys = keys
        self._codes = codes
        self._ephemeral = ephemeral
        self._rows: dict[str, list] = {}

    def key(self, iname: str, row: int, bags) -> tuple[str, str] | None:
        """("memo" | "built", the memquota key) of instance `iname` for
        row `row`; None where the row takes the per-row path (an
        instance the ids do not key, a value that is no string, a build
        that raised). A miss builds the instance on `bags[row]`."""
        keys = self._keys
        at = keys.at.get(iname)
        if at is None:
            return None
        codes = self._rows.get(iname)
        if codes is None:
            codes = self._rows[iname] = self._codes[:, at[0]].tolist()
        ident: list = [iname]
        for c in codes[row]:
            if _ABSENT < c < 0:         # the shim's runtime value
                c = self._ephemeral[-1 - c]
                if type(c) is not str:
                    return None
            ident.append(c)
        ident = tuple(ident)
        key = keys.memo.get(ident)
        if key is not None:
            return "memo", key
        if not keys.exact(ident, at[1]):
            return None
        try:
            key = dims_key(keys.instances[iname].build(bags[row]))
        except Exception:   # answered by the per-row path, as before
            return None
        if len(keys.memo) >= MEMO_CAP:
            keys.memo.clear()
        keys.memo[ident] = key
        return "built", key
