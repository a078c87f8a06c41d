"""The attribute-generation phase (APA) of a Check, on the host and in
the step.

Reference: mixer/pkg/runtime/dispatcher.go:285 Preprocess, and the
generated code of an ATTRIBUTE_GENERATOR template (template.gen.go):
the instance is built on the request's bag, the adapter answers with
outputs, and each of the instance's `attribute_bindings` is an
expression over them — `$out.<key>`, optionally `| <default>` — whose
value is set on a bag over the request's. Rules then read that bag.

Two homes, one semantics:

  * the host (`bind`, `GeneratedBag`; Dispatcher.preprocess_batch):
    any adapter, a row at a time, its outputs bound into a child bag.
    It serves an APA the step cannot hold, the breaker's fallback and
    the harness's oracle (Dispatcher.check_host_oracle runs it on a bag
    that does not carry its outputs yet).
  * the step (`ApaStep`): the kubernetesenv adapter over a static pod
    cache, exported at plan build as a device table: `table[row]`
    holds, a column each, the intern id of every output a rule reads
    (-1: the pod has none), and for a map output a column that says the
    pod has it plus one a key the rules index. The pods' uids and IPs
    are NOT interned (the shim's intern table stays the rules' size, so
    every other value of a row is found in a table that fits the
    cache): the host finds each key column's pod row from the value
    behind its id, by the adapter's own uid / IP keys, once a distinct
    value (`stamp`: the seeded ids at build, a runtime value the first
    time a batch carries it), and writes it into that column of the
    batch's `hash_ids` plane, which no step program reads for a key
    column (the fused engine has no quota; the in-step quota program
    refuses an APA snapshot). Inside the step, under
    `jax.named_scope("apa")`, each row's source and destination pod are
    taken by uid and then by IP, the columns gathered, and the bound
    slots' `ids` / `present` written, each binding's default taken
    where its output is missing. Nothing else in the step changes.
    A quota instance that reads a binding is keyed by the pod rows the
    stamp found for its row (runtime/quota_keys.py), and built on the
    host, once a pod pair a plan, on a bag with EVERY binding applied
    by `bind` from those pods (`ApaStep.bind_rows`, `PodRows`): no
    adapter call and no second lookup.

A binding whose `$out` output is missing and that has no default
leaves the attribute as the request sent it (the host path has always
done so; Mixer fails the Check there). An instance whose input has no
`|` default and is absent builds nothing and binds nothing, on both.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from collections.abc import Mapping
from typing import Any, Sequence

import numpy as np

from istio_tpu.attribute.bag import MutableBag

_OUT_REF = re.compile(r"^\$out\.([A-Za-z0-9_]+)$")
# kubernetesenv's lookup prefixes (adapters/kubernetesenv.py); `origin`
# lookups are the host's alone
STEP_PREFIXES = ("source", "destination")
UID, IP = 0, 1                 # a key column's kind
ABSENT = -1                    # a table cell or a query with no value


class GeneratedBag(MutableBag):
    """A request's bag with the APA's outputs bound over it."""


@dataclasses.dataclass(frozen=True)
class Binding:
    """`attr: $out.<key> [| <default>]`. `key` None: an expression of
    another shape, which binds nothing. `default`: the OracleProgram of
    the `|` default, evaluated on the request's bag where the output is
    missing."""
    attr: str
    key: str | None
    default: Any = None


def parse_bindings(bindings: Mapping[str, Any], finder) -> list[Binding]:
    from istio_tpu.expr.oracle import OracleProgram

    out = []
    for attr, text in bindings.items():
        ref, bar, rest = str(text).partition("|")
        m = _OUT_REF.match(ref.strip())
        if m is None:
            out.append(Binding(attr, None))
            continue
        default = None
        if bar:
            try:
                default = OracleProgram(rest.strip(), finder)
            except Exception:
                out.append(Binding(attr, None))
                continue
        out.append(Binding(attr, m.group(1), default))
    return out


def bind(child: MutableBag, bindings: Sequence[Binding],
         outputs: Mapping[str, Any], bag) -> None:
    """Set each binding's value on `child`: the output, else its
    default evaluated on the request's `bag`, else nothing."""
    from istio_tpu.expr.oracle import EvalError

    for b in bindings:
        if b.key is None:
            continue
        if b.key in outputs:
            child.set(b.attr, outputs[b.key])
        elif b.default is not None:
            try:
                child.set(b.attr, b.default.evaluate(bag))
            except EvalError:
                pass


def _constant(prog) -> tuple[bool, Any]:
    """(evaluates, value) of an expression that reads no attribute."""
    from istio_tpu.attribute.bag import DictBag

    try:
        return True, prog.evaluate(DictBag({}))
    except Exception:
        return False, None


def _reads(ast) -> set:
    from istio_tpu.templates.framework import _collect_attrs

    found: set = set()
    _collect_attrs(ast, found)
    return found


def _input(ast) -> tuple[str | None, bool, Any] | None:
    """An instance input the step can read → (attribute or None,
    whether it has a default or is a constant, that value); None for
    any other shape. A bare attribute has none: its absence fails the
    instance."""
    from istio_tpu.expr.oracle import OracleProgram
    from istio_tpu.expr.checker import AttributeDescriptorFinder

    def const(e) -> tuple[bool, Any]:
        if _reads(e):
            return False, None
        return _constant(OracleProgram.from_ast(
            e, AttributeDescriptorFinder({})))

    if ast.var is not None:
        return ast.var.name, False, None
    f = ast.fn
    if f is not None and f.name == "OR" and f.args[0].var is not None:
        ok, value = const(f.args[1])
        return (f.args[0].var.name, True, value) if ok else None
    ok, value = const(ast)
    return (None, True, value) if ok else None


def _find(kind: int, value, by_uid: Mapping, by_ip: Mapping) -> int:
    """The pod row an input value finds, as the adapter looks it up
    (KubernetesEnvHandler.generate_attributes), -1 for none."""
    from istio_tpu.adapters.kubernetesenv import UID_PREFIX, ip_key

    if kind == UID:
        return by_uid.get(str(value).removeprefix(UID_PREFIX),
                          ABSENT) if value else ABSENT
    key = ip_key(value)
    return ABSENT if key is None else by_ip.get(key, ABSENT)


def pod_rows(xp, queries, stamped, present):
    """Each row's pod row for each lookup prefix, [B, len(queries)],
    -1 for none: by uid, then by IP (generate_attributes' order).
    `queries`: ((uid col, uid const row), (ip col, ip const row)) per
    prefix, col -1 where the input is a constant, the const row what
    the input's default finds; `stamped`: the stamped hash_ids plane.
    Traced with jnp in the step, run with np on the host for the
    identity's namespace and the quota's binder."""
    b = present.shape[0]

    def one(col: int, const: int):
        if col < 0:
            return xp.full((b,), const, np.int32)
        return xp.where(present[:, col], stamped[:, col], np.int32(const))

    out = []
    for (ucol, uconst), (icol, iconst) in queries:
        by_uid = one(ucol, uconst)
        out.append(xp.where(by_uid >= 0, by_uid, one(icol, iconst)))
    return xp.stack(out, axis=1)


@dataclasses.dataclass
class ApaStep:
    """The kubernetesenv APA as a table, the host's key lookups and the
    writes the step applies.

    slot_writes: (prefix, column, table column, default id, has
    default) per scalar attribute a rule reads; map_writes: (prefix,
    map column, its has-column, has default, ((derived column, table
    column, default id), ...)) per map attribute; `ok_cols`: the
    columns of inputs without a default, all present or no write;
    `key_cols`: (column, kind) of each input read from the request;
    `by_uid` / `by_ip`: the adapter's indexes, uid / pod IP text → row;
    `pods`: the adapter's pods in row order; `quota_bindings`: every
    binding of the instance where a quota instance reads one (else
    empty), which `bind_rows` applies."""
    queries: tuple
    ok_cols: tuple
    key_cols: tuple
    slot_writes: tuple
    map_writes: tuple
    table: np.ndarray           # int32 [pods, columns]
    by_uid: dict
    by_ip: dict
    interner: Any
    params: dict = dataclasses.field(default_factory=dict)
    pods: tuple = ()
    quota_bindings: tuple = ()

    def __post_init__(self) -> None:
        self._lock = threading.Lock()
        # kind -> pod row of each interned id / of each runtime value
        # of the side table it was computed from (replaced, never
        # mutated: readers take the tuple without the lock)
        self._seeded = {UID: np.empty(0, np.int32),
                        IP: np.empty(0, np.int32)}
        self._runtime = {UID: (None, np.empty(0, np.int32)),
                         IP: (None, np.empty(0, np.int32))}

    @property
    def table_bytes(self) -> int:
        return int(self.table.nbytes)

    @property
    def rows(self) -> int:
        return int(self.table.shape[0])

    @property
    def written_cols(self) -> frozenset:
        return frozenset([w[1] for w in self.slot_writes]
                         + [d[0] for w in self.map_writes for d in w[4]])

    def _rows_of(self, kind: int, values) -> np.ndarray:
        return np.fromiter((_find(kind, v, self.by_uid, self.by_ip)
                            for v in values), np.int32, len(values))

    def _seeded_rows(self, kind: int, top: int) -> np.ndarray:
        rows = self._seeded[kind]
        if top < len(rows):
            return rows
        with self._lock:
            rows = self._seeded[kind]
            n = max(top + 1, len(self.interner))
            if len(rows) < n:
                rows = np.concatenate([rows, self._rows_of(
                    kind, [self.interner.value_of(i)
                           for i in range(len(rows), n)])])
                self._seeded[kind] = rows
        return rows

    def _runtime_rows(self, kind: int, values, top: int) -> np.ndarray:
        """Rows of a batch's side table (AttributeBatch.ephemeral_values),
        computed for each value once: the native tensorizer's table is
        one list that grows until a flush replaces it."""
        seen, rows = self._runtime[kind]
        if seen is values and top < len(rows):
            return rows
        with self._lock:
            seen, rows = self._runtime[kind]
            if seen is not values:
                rows = np.empty(0, np.int32)
            n = len(values)
            if len(rows) < n:
                rows = np.concatenate(
                    [rows, self._rows_of(kind, values[len(rows):n])])
                self._runtime[kind] = (values, rows)
        return rows

    def stamp(self, batch):
        """`batch` with each key column's pod row (-1 none) in its
        `hash_ids` column, where the column is present: a few array
        takes a batch, the adapter's lookup once a distinct value."""
        ids = np.asarray(batch.ids)          # hotpath: sync-ok host planes
        present = np.asarray(batch.present)  # hotpath: sync-ok host planes
        stamped = batch.hash_ids
        if not isinstance(stamped, np.ndarray) or \
                not stamped.flags.writeable:
            stamped = np.array(stamped)      # hotpath: sync-ok host planes
            batch = dataclasses.replace(batch, hash_ids=stamped)
        for col, kind in self.key_cols:
            v = ids[:, col]
            rows = np.full(v.shape[0], ABSENT, np.int32)
            seeded = v >= 0
            if seeded.any():
                rows[seeded] = self._seeded_rows(
                    kind, int(v.max()))[v[seeded]]
            runtime = ~seeded & present[:, col]
            if runtime.any():
                at = -1 - v[runtime]
                rows[runtime] = self._runtime_rows(
                    kind, batch.ephemeral_values, int(at.max()))[at]
            stamped[:, col] = np.where(present[:, col], rows, ABSENT)
        return batch

    def host_rows(self, batch) -> tuple[np.ndarray, np.ndarray]:
        """pod_rows on a stamped batch's host planes, and whether the
        instance builds, a row."""
        present = np.asarray(batch.present)  # hotpath: sync-ok host planes
        rows = pod_rows(np, self.queries, np.asarray(batch.hash_ids),  # hotpath: sync-ok host planes
                        present)
        ok = present[:, list(self.ok_cols)].all(axis=1) \
            if self.ok_cols else np.ones(present.shape[0], bool)
        return rows, ok

    def bind_rows(self, bags, which, rows: np.ndarray,
                  ok: np.ndarray) -> list[GeneratedBag]:
        """A bag for each row `which` of `bags`, with every binding in
        `quota_bindings` applied as the host's APA applies it
        (Dispatcher._generate): the outputs generate_attributes gives
        for the pods `rows` holds (host_rows: uid, then IP), bound by
        `bind`; nothing where the instance does not build (`ok`)."""
        from istio_tpu.adapters.kubernetesenv import _OUTPUTS

        out = []
        for b in which:
            bag = bags[b]
            child = GeneratedBag(parent=bag)
            if ok[b]:
                outputs = {}
                for prefix, row in zip(STEP_PREFIXES, rows[b].tolist()):
                    if row >= 0:
                        pod = self.pods[row]
                        outputs.update((f"{prefix}_{k}", pod[k])
                                       for k in _OUTPUTS if k in pod)
                bind(child, self.quota_bindings, outputs, bag)
            out.append(child)
        return out

    def apply(self, params, batch):
        """The step's APA: a stamped `batch` with the bound slots
        written."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("apa"):
            ids, present, mp = batch.ids, batch.present, batch.map_present
            rows = pod_rows(jnp, self.queries, batch.hash_ids, present)
            got = jnp.take(params["apa_table"], jnp.maximum(rows, 0),
                           axis=0)                     # [B, prefixes, K]
            got = jnp.where(rows[:, :, None] >= 0, got, ABSENT)
            ok = jnp.all(present[:, list(self.ok_cols)], axis=1) \
                if self.ok_cols else jnp.ones(ids.shape[0], bool)
            for p, col, k, dflt, has_dflt in self.slot_writes:
                val = got[:, p, k]
                hit = val >= 0
                if has_dflt:
                    new, newp = jnp.where(hit, val, dflt), True
                else:
                    new = jnp.where(hit, val, ids[:, col])
                    newp = hit | present[:, col]
                ids = ids.at[:, col].set(jnp.where(ok, new, ids[:, col]))
                present = present.at[:, col].set(
                    jnp.where(ok, newp, present[:, col]))
            for p, mcol, k_has, has_dflt, derived in self.map_writes:
                has = got[:, p, k_has] >= 0
                take = ok if has_dflt else ok & has
                mp = mp.at[:, mcol].set(take | mp[:, mcol])
                for col, k, dflt in derived:
                    val = jnp.where(has, got[:, p, k], dflt)
                    hit = val >= 0
                    ids = ids.at[:, col].set(
                        jnp.where(take, jnp.where(hit, val, 0), ids[:, col]))
                    present = present.at[:, col].set(
                        jnp.where(take, hit, present[:, col]))
            return dataclasses.replace(batch, ids=ids, present=present,
                                       map_present=mp)


@dataclasses.dataclass(frozen=True)
class PodRows:
    """A served batch's pod rows (ApaStep.host_rows on its stamped
    planes), carried with its responses to the quota rows that key and
    build their instances on what the APA binds."""
    apa: ApaStep
    rows: np.ndarray      # [B, len(STEP_PREFIXES)]
    ok: np.ndarray        # [B]

    def bind(self, bags, which) -> list[GeneratedBag]:
        return self.apa.bind_rows(bags, which, self.rows, self.ok)


def attr_names(items) -> set:
    """Attribute names of referenced items, a (map, key) pair as its
    map's name."""
    return {i[0] if isinstance(i, tuple) else i for i in items}


def build_apa_step(snapshot, layout, interner, lists: Sequence = (),
                   host_attrs: set = frozenset(),
                   quota_attrs: set = frozenset()
                   ) -> tuple[ApaStep | None, str]:
    """(the step's APA, "") for a snapshot whose one APA the step can
    hold, else (None, why not). `lists`: the fused ListEntrySpecs, whose
    value slots the step reads; `host_attrs`: what the host reads from a
    served row's bag (host actions, host-fallback predicates), which the
    step's writes must not touch; `quota_attrs`: what quota instances
    read, bound for them on the host (ApaStep.bind_rows) where it is a
    binding."""
    from istio_tpu.attribute.types import ValueType
    from istio_tpu.templates import Variety

    rs = snapshot.ruleset
    actions = [(r, a) for r in range(len(snapshot.rules))
               for a in snapshot.actions_for(r, Variety.ATTRIBUTE_GENERATOR)]
    if not actions:
        return None, "no APA"
    if len(actions) > 1 or len(actions[0][1][2]) != 1:
        return None, "more than one APA instance"
    ridx, (hc, template, (iname,)) = actions[0]
    if hc.adapter != "kubernetesenv" or template != "kubernetes":
        return None, f"adapter {hc.adapter}"
    if hc.params.get("pod_source") is not None \
            or hc.params.get("cluster") is not None:
        return None, "a live pod source"
    pods = hc.params.get("pods") or {}
    if not isinstance(pods, Mapping):
        return None, "pods are no mapping"
    if snapshot.rules[ridx].match.strip() not in ("", "true") \
            or int(rs.rule_ns[ridx]) != rs.ns_ids[""]:
        return None, "the APA rule is not mesh-wide and unconditional"
    if layout.extern_slots:
        return None, "ingest-converted extern columns"
    ib = snapshot.instances[iname]
    raw = getattr(ib, "attribute_bindings", None)
    if not raw:
        return None, "no attribute_bindings"
    bindings = parse_bindings(raw, snapshot.finder)

    read = set()
    for r in range(rs.n_rules):
        read |= attr_names(rs.attr_names[r])
    read |= attr_names(spec.value_attr for spec in lists)
    byte_read = attr_names(layout.byte_slots)
    writes = []
    for b in bindings:
        if b.key is None or b.attr not in read:
            continue
        prefix, _, field = b.key.partition("_")
        if prefix not in STEP_PREFIXES:
            return None, f"{b.attr} binds an {prefix} output"
        if b.attr in byte_read:
            return None, f"{b.attr} is read as bytes"
        if b.attr in host_attrs:
            return None, f"{b.attr} is read on the host"
        has_dflt, dflt = False, None
        if b.default is not None:
            if _reads(b.default.ast):
                return None, f"{b.attr}'s default reads the request"
            has_dflt, dflt = _constant(b.default)
        writes.append((b.attr, STEP_PREFIXES.index(prefix), field,
                       has_dflt, dflt))
    if not writes:
        return None, "no binding a rule reads"

    # the instance's inputs, as the host's build evaluates them
    fields = {name: payload for name, kind, payload in ib.compiled_plan()
              if kind in ("expr", "const")}
    kinds = {name: kind for name, kind, _ in ib.compiled_plan()}
    # the adapter's indexes (StaticPodSource): uid as the cache holds
    # it, IP as the pod's `pod_ip` (the last pod that holds it)
    by_uid = {str(uid): row for row, uid in enumerate(pods)}
    by_ip = {pod["pod_ip"]: row for row, pod in enumerate(pods.values())
             if "pod_ip" in pod}
    queries, ok_cols, key_cols = [], [], {}
    for prefix in ("source", "destination", "origin"):
        pair = []
        for kind, suffix in ((UID, "uid"), (IP, "ip")):
            name = f"{prefix}_{suffix}"
            if name not in fields:
                pair.append((-1, ABSENT))
                continue
            if kinds[name] == "const":
                got = (None, True, fields[name])
            else:
                got = _input(fields[name].ast)
                if got is None:
                    return None, f"input {name} does not lower"
            attr, defaulted, value = got
            col = -1
            if attr is not None:
                if attr not in layout.slots:
                    return None, f"input {attr} has no column"
                col = layout.slots[attr]
                if not defaulted:
                    ok_cols.append(col)
                if prefix != "origin" and \
                        key_cols.setdefault(col, kind) != kind:
                    return None, f"input {attr} is a uid and an IP"
            const = _find(kind, value, by_uid, by_ip) if defaulted \
                else ABSENT
            pair.append((col, const))
        if prefix != "origin":
            queries.append(tuple(pair))
    # a quota instance that reads a binding: every binding, from the
    # pods the stamp finds (it finds no origin pod)
    quota_bindings = ()
    if attr_names(quota_attrs) & {b.attr for b in bindings if b.key}:
        if any(b.key.partition("_")[0] not in STEP_PREFIXES
               for b in bindings if b.key):
            return None, "a quota reads a binding and one binds an " \
                "origin output"
        quota_bindings = tuple(bindings)
    # the table's columns: a scalar output, a map output's presence,
    # a map output's key; each pod's intern ids
    columns: dict = {}

    def column(key) -> int:
        return columns.setdefault(key, len(columns))

    slot_writes, map_writes = [], []
    for attr, p, field, has_dflt, dflt in writes:
        if attr in layout.map_slots:
            if has_dflt and not isinstance(dflt, Mapping):
                return None, f"{attr}'s default is no map"
            derived = []
            for (m, key), col in layout.derived_slots.items():
                if m != attr:
                    continue
                d = interner.intern(dflt[key]) \
                    if has_dflt and key in dflt else ABSENT
                derived.append((col, column(("key", field, key)), d))
            map_writes.append((p, layout.map_slots[attr],
                               column(("has", field)), has_dflt,
                               tuple(derived)))
        elif attr in layout.slots:
            if layout.manifest.get(attr) == ValueType.STRING_MAP:
                return None, f"{attr} is a map"
            d = ABSENT
            if has_dflt:
                try:
                    d = interner.intern(dflt)
                except TypeError:
                    return None, f"{attr}'s default does not intern"
            slot_writes.append((p, layout.slots[attr], column(("val", field)),
                                d, has_dflt))

    from istio_tpu.adapters.kubernetesenv import _OUTPUTS

    table = np.full((max(len(pods), 1), max(len(columns), 1)), ABSENT,
                    np.int32)
    cells: list = []                # (row, column) of each table value
    cell_values: list = []
    for row, pod in enumerate(pods.values()):
        for (what, field, *key), k in columns.items():
            if field not in _OUTPUTS or field not in pod:
                continue
            value = pod[field]
            if what == "has":
                table[row, k] = 1
            elif what == "val":
                cells.append((row, k))
                cell_values.append(value)
            elif isinstance(value, Mapping) and key[0] in value:
                cells.append((row, k))
                cell_values.append(value[key[0]])
    try:
        cell_ids = interner.intern_all(cell_values)
    except TypeError as exc:
        return None, f"a pod's output does not intern: {exc}"
    if cells:
        at = np.asarray(cells, np.int64)
        table[at[:, 0], at[:, 1]] = cell_ids
    return ApaStep(queries=tuple(queries),
                   ok_cols=tuple(sorted(set(ok_cols))),
                   key_cols=tuple(sorted(key_cols.items())),
                   slot_writes=tuple(slot_writes),
                   map_writes=tuple(map_writes),
                   table=table, by_uid=by_uid, by_ip=by_ip,
                   interner=interner, pods=tuple(pods.values()),
                   quota_bindings=quota_bindings), ""
