"""Compilation caching — the delta-compilation plane's shared vocabulary.

A production mesh republishes config constantly; the point of this
module is that NOTHING recompiles unless its inputs changed. Three
layers, cheapest first:

  1. Content digests (`stable_digest` / `manifest_digest`) — the
     deterministic hashes the sharding plane keys its content-addressed
     bank cache on (istio_tpu/sharding/banks.bank_content_key): a bank
     whose rules, referenced handlers/instances, layout inputs and
     manifest are byte-identical across generations IS the same
     compiled artifact and is carried over, prewarmed shapes, breaker
     state and rulestats bindings included.

  2. DecompCache — per-rule parse + DNF-decomposition memo across
     snapshot builds. compile_ruleset's host cost is dominated by
     parsing and decomposing match predicates (measured ~85% of the
     build at fleet scale); a config delta re-presents almost every
     rule unchanged, so the builder replays the cached decomposition
     (atom ASTs re-interned into the new _AtomTable, conjunction sets
     re-indexed) and pays parse/DNF only for rules it has never seen.
     Guarded by the manifest digest + dnf_cap: a vocabulary change
     invalidates everything (eval_type / lowering decisions depend on
     attribute types).

  3. The JAX persistent compilation cache — XLA artifacts on disk
     (`jax_compilation_cache_dir`), so process restarts and rolling
     deploys skip the warm compile for every program whose HLO is
     unchanged. Our compiled programs take their index tensors as
     ARGUMENTS, never closure constants (compiler/ruleset.py), so a
     constant-only rule edit keeps the HLO — and therefore the cache
     key — bit-identical: only SHAPE changes (new atoms, wider
     conjunctions, different bank sizes) recompile. ONE function,
     resolve_cache_dir, decides where it lives: the
     JAX_COMPILATION_CACHE_DIR environment variable when set, else
     ServerArgs.jax_compile_cache_dir / `mixs --jax-compile-cache-dir`,
     else the fixed `<checkout>/.jax_cache` (the path is part of the
     cache key, so a directory that moves never hits).

Hit/miss accounting rides jax's monitoring events
('/jax/compilation_cache/cache_hits' / 'cache_misses') — the delta
smoke gate asserts a warm restart compiles NOTHING for unchanged
banks, and /debug/shards surfaces the counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import threading
from typing import Any, Mapping

# -- content digests ---------------------------------------------------


def stable_digest(obj: Any) -> str:
    """sha256 of the canonical-JSON rendering of `obj` — deterministic
    across processes and PYTHONHASHSEED (sorted keys, no whitespace,
    default=str for the odd non-JSON leaf)."""
    blob = json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


def manifest_digest(finder) -> str:
    """Digest of an AttributeDescriptorFinder's vocabulary — the
    (name, value type) set every type-check and lowering decision
    depends on. Two finders with equal digests make identical
    eval_type / tier-classification decisions for any expression."""
    items = sorted((n, getattr(finder.get_attribute(n), "name",
                               str(finder.get_attribute(n))))
                   for n in finder.names())
    return stable_digest(items)


# -- persistent XLA compilation cache ---------------------------------

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(explicit: str | None = None) -> str:
    """THE persistent-cache directory decision: the
    JAX_COMPILATION_CACHE_DIR environment variable when set (whoever
    runs the process placed the cache; nothing in code overrides it),
    else explicit config (ServerArgs / --jax-compile-cache-dir), else
    the fixed `<checkout>/.jax_cache`."""
    env = os.environ.get(ENV_CACHE_DIR, "").strip()
    return env or explicit or CHECKOUT_CACHE_DIR


def configure_persistent_cache(explicit: str | None = None,
                               min_compile_time_s: float = 0.0) -> str:
    """Point jax's persistent compilation cache at
    resolve_cache_dir(explicit) (created if missing) and lower the
    entry thresholds so every serving program is cached — bank
    programs at small shard sizes compile in well under jax's 1s
    default threshold, and they are exactly the artifacts a rolling
    deploy wants to skip. Returns the directory. Repeat calls with an
    unchanged config are no-ops."""
    import jax

    cache_dir = resolve_cache_dir(explicit)
    min_s = float(min_compile_time_s)
    os.makedirs(cache_dir, exist_ok=True)
    cfg = jax.config
    if (cfg.jax_compilation_cache_dir == cache_dir
            and cfg.jax_persistent_cache_min_compile_time_secs == min_s
            and cfg.jax_persistent_cache_min_entry_size_bytes == -1):
        return cache_dir
    cfg.update("jax_compilation_cache_dir", cache_dir)
    cfg.update("jax_persistent_cache_min_compile_time_secs", min_s)
    # cache entries below 0 bytes never exist; -1 = "cache all"
    cfg.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax memoizes its "is the cache used?" decision at the FIRST
    # compile of the process — a server configured after any earlier
    # compile (a long-lived test process, a REPL) would silently keep
    # the cache off forever without this reset
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    return cache_dir


@contextlib.contextmanager
def private_cache_dir(cache_dir: str, min_compile_time_s: float = 0.0):
    """Scope for the cache-BEHAVIOUR gates only (delta_smoke,
    test_delta_compile), which count cold misses and so need a
    directory nothing else has written: clears
    JAX_COMPILATION_CACHE_DIR for the scope, points jax at
    `cache_dir`, and on exit restores the variable and the process's
    resolved cache config."""
    import jax

    prev_env = os.environ.pop(ENV_CACHE_DIR, None)
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        yield configure_persistent_cache(cache_dir, min_compile_time_s)
    finally:
        if prev_env is not None:
            os.environ[ENV_CACHE_DIR] = prev_env
        configure_persistent_cache(min_compile_time_s=prev_min)


def persistent_cache_entries(cache_dir: str) -> int:
    """Number of compiled-artifact entries on disk (the `*-cache`
    files; jax writes a sibling `-atime` touch file per entry)."""
    try:
        return sum(1 for f in os.listdir(cache_dir)
                   if f.endswith("-cache"))
    except OSError:
        return 0


_EVENTS = {"hits": 0, "misses": 0}
_EVENTS_LOCK = threading.Lock()
_EVENTS_INSTALLED = False


def _on_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _EVENTS_LOCK:
            _EVENTS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _EVENTS_LOCK:
            _EVENTS["misses"] += 1


# what a jit's first call at a shape is made of, process-wide: tracing
# python to a jaxpr, lowering it to an MLIR module, and the backend
# compile — which is where a persistent-cache HIT is paid too
# (deserializing the executable), so a prewarm "with every executable
# a cache hit" still shows its trace + lower seconds here. Kept as
# time spans, not summed durations: a jit traced inside another's
# trace reports both, and the union counts that wall once.
_PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
# per phase: the disjoint spans seen so far, sorted by time
_PHASE_SPANS: dict[str, list] = {
    phase: [] for phase in _PHASE_EVENTS.values()}


def _merge_span(spans: list, start: float, end: float) -> None:
    """Merge (start, end) into `spans`, kept disjoint and sorted. A
    listener fires when its span closes, so spans arrive in end order
    and a nested one before the span that holds it: the new span
    swallows from the tail whatever it overlaps — amortized O(1). The
    step at 1k RBAC roles fires 10 000 nested trace spans a shape;
    anything per-event that walks the list shows up in set-up."""
    later = []
    while spans and spans[-1][0] > end:    # another thread's, rare
        later.append(spans.pop())
    while spans and spans[-1][1] >= start:
        held_start, held_end = spans.pop()
        start, end = min(start, held_start), max(end, held_end)
    spans.append((start, end))
    spans.extend(reversed(later))


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    phase = _PHASE_EVENTS.get(event)
    if phase is not None:
        with _EVENTS_LOCK:
            _merge_span(_PHASE_SPANS[phase], start, end)


def install_event_counters() -> None:
    """Register the jax monitoring listeners that feed
    cache_event_counts() and phase_seconds(). Idempotent."""
    global _EVENTS_INSTALLED
    if _EVENTS_INSTALLED:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    _EVENTS_INSTALLED = True


def phase_seconds() -> dict:
    """{"trace_s", "lower_s", "backend_s"}: wall seconds this process
    has spent tracing, lowering and backend-compiling (or loading from
    the persistent cache) since the counters were installed, nested
    and concurrent spans of one phase counted once. Snapshot and diff
    for a phase-scoped view."""
    with _EVENTS_LOCK:
        return {phase: sum(end - start for start, end in spans)
                for phase, spans in _PHASE_SPANS.items()}


def cache_event_counts() -> dict:
    """{"hits": n, "misses": n} persistent-cache lookups since the
    counters were installed (process-wide; snapshot-and-diff for a
    phase-scoped view)."""
    with _EVENTS_LOCK:
        return dict(_EVENTS)


# -- per-rule decomposition cache -------------------------------------


@dataclasses.dataclass
class DecompEntry:
    """One rule predicate's cached compile front half. `atom_asts`
    are the decomposition's primitive predicates in entry-local
    order; `m`/`n` are the monotone DNFs as tuples of
    ((local_atom_pos, kind), ...) literals. `oracle`/`reason` are set
    instead when the predicate host-falls-back (DNF blowup /
    unlowerable shape) — the oracle program is reused too, it is
    finder-pure and the cache is finder-guarded."""
    ast: Any
    atom_asts: tuple = ()
    m: tuple = ()
    n: tuple = ()
    oracle: Any = None
    reason: str = ""
    last_gen: int = 0

    @property
    def is_fallback(self) -> bool:
        return self.oracle is not None


class DecompCache:
    """Parse + DNF-decomposition memo across compile_ruleset calls.

    Keyed by the rule's raw match string (rules carrying a pre-built
    AST — rbac pseudo-rules — bypass the cache: they never parse and
    the sharding plane refuses them anyway). Bound to one
    (manifest digest, dnf_cap) world via begin(): a changed attribute
    vocabulary or cap clears everything, because type checking, the
    decomposition's HostFallback decisions and the cached oracles all
    depend on it.

    Writers are the controller's serialized rebuild thread (parent
    snapshot build, then each changed bank's sub-compile — the bank
    compiles are where the hits pay off twice); a lock keeps the memo
    safe for any stray concurrent compile anyway. Entries unused for
    PRUNE_AFTER_GENS begin() cycles are dropped so deleted rules do
    not accumulate forever."""

    PRUNE_AFTER_GENS = 64

    def __init__(self) -> None:
        self._entries: dict[str, DecompEntry] = {}
        self._digest: str | None = None
        self._gen = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def begin(self, finder, dnf_cap: int) -> None:
        """Open a compile generation: validate the finder/cap guard
        (clearing on mismatch) and advance the pruning clock."""
        digest = manifest_digest(finder) + f":{dnf_cap}"
        with self._lock:
            if digest != self._digest:
                self._entries.clear()
                self._digest = digest
            self._gen += 1
            if self._gen % 16 == 0:
                floor = self._gen - self.PRUNE_AFTER_GENS
                stale = [k for k, e in self._entries.items()
                         if e.last_gen < floor]
                for k in stale:
                    del self._entries[k]

    def get(self, key: str) -> DecompEntry | None:
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            e.last_gen = self._gen
            self.hits += 1
            return e

    def put(self, key: str, entry: DecompEntry) -> None:
        entry.last_gen = self._gen
        with self._lock:
            self._entries[key] = entry

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses, "generation": self._gen}
