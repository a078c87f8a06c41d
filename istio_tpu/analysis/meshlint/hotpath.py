"""Inferred hot-path reachability pass.

`scripts/hotpath_lint.py` enforced the one-sync-per-batch doctrine
over a HAND-MAINTAINED file→function list that every PR had to
remember to extend. This pass replaces the list with REACHABILITY:
start from the hot ENTRY POINTS (the functions whose latency is the
serving SLO — batch admission, the batch step, the fused device
trips, shard/replica routing, adapter fan-out, discovery cache serve)
and walk the call graph; every function reachable from a root IS hot,
and the same host-sync/blocking/allocation checks apply to all of
them. New helpers called from hot code are covered the moment they
are called — no list to extend.

Two pieces of declared data remain, both reviewable:

  * DYNAMIC_EDGES — callback seams the AST cannot see (the batcher
    invokes `self._run_batch`, which the server wired to its batch
    hooks at construction; the resilient checker fans out to the
    device/oracle callables it was built with). Each entry is a
    (caller, callee) qualname pair mirroring one `=` in the wiring
    code.
  * COLD_BOUNDARIES — functions reachable from hot code that are, by
    design, OFF the latency path: scrape/serve surfaces, failure
    forensics dumps, drain/shutdown legs. Traversal stops there (the
    boundary function itself is still scanned unless listed in
    COLD_BOUNDARIES — stopping means its callees are not dragged in).

The old `# hotpath: sync-ok` pragma grammar is honored unchanged (and
`# meshlint: sync-ok` works too). Violations are ERRORs carrying the
root→function call chain as witness. `hotpath-root-missing` fires
when a configured root no longer resolves — config drift is a gate
failure, exactly like the old script's `<config>` violation."""
from __future__ import annotations

import ast

from istio_tpu.analysis.findings import Severity
from istio_tpu.analysis.meshlint import callgraph as cg
from istio_tpu.analysis.meshlint import model

# hot entry points — the functions a request's latency budget pays
# for. Qualnames are matched per module via Universe.find (unique
# suffix) so the manifest survives file moves.
HOT_ROOTS: tuple[str, ...] = (
    # batch admission + the batcher worker step
    "CheckBatcher.submit", "CheckBatcher._loop", "CheckBatcher._run_one",
    "CheckBatcher._drain_on_close",
    # dispatch: direct + fused check, report coalescer dispatch
    "Dispatcher.check", "Dispatcher._check_fused", "Dispatcher.report",
    # packed device trips
    "FusedPlan.packed_check", "FusedPlan._launch_step",
    "FusedPlan._launch_apart", "FusedPlan.packed_report",
    "FusedPlan.packed_check_instep",
    # report ingestion (ack-after-enqueue admission + worker hook)
    "RuntimeServer.submit_report", "RuntimeServer._run_report_batch",
    # quota-plane worker flush (device trip under _counts_lock)
    "DeviceQuotaPool._flush",
    # adapter-executor plane
    "HandlerLane.submit", "AdapterExecutor.submit",
    "AdapterExecutor.resolve",
    # sharded serving plane
    "ShardRouter.check", "ReplicaRouter.submit",
    # discovery serving plane (poll-storm path)
    "SnapshotCache.lookup", "SnapshotCache.peek", "SnapshotCache.store",
    "DiscoveryService._serve_cached",
    "DiscoveryService._generate_rds_batch",
    "RouteScopeProgram.admit_rows",
    # canary tap + rule telemetry fold (run inside the batch step)
    "TrafficRecorder.tap",
    "RuleTelemetry.observe", "RuleTelemetry.chain",
    "RuleTelemetry.add_host",
    "RuleTelemetry.sample_rows", "RuleTelemetry.drain",
    # flight-recorder tape primitives (per-batch/per-stage)
    "FlightRecorder.batch_begin", "FlightRecorder.stage_mark",
    "FlightRecorder.host_wait", "FlightRecorder.note_wire_decode",
    "FlightRecorder.note_batch", "FlightRecorder.note_direct",
    "EventTimeline.record",
)

# callback seams: each pair mirrors one constructor/wiring assignment
# the resolver cannot follow. (caller, callee) — callee becomes
# reachable whenever caller is.
DYNAMIC_EDGES: tuple[tuple[str, str], ...] = (
    # CheckBatcher(self._run_check_batch) / CheckBatcher(
    #   self._run_report_batch) — the worker invokes self._run_batch
    ("CheckBatcher._run_one", "RuntimeServer._run_check_batch"),
    ("CheckBatcher._run_one", "RuntimeServer._run_report_batch"),
    # ResilientChecker(device=…, oracle=…) fan-out
    ("ResilientChecker.run_batch",
     "RuntimeServer._run_check_batch_device"),
    ("ResilientChecker.run_batch",
     "RuntimeServer._run_check_batch_oracle"),
    # executor lanes run registered adapter handlers via HandlerTable
    ("ReplicaRouter.submit", "ShardRouter.check"),
    # Dispatcher.fused is an untyped ctor param (plan = self.fused);
    # the swap-warm oracle bridge consults it on every served batch
    ("Dispatcher._check_fused", "FusedPlan.swap_warm_pending"),
    # _fold_respond's nested respond_row (the resolver follows no call
    # into or out of a nested def): every call the row builder makes
    ("Dispatcher._fold_respond", "Dispatcher._apply_device_status"),
    ("Dispatcher._fold_respond", "Dispatcher._safe_check"),
    ("Dispatcher._fold_respond", "Dispatcher._combine"),
    ("Dispatcher._fold_respond", "Dispatcher._handler_for"),
    ("Dispatcher._fold_respond", "AdapterExecutor.resolve"),
)

# reachable-but-cold: traversal stops AT these functions and they are
# not scanned — scrape/serve/drain surfaces invoked from hot frames
# only on failure or at scrape rate.
COLD_BOUNDARIES: frozenset[str] = frozenset()

_SYNC_ATTRS = ("item", "block_until_ready")
_PULL_FUNCS = {("np", "asarray"), ("np", "array"),
               ("numpy", "asarray"), ("numpy", "array"),
               ("jax", "device_get")}
_CAST_FUNCS = {"float", "int", "bool"}
_BLOCKING_NAMES = {"open", "input", "print", "breakpoint"}
_BLOCKING_ATTRS = {("time", "sleep")}
_BLOCKING_MODULES = {"subprocess", "urllib", "requests", "socket"}
# cast-over-a-call is only a sync when the call can return a device
# scalar — container/string accessors are provably host work, so
# `int(spec.get("port", 80))` does not need a pragma
_HOST_ACCESSORS = {"get", "pop", "split", "rsplit", "strip", "lstrip",
                   "rstrip", "lower", "upper", "join", "items", "keys",
                   "values", "copy", "decode", "encode", "format",
                   "replace", "len"}


def sync_sites(fn_node: ast.AST, lines: list[str]) -> list[tuple[int, str]]:
    """(line, message) for every un-pragma'd host-sync/blocking site in
    one function body — nested defs INCLUDED (they run on the same
    thread when called; matching the old linter's semantics keeps the
    superset pin honest)."""
    out: list[tuple[int, str]] = []

    def pragma(node: ast.AST) -> bool:
        return model.has_pragma(lines, node.lineno, "sync-ok")

    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if fn.attr in _SYNC_ATTRS and not pragma(node):
                out.append((node.lineno,
                            f".{fn.attr}() is a host sync"))
            chain = cg._dotted(fn)
            if chain is not None:
                if chain[-2:] in _PULL_FUNCS or chain in _PULL_FUNCS:
                    arg = node.args[0] if node.args else None
                    if not isinstance(arg, (ast.List, ast.ListComp)) \
                            and not pragma(node):
                        out.append((node.lineno,
                                    f"{'.'.join(chain)}() pulls "
                                    f"device buffers to host"))
                if (chain[:2] in _BLOCKING_ATTRS
                        or chain[0] in _BLOCKING_MODULES) \
                        and not pragma(node):
                    out.append((node.lineno,
                                f"blocking call {'.'.join(chain)}()"))
        elif isinstance(fn, ast.Name):
            if fn.id in _CAST_FUNCS and node.args \
                    and isinstance(node.args[0], ast.Call) \
                    and not (isinstance(node.args[0].func,
                                        ast.Attribute)
                             and node.args[0].func.attr
                             in _HOST_ACCESSORS) \
                    and not (isinstance(node.args[0].func, ast.Name)
                             and node.args[0].func.id
                             in _HOST_ACCESSORS) \
                    and not pragma(node):
                out.append((node.lineno,
                            f"{fn.id}(<call>) syncs the wrapped "
                            f"computation"))
            if fn.id in _BLOCKING_NAMES and not pragma(node):
                out.append((node.lineno,
                            f"blocking builtin {fn.id}()"))
    return out


def reachable(u: cg.Universe,
              roots: tuple[str, ...] = HOT_ROOTS,
              dynamic_edges: tuple[tuple[str, str], ...] = DYNAMIC_EDGES,
              cold: frozenset[str] = COLD_BOUNDARIES,
              ) -> tuple[dict[str, tuple[str, ...]], list[str]]:
    """BFS from roots → {reached fqn: witness chain of frames},
    plus the list of roots that no longer resolve."""
    missing: list[str] = []
    dyn: dict[str, list[str]] = {}
    for caller, callee in dynamic_edges:
        c = u.find(caller)
        t = u.find(callee)
        if c is not None and t is not None:
            dyn.setdefault(c.fqn, []).append(t.fqn)
    chains: dict[str, tuple[str, ...]] = {}
    queue: list[str] = []
    for r in roots:
        fi = u.find(r)
        if fi is None:
            missing.append(r)
            continue
        if fi.fqn not in chains:
            chains[fi.fqn] = (f"{fi.path}:{fi.line} {fi.qual} — "
                              f"hot entry point",)
            queue.append(fi.fqn)
    while queue:
        fqn = queue.pop(0)
        fi = u.functions[fqn]
        if fi.qual in cold or fqn in cold:
            continue
        nxt: list[tuple[int, str]] = list(u.calls_in(fi))
        nxt += [(fi.line, d) for d in dyn.get(fqn, ())]
        for line, callee in nxt:
            ci = u.functions.get(callee)
            if ci is None or callee in chains:
                continue
            if ci.qual in cold or callee in cold:
                continue
            chains[callee] = chains[fqn] + (
                f"{fi.path}:{line} {fi.qual} — calls {ci.qual}",)
            queue.append(callee)
    return chains, missing


def run(u: cg.Universe, report: model.MeshlintReport,
        roots: tuple[str, ...] = HOT_ROOTS,
        dynamic_edges: tuple[tuple[str, str], ...] = DYNAMIC_EDGES,
        cold: frozenset[str] = COLD_BOUNDARIES) -> dict:
    chains, missing = reachable(u, roots, dynamic_edges, cold)
    for r in missing:
        report.add(model.LintFinding(
            model.HOTPATH_ROOT_MISSING, Severity.ERROR, "<config>", 0,
            "<config>",
            f"hot root {r!r} no longer resolves — update "
            f"meshlint.hotpath.HOT_ROOTS"))
    # scan parents only: nested defs are inside their parent's scan
    nested_of: set[str] = set()
    for fqn, fi in u.functions.items():
        for n in ast.walk(fi.node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not fi.node:
                nested_of.add(f"{fi.module}:{fi.qual}.{n.name}")
    seen_sites: set[tuple[str, int, str]] = set()
    for fqn in sorted(chains):
        if fqn in nested_of:
            continue
        fi = u.functions[fqn]
        for line, message in sync_sites(fi.node, u.lines_of(fi)):
            key = (fi.path, line, message)
            if key in seen_sites:
                continue
            seen_sites.add(key)
            report.add(model.LintFinding(
                model.HOTPATH_SYNC, Severity.ERROR, fi.path, line,
                fi.qual, message, chain=chains[fqn]))
    coverage: dict[str, list[str]] = {}
    for fqn in chains:
        fi = u.functions[fqn]
        coverage.setdefault(fi.path, []).append(fi.qual)
    cov = {p: sorted(q) for p, q in sorted(coverage.items())}
    report.stats["hot_roots"] = len(roots)
    report.stats["hot_reachable"] = len(chains)
    report.stats["hot_coverage"] = cov
    return cov
