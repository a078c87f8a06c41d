"""Hot-path lint — THIN SHIM over istio_tpu/analysis/meshlint.

The detection logic (host-sync/blocking/allocation checks, the
`# hotpath: sync-ok` pragma grammar) and, more importantly, the
COVERAGE now live in `istio_tpu.analysis.meshlint.hotpath`: instead
of this file's hand-maintained HOT_SECTIONS list, the analyzer
computes reachability from the hot entry points, so a new helper
called from hot code is covered the moment it is called — with no
list to extend per PR.

What stays here:

  * `HOT_SECTIONS` — FROZEN as the historical baseline. It is no
    longer the coverage source; it is the floor the superset test
    (tests/test_meshlint_smoke.py) pins the inferred coverage
    against, so a call-graph regression that silently drops a
    once-hot function fails loudly. Do NOT extend it for new code —
    new hot helpers are inferred.
  * `lint_source` / `Violation` — the single-module lint surface
    tests and downstream tooling import; delegates to the meshlint
    detector.
  * `main()` — runs the meshlint hot-path pass over the repo
    (tier-1 calls this via tests/test_hotpath_lint.py).

Usage: python scripts/hotpath_lint.py [--root DIR]   (exit 1 on
violations)
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PRAGMA = "hotpath: sync-ok"

# FROZEN baseline (see module docstring): the last hand-maintained
# coverage list, kept as the superset-pin floor for the inferred
# reachability in istio_tpu/analysis/meshlint/hotpath.py.
HOT_SECTIONS: dict[str, frozenset[str]] = {
    "istio_tpu/runtime/batcher.py": frozenset({
        "CheckBatcher.submit", "CheckBatcher._loop",
        "CheckBatcher._flush", "CheckBatcher._shed_stale",
        "CheckBatcher._run_one", "CheckBatcher._min_deadline",
        "CheckBatcher._drain_on_close",
    }),
    "istio_tpu/runtime/dispatcher.py": frozenset({
        "Dispatcher.check", "Dispatcher._check_fused",
        "Dispatcher._resolve", "Dispatcher._overlay_fallback",
        "Dispatcher._overlay_active",
        "Dispatcher._tensorize_for_device",
        "Dispatcher._ns_ids_from_batch",
        "Dispatcher._request_ns_ids",
        "Dispatcher._report_active_fused",
        "Dispatcher.report",
        "Dispatcher._apply_device_status", "Dispatcher._combine",
    }),
    "istio_tpu/runtime/fused.py": frozenset({
        "FusedPlan.packed_check", "FusedPlan._launch_step",
        "FusedPlan._launch_apart", "FusedPlan.packed_report",
        "FusedPlan.packed_check_instep", "FusedPlan.narrow_batch",
        "FusedPlan.swap_warm_pending", "FusedPlan._serve_width",
    }),
    "istio_tpu/runtime/server.py": frozenset({
        "RuntimeServer.submit_report",
        "RuntimeServer._run_report_batch",
    }),
    "istio_tpu/runtime/device_quota.py": frozenset({
        "DeviceQuotaPool._flush",
    }),
    "istio_tpu/runtime/rulestats.py": frozenset({
        "RuleTelemetry.observe", "RuleTelemetry.chain",
        "RuleTelemetry.add_host",
        "RuleTelemetry.sample_rows", "RuleTelemetry.drain",
    }),
    "istio_tpu/canary/recorder.py": frozenset({
        "TrafficRecorder.tap",
    }),
    "istio_tpu/runtime/executor.py": frozenset({
        "HandlerLane.submit", "AdapterExecutor.submit",
        "AdapterExecutor.resolve",
    }),
    "istio_tpu/runtime/forensics.py": frozenset({
        "FlightRecorder.batch_begin", "FlightRecorder.stage_mark",
        "FlightRecorder.host_wait", "FlightRecorder.note_wire_decode",
        "FlightRecorder.note_batch", "FlightRecorder.note_direct",
        "FlightRecorder._capture", "EventTimeline.record",
        "EventTimeline._mergeable",
    }),
    "istio_tpu/sharding/router.py": frozenset({
        "ShardRouter.check", "ReplicaRouter.submit",
        "ReplicaRouter.lane_of",
    }),
    "istio_tpu/pilot/discovery.py": frozenset({
        "SnapshotCache.lookup", "SnapshotCache.peek",
        "SnapshotCache.store", "DiscoveryService._serve_cached",
        "DiscoveryService._generate_rds_batch",
    }),
    "istio_tpu/pilot/route_nfa.py": frozenset({
        "RouteScopeProgram.admit_rows",
    }),
}


@dataclasses.dataclass
class Violation:
    path: str
    line: int
    func: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.func}] {self.message}"


def lint_source(source: str, hot_names: frozenset[str],
                path: str = "<memory>") -> list[Violation]:
    """AST-lint one module's named hot functions (the pre-meshlint
    surface, kept for tests/tooling); detection delegates to
    meshlint's hot-path detector so there is exactly one definition
    of "host sync"."""
    from istio_tpu.analysis.meshlint.hotpath import sync_sites

    tree = ast.parse(source)
    lines = source.splitlines()
    out: list[Violation] = []

    def walk(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                if qual in hot_names:
                    for line, message in sync_sites(child, lines):
                        out.append(Violation(path, line, qual,
                                             message))
                else:
                    walk(child, f"{qual}.")
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


def main(root: str | None = None) -> int:
    root = root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    from istio_tpu.analysis.meshlint import run_meshlint

    report = run_meshlint(root=root, passes=("hotpath",))
    violations = [
        Violation(f.path, f.line, f.func, f.message)
        for f in report.findings]
    for v in violations:
        print(f"hotpath_lint: {v}")
    if not violations:
        print(f"hotpath_lint: ok "
              f"({report.stats.get('hot_reachable', 0)} inferred hot "
              f"functions from {report.stats.get('hot_roots', 0)} "
              f"roots clean)")
    return 1 if violations else 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None)
    sys.exit(main(root=ap.parse_args().root))
