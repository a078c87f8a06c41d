"""The DFA scans' share of the memory roofline where subjects are long:
the least time the chip's HBM could move what the scans have to move
(rooflines/dfa.py, unedited: rows x the blocks the row's host holds on
each subject x the subject's FULL mean bytes x one 4-byte transition,
plus the subjects), over the time a batch spends under `dfa` in both
its programs (`step` and `step_wide`: scopes.scope_ms_per_step divides
the scope's time by the `jit_step` programs, one a batch). The same
work whatever implements it: a scan that stops at a dead state, or
that the host takes over, reads a larger share. Counted from the
deployment, not the program: blocks a host and the subjects' lengths
from routelong.py's own generator (seed 0), at the sizes the served
snapshot was built from; the rows from the front's batch counters. A
step without the scope, or a snapshot no `routelong` configuration
built, is not read."""
import json
import time
from pathlib import Path

from scopes import read_window

BENCH = Path(__file__).resolve().parent.parent
MODULE = "routelong"
SAMPLE, SAMPLE_SEED = 2048, 0


def _load(path: Path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def subjects_of(sizes: dict, config) -> list:
    """(blocks a host holds on the subject, mean bytes of it a row,
    whole) for the request line and the cookie."""
    hosts = sizes["services"]
    on_cookie = sum("cookie" in spec["match"]["request"]["headers"]
                    for spec in config.rule_specs(sizes))
    requests = config.make_requests(sizes, SAMPLE, SAMPLE_SEED)
    path = sum(len(r["request.path"]) for r in requests) / SAMPLE
    cookie = sum(len(r["request.headers"].get("cookie", ""))
                 for r in requests) / SAMPLE
    return [((sizes["rules"] - on_cookie) / hosts, path),
            (on_cookie / hosts, cookie)]


def served_sizes(n_rules: int) -> dict | None:
    """The sizes a snapshot of `n_rules` rules was built from: a
    configuration of MODULE in BENCHMARK.json, as it stands or with
    its `smoke` sizes laid over it."""
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for entry in manifest["configs"]:
        sizes = json.loads((BENCH.parent / entry["file"]).read_text())
        if sizes.get("module") != MODULE:
            continue
        for candidate in (sizes, {**sizes, **sizes.get("smoke", {})}):
            if candidate["rules"] == n_rules:
                return candidate
    return None


def begin(ctx):
    return time.time(), ctx.native.counters()


def read(ctx, base):
    import jax

    since, counters = base
    scan_ms = read_window(ctx, since, "dfa")
    now = ctx.native.counters()
    batches = now["batches_formed"] - counters["batches_formed"]
    if scan_ms is None or not batches:
        return None
    rows = (now["batch_rows"] - counters["batch_rows"]) / batches
    ruleset = ctx.srv.controller.dispatcher.fused.engine.ruleset
    sizes = served_sizes(len(ruleset.rules))
    if sizes is None:
        return None
    config = _load(BENCH / "configs" / f"{MODULE}.py")
    dfa = _load(BENCH / "rooflines" / "dfa.py")
    return dfa.roofline_share_pct(rows, subjects_of(sizes, config),
                                  scan_ms, jax.devices()[0].device_kind)
