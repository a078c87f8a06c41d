"""The DFA scan's share of the memory roofline: the least time the
chip's HBM could move what the scan has to move (rooflines/dfa.py: rows
x the blocks the row's host holds on each subject x the subject's bytes
x one 4-byte transition, plus the subjects themselves), over the time
the step spends under `dfa` (device_dfa_ms). Counted from the
deployment, not from the program: blocks a host and the subjects'
lengths come from the configuration's own generator, the rows from the
front's batch counters. Which configuration: the one of this module
in the manifest whose rule count, at full or at smoke sizes, is the
served snapshot's (`ctx` carries the server, not the cell's sizes). A
step without the scope, or a snapshot no such configuration built, is
not read."""
import json
import time
from pathlib import Path

from scopes import read_window

BENCH = Path(__file__).resolve().parent.parent
MODULE = "routematch"             # the generator subjects_of reads
SAMPLE, SAMPLE_SEED = 2048, 0     # request lengths do not hang on a seed


def _load(path: Path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def subjects_of(sizes: dict, config) -> list:
    """(blocks a host holds on the subject, mean bytes of it a row) for
    the request line and the cookie, from the generator's own data."""
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
