"""The `routelong10k` configuration's own pieces: its five readers on
recorded inputs, the roofline count for its generator against a hand
count, what the manifest gained, and a rehearsal of its cell."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

import run

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = ROOT / "benchmark"
CELL = "routelong10k-check-deep"
READERS = ("wide_rows_share", "host_decided_share",
           "undecided_ms_per_batch", "device_wide_step_ms",
           "longscan_roofline_share")
APPENDED = ("wire_p99_ms.deep", "device_lists_ms", "device_dfa_ms",
            "dfa_bank_mb", "dfa_candidates_per_row", "setup_dfa_build_s",
            "respond_classes_mean")
OLD_CELLS = ["mixer10k-check-deep", "mixer10k-check-shallow",
             "rbac1k-check-deep", "fullmesh5k-check-deep",
             "routematch10k-check-deep"]


def reader(name: str):
    return run.load_module(BENCH / "layer_metrics" / f"{name}.py")


def served(**trace):
    """A stand-in for run.py's ctx: a plan with the two narrow tiers, a
    front with batch counters, and the reduced trace given."""
    plan = types.SimpleNamespace(str_tiers=(32, 128))
    return types.SimpleNamespace(
        trace=trace or None,
        srv=types.SimpleNamespace(controller=types.SimpleNamespace(
            dispatcher=types.SimpleNamespace(fused=plan))),
        native=types.SimpleNamespace(
            counters=lambda: {"batches_formed": 0, "batch_rows": 0}))


@pytest.fixture(scope="module")
def smoke():
    sizes = json.loads((BENCH / "configs" / "routelong10k.json").read_text())
    sizes.update(sizes["smoke"])
    return sizes, run.load_module(BENCH / "configs" / "routelong.py")


@pytest.mark.parametrize("name", READERS[:3])
def test_counter_readers_survive_a_program_without_them(monkeypatch, name):
    """The parent has neither counter nor span: nothing, not a raise."""
    monkeypatch.delattr(monitor, "length_split_counters", raising=False)
    r = reader(name)
    token = r.begin(served())
    assert r.read(served(), token) is None


def test_wide_rows_share_counts_rows_above_the_narrow_tiers():
    r = reader("wide_rows_share")
    token = r.begin(served())
    assert r.read(served(), token) is None            # no row: nothing
    monitor.note_rows_by_width({32: 600, 128: 40})
    monitor.note_rows_by_width({128: 0, 2048: 720})
    assert r.read(served(), token) == 100.0 * 720 / 1360


def test_host_decided_share_is_undecided_rows_over_rows(capsys):
    r = reader("host_decided_share")
    token = r.begin(served())
    assert r.read(served(), token) is None
    monitor.note_rows_by_width({32: 653, 2048: 707})
    for _ in range(6):
        monitor.CHECK_UNDECIDED_ROWS.inc(subject="request.headers[cookie]")
    monitor.CHECK_UNDECIDED_ROWS.inc(subject="request.path")
    assert r.read(served(), token) == 100.0 * 7 / 1360
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["by_subject"] == {"request.headers[cookie]": 6,
                                  "request.path": 1}


def test_undecided_ms_is_the_spans_own_mean():
    r = reader("undecided_ms_per_batch")
    token = r.begin(served())
    assert r.read(served(), token) is None
    for _ in range(3):
        with monitor.stage("fold"), monitor.span("fold.undecided"):
            pass
    value = r.read(served(), token)
    assert value is not None and 0 <= value < 5


@pytest.mark.parametrize("modules, value", [
    ({"jit_step": (10, 0.05)}, None),                  # no wide program
    ({"jit_step_wide": (3, 0.09)}, None),              # no batch to divide by
    ({"jit_step": (10, 0.05), "jit_step_wide": (27, 0.09)}, 9.0),
])
def test_wide_step_ms_divides_by_batches_not_programs(modules, value):
    r = reader("device_wide_step_ms")
    assert r.read(served(), None) is None              # no trace
    got = r.read(served(modules=modules), None)
    assert got == (pytest.approx(value) if value else None)


def test_scan_bytes_for_the_new_generator_against_a_hand_count(smoke):
    sizes, config = smoke
    r = reader("longscan_roofline_share")
    (on_path, path), (on_cookie, cookie) = r.subjects_of(sizes, config)
    assert (on_path, on_cookie) == (7.5, 2.5)
    requests = config.make_requests(sizes, r.SAMPLE, r.SAMPLE_SEED)
    by_hand_path = sum(len(d["request.path"]) for d in requests) / r.SAMPLE
    by_hand_cookie = sum(len(d["request.headers"].get("cookie", ""))
                         for d in requests) / r.SAMPLE
    assert (path, cookie) == (by_hand_path, by_hand_cookie)
    # whole strings, three to four times routematch10k's: the class
    # means of the configuration's table (paths 48 / 264 / 756 at
    # 75 / 20 / 5 %; cookies 80 / 304 / 1256 / 5050 on three in four)
    assert 100 < path < 145 and 230 < cookie < 320
    dfa = run.load_module(BENCH / "rooflines" / "dfa.py")
    rows = 1360
    assert dfa.scan_bytes(rows, [(on_path, path), (on_cookie, cookie)]) \
        == rows * ((7.5 * path * 4 + path) + (2.5 * cookie * 4 + cookie))
    # by the table's means: 1360 rows move about 11 MB, 13 us of HBM
    assert 8e6 < dfa.scan_bytes(rows, [(7.5, 125.0), (2.5, 270.0)]) < 12e6


def test_roofline_reader_takes_the_sizes_of_the_served_snapshot():
    r = reader("longscan_roofline_share")
    full, small = r.served_sizes(10000), r.served_sizes(60)
    assert full["module"] == small["module"] == "routelong"
    assert (full["services"], small["services"]) == (1000, 6)
    assert r.served_sizes(300) is None      # routematch10k's smoke store
    # and the reader it was copied from does not find this deployment
    old = reader("dfa_roofline_share")
    assert old.served_sizes(60) is None
    assert old.served_sizes(10000)["module"] == "routematch"
    # no trace, no step under `dfa`: nothing
    token = r.begin(served())
    assert r.read(served(), token) is None


def test_the_manifest_gained_one_deployment_and_lost_nothing():
    """What PR 35 added is there and as it was added: the deployment,
    its cell, its five readers and the cell's name on the metrics it
    joined. What later PRs add or take away is theirs to pin."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in manifest["configs"]}
    assert configs["routelong10k"]["file"] == \
        "benchmark/configs/routelong10k.json"
    assert configs["routelong10k"]["reduced"] == ["route_selection"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(OLD_CELLS) <= set(cells)
    assert cells[CELL] == {
        "name": CELL, "config": "routelong10k", "traffic": "check-deep",
        "chips": 1, "why": cells[CELL]["why"]}
    assert manifest["run_seconds"] == 50
    bounds = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    assert (bounds["check_p99_ms"], bounds["setup_s"]) == (0.12, 0.25)
    # loosened in PR 39 (PERF.md 2): 0.12 and 0.08 until then
    assert (bounds["check_rate"], bounds["check_p50_ms"]) == (0.22, 0.16)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert m["workloads"] == [CELL] and m["moves"] == "check_rate"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in APPENDED:
        assert CELL in per_layer[name]["workloads"], name


def test_the_new_cell_resolves_to_its_files():
    cell = run.resolve_cell(CELL, smoke=False)
    assert cell.chips == 1 and cell.sizes["module"] == "routelong"
    assert cell.sizes["rules"] == 10000 and cell.mix["depth"] == 4096
    assert len(cell.sizes["guarantees"]) == 3
    assert set(cell.sizes["assumed"]["length_shares"]) == {
        "cookie", "request.path"}
    # its table is routematch10k's: every size the store is built from
    other = run.resolve_cell("routematch10k-check-deep", smoke=False).sizes
    for key in ("rules", "services", "namespaces", "deny_every",
                "whitelist_every", "request_source_namespaces",
                "max_batch", "buckets", "manifest", "quota_name",
                "reduced"):
        assert cell.sizes[key] == other[key], key
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) | set(APPENDED) <= names
    assert not {"dfa_roofline_share", "device_rbac_ms"} & names
    assert [m["name"] for m in cell.end_to_end] == ["check_rate", "setup_s"]
    for name in READERS:
        assert callable(reader(name).read)
        for other_cell in OLD_CELLS:
            assert name not in {m["name"] for m in run.resolve_cell(
                other_cell, False).per_layer}


def test_a_rehearsal_of_the_new_cell_ends_at_parity_and_never_correct():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4000000011", "--seconds", "2", "--trace", "1", "--smoke"],
        cwd=ROOT, text=True, capture_output=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 1, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    phases = {line.get("phase"): line for line in lines[:-1]}
    assert phases["parity_wire"]["mismatches"] == 0
    assert len(phases["parity_wire"]["status_hist"]) > 1
    assert phases["parity_top_bucket"]["mismatches"] == 0
    assert {"host_decided_share", "wide_rows_share"} <= set(
        phases["window"]["layers_read"])
    last = lines[-1]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["failed"] == 0 and last["device"]["platform"] == "cpu"
    assert '"value"' not in done.stdout and '"client"' not in done.stdout
