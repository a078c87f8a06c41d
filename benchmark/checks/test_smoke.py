"""run.py off the chip: a rehearsal ends with a well-formed last line
that can never read as a measurement; without --smoke it prints none."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [M["workloads"][0]["name"], "mixer10k-quota-deep"]


def bench(cell, *extra):
    return subprocess.run(
        [sys.executable, *M["command"][1:], "--workload", cell, "--seed",
         "4000000011", "--seconds", "2", *extra], cwd=ROOT, text=True,
        capture_output=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("cell", CELLS)
def test_smoke_on_cpu_is_well_formed_and_never_correct(cell):
    done = bench(cell, "--trace", "1", "--smoke")
    assert done.returncode == 1, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    last = lines[-1]
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    # a rehearsal names what it read, never a value of it
    assert '"value"' not in done.stdout and '"client"' not in done.stdout
    # every number compared, beside its limit: the line's last key and
    # the last lines of standard error
    held = last["compared"]
    assert held["wire_parity_mismatches"] == [0, "<=", 0]
    assert held["client_failed"] == [0, "<=", 0]
    assert done.stderr.strip().splitlines()[-len(held):] == [
        f"compared {name}: {value} {op} {limit}"
        for name, (value, op, limit) in held.items()]
    phases = {line.get("phase") for line in lines[:-1]}
    asks = cell.endswith("quota-deep")
    assert ("parity_quota" in phases) is asks
    assert ("window_quota" in phases) is asks
    assert ("quota_parity_mismatches" in held) is asks
    assert ("short_grants" in held) is asks
    assert ("read_back_quota" in phases) is asks
    assert ("quota_counter_lags_mismatches" in held) is asks


def test_no_result_without_a_tpu():
    done = bench(CELLS[0], "--trace", "0")
    assert done.returncode == 2 and done.stdout.strip() == ""
