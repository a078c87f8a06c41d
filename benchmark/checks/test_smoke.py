"""run.py off the chip: a rehearsal ends with a well-formed last line
that can never read as a measurement; without --smoke it prints none."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = M["workloads"][0]["name"]


def bench(*extra):
    return subprocess.run(
        [sys.executable, *M["command"][1:], "--workload", CELL, "--seed",
         "4000000011", "--seconds", "2", *extra], cwd=ROOT, text=True,
        capture_output=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_smoke_on_cpu_is_well_formed_and_never_correct():
    done = bench("--trace", "1", "--smoke")
    assert done.returncode == 1, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    # a rehearsal names what it read, never a value of it
    assert '"value"' not in done.stdout and '"client"' not in done.stdout


def test_no_result_without_a_tpu():
    done = bench("--trace", "0")
    assert done.returncode == 2 and done.stdout.strip() == ""
