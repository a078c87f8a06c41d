"""Tier-1 wrapper for scripts/soak_smoke.py — the whole-mesh chaos
soak must pass its recovery gates in-process, twice, with the SAME
seed producing the SAME injection schedule and the SAME gate verdicts
(the seed/replay contract), inside a CPU-time budget (the soak's wall
is bounded by the harness's own recovery and quiesce timeouts; what
the budget guards is the work it does, which other xdist workers'
load does not change)."""
import importlib.util
import os
import sys
import time

import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), os.pardir,
                      "scripts", "soak_smoke.py")
CPU_BUDGET_S = 90.0     # the pair costs ~35 s of CPU alone


def _run(seed: int) -> dict:
    spec = importlib.util.spec_from_file_location(
        "soak_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["soak_smoke"] = mod
    sink: dict = {}
    try:
        spec.loader.exec_module(mod)
        rc = mod.main(seed=seed, result_sink=sink)
    finally:
        sys.modules.pop("soak_smoke", None)
    assert rc == 0, f"soak smoke failed (seed {seed})"
    return sink


@pytest.mark.filterwarnings("ignore")
def test_soak_smoke_deterministic():
    t0 = time.process_time()
    a = _run(0)
    b = _run(0)
    cpu = time.process_time() - t0

    # seed/replay contract: same seed -> byte-identical injection
    # schedule and identical gate verdicts
    assert a["schedule"] == b["schedule"], \
        "same seed produced different injection schedules"
    assert a["gates"] == b["gates"], (
        f"same seed produced different gate verdicts: "
        f"{a['gates']} vs {b['gates']}")
    assert a["all_ok"] and b["all_ok"]

    # >= 3 distinct fault kinds injected AND explained
    assert len(a["metrics"]["soak_fault_kinds"]) >= 3, \
        a["metrics"]["soak_fault_kinds"]
    assert a["metrics"]["soak_violations_after_recovery"] == 0
    assert a["metrics"]["soak_explainability_rate"] == 1.0
    assert a["restarts"] == 1

    assert cpu <= CPU_BUDGET_S, (
        f"soak smoke pair took {cpu:.1f}s of CPU "
        f"(budget {CPU_BUDGET_S}s)")
