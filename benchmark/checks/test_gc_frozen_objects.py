"""gc_frozen_objects: the reader on a program with and without the
gauge, and its manifest entry as the only thing BENCHMARK.json gained
with it."""
import json
import subprocess
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "gc_frozen_objects.py")
ENTRY = {"name": "gc_frozen_objects", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "pump", "moves": "check_rate"}
PARENT = "d231e341bc2bb962908b0308cee1fa01914e0fa5"   # PR 29


@pytest.mark.parametrize("snapshot, value", [
    ({"count": 3, "sum_s": 0.9}, None),             # the parent's dict
    ({"count": 3, "sum_s": 0.9, "frozen": 0, "settles": {}}, 0),
    ({"count": 0, "sum_s": 0.0, "frozen": 812345, "settles": {}}, 812345),
])
def test_reader_returns_the_gauge_or_nothing(monkeypatch, snapshot, value):
    monkeypatch.setattr(monitor, "gc_pause_snapshot", lambda: snapshot)
    token = READER.begin(None)
    # the value is the one taken as the window opened, whatever a
    # later settle makes of the gauge
    monkeypatch.setattr(monitor, "gc_pause_snapshot",
                        lambda: {**snapshot, "frozen": 1})
    assert READER.read(None, token) == value


def test_reader_survives_a_program_without_the_hook(monkeypatch):
    monkeypatch.delattr(monitor, "gc_pause_snapshot")
    assert READER.read(None, READER.begin(None)) is None


def test_reader_reads_the_program_as_it_is():
    if not hasattr(monitor, "settle_heap"):
        pytest.skip("a program from before settle_heap")
    monitor.install_gc_hook()
    try:
        monitor.settle_heap("start")
        assert READER.read(None, READER.begin(None)) > 0
    finally:
        monitor.remove_gc_hook()


def test_the_manifest_gained_one_entry_and_lost_nothing():
    now = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [m for m in now["per_layer"] if m["name"] == ENTRY["name"]] \
        == [ENTRY]
    shown = subprocess.run(
        ["git", "show", f"{PARENT}:BENCHMARK.json"], cwd=ROOT,
        capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here to compare with")
    was = json.loads(shown.stdout)
    at = len(was["per_layer"])
    assert now["per_layer"][at] == ENTRY
    for key, value in was.items():
        if isinstance(value, list) and key != "command":
            assert now[key][:len(value)] == value, key
        else:
            assert now[key] == value, key
