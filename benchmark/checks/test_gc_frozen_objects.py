"""gc_frozen_objects: the reader on a program with and without the
gauge, and its manifest entry as PR 30 added it."""
import json
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "gc_frozen_objects.py")
ENTRY = {"name": "gc_frozen_objects", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "pump", "moves": "check_rate"}


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
    """What PR 30 added is there and as it was added; what later PRs
    add or take away is theirs to pin."""
    now = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [m for m in now["per_layer"] if m["name"] == ENTRY["name"]] \
        == [ENTRY]
    assert (ROOT / "benchmark" / "layer_metrics"
            / f"{ENTRY['name']}.py").is_file()
