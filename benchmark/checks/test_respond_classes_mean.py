"""respond_classes_mean: the reader on a program with and without the
counter, and over batches it can count."""
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "respond_classes_mean.py")


def test_reader_survives_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(monitor, "respond_class_counters", raising=False)
    assert READER.read(None, READER.begin(None)) is None


@pytest.mark.parametrize("batches, value", [
    ([], None),                                  # no batch: nothing
    ([(2, 1361, 2)], 2.0),
    ([(25, 1340, 23), (140, 1250, 113), (32, 0, 32)], 197 / 3),
])
def test_reader_divides_classes_by_served_batches(batches, value):
    if not hasattr(monitor, "note_respond_classes"):
        pytest.skip("a program from before the counter")
    token = READER.begin(None)
    for classes, classed, row in batches:
        with monitor.stage("respond"):
            pass
        monitor.note_respond_classes(classes, classed, row)
    assert READER.read(None, token) == value


def test_the_manifest_lists_the_reader_in_every_cell():
    import json
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "respond_classes_mean"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    assert entry | {"workloads": cells} == {
        "name": "respond_classes_mean", "unit": "count",
        "better": "lower", "source": "program_counter",
        "layer": "dispatch", "moves": "check_rate",
        "workloads": [w["name"] for w in manifest["workloads"]]}
