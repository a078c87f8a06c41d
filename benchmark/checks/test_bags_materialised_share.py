"""bags_materialised_share: the reader on a program with and without
the counter, and over rows it can count."""
import json
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "bags_materialised_share.py")


def test_reader_survives_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(monitor, "front_bag_counters", raising=False)
    assert READER.read(None, READER.begin(None)) is None


@pytest.mark.parametrize("rows, made, value", [
    (0, 0, None),                                # no row: nothing
    (1300, 0, 0.0), (1300, 26, 2.0), (32, 32, 100.0)])
def test_reader_divides_bags_by_rows(rows, made, value):
    if not hasattr(monitor, "front_bag_counters"):
        pytest.skip("a program from before the counter")
    token = READER.begin(None)
    monitor.CHECK_REQUESTS.inc(rows)
    monitor.FRONT_BAGS_MATERIALISED.inc(made)
    assert READER.read(None, token) == value


def test_the_manifest_lists_the_reader_in_every_cell():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "bags_materialised_share"]
    cells = [w["name"] for w in manifest["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    assert entry | {"workloads": cells} == {
        "name": "bags_materialised_share", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "decode + staging", "moves": "check_rate",
        "workloads": cells}
