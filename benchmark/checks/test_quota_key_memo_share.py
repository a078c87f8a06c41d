"""quota_key_memo_share: the reader on a program with and without the
counter, over asks it can count, and its place in the manifest."""
import json
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "quota_key_memo_share.py")


def test_reader_survives_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(monitor, "quota_key_counters", raising=False)
    assert READER.read(None, READER.begin(None)) is None


@pytest.mark.parametrize("memo, built, row, value", [
    (0, 0, 0, None),                             # no ask keyed: nothing
    (990, 10, 0, 99.0), (0, 0, 1300, 0.0), (3, 1, 0, 75.0)])
def test_reader_divides_memo_by_every_ask(memo, built, row, value):
    if not hasattr(monitor, "quota_key_counters"):
        pytest.skip("a program from before the counter")
    token = READER.begin(None)
    monitor.note_quota_keys(memo, built, row)
    assert READER.read(None, token) == value


def test_the_manifest_lists_the_reader_in_the_quota_cells():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "quota_key_memo_share"]
    assert manifest["per_layer"][-1] is entry
    assert entry == {
        "name": "quota_key_memo_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "pump",
        "moves": "check_rate",
        "workloads": [w["name"] for w in manifest["workloads"]
                      if w["traffic"] == "quota-deep"]}
