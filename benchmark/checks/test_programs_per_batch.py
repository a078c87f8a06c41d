"""programs_per_batch: the reader on a program with and without the
counter, and over launches it can count."""
from pathlib import Path

import pytest

from istio_tpu.runtime import monitor

from run import load_module

ROOT = Path(__file__).resolve().parent.parent.parent
READER = load_module(ROOT / "benchmark" / "layer_metrics"
                     / "programs_per_batch.py")


def test_reader_survives_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(monitor, "device_program_counters", raising=False)
    assert READER.read(None, READER.begin(None)) is None


@pytest.mark.parametrize("launches, value", [
    ([], None),                                  # no batch: nothing
    ([("check", 1)] * 3, 1.0),
    ([("check", 1), ("instep", 4)], 2.5),
])
def test_reader_divides_programs_by_served_batches(launches, value):
    if not hasattr(monitor, "note_device_programs"):
        pytest.skip("a program from before the counter")
    token = READER.begin(None)
    for path, programs in launches:
        with monitor.span("dispatch.step"):
            pass
        monitor.note_device_programs(path, programs)
    assert READER.read(None, token) == value
