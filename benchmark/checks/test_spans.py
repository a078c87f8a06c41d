"""spans.py: idle attribution on a hand-made trace, and the search for
the window's trace file."""
import os
import time

import spans

MS = 1_000_000


def test_idle_intervals_are_the_complement_of_the_busy_union():
    busy = [(10, 20), (15, 30), (50, 60), (-5, 2), (95, 120)]
    assert spans.idle_intervals(busy, 0, 100) == [
        (2, 10), (30, 50), (60, 95)]
    assert spans.idle_intervals([], 0, 100) == [(0, 100)]


def test_attribute_two_threads_overlapping_spans_gc_and_a_bare_gap():
    pump0, pump1 = ("/host:CPU", "pump-0"), ("/host:CPU", "pump-1")
    host = {
        # gap A (0-100 ms): pump-0 responds for 60, pump-1 tensorizes
        # for 30 + 20 and folds for 10; the nested span and the cycle
        # label nothing
        (pump0, "respond"): [(0 * MS, 60 * MS)],
        (pump0, "pump_cycle"): [(0 * MS, 400 * MS)],
        (pump1, "tensorize"): [(10 * MS, 40 * MS), (70 * MS, 90 * MS)],
        (pump1, "tensorize.decode"): [(10 * MS, 40 * MS)],
        (pump1, "fold"): [(90 * MS, 100 * MS)],
        # gap B (200-500 ms): a full collection covers 200 of its 300,
        # though serialize overlaps all of it on the other thread
        (pump0, "gc"): [(250 * MS, 450 * MS)],
        (pump1, "serialize"): [(200 * MS, 500 * MS)],
        # gap C (600-640 ms): under no span at all
        # gap D (700-720 ms): half under take_wait
        (pump0, "take_wait"): [(710 * MS, 800 * MS)],
    }
    gaps = [(0, 100 * MS), (200 * MS, 500 * MS), (600 * MS, 640 * MS),
            (700 * MS, 720 * MS)]
    found = spans.attribute(gaps, host)
    assert found["longest_gaps"] == [
        ["gc", 0.3], ["respond", 0.1], ["host:unattributed", 0.04],
        ["take_wait", 0.02]]
    by = found["idle_s_by_label"]
    # gap A is covered in full (0-60 | 10-40 | 70-100 -> 0-60, 70-100 =
    # 90 ms): its 90 ms are shared 60 : 50 : 10 of 120 ms of overlap
    assert abs(by["respond"] - 0.090 * 60 / 120) < 1e-12
    assert abs(by["tensorize"] - 0.090 * 50 / 120) < 1e-12
    assert abs(by["fold"] - 0.090 * 10 / 120) < 1e-12
    assert by["gc"] == 0.3 and "serialize" not in by
    assert abs(by["take_wait"] - 0.010) < 1e-12
    assert abs(by["host:unattributed"] - (0.010 + 0.040 + 0.010)) < 1e-12
    assert "pump_cycle" not in by and "tensorize.decode" not in by
    assert abs(sum(by.values()) - found["idle_s"]) < 1e-12
    assert found["idle_s"] == 0.46
    assert abs(found["attributed_share_pct"]
               - 100 * (0.46 - 0.06) / 0.46) < 1e-9


def test_attribute_with_nothing_idle():
    found = spans.attribute([], {(("h", "t"), "fold"): [(0, 5)]})
    assert found["idle_s"] == 0 and found["attributed_share_pct"] is None
    assert found["longest_gaps"] == []


def _write_trace(root, session, name, mtime):
    folder = root / session / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    path = folder / name
    path.write_bytes(b"")
    os.utime(path, (mtime, mtime))
    return str(path)


def test_find_window_trace_ignores_a_stale_older_directory(
        tmp_path, monkeypatch):
    monkeypatch.setattr(spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    began = time.time()
    _write_trace(tmp_path, "tmpstale", "old.xplane.pb", began - 3600)
    assert spans.find_window_trace(began) is None
    first = _write_trace(tmp_path, "tmpwindow", "a.xplane.pb", began + 1)
    newest = _write_trace(tmp_path, "tmpother", "b.xplane.pb", began + 2)
    assert first != newest
    assert spans.find_window_trace(began) == newest
    (tmp_path / "tmpwindow" / "plugins" / "profile" / "2026_01_01"
     / "notes.txt").write_text("not a trace")
    assert spans.find_window_trace(began + 1.5) == newest
