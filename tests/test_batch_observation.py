"""One observation a batch: Histogram.observe_key(key, value, n) and
SlidingWindow.observe(value, n) count a batch's rows, which share
their batch's wall, as n single calls would (utils/metrics.py), so
monitor.observe_check_e2e is called once a batch and still counts a
row a request."""
import pytest

from istio_tpu.runtime import monitor
from istio_tpu.utils.metrics import Histogram, SlidingWindow

VALUES = (0.0, 0.00049, 0.0005, 0.0125, 0.3, 7.0, 1e9)


@pytest.mark.parametrize("n", [0, 1, 2, 1300, 5000])
@pytest.mark.parametrize("value", VALUES)
def test_observe_key_n_equals_n_calls(value, n):
    once, each = Histogram("h"), Histogram("h")
    for hist in (once, each):
        hist.observe(0.02, stage="a")       # another key stays as it is
        hist.observe(0.0125)
    once.observe_key((), value, n)
    for _ in range(n):
        each.observe_key((), value)
    counts, total, count = once.state()
    want_counts, want_total, want_count = each.state()
    assert counts == want_counts and count == want_count == n + 1
    assert total == pytest.approx(want_total, rel=1e-12)
    assert once.state(stage="a") == each.state(stage="a")
    assert [line for line in once.expose() if "_sum" not in line] == \
        [line for line in each.expose() if "_sum" not in line]


@pytest.mark.parametrize("n", [0, 1, 3, 8, 9, 20, 4096])
def test_window_observe_n_equals_n_calls(n):
    """Contents, length and total, with n under, at and over the
    capacity: the window keeps the newest `capacity` values."""
    once, each = SlidingWindow(8), SlidingWindow(8)
    for window in (once, each):
        for v in (0.1, 0.2, 0.3):
            window.observe(v)
    once.observe(0.5, n)
    for _ in range(n):
        each.observe(0.5)
    assert list(once._buf) == list(each._buf)
    assert len(once) == len(each) == min(3 + n, 8)
    assert once.total == each.total == 3 + n
    qs = (0.0, 0.5, 0.99)
    assert once.quantiles(qs) == each.quantiles(qs)


@pytest.mark.parametrize("n", [0, 1, 70, 5000])
def test_the_e2e_observation_counts_a_row_a_request(n):
    counts0, sum0, n0 = monitor.CHECK_E2E_SECONDS.state()
    total0, len0 = monitor.CHECK_WINDOW.total, len(monitor.CHECK_WINDOW)
    monitor.observe_check_e2e(0.0125, n)
    counts, total, count = monitor.CHECK_E2E_SECONDS.state()
    assert count - n0 == n
    assert total - sum0 == pytest.approx(0.0125 * n)
    moved = [b - a for a, b in zip(counts0 or [0] * len(counts), counts)] \
        if n else []
    assert sum(moved) == n and (not n or max(moved) == n)
    assert monitor.CHECK_WINDOW.total - total0 == n
    assert len(monitor.CHECK_WINDOW) == min(len0 + n, 4096)
