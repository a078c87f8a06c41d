import pytest

import observe


def test_union_counts_overlaps_once():
    assert observe.merge_intervals([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]


def test_busy_and_gaps_with_a_gap_at_each_end():
    # window 0..100; busy 10-30 (two overlapping), 50-60, 60-70 (touching)
    busy, gaps = observe.busy_and_gaps(
        [(10, 25), (20, 30), (50, 60), (60, 70)], 0, 100)
    assert busy == 40
    assert sorted(gaps) == [10, 20, 30]      # 0-10, 30-50, 70-100
    assert busy + sum(gaps) == 100


def test_intervals_are_clipped_to_the_window():
    busy, gaps = observe.busy_and_gaps([(-5, 5), (95, 120), (200, 300)],
                                       0, 100)
    assert (busy, gaps) == (10, [90])


def test_empty_window_is_all_idle():
    assert observe.busy_and_gaps([], 0, 7) == (0, [7])


def test_idle_share():
    assert observe.idle_share_pct(0.4, 3.2) == pytest.approx(87.5)
