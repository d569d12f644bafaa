"""The benchmark's end-to-end arithmetic on hand-made logs."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import metrics  # noqa: E402

# changes 1..6 due every 100 ms from t=10.0
CHANGES = [(k, 10.0 + 0.1 * k) for k in range(1, 7)]


def test_each_change_waits_for_the_first_frame_at_or_past_it():
    frames = [(1, 10.15), (2, 10.25), (3, 10.35), (4, 10.45), (5, 10.55),
              (6, 10.65)]
    lat, never = metrics.latencies_ms(CHANGES, frames, 30.0)
    assert never == 0
    assert lat == pytest.approx([50.0] * 6)


def test_a_coalesced_change_waits_for_the_later_frame():
    # changes 2 and 3 never get a frame of their own: frame 4 shows them
    frames = [(1, 10.15), (4, 10.45), (5, 10.55), (6, 10.65)]
    lat, never = metrics.latencies_ms(CHANGES, frames, 30.0)
    assert never == 0
    assert lat == pytest.approx([50.0, 250.0, 150.0, 50.0, 50.0, 50.0])


def test_a_stall_moves_the_median_and_the_tail():
    # the stream stalls after change 1 and resumes with change 6 at 11.0
    frames = [(1, 10.15), (6, 11.0)]
    lat, never = metrics.latencies_ms(CHANGES, frames, 30.0)
    assert never == 0
    assert lat == pytest.approx([50.0, 800.0, 700.0, 600.0, 500.0, 400.0])
    assert metrics.percentile(lat, 50) == pytest.approx(500.0)
    assert metrics.percentile(lat, 95) == pytest.approx(800.0)
    # a median over delivered frames (what PR 23 read) would have said 50 ms


def test_a_change_never_shown_counts_at_the_windows_length():
    frames = [(1, 10.15), (4, 10.45)]
    lat, never = metrics.latencies_ms(CHANGES, frames, 30.0)
    assert never == 2
    assert lat[-2:] == [30000.0, 30000.0]
    assert lat[:4] == pytest.approx([50.0, 250.0, 150.0, 50.0])


def test_a_frame_that_arrives_out_of_order_does_not_hide_an_earlier_one():
    # frame 5 completes before frame 3 (two sessions never mix, but a
    # reordered log must not make change 3 look shown at 10.30)
    frames = [(5, 10.30), (3, 10.40)]
    shown = metrics.shown_times(CHANGES, frames)
    assert shown[:5] == [10.30] * 5 and shown[5] is None


def test_no_frames_at_all():
    lat, never = metrics.latencies_ms(CHANGES, [], 2.0)
    assert never == 6 and lat == [2000.0] * 6


@pytest.mark.parametrize("q,want", [(50, 3.0), (95, 5.0), (100, 5.0),
                                    (1, 1.0), (20, 1.0), (21, 2.0)])
def test_percentile_is_nearest_rank(q, want):
    assert metrics.percentile([5.0, 1.0, 4.0, 2.0, 3.0], q) == want


def test_delivered_counts_the_whole_window_and_nothing_else():
    frames = [(9.99, 100), (10.0, 200), (10.5, 300), (11.0, 400)]
    assert metrics.delivered(frames, 10.0, 11.0) == (2, 500)


def test_spread_is_quartile_distance_over_median():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    import statistics
    q1, _q2, q3 = statistics.quantiles(vals, n=4)
    assert metrics.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
