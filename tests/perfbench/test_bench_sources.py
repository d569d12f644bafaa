"""The traffic generator: clock-driven content and the scroll ruler."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import check  # noqa: E402
from benchmark.sources import scroll  # noqa: E402
from benchmark.sources.desktop import CallLog, draw_desktop  # noqa: E402


class Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def make(mod, seed=3, params=None, w=256, h=144):
    clock = Clock()
    src = mod.Source(w, h, 60.0, 0, CallLog(), seed, params or {},
                     clock=clock)
    return src, clock


def test_desktop_stays_inside_the_gamut_and_depends_on_the_seed():
    a = draw_desktop(256, 288, 1)
    assert a.min() >= 16 and a.max() <= 235
    # one picture for every seed, rolled down by whole stripes
    others = [draw_desktop(256, 288, s) for s in range(2, 10)]
    assert any((a != b).any() for b in others)
    assert all(any((np.roll(a, 32 * r, axis=0) == b).all() for r in range(9))
               for b in others)
    assert (a == draw_desktop(256, 288, 1)).all()


def test_a_large_seed_is_taken():
    assert draw_desktop(64, 48, 2**31 + 12345).shape == (48, 64, 3)


def test_scroll_content_follows_the_clock_not_the_call_count():
    src, clock = make(scroll)
    first = src.next_frame().copy()
    clock.t += 0.5                       # the server missed 29 captures
    later = src.next_frame()
    assert src.log.entries == [(0, 0), (0, 30)]
    assert (later == np.roll(first, -4 * 30, axis=0)).all()


def test_scroll_changes_fall_due_on_the_content_clock():
    src, clock = make(scroll)
    due = src.due_times(clock.t + 1.0, clock.t + 1.1)
    assert [k for k, _ in due] == [60, 61, 62, 63, 64, 65]
    assert due[0][1] == pytest.approx(clock.t + 1.0)


@pytest.mark.parametrize("index", [0, 1, 35, 36, 37, 1000])
def test_the_ruler_says_which_step_a_picture_shows(index):
    src, clock = make(scroll)
    y = check.ycbcr_of(src.frame(index))[0]
    assert src.read_index(y[:16], 0.0, hint=index + 3) == index
    assert src.read_index(y[:16], 0.0, hint=index - 5 if index > 5
                          else index) == index
    # without a hint: the newest such step not after the arrival time
    assert src.read_index(y[:16], clock.t + (index + 2) / 60.0) == index


def test_a_picture_without_a_ruler_reads_nothing():
    src, _ = make(scroll)
    assert src.read_index(np.full((16, 256), 128.0), 0.0) is None


def test_a_stopped_source_hands_out_nothing():
    src, _ = make(scroll)
    src.stopped = True
    assert src.next_frame() is None and src.log.entries == []


def test_anchor_pins_the_content_phase_to_the_capture_ticks():
    src, clock = make(scroll, params={"phase_ticks": 0.5})
    tick = 1.0 / 60.0
    for n in range(40):                  # calls 2 ms after each tick
        clock.t = 200.0 + n * tick + 0.002
        src.next_frame()
    src.anchor()
    # content steps now fall half a tick before each call's tick phase
    phase = ((200.002 - src.origin) % tick) / tick
    assert phase == pytest.approx(0.5, abs=0.02)


# -- scroll120: the same generator, another data file ------------------------

def mix(name):
    import json

    with open(os.path.join(os.path.dirname(scroll.__file__), "..", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def make120(seed=3, w=256, h=144):
    clock = Clock()
    src = scroll.Source(w, h, 120.0, 0, CallLog(), seed,
                        mix("scroll120")["params"], clock=clock)
    return src, clock


def test_scroll120_is_scroll_at_twice_the_steps_and_half_the_step():
    a, b = mix("scroll"), mix("scroll120")
    assert b["generator"] == a["generator"] == "scroll"
    assert b["params"] == {"px_per_step": 2, "steps_per_s": 120.0,
                           "phase_ticks": 0.5}
    # the same 240 px a second on the screen
    assert b["params"]["px_per_step"] * b["params"]["steps_per_s"] == \
        a["params"]["px_per_step"] * a["params"]["steps_per_s"]
    for key in ("steady", "drain_s", "check_frames", "trace"):
        assert b[key] == a[key], key


def test_scroll120_steps_120_times_a_second_by_the_clock():
    src, clock = make120()
    first = src.next_frame().copy()
    clock.t += 0.5                       # half a second, however many calls
    later = src.next_frame()
    assert src.log.entries == [(0, 0), (0, 60)]
    assert (later == np.roll(first, -2 * 60, axis=0)).all()
    # one step moves the picture 2 px, and the step before it differs
    assert (src.frame(61) == np.roll(src.frame(60), -2, axis=0)).all()
    assert (src.frame(61) != src.frame(60)).any()


def test_scroll120_changes_fall_due_every_120th_of_a_second():
    src, clock = make120()
    due = src.due_times(clock.t + 1.0, clock.t + 1.05)
    assert [k for k, _ in due] == [120, 121, 122, 123, 124, 125]
    assert due[0][1] == pytest.approx(clock.t + 1.0)
    assert due[1][1] - due[0][1] == pytest.approx(1 / 120.0)
    assert len(src.due_times(clock.t, clock.t + 30.0)) == 3599   # not k = 0


@pytest.mark.parametrize("index", [0, 1, 35, 36, 37, 71, 72, 1000])
def test_a_ruler_of_two_row_groups_says_which_step_a_picture_shows(index):
    src, clock = make120()
    y = check.ycbcr_of(src.frame(index))[0]
    assert src.read_index(y[:16], 0.0, hint=index + 3) == index
    assert src.read_index(y[:16], 0.0, hint=max(0, index - 5)) == index
    assert src.read_index(y[:16], clock.t + (index + 2) / 120.0) == index
    # the step before it is another picture to the ruler
    assert src.read_index(check.ycbcr_of(src.frame(index + 1))[0][:16], 0.0,
                          hint=index) == index + 1


def test_scroll120_pins_the_phase_at_half_of_an_8_ms_tick():
    src, clock = make120()
    tick = 1.0 / 120.0
    for n in range(40):                  # calls 2 ms after each tick
        clock.t = 200.0 + n * tick + 0.002
        src.next_frame()
    src.anchor()
    phase = ((200.002 - src.origin) % tick) / tick
    assert phase == pytest.approx(0.5, abs=0.02)
    # and the lateness of calls is told against 120 Hz ticks
    late = src.tick_lateness_ms(200.0, 200.0 + 40 * tick)
    assert len(late) == 40 and max(late) < 0.01
