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


# -- typing: bursts of keystrokes, one order for every seed and every run ----

from benchmark.sources import typing as typing_mod  # noqa: E402
from benchmark.sources.desktop import GLYPH_H, GLYPH_W  # noqa: E402

TICK = 1.0 / 60.0


def make_typing(seed=3, t=100.0, w=1920, h=1080, fps=60.0, joined_after=0.4,
                anchored_after=4.6, calls=True):
    """A typing source as a run drives it: the capture loop starts, the
    client holds its first frame ``joined_after`` later, the loop calls at
    ``fps`` (2 ms after its ticks), the window opens ``anchored_after`` the
    join."""
    clock = Clock(t)
    src = typing_mod.Source(w, h, fps, 0, CallLog(), seed,
                            mix("typing")["params"], clock=clock)
    clock.t = t + joined_after
    src.joined()
    if calls:
        n0 = int((clock.t - t) * fps) + 1
        for n in range(n0, n0 + int(anchored_after * fps)):
            clock.t = t + n / fps + 0.002
            src.next_frame()
    clock.t = t + joined_after + anchored_after
    src.anchor()
    return src, clock


def test_typing_is_a_mix_of_its_own_generator_and_says_where_its_numbers_are_from():
    m = mix("typing")
    assert m["generator"] == "typing" and "client" not in m
    p = m["params"]
    # the gaps are the study's; what has no public source says so itself
    assert p["gap_ms"] == {"mean": 238.656, "sd": 111.6}
    assert "10.1145/3173574.3174220" in m["source"]["gap_ms"]
    assert "10.1145/3173574.3174220" in m["what"]
    assert (p["burst_keys"], p["pause_ticks"]) == ([5, 20], [24, 96])
    assert set(m["assumed"]) == {"gap_ms", "burst_keys", "pause_ticks"}
    assert set(m["source"]) | set(m["assumed"]) >= {
        k for k in p if k.split("_")[0] in ("gap", "burst", "pause")}
    # the mix states every parameter: the source has no default to fall to
    for k in p:
        with pytest.raises(KeyError):
            typing_mod.Source(256, 144, 60.0, 0, CallLog(), 1,
                              {n: v for n, v in p.items() if n != k})
    assert (p["tick_hz"], p["phase_ticks"], p["lead_s"]) == (60.0, 0.5, 0.5)
    assert m["check_frames"] == 48 and m["drain_s"] == 3.0
    # the window opens on a stream that typed for at least 3 s and is quiet
    assert p["pre_s"] >= 3.0
    assert m["steady"]["seconds"] >= p["pre_s"] + 0.5
    src, _ = make_typing()
    assert (src.win_w, src.win_h, src.cols, src.lines, src.pitch) == (
        652, 453, 39, 11, 40)
    assert not hasattr(src, "read_index")      # content from the recorder


def test_typing_gaps_are_the_studys_and_fall_on_both_sides_of_the_paint_over_trigger():
    src, _ = make_typing(calls=False)
    s = src.schedule
    s.upto_count(20000)
    ticks, first = np.array(s.ticks), set(s.first)
    gaps = np.diff(ticks)
    inside = np.array([g for j, g in enumerate(gaps) if j + 1 not in first])
    pauses = np.array([g for j, g in enumerate(gaps) if j + 1 in first])
    # on the tick grid the gaps keep the study's mean and spread
    ms = inside * 1e3 * TICK
    want = mix("typing")["params"]["gap_ms"]
    assert ms.mean() == pytest.approx(want["mean"], rel=0.01)
    assert ms.std() == pytest.approx(want["sd"], rel=0.04)
    assert np.median(ms) < ms.mean()                     # right-skewed
    # nothing holds a gap under the paint-over trigger (15 static frames):
    # a third of them outlast it, and a few outlast it twice
    assert inside.min() >= 1 and inside.max() > 30
    assert 0.30 < (inside > 15).mean() < 0.40
    assert 0.01 < (inside > 30).mean() < 0.05
    # in the window itself too: the first 30 s are no tamer than the rest
    in_window = inside[:90]
    assert 0.25 < (in_window > 15).mean() < 0.45
    assert 24 <= pauses.min() and pauses.max() <= 96
    sizes = np.diff(s.first)
    assert 5 <= sizes.min() and sizes.max() <= 20
    assert 0 <= min(s.glyphs) and max(s.glyphs) < len(src.font)
    assert all(src.font[g].any() for g in set(s.glyphs))   # every key inks


def test_the_typing_schedule_is_the_same_for_two_seeds_and_two_origins():
    a, ca = make_typing(seed=3, t=100.0, joined_after=0.4, anchored_after=4.6)
    b, cb = make_typing(seed=2**31 + 77, t=7031.37, joined_after=1.9,
                        anchored_after=5.3)
    da, db = a.due_times(ca.t, ca.t + 30.0), b.due_times(cb.t, cb.t + 30.0)
    # the same keystrokes, the same ticks after the window opened (to the
    # phase: a tick's worth at most), the count pinned
    assert len(da) == len(db) == 107
    assert [k for k, _ in da] == [k for k, _ in db]
    assert a.typed_before == b.typed_before == da[0][0] - 1
    off = np.array([t - ca.t for _k, t in da]) - \
        np.array([t - cb.t for _k, t in db])
    assert np.ptp(off) < 1e-6 and abs(off[0]) < TICK
    # the first burst begins lead_s into the window, on the phase the loop's
    # calls pin: half a tick before them
    assert 0.5 <= da[0][1] - ca.t < 0.5 + TICK
    assert ((100.002 - da[0][1]) % TICK) / TICK == pytest.approx(0.5, abs=0.02)
    assert a.bursts_in(ca.t, ca.t + 30.0) == b.bursts_in(cb.t, cb.t + 30.0) \
        == 10
    # the same glyphs at the same places; only the wallpaper differs, and
    # only above and below the bands of 64 rows the editor lies in: a stripe
    # that a keystroke or a paint-over re-codes costs every seed the same
    k = da[-1][0]
    fa, fb = a.frame(k), b.frame(k)
    ed = (slice(a.y0, a.y0 + a.win_h), slice(a.x0, a.x0 + a.win_w))
    assert (fa[ed] == fb[ed]).all() and (fa != fb).any()
    r0, r1 = a.y0 // 64 * 64, -(-(a.y0 + a.win_h) // 64) * 64
    assert (r0, r1) == (320, 832)
    assert (fa[r0:r1] == fb[r0:r1]).all()
    assert (fa[:r0] != fb[:r0]).any() and (fa[r1:] != fb[r1:]).any()
    # the traced seconds hold two bursts' beginnings
    tr = mix("typing")["trace"]
    assert a.bursts_in(ca.t + tr["start_s"],
                       ca.t + tr["start_s"] + tr["seconds"]) >= 2


def test_typing_before_the_window_runs_for_pre_s_and_falls_silent():
    src, clock = make_typing(calls=False)
    began = src.began
    assert began == pytest.approx(100.4)
    pre = src.due_times(100.0, clock.t)
    assert len(pre) == src.typed_before == 14
    assert all(began <= t <= began + 3.0 for _k, t in pre)
    # nothing between the end of pre_s and the first burst of the window
    assert src.due_times(began + 3.0 + 1e-6, clock.t + 0.5 - 1e-6) == []
    assert src.index_at(clock.t + 0.49 - src.origin) == 14
    # before the client has joined nothing is typed at all
    early = typing_mod.Source(256, 144, 60.0, 0, CallLog(), 1,
                              mix("typing")["params"], clock=Clock(5.0))
    assert early.index_at(100.0) == 0 and early.due_times(0.0, 1e3) == []


def test_typing_due_times_and_index_at_agree():
    src, clock = make_typing()
    due = src.due_times(clock.t, clock.t + 30.0)
    for k, t in due[:40] + due[-5:]:
        assert src.index_at(t - 1e-4 - src.origin) == k - 1
        assert src.index_at(t + 1e-4 - src.origin) == k
    clock.t = due[9][1] + 0.001
    src.next_frame()
    assert src.log.entries[-1] == (0, due[9][0])
    # content follows the clock, not the call count
    clock.t = due[30][1] + 0.001
    assert (src.next_frame() == src.frame(due[30][0])).all()


def stripes_of(mask, stripe_h=64):
    return sorted(set((np.nonzero(mask.any(axis=(1, 2)))[0] // stripe_h)
                      .tolist()))


def test_a_keystroke_changes_its_glyph_cell_and_no_stripe_but_those_it_crosses():
    src, clock = make_typing()
    first = src.typed_before + 1
    crossed = set()
    for k in range(first, first + 3 * src.cols + 2):
        line, col, glyph, clear = src.keystroke(k)
        before, after = src.frame(k - 1), src.frame(k)
        assert not after.flags.writeable
        diff = before != after
        ty, tx = src.cell(line, col)
        box = np.zeros(diff.shape[:2], bool)
        box[ty:ty + GLYPH_H, tx:tx + GLYPH_W] = True
        if clear:
            # the caret wrapped: the line's old text goes in the same change
            box[ty:ty + GLYPH_H, src.x0 + 12:src.x0 + 12 + src.cols * GLYPH_W] \
                = True
        assert diff.any() and not diff[~box].any(), k
        assert (after[ty:ty + GLYPH_H, tx:tx + GLYPH_W][src.font[glyph]]
                == typing_mod.INK).all()
        want = list(range(ty // 64, (ty + GLYPH_H - 1) // 64 + 1))
        assert stripes_of(diff) and set(stripes_of(diff)) <= set(want)
        crossed.add(len(want))
    assert crossed == {1, 2}       # lines that cross stripe boundaries too
    # the window starts at the first line's first column; at a line's end the
    # caret wraps to the next line
    assert src.keystroke(first)[:2] == (0, 0)
    assert src.keystroke(first + src.cols)[:2] == (1, 0)
    assert src.keystroke(first + src.cols * src.lines)[:2] == (0, 0)
    assert src.keystroke(first + src.cols * src.lines)[3] is True
    # before the window the caret stays on the last line
    assert {src.keystroke(k)[0] for k in range(1, first)} == {src.lines - 1}


def test_a_wrapped_line_loses_its_old_text_in_the_same_change():
    src, _ = make_typing(w=256, h=144)        # 3 columns, one line
    assert (src.cols, src.lines) == (3, 1)
    first = src.typed_before + 1
    full, wrapped = src.frame(first + 2), src.frame(first + 3)
    ty, tx = src.cell(0, 0)
    rest = wrapped[ty:ty + GLYPH_H, tx + GLYPH_W:tx + 3 * GLYPH_W]
    assert (rest == typing_mod.SHADE).all()
    assert (full[ty:ty + GLYPH_H, tx + GLYPH_W:tx + 3 * GLYPH_W]
            != typing_mod.SHADE).any()
    # a frame is a function of its index: asked again, out of order, the same
    assert (src.frame(first + 2) == full).all()
    assert src.frame(first + 3) is wrapped


def test_a_typing_picture_handed_out_is_never_written_again():
    src, clock = make_typing(w=256, h=144)
    due = src.due_times(clock.t, clock.t + 10.0)
    clock.t = due[0][1] + 0.001
    held = src.next_frame()
    kept = held.copy()
    clock.t = due[5][1] + 0.001
    assert (src.next_frame() != kept).any()
    assert (held == kept).all()
