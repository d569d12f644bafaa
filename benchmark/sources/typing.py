"""``typing``: a person types into an editor window. Bursts of keystrokes,
pauses between them; a keystroke changes one glyph cell, nothing else moves.

A **keystroke** is one change: one 16x32 glyph of ``desktop.glyph_font()``
drawn at the caret, dark on light, and the caret moves one cell right. At a
line's end the caret wraps to the next line, whose old text is cleared as part
of that same change; after the last line, the first. The editor is a window of
``window`` (a share of the desktop) drawn at ``place`` over the rolled
wallpaper: ``--seed`` moves the wallpaper above and below the bands of
``band_rows`` rows the editor lies in, and never the text. Lines lie
``line_pitch`` rows apart, so they cross stripe boundaries as real ones do.

**The gaps are a published typist's, and fall where they fall.** The time
between two keystrokes of a burst is drawn from a log-normal distribution
with the mean and the standard deviation that the mix states in
milliseconds (``gap_ms``: the inter-key interval of Dhakal et al., CHI 2018),
rounded to whole ticks of ``tick_hz`` and never under one. Nothing holds a
gap under the encoders' paint-over trigger: a gap that outlasts it is
painted over in the middle of the burst, as a person's is.

**One order for every seed and every run.** How many keystrokes a burst has
(``burst_keys``), the gaps between them, the pause after it (``pause_ticks``)
and which glyphs are typed are drawn once from ``schedule_seed`` (a constant
of the mix, never ``--seed``), all on the tick grid. The schedule runs twice:

* from the instant every client holds its first frame (``joined()``: the
  first frame may take a compile's time), for ``pre_s`` seconds and no
  longer: the stream reaches its steady state by typing, on the editor's
  last line only, and has gone quiet (painted over) when the window opens,
  so that nothing of it falls into the window;
* again from its beginning once the harness calls ``anchor()`` as the window
  opens: the first burst begins ``lead_s`` after the anchor, ``phase_ticks`` of
  a capture tick before the capture loop's own calls, at the editor's first
  line. Every run's window then holds the same keystrokes, at the same places,
  the same ticks after it opened.

The content index is the number of keystrokes typed so far. A picture carries
no ruler: the harness reads which index a frame shows from the server's
flight recorder (no ``read_index`` here).
"""

from __future__ import annotations

import bisect
import math
from typing import List, Optional, Tuple

import numpy as np

from .desktop import (GLYPH_H, GLYPH_W, PICTURE_SEED, ClockedSource,
                      draw_picture, glyph_font, seed_roll)

SHADE, EDGE, INK, TITLE = 205, 60, 40, (70, 90, 150)
TITLE_ROWS, TEXT_TOP, TEXT_LEFT = 16, 18, 12


class Schedule:
    """Keystroke ``j`` (from 0) falls ``ticks[j]`` ticks after the schedule's
    beginning and types ``glyphs[j]``; ``first[b]`` is the first keystroke of
    burst ``b``. Grown on demand, always from the same draws."""

    def __init__(self, seed: int, burst_keys, gap_ms, pause_ticks,
                 tick_ms: float, n_glyphs: int) -> None:
        self._rng = np.random.default_rng([int(seed), 0x7197])
        self._keys, self._pause = burst_keys, pause_ticks
        # a log-normal with the stated mean and standard deviation
        mean, sd = float(gap_ms["mean"]), float(gap_ms["sd"])
        var = math.log1p((sd / mean) ** 2)
        self._gap_mu, self._gap_sigma = math.log(mean) - var / 2, var ** 0.5
        self._tick_ms = tick_ms
        self._n_glyphs = n_glyphs
        self.ticks: List[int] = []
        self.glyphs: List[int] = []
        self.first: List[int] = []
        self._next_burst = 0

    def _burst(self) -> None:
        rng = self._rng
        n = int(rng.integers(self._keys[0], self._keys[1] + 1))
        gaps = np.maximum(1, np.rint(rng.lognormal(
            self._gap_mu, self._gap_sigma, n - 1) / self._tick_ms))
        pause = int(rng.integers(self._pause[0], self._pause[1] + 1))
        glyphs = rng.integers(0, self._n_glyphs, n)
        self.first.append(len(self.ticks))
        at = self._next_burst
        for i in range(n):
            self.ticks.append(at)
            self.glyphs.append(int(glyphs[i]))
            if i < n - 1:
                at += int(gaps[i])
        self._next_burst = at + pause

    def upto_tick(self, tick: float) -> int:
        """How many keystrokes fall at or before ``tick``."""
        while self._next_burst <= tick:
            self._burst()
        return bisect.bisect_right(self.ticks, tick)

    def upto_count(self, n: int) -> None:
        while len(self.ticks) < n:
            self._burst()


class Source(ClockedSource):
    def __init__(self, width, height, fps, number, log, seed, params,
                 **kw) -> None:
        super().__init__(width, height, fps, number, log, **kw)
        p = params
        self.phase_ticks = p["phase_ticks"]
        self.tick = 1.0 / float(p["tick_hz"])
        self.lead_s = float(p["lead_s"])
        self.pre_s = float(p["pre_s"])
        self.font = glyph_font()
        self.schedule = Schedule(
            p["schedule_seed"], p["burst_keys"], p["gap_ms"],
            p["pause_ticks"], 1e3 * self.tick, len(self.font))
        # the editor: a fixed place, whatever the seed rolls under it
        fw, fh = p["window"]
        fx, fy = p["place"]
        self.win_w, self.win_h = max(48, int(fw * width)), \
            max(TEXT_TOP + GLYPH_H + 4, int(fh * height))
        self.x0 = min(int(fx * width), width - self.win_w)
        self.y0 = min(int(fy * height), height - self.win_h)
        self.pitch = int(p["line_pitch"])
        self.cols = (self.win_w - 2 * TEXT_LEFT) // GLYPH_W
        self.lines = (self.win_h - TEXT_TOP - 2 - GLYPH_H) // self.pitch + 1
        if self.x0 < 0 or self.y0 < 0 or self.cols < 1:
            raise ValueError("typing: the editor does not fit the desktop")
        x0, y0, w, h = self.x0, self.y0, self.win_w, self.win_h
        # the seed rolls the wallpaper above and below the editor; the bands
        # of ``band_rows`` rows the editor lies in (the stripes a keystroke or
        # a paint-over re-codes) show every seed the same picture, else the
        # bytes of a run follow its seed (PERF.md, PR 44: 13.5-17.7 kB a frame)
        picture = draw_picture(width, height, PICTURE_SEED)
        base = np.roll(picture, seed_roll(height, seed), axis=0)
        band = int(p["band_rows"])
        r0, r1 = y0 // band * band, min(height, -(-(y0 + h) // band) * band)
        base[r0:r1] = picture[r0:r1]
        base[y0:y0 + h, x0:x0 + w] = SHADE
        base[y0:y0 + h, x0:x0 + 2] = base[y0:y0 + h, x0 + w - 2:x0 + w] = EDGE
        base[y0:y0 + 2, x0:x0 + w] = base[y0 + h - 2:y0 + h, x0:x0 + w] = EDGE
        base[y0 + 2:y0 + TITLE_ROWS, x0 + 2:x0 + w - 2] = TITLE
        base.setflags(write=False)
        self._base = base
        #: when the schedule first began (None: nothing typed yet), how many
        #: keystrokes it typed before the anchor (on the last line), and
        #: when it began again; None until the harness joins and anchors
        self.began: Optional[float] = None
        self.typed_before: Optional[int] = None
        self.epoch: Optional[float] = None
        self._shown: Tuple[int, np.ndarray] = (0, base)

    # -- when ----------------------------------------------------------------
    def joined(self) -> None:
        self.began = self.clock()

    def _typed_by(self, seconds: float) -> int:
        """Keystrokes of the schedule that fall in its first ``seconds``."""
        return self.schedule.upto_tick(seconds / self.tick + 1e-6)

    def _before(self, at: float) -> int:
        """Keystrokes of the first run of the schedule by the instant
        ``at``: it begins at ``began`` and ends ``pre_s`` later."""
        if self.began is None or at < self.began:
            return 0
        return self._typed_by(min(at - self.began, self.pre_s))

    def index_at(self, t: float) -> int:
        if self.epoch is None:
            return self._before(self.origin + t)
        since = self.origin + t - self.epoch
        if since < 0:
            return self.typed_before
        return self.typed_before + self._typed_by(since)

    def anchor(self) -> None:
        """The window opens: the schedule begins again, ``lead_s`` from now,
        its ticks ``phase_ticks`` of a capture tick before the capture loop's
        own calls (where the mix pins the phase and the loop has run)."""
        now = self.clock()
        self.typed_before = self._before(now)
        epoch = now + self.lead_s
        want = self.wanted_phase()
        if want is not None:
            tick = 1.0 / self.fps
            epoch += (want - epoch % tick) % tick
        self.epoch = epoch

    def due_times(self, t_from: float, t_to: float) -> List[Tuple[int, float]]:
        """(index, due) of the keystrokes due in [t_from, t_to), absolute."""
        out = []
        before = self.typed_before
        n = self._before(t_to)
        for j in range(n if before is None else min(n, before)):
            due = self.began + self.schedule.ticks[j] * self.tick
            if t_from <= due < t_to:
                out.append((j + 1, due))
        if self.epoch is not None:
            for j in range(self._typed_by(t_to - self.epoch)):
                due = self.epoch + self.schedule.ticks[j] * self.tick
                if t_from <= due < t_to:
                    out.append((before + j + 1, due))
        return out

    def bursts_in(self, t_from: float, t_to: float) -> int:
        """How many bursts of the anchored schedule begin in [t_from, t_to)."""
        if self.epoch is None:
            return 0
        self._typed_by(t_to - self.epoch)
        return sum(1 for j in self.schedule.first if t_from
                   <= self.epoch + self.schedule.ticks[j] * self.tick < t_to)

    # -- what ----------------------------------------------------------------
    def keystroke(self, k: int) -> Tuple[int, int, int, bool]:
        """(line, column, glyph, the line is cleared first) of keystroke
        ``k`` (from 1). Before the anchor the caret stays on the last line;
        from the anchor on it starts at the first line's first column."""
        before = self.typed_before
        self.schedule.upto_count(k)
        last_line = before is None or k <= before
        j = k - 1 if last_line else k - before - 1
        line = self.lines - 1 if last_line else (j // self.cols) % self.lines
        return line, j % self.cols, self.schedule.glyphs[j], \
            j % self.cols == 0 and j > 0

    def cell(self, line: int, col: int) -> Tuple[int, int]:
        """(top row, left column) of a glyph cell on the desktop."""
        return self.y0 + TEXT_TOP + line * self.pitch, \
            self.x0 + TEXT_LEFT + col * GLYPH_W

    def _type(self, img: np.ndarray, k: int) -> None:
        line, col, glyph, clear = self.keystroke(k)
        ty, tx = self.cell(line, col)
        if clear:
            img[ty:ty + GLYPH_H, self.x0 + TEXT_LEFT:
                self.x0 + TEXT_LEFT + self.cols * GLYPH_W] = SHADE
        cell = img[ty:ty + GLYPH_H, tx:tx + GLYPH_W]
        cell[:] = SHADE
        cell[self.font[glyph]] = INK

    def frame(self, index: int) -> np.ndarray:
        """The desktop after ``index`` keystrokes. A picture that was handed
        out is never written again (the encoder may still be staging it):
        each keystroke is typed onto a copy of the one before."""
        at, img = self._shown
        if index == at:
            return img
        if index < at:
            at, img = 0, self._base
        img = img.copy()
        for k in range(at + 1, index + 1):
            self._type(img, k)
        img.setflags(write=False)
        if index > self._shown[0]:
            self._shown = (index, img)
        return img
