"""The synthetic desktop every traffic mix draws on, and the clock that
drives it.

Copied in spirit from ``selkies_tpu/capture/synthetic.py`` (wallpaper plus
window rectangles) and changed in two ways the benchmark needs:

* the picture is the same for every ``--seed``, rolled to another place, so
  that a seed moves neither the bytes per frame nor the coding error;
* content is a function of the wall clock, not of the call count: the
  desktop a capture sees is the desktop as it stands *now*.

All colours stay inside [16, 235], so that a decoder's clipping to the RGB
gamut never enters the comparison with the source.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

GLYPH_W, GLYPH_H = 16, 32
#: window sizes (w, h) as fractions of the desktop, the same for every seed
WINDOW_SHAPES = ((0.34, 0.42), (0.28, 0.30), (0.22, 0.36), (0.30, 0.24),
                 (0.18, 0.20), (0.26, 0.18))


def glyph_font(n: int = 64) -> np.ndarray:
    """``n`` glyph bitmaps [n, 32, 16] of bool: strokes on a 4x8 grid of
    4x4 cells, about a third inked. One fixed font for every seed."""
    rng = np.random.default_rng(0x5E1C1E5)
    cells = rng.random((n, GLYPH_H // 4, GLYPH_W // 4)) < 0.38
    cells[:, 0, :] = False          # leading between lines
    cells[:, :, 0] = False          # gap between glyphs
    return np.repeat(np.repeat(cells, 4, axis=1), 4, axis=2)


#: the one picture every seed shows, shifted
PICTURE_SEED = 1


def draw_desktop(width: int, height: int, seed: int) -> np.ndarray:
    """[H, W, 3] uint8: the desktop for ``seed``: one picture for every
    seed, rolled down by whole stripes that the seed picks. Two seeds then
    cost the same bytes and the same coding error. (A fresh picture per seed
    moved ``wire_kB_per_frame`` by 9% between seeds and by 0.5% between two
    runs of one seed; a roll sideways as well still moved it by 7%, because
    the scroll ruler then covers other columns of the picture: my chip
    runs, PR 24.)"""
    return np.roll(draw_picture(width, height, PICTURE_SEED),
                   seed_roll(height, seed), axis=0)


def seed_roll(height: int, seed: int) -> int:
    """How many rows (whole stripes of 64) ``seed`` rolls the picture down."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    return 64 * int(rng.integers(0, max(1, height // 64)))


def draw_picture(width: int, height: int, seed: int) -> np.ndarray:
    """[H, W, 3] uint8: textured wallpaper, six windows with a title bar
    and lines of text."""
    rng = np.random.default_rng([int(seed), 0xDE5C])
    yy, xx = np.mgrid[0:height, 0:width].astype(np.float32)
    ph = rng.uniform(0, 2 * np.pi, 4)
    img = np.stack([
        120 + 50 * np.sin(xx / 181.0 + ph[0]) * np.cos(yy / 127.0 + ph[1]),
        115 + 50 * np.cos(xx / 149.0 + ph[2]),
        135 + 45 * np.sin(yy / 167.0 + ph[3]),
    ], axis=-1)
    # fine texture: what makes a codec's quantiser show
    img += rng.integers(-10, 11, (height, width, 1)).astype(np.float32)
    font = glyph_font()
    order = rng.permutation(len(WINDOW_SHAPES))
    for k in order:
        fw, fh = WINDOW_SHAPES[k]
        w, h = max(48, int(fw * width)), max(40, int(fh * height))
        x0 = int(rng.integers(0, max(1, width - w)))
        y0 = int(rng.integers(0, max(1, height - h)))
        shade = 205 + 5 * int(k)
        img[y0:y0 + h, x0:x0 + w] = shade
        img[y0:y0 + h, x0:x0 + 2] = img[y0:y0 + h, x0 + w - 2:x0 + w] = 60
        img[y0:y0 + 2, x0:x0 + w] = img[y0 + h - 2:y0 + h, x0:x0 + w] = 60
        img[y0 + 2:y0 + min(h, 22), x0 + 2:x0 + w - 2] = (70, 90, 150)
        # text: every second line of the window, left-aligned, ragged right
        cols = (w - 24) // GLYPH_W
        for row, ty in enumerate(range(y0 + 28, y0 + h - GLYPH_H - 4,
                                       GLYPH_H + 8)):
            n = int(cols * (0.55 + 0.4 * ((row * 7 + k * 3) % 5) / 4.0))
            ids = rng.integers(0, len(font), n)
            for c, g in enumerate(ids):
                tx = x0 + 12 + c * GLYPH_W
                cell = img[ty:ty + GLYPH_H, tx:tx + GLYPH_W]
                cell[font[g][:cell.shape[0], :cell.shape[1]]] = 40
    return np.clip(img, 16, 235).astype(np.uint8)


RULER_CELL = 16


def ruler_bits(height: int, px: int) -> int:
    return max(1, int(np.ceil(np.log2(max(2, height // px)))))


def draw_ruler(img: np.ndarray, px: int) -> None:
    """A ruler down the left edge: rows ``[g*px, (g+1)*px)`` carry ``g`` in
    binary, one 16-px cell a bit. A desktop that scrolls by ``px`` rows a
    step then says in its own top rows which step it shows, whatever the
    server's tracing believes."""
    h = img.shape[0]
    bits = ruler_bits(h, px)
    for g in range(h // px):
        for b in range(bits):
            on = (g >> b) & 1
            img[g * px:(g + 1) * px,
                b * RULER_CELL:(b + 1) * RULER_CELL] = 225 if on else 30


def read_ruler(y_top: np.ndarray, height: int, px: int):
    """The group number in the first ``px`` rows of a decoded luma plane,
    or None where the two groups below it do not count on from it."""
    bits = ruler_bits(height, px)
    groups = height // px

    # a group's outer rows are left out where it has inner ones (a codec
    # blurs across the edge); a group of two rows is read whole
    trim = 1 if px >= 3 else 0

    def group(k: int) -> int:
        rows = y_top[k * px + trim:(k + 1) * px - trim]
        g = 0
        for b in range(bits):
            cell = rows[:, b * RULER_CELL + 3:(b + 1) * RULER_CELL - 3]
            g |= int(cell.mean() > 128) << b
        return g
    g0 = group(0)
    if group(1) != (g0 + 1) % groups or group(2) != (g0 + 2) % groups:
        return None
    return g0


class CallLog:
    """Every ``next_frame()`` call of every source of a run: when it ran and
    which content index it returned. One list for all displays, because the
    capture loops share one thread and the recorder's ``capture`` marks
    bracket exactly one call each."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.times: List[float] = []
        self.entries: List[Tuple[int, int]] = []     # (source number, index)

    def add(self, t: float, source: int, index: int) -> None:
        with self._lock:
            self.times.append(t)
            self.entries.append((source, index))

    def find(self, t0: float, t1: float) -> Optional[Tuple[int, int]]:
        """The call that ran inside [t0, t1], if exactly there."""
        i = bisect.bisect_left(self.times, t0)
        if i < len(self.times) and self.times[i] <= t1:
            return self.entries[i]
        return None


class ClockedSource:
    """Base of the traffic generators: a frame source the server's capture
    loop calls, whose content is a function of the wall clock.

    Subclasses give ``index_at(t)`` (content index at ``t`` seconds after
    the origin), ``due_times(t_from, t_to)`` (when each change fell due) and
    ``frame(index)`` (the desktop at that index: also what the comparison
    after the window asks for)."""

    #: where in a capture tick content steps fall, if the mix pins it
    phase_ticks: Optional[float] = None

    def __init__(self, width: int, height: int, fps: float, number: int,
                 log: CallLog, clock=time.monotonic) -> None:
        self.width, self.height, self.fps = width, height, float(fps)
        self.number, self.log, self.clock = number, log, clock
        self.origin = clock()
        self.stopped = False
        self._calls: List[float] = []

    # -- the server's side -------------------------------------------------
    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass

    def next_frame(self):
        if self.stopped:
            return None
        now = self.clock()
        index = self.index_at(now - self.origin)
        self._calls.append(now)
        self.log.add(now, self.number, index)
        return self.frame(index)

    # -- the harness's side ------------------------------------------------
    def joined(self) -> None:
        """Every client of the run holds its first frame (a first frame may
        take a compile's time to come): traffic that is meant to run for a
        stated time before the window begins here."""

    def anchor(self) -> None:
        """Pin where content steps fall inside the capture loop's tick, from
        the phase of the loop's own recent calls: a run's latency then does
        not carry a random share of one tick that another run lacks."""
        want = self.wanted_phase()
        if want is not None:
            tick = 1.0 / self.fps
            self.origin += (want - self.origin % tick) % tick

    def wanted_phase(self) -> Optional[float]:
        """Where in a capture tick (seconds into it) content steps have to
        fall to lie ``phase_ticks`` of a tick before the capture loop's own
        recent calls; None where the mix pins no phase or the loop has not
        run yet."""
        if self.phase_ticks is None or len(self._calls) < 20:
            return None
        tick = 1.0 / self.fps
        ph = np.array(self._calls[-60:]) % tick
        # circular mean of the call phase
        ang = np.angle(np.mean(np.exp(2j * np.pi * ph / tick)))
        call_phase = (ang / (2 * np.pi)) % 1.0 * tick
        return (call_phase - self.phase_ticks * tick) % tick

    def tick_lateness_ms(self, t_from: float, t_to: float) -> List[float]:
        """How late each call in [t_from, t_to) ran against a tick grid laid
        through the calls themselves (least squares on the call number)."""
        c = np.array([t for t in self._calls if t_from <= t < t_to])
        if len(c) < 3:
            return []
        n = np.round((c - c[0]) * self.fps)
        grid = c[0] + n / self.fps
        late = (c - grid)
        return list((late - late.min()) * 1000.0)

    def index_at(self, t: float) -> int:
        raise NotImplementedError

    def due_times(self, t_from: float, t_to: float) -> List[Tuple[int, float]]:
        raise NotImplementedError

    def frame(self, index: int) -> np.ndarray:
        raise NotImplementedError
