"""``scroll``: the whole desktop scrolls by ``px_per_step`` rows, ``steps_per_s``
times a second. Every stripe is damaged in every frame."""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

from .desktop import ClockedSource, draw_desktop, draw_ruler, read_ruler


class Source(ClockedSource):
    def __init__(self, width, height, fps, number, log, seed, params,
                 **kw) -> None:
        super().__init__(width, height, fps, number, log, **kw)
        self.px = int(params.get("px_per_step", 4))
        self.rate = float(params.get("steps_per_s", 60.0))
        self.phase_ticks = params.get("phase_ticks")
        if height % self.px:
            raise ValueError("scroll: the height must be a multiple of the step")
        base = draw_desktop(width, height, seed)
        draw_ruler(base, self.px)
        # pre-drawn: a frame is a view into the desktop laid out twice,
        # so that the generator takes none of the server's host time
        self._twice = np.concatenate([base, base], axis=0)
        self._twice.setflags(write=False)

    def index_at(self, t: float) -> int:
        return max(0, int(math.floor(t * self.rate)))

    def due_times(self, t_from: float, t_to: float) -> List[Tuple[int, float]]:
        """(index, due) of the changes due in [t_from, t_to), absolute."""
        k0 = max(1, int(math.ceil((t_from - self.origin) * self.rate)))
        out = []
        k = k0
        while True:
            due = self.origin + k / self.rate
            if due >= t_to:
                return out
            if due >= t_from:
                out.append((k, due))
            k += 1

    def frame(self, index: int) -> np.ndarray:
        off = (self.px * index) % self.height
        return self._twice[off:off + self.height]

    # -- the picture says which step it shows --------------------------------
    picture_rows = 16          # rows of the top stripe that read_index needs

    def read_index(self, y_top: np.ndarray, t: float,
                   hint: Optional[int] = None) -> Optional[int]:
        """The content index a decoded picture shows, from the ruler in its
        top rows. Indices one scroll period apart look alike: ``hint`` (what
        the server's tracing says, good to a few steps) picks the nearest
        of them; without it, the newest one not after ``t``, when the
        picture arrived."""
        g = read_ruler(y_top, self.height, self.px)
        if g is None:
            return None
        period = self.height // self.px
        if hint is not None:
            return hint + (g - hint + period // 2) % period - period // 2
        newest = self.index_at(t - self.origin)
        return newest - ((newest - g) % period)
