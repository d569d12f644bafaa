"""The comparison that decides ``correct``: what a client decoded against
the desktop the source drew, in the configuration's own quantiser.

For each sampled frame the harness gives the decoded planes and the source
frame the frame claims to show (the content index logged when it was
captured). Compared, each with a limit of its own:

* ``undecodable``     stripes the client could not decode (limit 0);
* ``y_outside_pct``   share of luma transform coefficients further from the
                      source's than the reference allows;
* ``c_outside_pct``   the same over both chroma planes;
* ``bad_tiles``       16x16 luma tiles whose coefficients are off by more,
                      root mean square, than the whole distance the
                      reference allows one coefficient: a stale stripe, a
                      missing glyph, a frame that shows another instant
                      (limit 0; a sound tile reads about a third of it).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

TILE = 16


def ycbcr_of(rgb: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """JFIF / BT.601 full range, float64, no rounding."""
    x = rgb.astype(np.float64)
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb = -0.168736 * r - 0.331264 * g + 0.5 * b + 128.0
    cr = 0.5 * r - 0.418688 * g - 0.081312 * b + 128.0
    return y, cb, cr


def coef_error(src: np.ndarray, dec: np.ndarray, basis: np.ndarray,
               steps: np.ndarray) -> np.ndarray:
    """|T (src - dec) T'| / step, per block: [rows/b, cols/b, b, b]."""
    b = basis.shape[0]
    h, w = (min(src.shape[0], dec.shape[0]) // b * b,
            min(src.shape[1], dec.shape[1]) // b * b)
    d = (src[:h, :w].astype(np.float64) - dec[:h, :w].astype(np.float64))
    blocks = d.reshape(h // b, b, w // b, b).transpose(0, 2, 1, 3)
    e = np.einsum("ij,yxjk,lk->yxil", basis, blocks, basis, optimize=True)
    return np.abs(e) / steps


def outside(e: np.ndarray, inside: float) -> np.ndarray:
    return e > inside


def bad_tile_count(e: np.ndarray, inside: float) -> int:
    """Tiles of TILE x TILE samples whose coefficient errors ``e`` (in
    steps) have a root mean square over ``inside``."""
    nby, nbx, b, _ = e.shape
    per = TILE // b
    ty, tx = nby // per, nbx // per
    if ty == 0 or tx == 0:
        return 0
    t = (e[:ty * per, :tx * per] / inside) ** 2
    ms = t.reshape(ty, per, tx, per, b, b).mean(axis=(1, 3, 4, 5))
    return int((ms > 1.0).sum())


class Fidelity:
    """Accumulates the comparison over a run's sampled frames."""

    def __init__(self, reference, quantiser: dict) -> None:
        self.ref = reference
        self.basis = reference.basis()
        self.y_steps, self.c_steps = reference.steps(quantiser)
        self.inside = float(reference.INSIDE)
        self.n = {"y": 0, "c": 0}
        self.out = {"y": 0, "c": 0}
        self.bad_tiles = 0
        self.undecodable = 0
        self.frames = 0
        self.y_sq = 0.0

    def add(self, source_rgb: np.ndarray, y: np.ndarray, cb: np.ndarray,
            cr: np.ndarray) -> None:
        """``y`` at full size; ``cb``/``cr`` as the client holds them (the
        reference says how both sides are brought to the coded 4:2:0)."""
        sy, scb, scr = ycbcr_of(source_rgb)
        e = coef_error(sy, y, self.basis, self.y_steps)
        o = outside(e, self.inside)
        self.n["y"] += o.size
        self.out["y"] += int(o.sum())
        self.bad_tiles += bad_tile_count(e, self.inside)
        hh, ww = min(sy.shape[0], y.shape[0]), min(sy.shape[1], y.shape[1])
        self.y_sq += float(np.mean((sy[:hh, :ww] - y[:hh, :ww]) ** 2))
        ccb, ccr = self.ref.chroma_planes_of_client(cb, cr)
        rcb, rcr = self.ref.chroma_planes_of_source(scb, scr)
        for s, d in ((rcb, ccb), (rcr, ccr)):
            oc = outside(coef_error(s, d, self.basis, self.c_steps),
                         self.inside)
            self.n["c"] += oc.size
            self.out["c"] += int(oc.sum())
        self.frames += 1

    def numbers(self) -> Dict[str, float]:
        return {
            "undecodable": float(self.undecodable),
            "y_outside_pct": 100.0 * self.out["y"] / max(1, self.n["y"]),
            "c_outside_pct": 100.0 * self.out["c"] / max(1, self.n["c"]),
            "bad_tiles": float(self.bad_tiles),
        }

    def y_psnr_db(self) -> float:
        mse = self.y_sq / max(1, self.frames)
        return 99.0 if mse <= 0 else 10.0 * np.log10(255.0 ** 2 / mse)


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            compared_frames: int) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(correct, [(name, number, limit)]): every number at or under its
    limit, every limit present, and something was compared."""
    rows = [(k, float(v), float(limits[k])) for k, v in numbers.items()]
    ok = compared_frames > 0 and all(v <= lim for _k, v, lim in rows)
    return ok, rows
