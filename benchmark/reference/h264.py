"""Plain reference for an H.264 stripe stream (ITU-T H.264, 4x4 residual
transform, flat scaling lists).

Nothing here is the program's. Qstep doubles every 6 QP from the standard's
table; the transform is the standard's 4x4 core transform with its rows
normalised, which is the basis in which Qstep is defined. What is stated:
a coefficient of the decoded picture lies within one quantiser step of the
source's (an encoder may round towards zero by up to a whole step: the
dead zone; it may not be further off)."""

from __future__ import annotations

import numpy as np

BLOCK = 4
INSIDE = 1.0
_QSTEP6 = (0.625, 0.6875, 0.8125, 0.875, 1.0, 1.125)
#: luma QP -> chroma QP (table 8-15, chroma_qp_index_offset 0)
_QPC = {30: 29, 31: 30, 32: 31, 33: 32, 34: 32, 35: 33, 36: 34, 37: 34,
        38: 35, 39: 35, 40: 36, 41: 36, 42: 37, 43: 37, 44: 37, 45: 38,
        46: 38, 47: 38, 48: 39, 49: 39, 50: 39, 51: 39}


def qstep(qp: int) -> float:
    return _QSTEP6[qp % 6] * 2.0 ** (qp // 6)


def basis() -> np.ndarray:
    m = np.array([[1, 1, 1, 1], [2, 1, -1, -2], [1, -1, -1, 1],
                  [1, -2, 2, -1]], np.float64)
    return m / np.sqrt((m * m).sum(axis=1, keepdims=True))


def steps(quantiser: dict):
    qp = int(quantiser["qp"])
    return (np.full((4, 4), qstep(qp)),
            np.full((4, 4), qstep(_QPC.get(qp, qp))))


def chroma_planes_of_client(cb: np.ndarray, cr: np.ndarray):
    """An H.264 client hands over the coded 4:2:0 planes as they are."""
    return cb, cr


def chroma_planes_of_source(cb_full: np.ndarray, cr_full: np.ndarray):
    from .jpeg import _mean2

    return _mean2(cb_full), _mean2(cr_full)
