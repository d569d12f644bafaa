"""Plain reference for a baseline-JPEG stripe stream (ITU-T T.81).

Nothing here is the program's: the tables are Annex K.1's, the scaling is
the IJG's, the transform is the orthonormal 8x8 DCT-II in float64. What the
reference states is the codec's one promise about fidelity: a coefficient of
the decoded picture lies within half a quantiser step of the source's, for
the quantiser the configuration names."""

from __future__ import annotations

import numpy as np

BLOCK = 8
#: rounding to nearest: half a step, plus what the decoder's integer IDCT,
#: its rounding to 8 bits and the encoder's float32 transform add
INSIDE = 0.5

LUMA_K1 = np.array([
    16, 11, 10, 16, 24, 40, 51, 61, 12, 12, 14, 19, 26, 58, 60, 55,
    14, 13, 16, 24, 40, 57, 69, 56, 14, 17, 22, 29, 51, 87, 80, 62,
    18, 22, 37, 56, 68, 109, 103, 77, 24, 35, 55, 64, 81, 104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101, 72, 92, 95, 98, 112, 100, 103, 99,
], np.float64).reshape(8, 8)
CHROMA_K2 = np.array([
    17, 18, 24, 47, 99, 99, 99, 99, 18, 21, 26, 66, 99, 99, 99, 99,
    24, 26, 56, 99, 99, 99, 99, 99, 47, 66, 99, 99, 99, 99, 99, 99,
] + [99] * 32, np.float64).reshape(8, 8)


def _scaled(base: np.ndarray, quality: int) -> np.ndarray:
    q = max(1, min(100, int(quality)))
    scale = 5000 // q if q < 50 else 200 - 2 * q
    return np.clip(np.floor((base * scale + 50) / 100), 1, 255)


def basis() -> np.ndarray:
    n = np.arange(8)
    m = np.cos((2 * n[None, :] + 1) * n[:, None] * np.pi / 16) * 0.5
    m[0] /= np.sqrt(2.0)
    return m


def steps(quantiser: dict):
    """(luma, chroma) step per coefficient, in the units of the orthonormal
    transform (T.81's DCT is the orthonormal one: no scale between them)."""
    q = int(quantiser["jpeg_quality"])
    return _scaled(LUMA_K1, q), _scaled(CHROMA_K2, q)


def chroma_planes_of_client(cb_full: np.ndarray, cr_full: np.ndarray):
    """A JPEG client shows chroma upsampled; its 2x2 means are the coded
    samples smoothed by a [1 6 1]/8 kernel each way (libjpeg's triangle
    filter). The comparison applies the same smoothing to the source."""
    return _mean2(cb_full), _mean2(cr_full)


def chroma_planes_of_source(cb_full: np.ndarray, cr_full: np.ndarray):
    return _smooth(_mean2(cb_full)), _smooth(_mean2(cr_full))


def _mean2(p: np.ndarray) -> np.ndarray:
    h, w = p.shape[0] // 2 * 2, p.shape[1] // 2 * 2
    return p[:h, :w].reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))


def _smooth(p: np.ndarray) -> np.ndarray:
    """[1 6 1]/8 down the rows, then along them, edges repeated."""
    q = np.pad(p, 1, mode="edge")
    q = (q[:-2] + 6 * q[1:-1] + q[2:]) / 8.0
    return (q[:, :-2] + 6 * q[:, 1:-1] + q[:, 2:]) / 8.0
