"""JPEG stripe decoder of the benchmark's client: PIL (libjpeg), decoding
straight to YCbCr so that no colour conversion enters the comparison."""

from __future__ import annotations

import io
from typing import Tuple

import numpy as np
from PIL import Image


def decode(payload: bytes) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(Y, Cb, Cr), all at the stripe's full size (chroma upsampled by the
    decoder, as a browser shows it)."""
    img = Image.open(io.BytesIO(payload))
    img.draft("YCbCr", img.size)
    img.load()
    if img.mode != "YCbCr":
        img = img.convert("YCbCr")
    a = np.asarray(img)
    return a[..., 0], a[..., 1], a[..., 2]
