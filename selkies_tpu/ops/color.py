"""Color-space transforms on device.

The reference's pixel pipeline does BGRX→YUV conversion inside pixelflux's
C++ SIMD code before x264/libjpeg; here ``rgb_to_ycbcr`` is nine
elementwise multiply-adds on the VPU, one fused pass per plane, and
``subsample_420`` a 2x2 window sum straight over the [H, W] plane. No
reshape to a minor dimension of 2 may come back: the chip tiles the last
dimension to 128 lanes, so ``reshape(h/2, 2, w/2, 2)`` of one 1088x1920
f32 plane is a 535 MB buffer, 63/64 of it padding, written and read back
at the chip's memory bandwidth: 3.1 ms of every step.

Coefficients are JFIF/BT.601 full-range, the convention both libjpeg-class
JPEG decoders and the browser `ImageDecoder` assume.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# Rows: Y, Cb, Cr; columns: R, G, B.
_RGB2YCC = jnp.array(
    [
        [0.299, 0.587, 0.114],
        [-0.168736, -0.331264, 0.5],
        [0.5, -0.418688, -0.081312],
    ],
    dtype=jnp.float32,
)
_YCC_OFFSET = jnp.array([0.0, 128.0, 128.0], dtype=jnp.float32)


def rgb_to_ycbcr(rgb):
    """[..., H, W, 3] uint8/float RGB → (Y, Cb, Cr) float32 planes [..., H, W].

    Values are in [0, 255]; no level shift here (the DCT stage subtracts
    128). Elementwise FMA form, not a matmul: a [N, 3] @ [3, 3] dot is the
    worst possible MXU shape (and at HIGHEST precision costs 6 passes) —
    the VPU does this in one fused pass per plane.
    """
    x = rgb.astype(jnp.float32)
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    m = _RGB2YCC
    y = m[0, 0] * r + m[0, 1] * g + m[0, 2] * b
    cb = m[1, 0] * r + m[1, 1] * g + m[1, 2] * b + 128.0
    cr = m[2, 0] * r + m[2, 1] * g + m[2, 2] * b + 128.0
    return y, cb, cr


def subsample_420(plane):
    """2x2 mean-pool chroma subsampling: [..., H, W] → [..., H/2, W/2].

    A window sum over the plane as it lies (see the module docstring for
    why no reshape); leading dimensions get window and stride 1."""
    win = (1,) * (plane.ndim - 2) + (2, 2)
    return lax.reduce_window(plane, 0.0, lax.add, win, win, "VALID") * 0.25
