"""H.264 stripe decoder of the benchmark's client: libavcodec through
ctypes, built from ``h264dec.cpp`` on first use into ``benchmark/.build``
(git-ignored), named by the source's hash."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(os.path.dirname(HERE), ".build")
_lib = None


def library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    src = os.path.join(HERE, "h264dec.cpp")
    with open(src, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    out = os.path.join(BUILD_DIR, f"h264dec.{tag}.so")
    if not os.path.exists(out):
        os.makedirs(BUILD_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=BUILD_DIR)
        os.close(fd)
        try:
            subprocess.run(["g++", "-O2", "-shared", "-fPIC", src, "-o", tmp,
                            "-lavcodec", "-lavutil"], check=True,
                           capture_output=True, text=True)
            os.replace(tmp, out)
        except subprocess.CalledProcessError as e:
            os.unlink(tmp)
            raise RuntimeError(f"h264dec.cpp did not build: {e.stderr}") from e
    lib = ctypes.CDLL(out)
    lib.bench_h264_new.restype = ctypes.c_void_p
    lib.bench_h264_free.argtypes = [ctypes.c_void_p]
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.bench_h264_decode.restype = ctypes.c_int
    lib.bench_h264_decode.argtypes = [
        ctypes.c_void_p, u8p, ctypes.c_int64, u8p, u8p, u8p,
        ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    _lib = lib
    return lib


Planes = Tuple[np.ndarray, np.ndarray, np.ndarray]


class Decoder:
    """One stripe's stream: feed access units in emission order."""

    def __init__(self, max_w: int = 4096, max_h: int = 256) -> None:
        self._lib = library()
        self._h = self._lib.bench_h264_new()
        if not self._h:
            raise RuntimeError("libavcodec has no H.264 decoder")
        self._y = np.empty(max_w * max_h, np.uint8)
        self._u = np.empty((max_w // 2) * (max_h // 2), np.uint8)
        self._v = np.empty_like(self._u)

    def decode(self, data: bytes) -> Optional[Planes]:
        """(Y, Cb, Cr) of the picture this unit completes, or None."""
        w, h = ctypes.c_int(), ctypes.c_int()
        buf = np.frombuffer(data, np.uint8)
        n = self._lib.bench_h264_decode(
            self._h, buf, len(data), self._y, self._u, self._v,
            self._y.size, self._u.size, ctypes.byref(w), ctypes.byref(h))
        if n < 0:
            raise ValueError(f"libavcodec refused the access unit ({n})")
        if n == 0:
            return None
        ww, hh = w.value, h.value
        cw, ch = (ww + 1) // 2, (hh + 1) // 2
        return (self._y[:ww * hh].reshape(hh, ww).copy(),
                self._u[:cw * ch].reshape(ch, cw).copy(),
                self._v[:cw * ch].reshape(ch, cw).copy())

    def close(self) -> None:
        if self._h:
            self._lib.bench_h264_free(self._h)
            self._h = None
