"""Native (C++) runtime components, built lazily with the system toolchain.

Each lib is a single ``g++ -O3 -shared`` invocation from the ``.cpp`` file
git tracks, cached next to the sources under a name that carries the hash
of that source and its flags — a copied tree (whose mtimes mean nothing)
can never load a binary built from other code, and no binary is tracked.
Builds are serialized by a lock file and land with ``os.replace``, so
concurrent processes (six test workers importing at once) each end up
loading a complete library.

If no toolchain is available the callers fall back to the pure-Python
implementations (slower but correct) and the failure is logged as an
error; paths that must not degrade (``chip_smoke.py``) call
:func:`require`, which raises instead.
"""

from __future__ import annotations

import ctypes
import fcntl
import glob
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Callable, Optional

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))


#: Set SELKIES_NATIVE_SANITIZE=address|thread|undefined to build every
#: native lib with the matching -fsanitize instrumentation (the sanitized
#: .so is cached under a distinct name, so it never shadows the production
#: build). Load the matching runtime first, e.g.
#: ``LD_PRELOAD=$(g++ -print-file-name=libasan.so)`` for address.
_SANITIZE_ENV = "SELKIES_NATIVE_SANITIZE"


def _sanitize_mode() -> str:
    mode = os.environ.get(_SANITIZE_ENV, "").strip()
    if mode and mode not in ("address", "thread", "undefined"):
        logger.warning("%s=%r not one of address|thread|undefined; ignored",
                       _SANITIZE_ENV, mode)
        return ""
    return mode


def _build_cmd(src: str, out: str, extra: tuple, sanitize: str) -> list:
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", out, src]
    if sanitize:
        cmd += [f"-fsanitize={sanitize}", "-g", "-fno-omit-frame-pointer"]
    return cmd + list(extra)


def _compile_lib(src: str, so: str, extra: tuple = (),
                 sanitize: str = "") -> None:
    """Build ``so`` from ``src`` atomically: compile to a temp file beside
    it, then ``os.replace`` — a reader never sees a half-written library.
    Raises RuntimeError (with the compiler's stderr) on failure."""
    fd, tmp = tempfile.mkstemp(prefix=os.path.basename(so) + ".",
                               suffix=".tmp", dir=os.path.dirname(so))
    os.close(fd)
    try:
        try:
            subprocess.run(_build_cmd(src, tmp, extra, sanitize), check=True,
                           capture_output=True, timeout=300)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build of {src} failed:\n"
                f"{e.stderr.decode(errors='replace')[-2000:]}") from e
        except (subprocess.SubprocessError, FileNotFoundError) as e:
            raise RuntimeError(f"native build of {src} failed: {e}") from e
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


class _LazyLib:
    """Build-once/load-once holder for one native lib."""

    def __init__(self, name: str, extra: tuple = (),
                 register: Optional[Callable] = None) -> None:
        self.name = name
        self.src = os.path.join(_DIR, name + ".cpp")
        # resolved once so the flags and the cache filename can't diverge
        # (an env-var change after import must not write an instrumented
        # binary under the production .so name)
        self.sanitize = _sanitize_mode()
        suffix = f"_{self.sanitize}" if self.sanitize else ""
        self._stem = os.path.join(_DIR, f"_libselkies_{name}{suffix}")
        self.extra = extra
        self.register = register
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._tried = False
        self.error: Optional[Exception] = None

    @property
    def so(self) -> str:
        """``<stem>.<hash of source + build command>.so`` — the name IS
        the staleness check."""
        h = hashlib.sha256()
        with open(self.src, "rb") as f:
            h.update(f.read())
        h.update(" ".join(_build_cmd("src", "out", self.extra,
                                     self.sanitize)).encode())
        return f"{self._stem}.{h.hexdigest()[:12]}.so"

    def _build(self, so: str) -> None:
        # one builder per library across processes; the losers of the
        # race wake to find the finished file and skip their own build
        with open(self._stem + ".lock", "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            if os.path.exists(so):
                return
            _compile_lib(self.src, so, self.extra, sanitize=self.sanitize)
            for old in glob.glob(self._stem + ".*.so"):
                if old != so:                 # builds of other sources
                    try:
                        os.unlink(old)
                    except OSError:
                        pass

    def get(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None or self._tried:
                return self._lib
            self._tried = True
            try:
                so = self.so
                if not os.path.exists(so):
                    self._build(so)
                lib = ctypes.CDLL(so)
            except (OSError, RuntimeError) as e:
                self.error = e
                logger.error("native lib %s unavailable, pure-Python "
                             "fallback in use: %s", self.name, e)
                return None
            if self.register is not None:
                self.register(lib)
            self._lib = lib
            return self._lib


_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i16p = np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")


def _register_entropy(lib: ctypes.CDLL) -> None:
    sig = [
        _i16p, _i16p, _i16p, ctypes.c_int, ctypes.c_int,
        _u32p, _u8p, _u32p, _u8p, _u32p, _u8p, _u32p, _u8p,
        _u8p, ctypes.c_int64,
    ]
    for name in ("jpeg_encode_scan_420", "jpeg_encode_scan_444"):
        fn = getattr(lib, name)
        fn.argtypes = sig
        fn.restype = ctypes.c_int64


def _register_cavlc(lib: ctypes.CDLL) -> None:
    fn = lib.h264_encode_picture
    fn.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        _i32p, _i32p, _i32p, _i32p, _i32p,
        _u8p, ctypes.c_int64, ctypes.c_int,
    ]
    fn.restype = ctypes.c_int64


def _register_conformance(lib: ctypes.CDLL) -> None:
    i32p = ctypes.POINTER(ctypes.c_int)
    lib.conf_h264_new.restype = ctypes.c_void_p
    lib.conf_mjpeg_new.restype = ctypes.c_void_p
    lib.conf_dec_free.argtypes = [ctypes.c_void_p]
    caps = [ctypes.c_int64, ctypes.c_int64]
    lib.conf_dec_decode.argtypes = [ctypes.c_void_p, _u8p, ctypes.c_int64,
                                    _u8p, _u8p, _u8p, *caps, i32p, i32p]
    lib.conf_dec_decode.restype = ctypes.c_int
    lib.conf_dec_flush.argtypes = [ctypes.c_void_p, _u8p, _u8p, _u8p,
                                   *caps, i32p, i32p]
    lib.conf_dec_flush.restype = ctypes.c_int
    # x264 reference encoder (quality-gate tooling)
    lib.conf_x264_new.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_char_p]
    lib.conf_x264_new.restype = ctypes.c_void_p
    lib.conf_enc_free.argtypes = [ctypes.c_void_p]
    lib.conf_enc_encode.argtypes = [ctypes.c_void_p, _u8p, _u8p, _u8p,
                                    _u8p, ctypes.c_int64]
    lib.conf_enc_encode.restype = ctypes.c_int64
    lib.conf_enc_flush.argtypes = [ctypes.c_void_p, _u8p, ctypes.c_int64]
    lib.conf_enc_flush.restype = ctypes.c_int64


def _register_audio(lib: ctypes.CDLL) -> None:
    lib.sa_opus_available.restype = ctypes.c_int
    lib.sa_pulse_available.restype = ctypes.c_int
    lib.sa_enc_new.argtypes = [ctypes.c_int] * 7
    lib.sa_enc_new.restype = ctypes.c_void_p
    lib.sa_enc_encode.argtypes = [ctypes.c_void_p, _i16p, ctypes.c_int,
                                  _u8p, ctypes.c_int32]
    lib.sa_enc_encode.restype = ctypes.c_int
    lib.sa_enc_free.argtypes = [ctypes.c_void_p]
    lib.sa_dec_new.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.sa_dec_new.restype = ctypes.c_void_p
    lib.sa_dec_decode.argtypes = [ctypes.c_void_p, _u8p, ctypes.c_int32,
                                  _i16p, ctypes.c_int]
    lib.sa_dec_decode.restype = ctypes.c_int
    lib.sa_dec_decode_fec.argtypes = [ctypes.c_void_p, _u8p, ctypes.c_int32,
                                      _i16p, ctypes.c_int]
    lib.sa_dec_decode_fec.restype = ctypes.c_int
    lib.sa_dec_plc.argtypes = [ctypes.c_void_p, _i16p, ctypes.c_int]
    lib.sa_dec_plc.restype = ctypes.c_int
    lib.sa_dec_free.argtypes = [ctypes.c_void_p]
    lib.sa_pa_new.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                              ctypes.c_int, ctypes.c_char_p]
    lib.sa_pa_new.restype = ctypes.c_void_p
    lib.sa_pa_read.argtypes = [ctypes.c_void_p, _i16p, ctypes.c_int64]
    lib.sa_pa_read.restype = ctypes.c_int
    lib.sa_pa_write.argtypes = [ctypes.c_void_p, _i16p, ctypes.c_int64]
    lib.sa_pa_write.restype = ctypes.c_int
    lib.sa_pa_free.argtypes = [ctypes.c_void_p]


_ENTROPY = _LazyLib("entropy", register=_register_entropy)
_CAVLC = _LazyLib("cavlc", register=_register_cavlc)
_CONFORMANCE = _LazyLib("conformance", ("-lavcodec", "-lavutil"),
                        _register_conformance)
_AUDIO = _LazyLib("audio", ("-ldl",), _register_audio)


def entropy_lib() -> Optional[ctypes.CDLL]:
    """The compiled JPEG entropy coder, or None if unavailable."""
    return _ENTROPY.get()


def cavlc_lib() -> Optional[ctypes.CDLL]:
    """The compiled H.264 CAVLC slice coder, or None if unavailable."""
    return _CAVLC.get()


def conformance_lib() -> Optional[ctypes.CDLL]:
    """libavcodec-backed conformance decoder, or None if unavailable.

    Test/debug oracle only (never on the hot path): decodes our Annex-B
    H.264 and JFIF output with a production decoder, standing in for the
    browser's WebCodecs decoders.
    """
    return _CONFORMANCE.get()


def audio_lib() -> Optional[ctypes.CDLL]:
    """Opus/Pulse audio runtime (the pcmflux equivalent), or None."""
    return _AUDIO.get()


def require(*names: str) -> None:
    """Build and load the named libs (``entropy``, ``cavlc``,
    ``conformance``, ``audio``) or raise with the builder's error — for
    paths where a silent pure-Python fallback would hide a broken build."""
    libs = {"entropy": _ENTROPY, "cavlc": _CAVLC,
            "conformance": _CONFORMANCE, "audio": _AUDIO}
    for n in names:
        if libs[n].get() is None:
            raise RuntimeError(
                f"native lib {n!r} required but unavailable: {libs[n].error}")
