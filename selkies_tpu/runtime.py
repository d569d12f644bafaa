"""Process-wide device/runtime policy shared by every entry point.

One place for the two decisions that the server, ``chip_smoke.py`` and
the benchmark must make the same way:

  * where JAX's persistent compilation cache lives;
  * whether Pallas kernels run compiled or in the interpreter (something
    a test run *asks for*, never something the program falls into).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Hashable, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: JAX's own variable. When the operator sets it, JAX reads it itself and
#: this module names no directory at all — the path is part of the cache
#: key's environment, so exactly one party may choose it.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: env form of the ``tpu_interpret`` setting (settings.py)
INTERPRET_ENV = "SELKIES_TPU_INTERPRET"

_TRUE = ("1", "true", "yes", "on")


def compile_cache_dir() -> str:
    """The persistent-cache directory in effect: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ONE fixed path inside the
    checkout (git-ignored). Never a temp name, pid or time — a directory
    that moves never hits."""
    return os.environ.get(CACHE_ENV) or os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent XLA compilation cache on and return its
    directory. A cold 1080p H.264 start compiles for minutes; across
    restarts it should cost a disk read. Raises if JAX refuses — a boot
    that cannot reach its runtime must not carry on quietly."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return compile_cache_dir()


def pallas_interpret() -> bool:
    """True when the run asked for Pallas interpreter mode
    (``SELKIES_TPU_INTERPRET`` / the ``tpu_interpret`` setting).
    tests/conftest.py asks; nothing else does. Without it a Pallas kernel
    is compiled for the attached device, and fails loudly where there is
    none to compile for."""
    return os.environ.get(INTERPRET_ENV, "").strip().lower() in _TRUE


#: how long one first-use compile may hold an encoder's dispatch before a
#: stalled pipeline reads as wedged after all: a cold 1080p H.264 P step
#: compiles for ~5 minutes (tests/test_chip_compile.py)
COMPILE_GRACE_S = 1200.0


class CompileWatch:
    """One encoder's "I am compiling" signal.

    Dispatch is asynchronous: a call into a jitted step returns as soon
    as the work is enqueued. What holds it for seconds to minutes is the
    program's first-use XLA compile. The object that OWNS the jitted
    programs wraps each dispatch in :meth:`first_use`, naming the program
    (every static argument that selects a different executable); only a
    program that has not yet completed a call here is timed (the block is
    handed that fact, for what belongs to a program's first use), so a
    warm dispatch that hangs on the device is never mistaken for a compile.
    The capture loop reads :meth:`compiling_for_s` from the encoder it
    holds — one display's compile says nothing about another's — before
    it reads a quiet pipeline as a dead one (data_server._display_loop).

    Nesting is safe (the outermost cold call owns the clock); the lock
    covers encoders whose dispatch and reader run on different threads."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._warm: set = set()
        self._depth = 0
        self._since: Optional[float] = None

    @contextlib.contextmanager
    def first_use(self, program: Hashable):
        with self._lock:
            cold = program not in self._warm
            if cold:
                if self._depth == 0:
                    self._since = time.monotonic()
                self._depth += 1
        try:
            yield cold
            if cold:
                with self._lock:
                    self._warm.add(program)
        finally:
            if cold:
                with self._lock:
                    self._depth -= 1
                    if self._depth == 0:
                        self._since = None

    def compiling_for_s(self) -> float:
        """Seconds the current first-use call has been blocked (0.0 when
        none is in progress)."""
        since = self._since
        return time.monotonic() - since if since is not None else 0.0
