"""Stall watch: when the whole process, or its event loop, could not run.

Always on (started by ``server.main.serve``):

* a thread sleeps :data:`BEAT_S` and records how late it woke. Nothing but
  the interpreter's lock (or the machine) can make a sleeping thread late,
  so a lateness over :data:`THRESHOLD_S` is a stall of kind ``interpreter``:
  some thread kept the lock (an import compiling a module, a collection of
  the heap, a C call that does not give it up), and every thread waited;
* a heartbeat on the event loop, whose age the thread reads each time it
  wakes on time: the loop is blocked *while threads run*. Kind ``loop``.

Every stall over the threshold goes to the flight recorder's stall ring as
``(kind, t0, t1)``, to the counter ``stalls_total`` and the histogram
``stall_ms``.

With ``capture_stacks`` (the ``stall_stacks`` setting; off by default)
every thread's stack is kept beside the stall's record and logged. For
``loop`` it is taken during the stall: the watch thread runs. For
``interpreter`` it is the first thing the watch thread does when it gets
the lock back: the thread that kept the lock has just been made to give it
up (or has just come back from its C call) and stands on the line it stood
on all the while, so its stack still names the call. The standard library's
way of looking *during* such a stall, ``faulthandler.dump_traceback_later``
re-armed at every beat, was built first and taken out: its watchdog walks
the interpreter's list of thread states without the lock, JAX's own C++
threads take and drop thread states all the time, and the dump crashed the
served process within a few stalls (SIGSEGV; PERF.md, PR 25).
"""

from __future__ import annotations

import logging
import sys
import threading
import time
import traceback
from typing import Any, Callable, Optional

logger = logging.getLogger("selkies_tpu.observability.stall_watch")

#: the watch thread's sleep, and the loop's heartbeat period
BEAT_S = 0.010
#: a lateness over this is a stall
THRESHOLD_S = 0.040
#: innermost frames kept of each thread's stack
STACK_DEPTH = 14


def every_threads_stack() -> str:
    """Every thread's stack, innermost frames last, the caller's left out.
    The frames are walked first, with no source line looked up: reading a
    file gives the interpreter's lock away, and the threads would move on
    under the walk."""
    me = threading.get_ident()
    walked = [(ident, traceback.StackSummary.extract(
        traceback.walk_stack(frame), limit=STACK_DEPTH, lookup_lines=False))
        for ident, frame in sys._current_frames().items() if ident != me]
    names = {t.ident: t.name for t in threading.enumerate()}
    return "\n".join(
        f"Thread {names.get(ident, '?')} ({ident:#x}):\n"
        + "".join(reversed(summary.format()))
        for ident, summary in walked)


def throttled_ms() -> Optional[float]:
    """How long the kernel has kept this process's control group off the
    CPU for want of quota, so far (cgroup v2 ``cpu.stat``); None where
    that is not to be read. A sleeping thread is late for two reasons: the
    interpreter's lock, or the machine. This tells the second."""
    for path, key, per_ms in (
            ("/sys/fs/cgroup/cpu.stat", "throttled_usec", 1e3),
            ("/sys/fs/cgroup/cpu/cpu.stat", "throttled_time", 1e6)):   # v1
        try:
            with open(path) as f:
                for line in f:
                    if line.startswith(key):
                        return int(line.split()[1]) / per_ms
        except (OSError, ValueError):
            continue
    return None


class StallWatch:
    def __init__(self, get_recorder: Callable[[], Any], loop=None,
                 capture_stacks: bool = False) -> None:
        self._get_recorder = get_recorder
        self._loop = loop
        self.capture_stacks = bool(capture_stacks)
        self._loop_beat = time.monotonic()
        self._beat_handle = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="stall-watch", daemon=True)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "StallWatch":
        if self._loop is not None:
            self._loop_beat = time.monotonic()
            self._beat_handle = self._loop.call_later(BEAT_S, self._beat)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._beat_handle is not None:
            self._beat_handle.cancel()
            self._beat_handle = None

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    # -- the loop's side ---------------------------------------------------
    def _beat(self) -> None:
        self._loop_beat = time.monotonic()
        if not self._stop.is_set():
            self._beat_handle = self._loop.call_later(BEAT_S, self._beat)

    # -- the watch thread --------------------------------------------------
    def _record(self, kind: str, t0: float, t1: float,
                stack: Optional[str]) -> None:
        if stack:
            # only with capture_stacks on: the operator asked to be told
            logger.warning("stall (%s) of %.1f ms; every thread's stack:\n%s",
                           kind, (t1 - t0) * 1000.0, stack)
        try:
            self._get_recorder().stall(kind, t0, t1, stack)
        except Exception:
            logger.debug("stall record failed", exc_info=True)

    def _run(self) -> None:
        capture = self.capture_stacks
        #: (when, cpu.stat's throttled time then), refreshed about once a
        #: second and after every stall, for the stack's first line
        quota = (time.monotonic(), throttled_ms()) if capture else None
        last = time.monotonic()
        #: the loop's heartbeat counts as fresh from here: whatever kept
        #: this thread from running kept the loop too, and is not the loop's
        fresh_from = last
        loop_t0: Optional[float] = None      # an open ``loop`` stall
        loop_stack: Optional[str] = None
        while not self._stop.is_set():
            time.sleep(BEAT_S)
            now = time.monotonic()
            if now - last - BEAT_S > THRESHOLD_S:
                # before anything else: where every thread stands now
                stack = every_threads_stack() if capture else None
                if capture and quota[1] is not None:
                    off = throttled_ms() - quota[1]
                    stack = (f"cpu.stat: the control group was throttled "
                             f"{off:.1f} ms in the {now - quota[0]:.2f} s "
                             f"before\n" + stack)
                self._record("interpreter", last + BEAT_S, now, stack)
                now = fresh_from = time.monotonic()
                quota = (now, throttled_ms()) if capture else None
                loop_t0 = None
            elif self._loop is not None:
                beat = max(self._loop_beat, fresh_from)
                if loop_t0 is not None and beat > loop_t0:
                    # the loop is back: the stall ran to its beat
                    if beat - loop_t0 > THRESHOLD_S:
                        self._record("loop", loop_t0, beat, loop_stack)
                    loop_t0 = loop_stack = None
                elif loop_t0 is None and now - beat > BEAT_S + THRESHOLD_S:
                    loop_t0 = beat + BEAT_S
                    loop_stack = every_threads_stack() if capture else None
            if capture and now - quota[0] > 1.0:
                quota = (now, throttled_ms())
            last = now
