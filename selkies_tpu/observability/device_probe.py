"""Device probe: one clock for the host and the chip, and how long new
work queues on the device.

About four times a second a thread of its own runs a tiny jitted program
with a name of its own, :func:`selkies_clock_probe`, on one device, blocks
for its result, and writes ``(device, t_enqueued, t_ready)`` on
``time.monotonic`` to the flight recorder's clock-pair ring.

* In any device trace, whoever started it (the benchmark's, /debug/jax-trace,
  xprof), the probe shows on the ``XLA Modules`` line as
  ``jit_selkies_clock_probe(...)``. Its end on the device's clock and
  ``t_ready`` on the host's are the same instant but for the wake-up of a
  blocked thread, so the smallest ``t_ready - device_end`` over a trace's
  probes is the offset between the two clocks.
* ``t_ready - t_enqueued`` is how long new work queues behind what the
  device already holds: the gauge ``device_queue_delay_ms``.

Nothing on a driver thread blocks for the probe. The same thread samples
``device.memory_stats()`` once a second, so that the server's stats tick
makes no device call on the event loop (PERF.md: a guess at a 110 ms stall).

:class:`ReadyWatch` is the same idea turned on the frames themselves: a
thread of its own blocks for each step's output and writes the instant it
became ready, ``t_ready``, so that a frame's time between launch and
harvest can be told apart into waiting behind earlier steps, running, and
lying ready unread (``device_wait``, ``device_run``, ``ready_wait``:
observability/tracing.py).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger("selkies_tpu.observability.device_probe")


def selkies_clock_probe(x):
    """The probe's whole program. Its name is its point: a device trace
    shows ``jit_selkies_clock_probe(...)``."""
    return x + 1


class DeviceProbe:
    """One probe thread on one device."""

    INTERVAL_S = 0.25
    #: memory_stats() every this many probes
    MEMORY_EVERY = 4

    def __init__(self, device, get_recorder: Callable[[], Any],
                 get_metrics: Callable[[], Any] = lambda: None) -> None:
        self.device = device
        self._get_recorder = get_recorder
        self._get_metrics = get_metrics
        #: the probe's program is compiled and has run once
        self.ready = threading.Event()
        self.error: Optional[BaseException] = None
        #: last ``device.memory_stats()`` (None until the first sample)
        self.memory: Optional[Dict[str, Any]] = None
        self.last_delay_ms: Optional[float] = None
        self.probes_total = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"device-probe-{device.id}", daemon=True)

    def start(self) -> "DeviceProbe":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    @property
    def thread_name(self) -> str:
        return self._thread.name

    def _run(self) -> None:
        try:
            import jax
            import numpy as np

            x = jax.device_put(np.int32(0), self.device)
            probe = jax.jit(selkies_clock_probe)
            probe(x).block_until_ready()      # compiles here, at start
            self.memory = self.device.memory_stats()
        except BaseException as e:     # no device, no probe: never fatal
            self.error = e
            logger.warning("device probe on %s disabled: %r", self.device, e)
            self.ready.set()
            return
        self.ready.set()
        dev = int(self.device.id)
        while not self._stop.wait(self.INTERVAL_S):
            try:
                t0 = time.monotonic()
                probe(x).block_until_ready()
                t1 = time.monotonic()
                self.probes_total += 1
                self.last_delay_ms = (t1 - t0) * 1000.0
                self._get_recorder().clock_pair(dev, t0, t1)
                m = self._get_metrics()
                if m is not None:
                    m.set_device_queue_delay(dev, self.last_delay_ms)
                if self.probes_total % self.MEMORY_EVERY == 0:
                    self.memory = self.device.memory_stats()
            except Exception as e:
                self.error = e
                logger.warning("device probe on %s stopped: %r",
                               self.device, e)
                return



class ReadyStamp:
    """When one launched step's output became ready on the chip
    (``t_ready``, None until the watch has seen it) and when the step
    launched before it on the same chip was (``t_before``, None for the
    first), on ``time.monotonic``."""

    __slots__ = ("t_ready", "t_before")

    def __init__(self) -> None:
        self.t_ready: Optional[float] = None
        self.t_before: Optional[float] = None


def ready_stages(launched: float, fetched: float, stamp: Optional[ReadyStamp]
                 ) -> Dict[str, Tuple[float, float]]:
    """The three stages that tile ``in_device`` + ``fetch_wait``, from
    L = ``launched`` (``dispatch`` end), F = ``fetched`` (``fetch_wait``
    end), R = the frame's ``t_ready`` clipped into [L, F] and R' = the
    ``t_ready`` of the step launched before it on the same chip:

    * ``device_wait``  L -> max(L, R'): queued behind earlier steps;
    * ``device_run``   max(L, R') -> R: the chip free for it to its output
      ready;
    * ``ready_wait``   R -> F: the result lies on the chip unread.

    Each is >= 0 and they add up to F - L. {} where the stamp has not
    landed."""
    if stamp is None or stamp.t_ready is None or fetched < launched:
        return {}
    r = min(max(stamp.t_ready, launched), fetched)
    free = launched if stamp.t_before is None \
        else min(max(stamp.t_before, launched), r)
    return {"device_wait": (launched, free), "device_run": (free, r),
            "ready_wait": (r, fetched)}


class ReadyWatch:
    """Stamps the instant each launched step's output became ready.

    The thread that launches (a pipe's driver thread, a coordinator's
    worker) reads :attr:`ahead` before a launch and hands the step's own
    output buffer over after it (:meth:`launched`); the watch's daemon
    thread blocks for each in launch order, takes one clock reading,
    writes it to the frame's :class:`ReadyStamp` and drops the array; at
    the frame's harvest :meth:`stages` turns the stamp into the three
    stages. Nothing but attribute writes and integer increments is shared:
    ``launches``, ``launches_into_idle`` and ``stamps_missed`` have the
    launching thread for their one writer, ``readied`` the watch's. The
    launching thread never blocks for the watch; an array that raises
    stops the watch with one warning and the owner goes on without stamps
    (the probe's rule: never fatal)."""

    def __init__(self, name: str) -> None:
        self.name = name
        #: steps handed over, and how many of them were launched with
        #: nothing of this stream unfinished on the chip (it stood idle
        #: before them)
        self.launches = 0
        self.launches_into_idle = 0
        #: stamps written
        self.readied = 0
        #: frames harvested without a landed stamp: they carry none of the
        #: three stages
        self.stamps_missed = 0
        self.error: Optional[BaseException] = None
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def ahead(self) -> int:
        """Steps launched whose output the watch has not seen ready."""
        return self.launches - self.readied

    @property
    def stopped(self) -> bool:
        """No more stamps: the owner stopped it, or an array raised."""
        return self._stop.is_set()

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def counts(self) -> Dict[str, int]:
        """What an owner's ``stats()`` says of its launches."""
        return {"launches": self.launches,
                "launches_into_idle": self.launches_into_idle,
                "ready_stamps_missed": self.stamps_missed}

    def launched(self, array: Any, ahead: int) -> Optional[ReadyStamp]:
        """One step was launched with ``ahead`` steps before it unfinished
        (read before the launch); ``array`` is its own output. The stamp
        the frame keeps for its harvest; None from a stopped watch."""
        if self._stop.is_set():
            return None
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, args=(self._q, self._stop),
                name=self.name, daemon=True)
            self._thread.start()
        self.launches += 1
        if ahead <= 0:
            self.launches_into_idle += 1
        stamp = ReadyStamp()
        self._q.put((array, stamp))
        return stamp

    def stages(self, launched: float, fetched: float,
               stamp: Optional[ReadyStamp]
               ) -> Dict[str, Tuple[float, float]]:
        """:func:`ready_stages` of a harvested frame; one that gets none
        (its stamp has not landed, or the watch had stopped) is counted."""
        split = ready_stages(launched, fetched, stamp)
        if not split:
            self.stamps_missed += 1
        return split

    def stop(self) -> None:
        """The owner is closing: the thread ends after the array it is
        blocked for, and takes no reference with it."""
        self._stop.set()
        self._q.put(None)

    def resume(self) -> None:
        """The owner starts again after :meth:`stop` (a coordinator whose
        worker is started again): the counts go on, the next launch starts
        a new thread; what was unstamped at the stop stays so. A watch
        that an array stopped stays stopped."""
        if self._stop.is_set() and self.error is None:
            self._q, self._stop = queue.SimpleQueue(), threading.Event()
            self._thread = None
            self.readied = self.launches

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def _run(self, q: "queue.SimpleQueue", stop: threading.Event) -> None:
        import jax

        t_before = None
        while True:
            entry = q.get()
            if entry is None or stop.is_set():
                break
            array, stamp = entry
            del entry
            try:
                jax.block_until_ready(array)
                if stop.is_set():
                    break
                stamp.t_before = t_before
                stamp.t_ready = t_before = time.monotonic()
                self.readied += 1
            except Exception as e:     # a dead buffer: no stamps, never fatal
                self.error = e
                stop.set()
                logger.warning("%s stopped: %r", self.name, e)
                break
            finally:
                del array
        # whatever was queued behind the stop holds device arrays
        while not q.empty():
            try:
                q.get_nowait()
            except queue.Empty:
                break
