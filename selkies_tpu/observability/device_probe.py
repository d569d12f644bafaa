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
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, Optional

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

