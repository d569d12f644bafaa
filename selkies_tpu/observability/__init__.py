"""Observability: Prometheus metrics + the frame flight recorder.

Two surfaces (docs/observability.md):

* :class:`Metrics` — the Prometheus registry (parity with
  ``legacy/metrics.py`` gauges plus the tpuenc/robustness/edge series)
  and the observability HTTP endpoint: ``/metrics``, ``/healthz``,
  ``/debug/trace`` (Perfetto-loadable flight-recorder export), and the
  opt-in ``/debug/jax-trace`` profiler hook.
* :class:`FlightRecorder` / :class:`FrameTrace` — per-frame stage
  tracing from capture to CLIENT_FRAME_ACK (:data:`STAGES`), the
  measurement substrate behind ``glass_to_glass_ms`` /
  ``encode_only_ms``, the ``system_health`` stage breakdown, and
  tools/trace_report.py.

The recorder also keeps the driver threads' timeline, the device probe's
clock pairs (:mod:`.device_probe`) and the stall watch's records
(:mod:`.stall_watch`); :mod:`.device_phases` names a step program's
operations by phase, on demand.
"""

from .metrics import Metrics
from .tracing import STAGES, FlightRecorder, FrameTrace

__all__ = ["Metrics", "FlightRecorder", "FrameTrace", "STAGES"]
