"""Frame flight recorder: per-stage tracing from capture to client ACK.

The reference has no tracer (SURVEY §5 row 1: client-side FPS counting
only), so its end-to-end latency was never attributable — and neither
was ours: the async driver (docs/pipeline.md) hides the dispatch/fetch
round trip, but nothing proved *where* the remaining glass-to-glass
milliseconds lived. This module is the measurement substrate for that
question (ROADMAP item 1's "measured at the glass, not the chip"), and
the feedback channel items 2-3 (SFE, rate control) will read from.

Every served frame carries a :class:`FrameTrace` — a trace context of
(display/session id, wire frame id) threaded through the full path::

    capture -> stage -> dispatch -> fetch_wait -> pack -> queue -> send -> ack

(the waits between them, and a mesh lane's ``lane_step``, are in
:data:`STAGES`).

Call sites mark stages with absolute monotonic intervals; the recorder
never reads the clock on the hot path. A span is *closed* exactly once,
with a terminal mark:

* ``acked``            — the client's CLIENT_FRAME_ACK landed (the ack
                         stage is true network RTT + client decode);
* ``empty``            — the frame encoded to zero emitted stripes
                         (damage gating; normal, not a loss);
* ``dropped@<stage>``  — the frame was lost at that stage (submit
                         backpressure, encoder error, send-queue
                         overflow, supervised restart, ...);
* ``expired@<stage>``  — no terminal event arrived within the expiry
                         window (e.g. a client that never ACKs).

Dropped and expired frames therefore NEVER leak an open span — the
open-span count is an invariant tools/chaos_run.py asserts to zero.

Concurrency: marks land from the event loop, the async-driver thread,
and mesh worker threads. The recorder is lock-free in the CPython
sense — the completed ring is a preallocated list written through a
single monotonically increasing index, and the open/awaiting tables are
plain dicts; every mutation is one GIL-atomic operation, so there are
no locks (and no possible lock-order inversions) anywhere on the frame
path.

Export surfaces:

* per-stage Prometheus histograms with a ``display`` label, plus
  ``glass_to_glass_ms`` / ``encode_only_ms`` (observability/metrics.py);
* Chrome trace-event JSON (Perfetto-loadable) of the last N seconds —
  served at ``/debug/trace`` and summarized by tools/trace_report.py;
* per-display stage summaries riding the ``system_health`` wire feed.

Beside the frames' ring the recorder keeps three more, all on the same
``time.monotonic`` clock and all written without a lock (one bounded list,
one write index each):

* the **thread track**: ``(thread, state, t0, t1)`` for every state a
  worker thread enters (the ``tpuenc-async`` driver writes ``stage``,
  ``dispatch``, ``fetch_wait``, ``pack``, ``emit`` and ``sleep``; a mesh
  lane's ``mesh-encode`` worker the same but ``emit``: its frames are
  taken by the sessions' polls); time of a running thread that no state
  covers is time it wanted to run and could not (a lock, the interpreter)
  — readers call it ``other``;
* the **clock pairs**: ``(device, t_enqueued, t_ready)`` of the device
  probe (observability/device_probe.py), which pair this clock with the
  device's in any profiler trace;
* the **stalls**: ``(kind, t0, t1)`` from the stall watch
  (observability/stall_watch.py), ``kind`` ``interpreter`` or ``loop``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "STAGES", "THREAD_STATES", "STALL_KINDS", "FlightRecorder",
    "FrameTrace", "PendingSpans",
]

#: the stages of a served frame's flight, in path order. Work stages time
#: a thread working on the frame; wait stages (``*_wait``, ``in_device``,
#: ``queue``) time the frame sitting somewhere, and are marked where the
#: wait ends. Together they run from capture to ACK without a hole, on the
#: solo driver (``encoder/async_driver.py``) and on a mesh lane
#: (``parallel/coordinator.py``) under the same names; ``lane_step`` lies
#: across the others, the three stages the ready watch gives lie inside
#: ``in_device`` + ``fetch_wait``, and none of the four is part of the path.
#:
#: capture       host wall time in ``source.next_frame()``
#: submit_wait   accepted into the driver's submit queue -> taken out (a
#:               lane: into the session's latest-wins mailbox -> the
#:               worker's tick takes it; a capture that was replaced there
#:               is not the one timed)
#: pipe_wait     taken out -> its staging begins (behind the rest of the
#:               pass's work, and in ``pipe.submit`` while the pipe is full;
#:               a lane: the worker makes room in the in-flight window)
#: stage         H2D staging (the donated ring; a lane: each chip's share
#:               of each new frame through that chip's ring)
#: dispatch      device program launch (not device compute)
#: in_device     dispatch done -> the driver sees the result ready or
#:               begins to block for it (queued and running on the device):
#:               ``device_wait``, ``device_run`` and the part of
#:               ``ready_wait`` before the fetch began
#: device_wait   dispatch done (L) -> the step launched before it on the
#:               same chip had its output ready (max(L, R')): queued behind
#:               earlier steps
#: device_run    max(L, R') -> the step's own output ready (R, as the ready
#:               watch stamped it, clipped into [L, F]): the chip free for
#:               it to its output ready: the step, and the small programs
#:               between steps
#: ready_wait    R -> ``fetch_wait`` done (F): the result lies on the chip
#:               and the host has not got it (the fetch program and the
#:               copy, a JPEG pair waiting to fill, the driver busy with
#:               the frame before or asleep); a frame the driver blocked
#:               for reads its copy alone. The three tile ``in_device`` +
#:               ``fetch_wait`` and, as ``lane_step``, are no part of the
#:               path; a frame harvested before its stamp landed has none
#: fetch_wait    host time blocked materializing the D2H fetch
#: pack          host-side entropy glue / stripe assembly
#: lane_step     mesh lanes only: the worker's occupied time in the tick
#:               that took the frame, from the take to the end of the lane's
#:               part of the tick (room-making, staging, launch and every
#:               harvest done there). Ticks that take no capture and only
#:               harvest are in no frame's ``lane_step``, so 1000 / its
#:               median is no bound on the lane's rate and does not follow
#:               it (docs/observability.md, "Mesh sessions")
#: harvest_wait  packed -> the capture loop's poll() takes the frame
#: queue         dwell in the owner's bounded send queue
#: send          transport send (websocket write)
#: ack           send completion -> CLIENT_FRAME_ACK (network RTT + decode)
#:
#: An injected lane encoder that does not say when it launched keeps
#: ``stage`` inside ``dispatch``; one without the harvest's split keeps
#: ``pack`` inside ``fetch_wait``.
STAGES = ("capture", "submit_wait", "pipe_wait", "stage", "dispatch",
          "in_device", "device_wait", "device_run", "ready_wait",
          "fetch_wait", "pack", "lane_step", "harvest_wait", "queue", "send",
          "ack")

#: states a worker thread writes to the thread track
THREAD_STATES = ("stage", "dispatch", "fetch_wait", "pack", "emit", "sleep")

#: what a stall record says was not to be had
STALL_KINDS = ("interpreter", "loop")


class FrameTrace:
    """One frame's flight: (display, wire frame id) + stage intervals.

    ``spans`` maps stage name to an absolute ``(start, end)`` monotonic
    interval. Stages may overlap or be missing (a mesh lane's
    ``lane_step`` lies across its frame's other stages; a host-rung frame
    has no device dispatch) — consumers read durations per stage, never
    assume contiguity.
    """

    __slots__ = ("display", "frame_id", "t0", "spans", "terminal",
                 "_token")

    def __init__(self, display: str, t0: float) -> None:
        self.display = display
        self.frame_id: int = -1        # wire id; assigned at pack time
        self.t0 = t0                   # span open (capture start)
        self.spans: Dict[str, Tuple[float, float]] = {}
        self.terminal: Optional[str] = None
        self._token: int = 0

    def mark(self, stage: str, t_start: float, t_end: float) -> None:
        """Record one stage's absolute interval (idempotent per stage:
        a re-mark overwrites, keeping one interval per stage)."""
        self.spans[stage] = (t_start, t_end)

    def merge(self, intervals: Optional[Dict[str, Tuple[float, float]]]
              ) -> None:
        """Fold in the encoder-side intervals harvested with the frame
        (the pipelines report stage/dispatch/fetch_wait/pack)."""
        if intervals:
            self.spans.update(intervals)

    def duration_ms(self, stage: str) -> Optional[float]:
        iv = self.spans.get(stage)
        if iv is None:
            return None
        return (iv[1] - iv[0]) * 1000.0

    @property
    def t_end(self) -> float:
        """Latest marked instant (== close time for terminal spans)."""
        if not self.spans:
            return self.t0
        return max(iv[1] for iv in self.spans.values())

    @property
    def total_ms(self) -> float:
        """Open -> latest mark. For acked spans this is glass-to-glass."""
        return (self.t_end - self.t0) * 1000.0

    @property
    def encode_only_ms(self) -> Optional[float]:
        """Submit -> stripes host-packed: the ROADMAP item 1 criterion
        (compare against ``h264_device_ms_per_frame``). Elapsed wall
        between the first encoder-side stage start and the pack end —
        queueing inside the async driver counts, because the glass does
        not care which thread was slow."""
        starts = [self.spans[s][0] for s in ("stage", "dispatch")
                  if s in self.spans]
        end = self.spans.get("pack") or self.spans.get("fetch_wait")
        if not starts or end is None:
            return None
        return max(0.0, (end[1] - min(starts)) * 1000.0)

    @property
    def last_stage(self) -> str:
        """The stage whose interval ends latest ('open' when none)."""
        if not self.spans:
            return "open"
        return max(self.spans.items(), key=lambda kv: kv[1][1])[0]

    def as_dict(self) -> Dict[str, Any]:
        return {
            "display": self.display,
            "frame_id": self.frame_id,
            "terminal": self.terminal,
            "total_ms": round(self.total_ms, 3),
            "stages": {s: round((iv[1] - iv[0]) * 1000.0, 3)
                       for s, iv in self.spans.items()},
        }


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(len(sorted_vals) * q / 100.0))
    return sorted_vals[idx]


class _Ring:
    """A bounded list written through one increasing index, no lock (the
    same discipline as the frames' ring: a racing writer can at worst
    overwrite one row). Rows are tuples; ``rows()`` gives oldest first."""

    __slots__ = ("capacity", "_rows", "_widx")

    def __init__(self, capacity: int) -> None:
        self.capacity = max(16, int(capacity))
        self._rows: List[Optional[tuple]] = [None] * self.capacity
        self._widx = 0

    def append(self, row: tuple) -> None:
        self._rows[self._widx % self.capacity] = row
        self._widx += 1

    def last(self) -> Optional[tuple]:
        return self._rows[(self._widx - 1) % self.capacity]

    def replace_last(self, row: tuple) -> None:
        self._rows[(self._widx - 1) % self.capacity] = row

    def rows(self) -> List[tuple]:
        w, cap = self._widx, self.capacity
        rows = self._rows[w % cap:] + self._rows[:w % cap] \
            if w > cap else self._rows[:w]
        return [r for r in rows if r is not None]


class PendingSpans:
    """One capture loop's spans between submit and harvest (ROADMAP D6:
    this bookkeeping lived in ``_capture_loop``).

    Keyed by the seq the encoder will harvest the frame under; encoders
    whose submit returns no seq correlate first in, first out (results
    arrive in submission order on every adapter). Both tables are capped:
    a pipeline that accepts submits and never harvests must not grow them
    until the watchdog fires. Every span that leaves here without being
    taken is closed ``dropped@<stage>``, so none can leak."""

    CAP = 512

    def __init__(self, recorder: "FlightRecorder") -> None:
        self._rec = recorder
        self._by_seq: Dict[int, FrameTrace] = {}
        self._fifo: deque = deque()

    def __len__(self) -> int:
        return len(self._by_seq) + len(self._fifo)

    def add(self, seq: Optional[int], tr: FrameTrace) -> None:
        """The encoder accepted the frame (under ``seq``, if it says)."""
        if seq is None:
            self._fifo.append(tr)
            while len(self._fifo) > self.CAP:
                self._rec.drop(self._fifo.popleft(), "submit")
            return
        old = self._by_seq.pop(seq, None)
        if old is not None:
            # seq reuse: the superseded frame's span closes, it does
            # not silently vanish
            self._rec.drop(old, "submit")
        self._by_seq[seq] = tr
        while len(self._by_seq) > self.CAP:
            self._rec.drop(self._by_seq.pop(next(iter(self._by_seq))),
                           "submit")

    def refuse(self, tr: FrameTrace,
               replaced_seq: Optional[int] = None) -> None:
        """The encoder did not take the frame as a new one. A queueing
        encoder dropped it: its span closes ``dropped@submit``. A mailbox
        encoder (a mesh lane keeps one pending frame per session) kept
        THIS frame and lost the one it had pending: it says under which
        seq (``replaced_seq``), the lost frame's span closes, and this
        one waits for the harvest in its place."""
        if replaced_seq is None:
            self._rec.drop(tr, "submit")
        else:
            self.add(replaced_seq, tr)

    def take(self, seq: Optional[int]) -> Optional[FrameTrace]:
        """The span of the frame harvested under ``seq``."""
        tr = self._by_seq.pop(seq, None) if seq is not None else None
        if tr is None and self._fifo:
            tr = self._fifo.popleft()
        return tr

    def drop_all(self, stage: str) -> None:
        """The encoder is going away with these frames inside it."""
        for tr in self._by_seq.values():
            self._rec.drop(tr, stage)
        self._by_seq.clear()
        while self._fifo:
            self._rec.drop(self._fifo.popleft(), stage)


class FlightRecorder:
    """Ring-buffer recorder of frame flights + open-span accounting.

    * :meth:`begin` opens a span; every opened span MUST reach exactly
      one of :meth:`close` / :meth:`drop` / :meth:`expire` /
      :meth:`drop_awaiting` — :meth:`open_spans` is the leak detector.
    * :meth:`sent` registers the span for ACK correlation under its
      (display, wire frame id); :meth:`ack` closes it with the true
      network round trip.
    * Completed spans land in a fixed ring (single write index, no
      locks); :meth:`summary` and :meth:`export_trace_events` read a
      consistent-enough snapshot of it (a torn read can at worst miss
      or double-see one in-rotation frame — fine for percentiles).

    ``clock`` is injectable for deterministic tests; call sites that
    already measured their own intervals pass absolute times instead.
    """

    #: default seconds before an un-terminated span is expired
    EXPIRE_AFTER_S = 30.0
    #: two marks of one thread state closer than this are one interval
    #: (the driver's 2 ms beat: sleep, a look at ``is_ready``, sleep)
    TRACK_MERGE_S = 1e-4

    def __init__(self, capacity: int = 4096, clock=time.monotonic) -> None:
        self.capacity = max(16, int(capacity))
        self._clock = clock
        #: thread track (thread, state, t0, t1): a driver thread writes
        #: a handful of rows per frame, so the ring is sized by the frames'
        self._track = _Ring(8 * self.capacity)
        #: clock pairs (device, t_enqueued, t_ready): four a second
        self._pairs = _Ring(self.capacity)
        #: stalls (kind, t0, t1), and the stacks caught during some
        self._stalls = _Ring(1024)
        self._stall_stacks: Dict[Tuple[str, float], str] = {}
        self._ring: List[Optional[FrameTrace]] = [None] * self.capacity
        self._widx = 0
        self._next_token = 1
        #: token -> open trace (every span not yet terminal)
        self._open: Dict[int, FrameTrace] = {}
        #: (display, frame_id) -> trace awaiting CLIENT_FRAME_ACK
        self._awaiting: Dict[Tuple[str, int], FrameTrace] = {}
        self.metrics = None          # observability.Metrics, wired lazily
        # terminal accounting (cheap mirrors, assertable without prom)
        self.closed_total = 0
        self.dropped_total = 0
        self.expired_total = 0
        self.acked_total = 0
        #: epoch anchor so trace-event timestamps are wall-clock-ish
        self._epoch_mono = clock()
        self._epoch_wall = time.time()

    # -- thread track, clock pairs, stalls ----------------------------------

    def thread_state(self, thread: str, state: str,
                     t0: float, t1: float) -> None:
        """``thread`` was in ``state`` over [t0, t1]. A mark that begins
        where the thread's last one of the same state ended extends it."""
        last = self._track.last()
        if (last is not None and last[0] == thread and last[1] == state
                and t0 - last[3] < self.TRACK_MERGE_S):
            self._track.replace_last((thread, state, last[2], t1))
        else:
            self._track.append((thread, state, t0, t1))

    def thread_track(self, thread: Optional[str] = None,
                     t0: Optional[float] = None,
                     t1: Optional[float] = None) -> List[tuple]:
        """Rows ``(thread, state, t0, t1)`` that overlap [t0, t1], by
        start time."""
        return sorted(
            (r for r in self._track.rows()
             if (thread is None or r[0] == thread)
             and (t0 is None or r[3] >= t0) and (t1 is None or r[2] <= t1)),
            key=lambda r: r[2])

    def clock_pair(self, device: int, t_enqueued: float,
                   t_ready: float) -> None:
        """One run of the device probe: enqueued at, seen ready at."""
        self._pairs.append((device, t_enqueued, t_ready))

    def clock_pairs(self, t0: Optional[float] = None,
                    t1: Optional[float] = None) -> List[tuple]:
        """Rows ``(device, t_enqueued, t_ready)`` seen ready in [t0, t1]."""
        return [r for r in self._pairs.rows()
                if (t0 is None or r[2] >= t0) and (t1 is None or r[2] <= t1)]

    def stall(self, kind: str, t0: float, t1: float,
              stack: Optional[str] = None) -> None:
        """The process could not have ``kind`` (``interpreter``: the
        interpreter's lock or the machine; ``loop``: the event loop)
        over [t0, t1]; ``stack`` is every thread's, caught meanwhile."""
        self._stalls.append((kind, t0, t1))
        if stack:
            self._stall_stacks[(kind, t0)] = stack
            while len(self._stall_stacks) > 64:
                self._stall_stacks.pop(next(iter(self._stall_stacks)))
        m = self.metrics
        if m is not None:
            try:
                m.observe_stall(kind, (t1 - t0) * 1000.0)
            except Exception:       # metrics must never break a watcher
                pass

    def stalls(self, t0: Optional[float] = None,
               t1: Optional[float] = None) -> List[tuple]:
        """Rows ``(kind, t0, t1)`` begun in [t0, t1]."""
        return [r for r in self._stalls.rows()
                if (t0 is None or r[1] >= t0) and (t1 is None or r[1] <= t1)]

    def stall_stack(self, kind: str, t0: float) -> Optional[str]:
        return self._stall_stacks.get((kind, t0))

    def pending(self) -> PendingSpans:
        """A capture loop's table of spans between submit and harvest."""
        return PendingSpans(self)

    # -- span lifecycle ----------------------------------------------------

    def begin(self, display: str, t: Optional[float] = None) -> FrameTrace:
        tr = FrameTrace(display, self._clock() if t is None else t)
        token = self._next_token
        self._next_token = token + 1
        tr._token = token
        self._open[token] = tr
        return tr

    def open_spans(self) -> int:
        """Spans opened but not yet terminal (the leak invariant)."""
        return len(self._open)

    def _retire(self, tr: FrameTrace, terminal: str) -> None:
        """Single exit gate: detach from the open/awaiting tables, stamp
        the terminal mark, rotate into the ring, publish metrics."""
        if tr.terminal is not None:     # already closed (idempotent)
            return
        tr.terminal = terminal
        self._open.pop(tr._token, None)
        if tr.frame_id >= 0:
            cur = self._awaiting.get((tr.display, tr.frame_id))
            if cur is tr:
                self._awaiting.pop((tr.display, tr.frame_id), None)
        self._ring[self._widx % self.capacity] = tr
        self._widx += 1
        self.closed_total += 1
        self._publish(tr)

    def close(self, tr: FrameTrace, terminal: str = "acked") -> None:
        if terminal == "acked":
            self.acked_total += 1
        self._retire(tr, terminal)

    def drop(self, tr: FrameTrace, stage: str) -> None:
        """Terminal ``dropped@<stage>``: the frame was lost there."""
        self.dropped_total += 1
        self._retire(tr, f"dropped@{stage}")

    def finish_empty(self, tr: FrameTrace) -> None:
        """Damage gating emitted nothing: a normal coalesced frame, not
        a loss — closed so the span cannot leak, kept out of the drop
        counters and the glass-to-glass series."""
        self._retire(tr, "empty")

    # -- ACK correlation ---------------------------------------------------

    def sent(self, tr: FrameTrace) -> None:
        """The frame's last stripe left the transport: register under
        its wire id so the client's CLIENT_FRAME_ACK can close it. A
        wire-id collision (2^16 wrap with a stalled client) expires the
        stale span rather than leaking it."""
        if tr.terminal is not None or tr.frame_id < 0:
            return
        key = (tr.display, tr.frame_id)
        old = self._awaiting.get(key)
        if old is not None and old is not tr:
            self.expired_total += 1
            self._retire(old, f"expired@{old.last_stage}")
        self._awaiting[key] = tr

    def ack(self, display: str, frame_id: int,
            t: Optional[float] = None) -> Optional[FrameTrace]:
        """CLIENT_FRAME_ACK landed: close the span with the true network
        round trip (send end -> ack arrival)."""
        tr = self._awaiting.pop((display, int(frame_id)), None)
        if tr is None:
            return None
        now = self._clock() if t is None else t
        send_iv = tr.spans.get("send")
        t0 = send_iv[1] if send_iv else tr.t_end
        tr.mark("ack", t0, max(t0, now))
        self.close(tr, "acked")
        return tr

    # -- leak control ------------------------------------------------------

    def expire(self, older_than_s: Optional[float] = None) -> int:
        """Close every open span older than the window (clients that
        never ACK, abandoned in-flight work). Returns how many."""
        horizon = self._clock() - (self.EXPIRE_AFTER_S
                                   if older_than_s is None
                                   else older_than_s)
        stale = [tr for tr in list(self._open.values()) if tr.t0 <= horizon]
        for tr in stale:
            self.expired_total += 1
            self._retire(tr, f"expired@{tr.last_stage}")
        return len(stale)

    def drop_awaiting(self, display: str, stage: str = "reset") -> int:
        """Pipeline reset / display teardown: frames sent but not yet
        ACKed will never be — their ids restart at 1. Returns how many
        spans were closed."""
        stale = [tr for (d, _fid), tr in list(self._awaiting.items())
                 if d == display]
        for tr in stale:
            self.drop(tr, stage)
        return len(stale)

    # -- metrics -----------------------------------------------------------

    def _publish(self, tr: FrameTrace) -> None:
        m = self.metrics
        if m is None:
            return
        try:
            for stage, iv in tr.spans.items():
                m.observe_stage(tr.display, stage,
                                (iv[1] - iv[0]) * 1000.0)
            if tr.terminal == "acked":
                m.observe_glass_to_glass(tr.display, tr.total_ms)
            enc = tr.encode_only_ms
            if enc is not None and tr.terminal != "empty":
                m.observe_encode_only(tr.display, enc)
            if tr.terminal and tr.terminal.startswith(("dropped@",
                                                       "expired@")):
                m.inc_trace_dropped(tr.terminal.split("@", 1)[1])
            m.set_trace_open_spans(len(self._open))
        except Exception:       # metrics must never break the frame path
            pass

    # -- readers -----------------------------------------------------------

    def _completed(self, display: Optional[str] = None,
                   last_s: Optional[float] = None) -> List[FrameTrace]:
        horizon = None if last_s is None else self._clock() - last_s
        out = []
        for tr in list(self._ring):
            if tr is None:
                continue
            if display is not None and tr.display != display:
                continue
            if horizon is not None and tr.t_end < horizon:
                continue
            out.append(tr)
        return out

    def summary(self, display: Optional[str] = None,
                last_s: Optional[float] = None) -> Dict[str, Any]:
        """Per-stage p50/p95/p99 plus the two headline series, over the
        ring (optionally filtered by display / recency)."""
        traces = self._completed(display, last_s)
        stages: Dict[str, Any] = {}
        for stage in STAGES:
            vals = sorted(d for tr in traces
                          if (d := tr.duration_ms(stage)) is not None)
            if vals:
                stages[stage] = {
                    "p50_ms": round(_pct(vals, 50), 3),
                    "p95_ms": round(_pct(vals, 95), 3),
                    "p99_ms": round(_pct(vals, 99), 3),
                    "n": len(vals),
                }
        g2g = sorted(tr.total_ms for tr in traces
                     if tr.terminal == "acked")
        enc = sorted(e for tr in traces if tr.terminal != "empty"
                     and (e := tr.encode_only_ms) is not None)
        out: Dict[str, Any] = {
            "frames": len(traces),
            "acked": sum(1 for t in traces if t.terminal == "acked"),
            "dropped": sum(1 for t in traces if t.terminal
                           and t.terminal.startswith("dropped@")),
            "open_spans": len(self._open),
            "stages": stages,
        }
        if g2g:
            out["glass_to_glass_p50_ms"] = round(_pct(g2g, 50), 1)
            out["glass_to_glass_p95_ms"] = round(_pct(g2g, 95), 1)
        if enc:
            out["encode_only_p50_ms"] = round(_pct(enc, 50), 1)
            out["encode_only_p95_ms"] = round(_pct(enc, 95), 1)
        return out

    def slowest(self, k: int = 5, display: Optional[str] = None
                ) -> List[Dict[str, Any]]:
        """Top-k slowest completed frames with their stage timelines."""
        traces = sorted(self._completed(display),
                        key=lambda t: t.total_ms, reverse=True)
        return [tr.as_dict() for tr in traces[:max(0, int(k))]]

    # -- Chrome trace-event (Perfetto) export ------------------------------

    def export_trace_events(self, last_s: Optional[float] = None,
                            include_open: bool = False) -> Dict[str, Any]:
        """The last N seconds as Chrome trace-event JSON: load the
        result at https://ui.perfetto.dev (docs/observability.md has the
        walkthrough). One process per display, one thread row per frame
        (rows recycle mod a small constant so the view stays readable),
        one complete ("X") slice per stage."""
        events: List[Dict[str, Any]] = []
        pids: Dict[str, int] = {}
        traces = self._completed(None, last_s)
        if include_open:
            traces = traces + list(self._open.values())
        for tr in traces:
            pid = pids.setdefault(tr.display, len(pids) + 1)
            tid = (tr.frame_id if tr.frame_id >= 0 else tr._token) % 64 + 1
            for stage, iv in sorted(tr.spans.items(),
                                    key=lambda kv: kv[1][0]):
                events.append({
                    "name": stage,
                    "cat": "frame",
                    "ph": "X",
                    "pid": pid,
                    "tid": tid,
                    "ts": round((iv[0] - self._epoch_mono) * 1e6, 1),
                    "dur": round(max(0.0, iv[1] - iv[0]) * 1e6, 1),
                    "args": {
                        "frame_id": tr.frame_id,
                        "display": tr.display,
                        "terminal": tr.terminal or "open",
                        # unique per span: consumers regrouping events
                        # must not merge distinct frames that share a
                        # recycled tid and frame_id -1 (never-sent drops)
                        "span": tr._token,
                    },
                })
        for display, pid in pids.items():
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"display:{display}"},
            })
        events.extend(self._export_tracks(len(pids) + 1, last_s))
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "recorder": "selkies-tpu flight recorder",
                "epoch_unix_s": round(self._epoch_wall, 3),
                "open_spans": len(self._open),
            },
        }


    def _export_tracks(self, pid: int,
                       last_s: Optional[float]) -> List[Dict[str, Any]]:
        """One more process: a row per worker thread (its states), one
        for the device probe (enqueued -> ready) and one for stalls."""
        horizon = None if last_s is None else self._clock() - last_s
        rows: List[Tuple[str, str, float, float]] = list(
            self.thread_track(t0=horizon))
        rows += [(f"device probe {dev}", "selkies_clock_probe", a, b)
                 for dev, a, b in self.clock_pairs(t0=horizon)]
        rows += [("stalls", kind, a, b)
                 for kind, a, b in self.stalls()
                 if horizon is None or b >= horizon]
        if not rows:
            return []
        tids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "threads"}}]
        for thread, state, a, b in rows:
            tid = tids.get(thread)
            if tid is None:
                tid = tids[thread] = len(tids) + 1
                events.append({"name": "thread_name", "ph": "M", "pid": pid,
                               "tid": tid, "args": {"name": thread}})
            events.append({
                "name": state, "cat": "thread", "ph": "X", "pid": pid,
                "tid": tid, "ts": round((a - self._epoch_mono) * 1e6, 1),
                "dur": round(max(0.0, b - a) * 1e6, 1)})
        return events


# ---------------------------------------------------------------------------
# jax.profiler capture hook (served at /debug/jax-trace)


_JAX_TRACE_LOCK = threading.Lock()


def capture_jax_trace(out_dir: str, duration_ms: float) -> Dict[str, Any]:
    """Run a ``jax.profiler`` trace for ``duration_ms`` into ``out_dir``
    so device-side stalls can be correlated with the host-side spans.
    Serialized (one capture at a time); raises on an unavailable
    profiler — the HTTP layer maps that to an error response."""
    import jax

    duration_s = min(30.0, max(0.01, float(duration_ms) / 1000.0))
    if not _JAX_TRACE_LOCK.acquire(blocking=False):
        raise RuntimeError("a jax trace capture is already running")
    try:
        with jax.profiler.trace(out_dir):
            time.sleep(duration_s)
    finally:
        _JAX_TRACE_LOCK.release()
    return {"path": out_dir, "duration_ms": duration_s * 1000.0}
