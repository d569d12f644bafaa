"""Async pipeline driver: kills the dispatch/fetch floor (ISSUE 12).

The device encodes a 1080p H.264 frame in ~15 ms, yet the served encode
share measured ~20x that: the capture loop drove the encoder in lockstep
— every frame paid a dispatch round trip plus a blocking D2H fetch on
the shared event loop (ThreadedEncoderAdapter serialized the two inside
one worker ``encode_frame`` call). The low-latency GPU-encoder
literature (PAPERS.md: NVENC 4K low-latency, NVENC-efficiency) says the
fix plainly: hardware encoders only hit their rated latency when the
submission queue never drains.

:class:`AsyncEncodeDriver` restructures the path so the chip never
idles waiting on the host:

* the capture loop's ``try_submit``/``poll`` become pure queue
  operations — no device interaction ever runs on the event loop;
* a dedicated driver thread owns the pipelined encoder
  (:mod:`.pipeline`) and keeps >=2 frames in flight end-to-end:
  frame N+1 is dispatched while frame N's eagerly-started
  ``copy_to_host_async`` completes;
* host frames double-buffer through the donated staging ring
  (:class:`.h264_device.StagingRing`), so H2D upload overlaps the
  previous frame's compute and donation never serializes dispatches;
* between the two stands a mailbox of ONE capture, latest wins: a
  capture that finds an older one still waiting for a slot of the pipe
  takes its place (under its seq; the older one is the frame lost,
  counted), so the chip's next frame is the newest picture and never
  the oldest of a backlog — the contract a mesh lane's facade speaks
  (``MeshSessionFacade.try_submit``), and the event loop never stalls;
* the pipe is bounded twice (``pipe.has_room``): ``depth`` frames
  between dispatch and pack, which is the host's slack and the staging
  ring, and of them two steps unfinished on the chip once a capture is
  launched, one running and one queued — a capture waits in the mailbox,
  replaceable, until the chip can nearly take it, and not on the chip
  behind work it was given earlier;
* ``flush()`` drains deterministically; ``close()`` mid-flight neither
  deadlocks nor leaks a staging slot, so PR 2 supervisor restarts and
  PR 3 evictions stay safe.

docs/pipeline.md describes the in-flight model and flush semantics.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

logger = logging.getLogger("selkies_tpu.encoder.async_driver")

#: fault point checked at the driver's harvest site (same name the
#: capture loop uses for its async stall, so one SELKIES_TPU_FAULTS
#: entry can wedge either side of the fetch)
FETCH_HANG_POINT = "fetch.hang"


class AsyncEncodeDriver:
    """Non-blocking facade + driver thread around a pipelined encoder.

    ``pipe`` is a :class:`~.pipeline.PipelinedJpegEncoder` or
    :class:`~.pipeline.PipelinedH264Encoder`; the driver is its only
    user after construction, so the pipe needs no locking of its own.

    Capture-loop surface (same duck type the server already speaks):
    ``try_submit`` / ``poll`` / ``flush`` / ``force_keyframe`` /
    ``close`` / ``stats`` / ``metrics`` / ``on_error`` — plus
    ``wire_fullframe`` for the server's stripe packer.
    """

    #: seconds the driver thread sleeps between harvest polls when work
    #: is in flight but nothing is ready (an ``is_ready`` check is
    #: cheap; the short beat keeps both submit and harvest latency low)
    POLL_INTERVAL_S = 0.002

    def __init__(self, pipe, *, wire_fullframe: bool = False,
                 metrics=None, faults=None) -> None:
        self.pipe = pipe
        self.wire_fullframe = bool(wire_fullframe)
        self._metrics = metrics
        pipe.metrics = metrics
        #: the server's FlightRecorder, attached like ``metrics``: the
        #: driver thread writes its states to the recorder's thread track
        #: (and the pipe its own, through ``pipe.track``)
        self._recorder = None
        #: fault injector (server wires its own in); checked with the
        #: sync variant at the harvest site, where a stalled D2H would
        #: really block
        self.faults = faults
        #: server ladder hook: called with the exception for every frame
        #: lost to a device/entropy error (driver thread context)
        self.on_error: Optional[Callable[[BaseException], None]] = None
        #: capture-loop hook: called with nothing, on the driver thread,
        #: each time results were appended to ``_out`` — the loop's cue to
        #: ``poll()`` now and not at its next tick. It only signals; the
        #: loop stays the one consumer of ``_out``
        self.on_ready: Optional[Callable[[], None]] = None
        self.on_ready_errors_total = 0
        #: frames the capture loop took at a tick, at such a cue
        #: (:meth:`count_harvests`)
        self.harvests_total = [0, 0]

        self._cond = threading.Condition()
        #: the mailbox: the one capture waiting for a slot of the pipe,
        #: (driver_seq, frame, t_accepted), or empty
        self._in_q: deque = deque()
        self._out: deque = deque()           # (driver_seq, stripes)
        #: see :meth:`try_submit`
        self.replaced_seq: Optional[int] = None
        #: driver seq -> (accepted into _in_q, taken out): the two ends of
        #: ``submit_wait``, and where ``pipe_wait`` begins
        self._waits: dict = {}
        #: driver_seq -> flight-recorder stage intervals harvested with
        #: the frame (pulled from the pipe at emit time, under _cond, so
        #: the event-loop pop never touches pipe state the driver thread
        #: is mutating); bounded like the pipe's own trace store
        self._trace_out: dict = {}
        #: pipe seq -> driver seq, recorded per successful submit: a
        #: frame the pipe never accepted has no entry, so its loss can
        #: never shift later results onto wrong driver seqs
        self._seq_map: dict = {}
        self._seq = 0
        self._flush_req = 0                  # flush generation counter
        self._flush_ack = 0
        self._stop = False
        self.frames_dropped_total = 0
        #: captures that took a waiting capture's place (each lost that
        #: older one: a subset of ``frames_dropped_total``)
        self.frames_replaced_total = 0
        self.encode_errors_total = 0
        self._error_streak = 0
        #: pipe.stats() snapshot maintained by the driver thread — the
        #: event-loop stats() surface must not iterate deques the driver
        #: thread is mutating
        self._stats_cache = dict(pipe.stats())
        self._thread = threading.Thread(
            target=self._run, name="tpuenc-async", daemon=True)
        self._thread.start()

    # -- event-loop surface (never blocks) --------------------------------

    @property
    def metrics(self):
        return self._metrics

    @metrics.setter
    def metrics(self, m) -> None:
        # the server attaches its Metrics after construction; the pipe
        # publishes the d2h/inflight gauges, so it needs the handle too
        self._metrics = m
        self.pipe.metrics = m

    @property
    def recorder(self):
        return self._recorder

    @recorder.setter
    def recorder(self, rec) -> None:
        # the server hands over the recorder it holds when the capture
        # loop starts (never the one build() made: the benchmark swaps it)
        self._recorder = rec
        self.pipe.track = None if rec is None else self._track

    def _track(self, state: str, t0: float, t1: float) -> None:
        """One state of this driver thread, to the recorder's thread
        track. The pipe calls it with the clock readings it already
        takes for the frame's stages."""
        rec = self._recorder
        if rec is not None:
            rec.thread_state(self._thread.name, state, t0, t1)

    def try_submit(self, frame) -> Optional[int]:
        """Hand the driver thread its next capture; never refuses one
        while the driver runs. The seq the frame will harvest under; None
        when it took the place of a capture still waiting for a slot of
        the pipe (the mailbox holds one): the OLDER capture is the one
        lost, and ``replaced_seq`` then says under which seq this one
        will harvest, so that the capture loop can hand the lost frame's
        place in its records to this one. ``replaced_seq`` is None after
        every call that replaced nothing."""
        t_accepted = time.monotonic()
        with self._cond:
            self.replaced_seq = None
            if self._stop:
                return None
            if self._in_q:
                # the survivor waits from its OWN acceptance on
                seq = self._in_q[0][0]
                self._in_q[0] = (seq, frame, t_accepted)
                self.frames_dropped_total += 1
                self.frames_replaced_total += 1
                if self._metrics is not None:
                    self._metrics.inc_frames_dropped()
                self.replaced_seq = seq
                return None
            seq = self._seq
            self._seq += 1
            self._in_q.append((seq, frame, t_accepted))
            self._cond.notify_all()
            return seq

    def submit(self, frame) -> Optional[int]:
        """Alias of :meth:`try_submit` — this facade NEVER blocks the
        caller (the capture loop's contract)."""
        return self.try_submit(frame)

    def poll(self) -> List[Tuple[int, list]]:
        """Harvest whatever the driver thread completed (pure queue
        drain; ordering follows submission order)."""
        with self._cond:
            out = list(self._out)
            self._out.clear()
        return out

    def count_harvests(self, n: int, on_ready: bool) -> None:
        """The capture loop says at which of its wake-ups it took ``n``
        polled frames, for the ``stats()`` line (the loop's thread is the
        only writer)."""
        self.harvests_total[on_ready] += n

    def flush(self, timeout: float = 60.0) -> List[Tuple[int, list]]:
        """Drain everything submitted so far (deterministic: on return
        the mailbox is empty and every capture accepted has been
        harvested, replaced by a later one that was, or accounted as an
        error). Blocks the caller — warm-up/teardown paths only."""
        with self._cond:
            if not self._thread.is_alive():
                out = list(self._out)
                self._out.clear()
                return out
            self._flush_req += 1
            want = self._flush_req
            self._cond.notify_all()
            self._cond.wait_for(
                lambda: self._flush_ack >= want or self._stop,
                timeout=timeout)
            out = list(self._out)
            self._out.clear()
        return out

    def close(self) -> None:
        """Stop the driver and abandon its frames, the waiting capture and
        those in flight (display teardown, supervised restart). NEVER
        blocks the caller: teardown runs on the event loop, where a join
        would stall every display sharing it. All cleanup (pipe.close +
        ring release) happens on the driver thread as it exits —
        releasing the rings from HERE would race the thread's current
        dispatch and defeat the use-after-donate guard. A thread wedged
        in a dead device fetch is abandoned with its (equally abandoned)
        pipe — the bounded exposure ThreadedEncoderAdapter also
        documents, policed by the server's wedge_faults cap; the
        supervised restart builds a fresh pipeline with fresh rings
        either way."""
        with self._cond:
            self._stop = True
            self._in_q.clear()
            self._cond.notify_all()

    # -- control passthrough ----------------------------------------------

    def request_keyframe(self) -> None:
        kick = getattr(self.pipe, "force_keyframe", None) \
            or getattr(self.pipe, "request_keyframe", None)
        if kick is not None:
            kick()

    force_keyframe = request_keyframe

    @property
    def qp(self):
        return getattr(self.pipe, "qp", None)

    @qp.setter
    def qp(self, value):
        if hasattr(type(self.pipe), "qp"):
            self.pipe.qp = value

    @property
    def n_inflight(self) -> int:
        return self.pipe.n_inflight + len(self._in_q)

    def stats(self) -> dict:
        """Pipe gauges plus the driver's own accounting (shape-compatible
        with the other encoder adapters for health feeds and bench).
        Reads the driver thread's snapshot of pipe.stats() — calling the
        pipe directly from here would iterate deques the driver thread
        mutates concurrently."""
        with self._cond:
            # (first: a reader that prints the head of the line shows it)
            st = {"frames_replaced": self.frames_replaced_total,
                  "harvests_on_ready": self.harvests_total[True],
                  "harvests_on_tick": self.harvests_total[False],
                  **self._stats_cache}
            st["submit_queue_depth"] = len(self._in_q)     # 0 or 1
        st["frames_dropped"] = (st.get("frames_dropped", 0)
                                + self.frames_dropped_total)
        st["encode_errors"] = self.encode_errors_total
        return st

    # -- driver thread ------------------------------------------------------

    def compiling_for_s(self) -> float:
        """Seconds the wrapped pipeline has been inside a program's
        first-use compile (0.0 otherwise) — the capture loop's cue that a
        quiet driver is compiling, not wedged."""
        probe = getattr(self.pipe, "compiling_for_s", None)
        return probe() if probe is not None else 0.0

    def pop_trace(self, seq: int):
        """Stage intervals for a harvested frame, keyed by DRIVER seq
        (the seq try_submit returned) — the capture loop's side of the
        flight-recorder contract."""
        with self._cond:
            return self._trace_out.pop(seq, None)

    def _emit(self, results) -> None:
        if not results:
            return
        pop_tr = getattr(self.pipe, "pop_trace", None)
        t_emit0 = time.monotonic()
        with self._cond:
            for pipe_seq, stripes in results:
                seq = self._seq_map.pop(pipe_seq, pipe_seq)
                waits = self._waits.pop(seq, None)
                if pop_tr is not None:
                    tr = pop_tr(pipe_seq)
                    if tr:
                        if waits is not None:
                            # the frame's two waits on this side of the
                            # pipe: in the mailbox, then behind the pass's
                            # work until its staging began
                            first = tr.get("stage") or tr.get("dispatch")
                            tr["submit_wait"] = waits
                            if first is not None:
                                tr["pipe_wait"] = (
                                    waits[1], max(waits[1], first[0]))
                        self._trace_out[seq] = tr
                        while len(self._trace_out) > 4 * self.pipe.depth:
                            self._trace_out.pop(
                                next(iter(self._trace_out)))
                self._out.append((seq, stripes))
            # results arrive in pipe order: mappings below the newest
            # emitted pipe seq belong to frames the pipe lost to errors
            # and will never be yielded — drop them so the map stays
            # bounded
            horizon = results[-1][0]
            for k in [k for k in self._seq_map if k < horizon]:
                self._waits.pop(self._seq_map.pop(k), None)
            self._cond.notify_all()
        on_ready = self.on_ready
        if on_ready is not None:
            # outside _cond: the hook wakes another thread's event loop
            try:
                on_ready()
            except Exception:
                # a loop that has gone (teardown) must not cost a frame
                self.on_ready_errors_total += 1
                logger.debug("on_ready hook failed", exc_info=True)
        self._track("emit", t_emit0, time.monotonic())

    def _harvest(self, flush_partial: bool, wait: bool = False) -> bool:
        """One harvest pass; True if anything completed. ``wait`` blocks
        until the pipe's oldest frame is in (the pipe marks that
        ``fetch_wait``): only ever asked with a capture waiting behind a
        pipe with no room, when nothing else is left for this thread to
        do."""
        if self.faults is not None:
            self.faults.maybe_hang_sync(FETCH_HANG_POINT)
        results = self.pipe.poll(flush_partial=flush_partial, wait=wait)
        self._emit(results)
        return bool(results)

    def _run(self) -> None:
        try:
            while self._run_pass():
                pass
        finally:
            # thread-side cleanup: close() must never block the event
            # loop, so the pipe teardown happens HERE, where the pipe's
            # single-owner discipline makes it race-free
            self._cleanup()

    def _feed(self) -> None:
        """Dispatch the waiting capture into a free slot of the pipe. A
        capture leaves the mailbox only when the pipe has room for it
        (fewer than ``depth`` frames unpacked and, of them, at most the
        running step unfinished on the chip: ``pipe.has_room``): what
        cannot be launched yet waits where ``try_submit`` sees it (and
        puts a newer one in its place), not in a list of this thread's
        behind a submit that blocks, nor on the chip behind steps it was
        given earlier. An erroring frame costs ITSELF
        (counted + reported), never the rest of the pass; a frame the
        pipe never accepted gets no seq mapping, so its loss cannot shift
        later results onto wrong seqs."""
        # (the pipe is asked only with a capture waiting: it counts the
        # launches it holds back)
        while self._in_q and self.pipe.has_room:
            with self._cond:
                if self._stop or not self._in_q:
                    return
                seq, frame, t_accepted = self._in_q.popleft()
                self._waits[seq] = (t_accepted, time.monotonic())
            try:
                pipe_seq = self.pipe.submit(frame)
            except Exception as exc:
                self._waits.pop(seq, None)
                self._count_error(exc)
            else:
                if pipe_seq is not None:
                    with self._cond:
                        self._seq_map[pipe_seq] = seq
                else:
                    self._waits.pop(seq, None)

    def _run_pass(self) -> bool:
        """One driver pass; False when the driver is stopping."""
        with self._cond:
            if self._stop:
                return False
            flush_want = self._flush_req
        flushing = flush_want > self._flush_ack
        # 1. feed the device first: a free slot of the pipe takes the
        # waiting capture
        self._feed()
        try:
            # 2. harvest whatever is ready. With a capture still waiting
            # the pipe has no room: block for its oldest frame, the one
            # whose end makes room (its slot, and the chip's queue: frames
            # are packed in order, so the oldest unpacked is the step that
            # runs or one that has ended); the step behind it keeps the
            # device busy while this thread packs it, and the next pass
            # launches the capture that is the newest then.
            with self._cond:
                backlog = bool(self._in_q)
            # (with the mailbox dry, JPEG's partly filled fetch group ships)
            self._harvest(
                flush_partial=not backlog,
                wait=backlog and not self.pipe.has_room)
            self._error_streak = 0
        except Exception as exc:
            # harvest failure: completed frames stay queued in the
            # pipe's ready list (surfacing next pass); the lost frame's
            # stale seq mapping is pruned at the next emit
            self._count_error(exc)
        # 3. explicit flush, once every capture accepted before it has
        # been dispatched: drain the pipe COMPLETELY — a mid-drain
        # error costs its frame (counted) and the drain resumes, so
        # the ack below never strands unharvested frames behind a
        # raising one. Each failed drain removes at least the raising
        # frame, so this terminates.
        if flushing and not backlog:
            while True:
                try:
                    self._emit(self.pipe.flush())
                    break
                except Exception as exc:
                    self._count_error(exc)
                    if self.pipe.n_inflight == 0:
                        break
        with self._cond:
            self._stats_cache = dict(self.pipe.stats())
            if flushing and not backlog:
                # flush() returns once everything submitted before it
                # either completed or was accounted as an error — never
                # strands (a capture accepted since is a later flush's)
                self._flush_ack = flush_want
                self._cond.notify_all()
            if self._stop:
                return False
            if self._in_q or self._flush_req > self._flush_ack:
                return True
            # in-flight work pending: short beat, then re-poll.
            # Otherwise sleep until new work arrives.
            waiting = self.pipe.n_inflight > 0
            t_sleep0 = time.monotonic()
            self._cond.wait(self.POLL_INTERVAL_S if waiting else 0.25)
            t_sleep1 = time.monotonic()
        self._track("sleep", t_sleep0, t_sleep1)
        return True

    def _cleanup(self) -> None:
        # pipe.close() owns ring release (both pipelines force-release
        # their staging lanes as their last close step)
        close = getattr(self.pipe, "close", None)
        if close is not None:
            try:
                close()
            except Exception:
                logger.exception("pipe close raised")

    def _count_error(self, exc: BaseException) -> None:
        """A device/entropy failure costs its frame; it is COUNTED,
        REPORTED to the ladder hook, and survived — the supervisor owns
        escalation, not this thread."""
        self.encode_errors_total += 1
        if self._metrics is not None:
            self._metrics.inc_encode_errors()
        logger.exception("async encode pass failed")
        if self.on_error is not None:
            try:
                self.on_error(exc)
            except Exception:
                logger.exception("on_error hook failed")
        self._error_streak += 1
        # interruptible backoff: close() must not wait out an error storm
        with self._cond:
            if not self._stop:
                self._cond.wait(min(1.0, 0.05 * self._error_streak))
