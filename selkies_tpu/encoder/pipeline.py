"""Pipelined frame encoder: overlaps device dispatch, D2H, and host assembly.

JAX dispatch is asynchronous; the only blocking points are host reads. This
wrapper keeps several frames in flight so per-frame round-trip latency is
hidden behind throughput: submit(frame_N) while harvesting frame_{N-depth}.

Transfer economics drive the design: every D2H read has a fixed cost
whatever its size. The encode step therefore packs the per-frame metadata
(sizes, stripe bases, overflow, damage) into the head of the bitstream
buffer (jpeg._device_pipeline), and this pipeline fetches metadata + payload
as ONE predicted-size read per frame; only a size-prediction miss (bitrate
spike) costs a second read. The prediction adapts to the recent largest
frame plus one bucket of headroom.

The reference achieves the same overlap with pixelflux's capture/encode C++
threads feeding an asyncio queue (selkies.py:2865-2894); here the "threads"
are the device stream plus async host copies.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..observability.device_probe import ReadyStamp, ReadyWatch
from .h264_device import StagingRing, StagingTicket
from .jpeg import META_WORDS_PER_STRIPE, JpegStripeEncoder, StripeOutput, split_meta


def _p50(samples) -> float:
    """Median of a bounded timing window (0.0 when empty)."""
    if not samples:
        return 0.0
    s = sorted(samples)
    return float(s[len(s) // 2])


class _PipelineTelemetry:
    """Shared dispatch/fetch instrumentation for the pipelined encoders
    (ISSUE 12): bounded timing windows, the in-flight high-water mark,
    and the stats()/metrics publication — one implementation so the two
    pipelines cannot drift. Subclasses provide ``inflight_batches`` and
    a ``metrics`` attribute.

    Flight-recorder hookup (ISSUE 13): per-frame stage intervals
    (stage/dispatch/in_device/fetch_wait/pack, absolute ``time.monotonic``
    ``(start, end)`` pairs) accumulate on the in-flight item and are
    published under the frame's seq at harvest; the capture loop pops
    them with :meth:`pop_trace` and folds them into that frame's
    :class:`~selkies_tpu.observability.tracing.FrameTrace`. The same
    clock readings go to ``track(state, t0, t1)`` when the owner (the
    async driver) has set it: the owning thread's timeline.

    The ready watch (ISSUE 42): each launch hands its step's own output to
    a ``tpuenc-ready`` thread, which stamps when it became ready; at
    harvest the stamp splits ``in_device`` + ``fetch_wait`` into
    ``device_wait``, ``device_run`` and ``ready_wait``
    (observability/device_probe.py ``ready_stages``).

    The pipe is bounded twice (ISSUE 47, :attr:`has_room`): ``depth``
    bounds the frames between dispatch and pack (the staging ring, and
    what the host has still to pack), ``CHIP_STEPS`` the steps of them
    that the chip has still to do."""

    #: ``callable(state, t0, t1)`` or None: where the thread that drives
    #: this pipe reports the states it is in (AsyncEncodeDriver sets it)
    track = None

    #: steps of this stream the chip holds once a capture is launched: the
    #: one that runs and one queued behind it, which is all it takes for
    #: the chip to go from a step to the next without waiting for the
    #: host. A step queued beyond that buys nothing and ages its capture
    #: by a whole step (PERF.md: ``device_wait`` 39 ms above the knee)
    CHIP_STEPS = 2

    def _init_telemetry(self) -> None:
        self._dispatch_ms: deque = deque(maxlen=256)
        self._fetch_wait_ms: deque = deque(maxlen=256)
        self.inflight_batches_max = 0
        #: seq -> {stage: (t_start, t_end)} for harvested frames, pruned
        #: oldest-first so an un-popping caller (tests, mesh) can
        #: never grow it unboundedly
        self._trace_out: "dict" = {}
        self._ready_watch = ReadyWatch("tpuenc-ready")
        #: launches that ``has_room`` held back for the chip's queue while
        #: ``depth`` alone would have admitted them (and whether the one
        #: to come has been)
        self.launches_held_for_chip = 0
        self._held_for_chip = False

    def compiling_for_s(self) -> float:
        """The base encoder's first-use compile signal (runtime.CompileWatch)."""
        return self.base.compile_watch.compiling_for_s()

    @property
    def has_room(self) -> bool:
        """What the async driver asks, with a capture waiting, before it
        takes it out of its mailbox. Two bounds: fewer than ``depth``
        frames between dispatch and pack (``submit`` would not block for
        the oldest first), and fewer than ``CHIP_STEPS`` steps of them
        unfinished on the chip, so that the capture launched is the
        freshest at the moment the chip can nearly take it. The steps
        unfinished are the ready watch's count, never taken to exceed the
        pipe's own frames: an empty pipe always has room, and stamps that
        never land cannot hold a capture back for ever (the driver blocks
        for the oldest frame while there is no room, and each one packed
        is one fewer). A stopped watch counts nothing: ``depth`` alone."""
        n = self.n_inflight
        if n >= self.depth:
            return False
        watch = self._ready_watch
        if watch.stopped or min(watch.ahead, n) < self.CHIP_STEPS:
            return True
        self._held_for_chip = True
        return False

    def _launched(self, step_out: Any, ahead: int) -> Optional[ReadyStamp]:
        """One step was launched with ``ahead`` before it unfinished:
        hand its own output to the ready watch, and count the launch if
        the chip's queue had held it back."""
        self.launches_held_for_chip += self._held_for_chip
        self._held_for_chip = False
        return self._ready_watch.launched(step_out, ahead)

    def _mark(self, trace: Optional[dict], state: str,
              t0: float, t1: float) -> None:
        """One work interval: a stage of the frame(s) it was done for,
        and a state of the thread that did it."""
        if trace is not None:
            trace[state] = (t0, t1)
        if self.track is not None:
            self.track(state, t0, t1)

    def _trace_store(self, seq: int, intervals: dict,
                     ready: Optional[ReadyStamp] = None) -> None:
        if not intervals:
            return
        d, f = intervals.get("dispatch"), intervals.get("fetch_wait")
        if d is not None and f is not None:
            # launched -> the driver saw the result ready (or began to
            # block for it): queued behind earlier steps and running
            intervals["in_device"] = (d[1], max(d[1], f[0]))
            # the same time and the fetch, told apart by when the step's
            # output became ready
            intervals.update(self._ready_watch.stages(d[1], f[1], ready))
        self._trace_out[seq] = intervals
        while len(self._trace_out) > 4 * max(8, getattr(self, "depth", 8)):
            self._trace_out.pop(next(iter(self._trace_out)))

    def pop_trace(self, seq: int):
        """Stage intervals for a harvested frame (once; None if unknown)."""
        return self._trace_out.pop(seq, None)

    def _note_inflight(self) -> None:
        self.inflight_batches_max = max(self.inflight_batches_max,
                                        self.inflight_batches)

    def _record_dispatch(self, ms: float) -> None:
        self._dispatch_ms.append(ms)
        self._note_inflight()
        if self.metrics is not None:
            self.metrics.observe_dispatch(ms)

    def _record_fetch_wait(self, ms: float) -> None:
        self._fetch_wait_ms.append(ms)
        if self.metrics is not None:
            self.metrics.observe_fetch_wait(ms)

    def _materialize(self, group: "_FetchGroup") -> None:
        """Bring one fetch group's host copy in (blocks until it is
        there): the ``fetch_wait`` of every member frame."""
        t0 = time.monotonic()
        group.host = np.asarray(group.arr)
        t1 = time.monotonic()
        group.fetch_iv = (t0, t1)
        self._mark(None, "fetch_wait", t0, t1)
        self._record_fetch_wait((t1 - t0) * 1000.0)
        self.d2h_bytes_total += group.host.nbytes

    def _telemetry_stats(self) -> dict:
        return {
            "inflight_batches": self.inflight_batches,
            "inflight_batches_max": self.inflight_batches_max,
            "dispatch_p50_ms": round(_p50(self._dispatch_ms), 3),
            "fetch_wait_p50_ms": round(_p50(self._fetch_wait_ms), 3),
            **self._ready_watch.counts(),
            "launches_held_for_chip": self.launches_held_for_chip,
        }

    def _publish_launch_idle(self, st: dict) -> None:
        if st["launches"]:
            self.metrics.set_launch_idle_share(
                st["launches_into_idle"] / st["launches"])
            self.metrics.set_launch_held_for_chip_share(
                st["launches_held_for_chip"] / st["launches"])


@dataclass
class _FetchGroup:
    """One D2H read and the JPEG frames it brings: their packed buffers
    concatenated on device. A ``fetch_group`` above 1 was sized for a
    device that paid a fixed latency per read; whether this one still
    wants it is ROADMAP D8."""

    arr: Any                        # device array, one async host copy
    stride: int = 0                 # member size in a 1-D concat; 0: one
    host: Optional[np.ndarray] = None
    #: host-blocked interval materializing this group's copy (shared by
    #: every member frame's trace: the wait gated them all)
    fetch_iv: Optional[Tuple[float, float]] = None


@dataclass
class _InFlight:
    seq: int
    paint_candidate: np.ndarray
    packed: Any                     # full device buffer (meta head + words)
    yq: Any
    cbq: Any
    crq: Any
    group: Optional[_FetchGroup] = None
    group_index: int = 0
    guess_words: int = 0
    meta_done: bool = False
    emit: Optional[np.ndarray] = None
    is_paint: Optional[np.ndarray] = None
    refetch: Any = None             # second read when prediction missed
    meta: Tuple[Optional[np.ndarray], ...] = (None, None, None)
    words_np: Optional[np.ndarray] = None
    ticket: Optional[StagingTicket] = None
    #: per-frame stage intervals for the flight recorder
    trace: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    #: when this frame's step was ready (and the step launched before it)
    ready: Optional[ReadyStamp] = None


class PipelinedJpegEncoder(_PipelineTelemetry):
    """Depth-N pipelined wrapper around a device-entropy JpegStripeEncoder.

    Usage::

        enc = PipelinedJpegEncoder(JpegStripeEncoder(w, h))
        enc.submit(frame)                 # non-blocking dispatch
        for seq, stripes in enc.poll():   # harvest whatever completed
            ...
        enc.flush()                       # drain everything (blocking)
    """

    def __init__(self, base: JpegStripeEncoder, depth: int = 8,
                 fetch_group: int = 1, metrics=None) -> None:
        if base.entropy != "device":
            raise ValueError("pipelining requires entropy='device'")
        self.base = base
        self.depth = depth
        self.fetch_group = max(1, fetch_group)
        self._inflight: deque[_InFlight] = deque()
        self._unfetched: List[_InFlight] = []
        self._ready: List[Tuple[int, List[StripeOutput]]] = []
        self._seq = 0
        self._meta_words = META_WORDS_PER_STRIPE * base.n_stripes
        self._guess = base._packer.bucket_words(8192)
        #: D2H / host-entropy accounting (observability/metrics.py gauges
        #: d2h_bytes_per_frame + host_entropy_ms_per_frame)
        self.metrics = metrics
        self.d2h_bytes_total = 0
        self.host_entropy_ms_total = 0.0
        self.frames_completed = 0
        #: frames rejected by try_submit because the pipeline was full —
        #: surfaced in stats()/metrics instead of vanishing (ISSUE 2)
        self.frames_dropped_total = 0
        #: donated H2D staging lane (ISSUE 12): host frames double-buffer
        #: through a ring instead of allocating per dispatch, so upload
        #: overlaps the previous frame's encode. Sized so every in-flight
        #: frame can hold a slot without stalling the ring.
        self._staging = StagingRing(depth=depth + 1)
        self._init_telemetry()

    @property
    def inflight_batches(self) -> int:
        """Fetch groups dispatched but not yet materialized on the host —
        the ISSUE 12 acceptance gauge (>=2 in steady state means the chip
        never waits on a lockstep host round trip). Dispatched-but-
        ungrouped frames count as one forming group."""
        groups = {id(it.group) for it in self._inflight
                  if it.group is not None and it.group.host is None}
        return len(groups) + (1 if self._unfetched else 0)

    def stats(self) -> dict:
        """Per-frame transfer/host-entropy gauges over the run so far."""
        n = max(1, self.frames_completed)
        return {
            "frames": self.frames_completed,
            "d2h_bytes_per_frame": self.d2h_bytes_total / n,
            "host_entropy_ms_per_frame": self.host_entropy_ms_total / n,
            "frames_dropped": self.frames_dropped_total,
            "host_fallback_stripes": self.base.host_fallback_stripes_total,
            "stripes_emitted": self.base.stripes_emitted_total,
            "staging_stalls": self._staging.stalls_total,
            **self._telemetry_stats(),
            "entropy": self.base.entropy,
        }

    def _publish_metrics(self) -> None:
        if self.metrics is not None and self.frames_completed:
            st = self.stats()
            self.metrics.set_d2h_bytes_per_frame(st["d2h_bytes_per_frame"])
            self.metrics.set_host_entropy_ms_per_frame(
                st["host_entropy_ms_per_frame"])
            self.metrics.set_inflight_batches(st["inflight_batches"])
            self._publish_launch_idle(st)

    @property
    def n_inflight(self) -> int:
        return len(self._inflight)

    def force_keyframe(self) -> None:
        """Next frame emits every stripe (viewer join / PIPELINE reset)."""
        self.base.force_keyframe()

    def try_submit(self, frame) -> Optional[int]:
        """Dispatch one frame without ever blocking; returns None (frame
        dropped) when the pipeline is full. This is the capture-loop entry
        point: with a single asyncio loop owning all displays, blocking here
        would stall every other client (SURVEY.md §5 concurrency invariant),
        so a saturated pipeline degrades by dropping frames instead."""
        self._advance_ready()
        if len(self._inflight) >= self.depth:
            self.frames_dropped_total += 1
            if self.metrics is not None:
                self.metrics.inc_frames_dropped()
            return None
        return self._dispatch(frame)

    def submit(self, frame) -> int:
        """Dispatch one frame; blocks (harvesting the oldest) if full."""
        while len(self._inflight) >= self.depth:
            # Harvest the oldest synchronously to free a slot; the result is
            # delivered by the next poll()/flush().
            self._ready.append(self._drain_one())
        return self._dispatch(frame)

    def _dispatch(self, frame) -> int:
        b = self.base
        t0 = time.monotonic()
        ticket = None
        stage_iv = None
        if isinstance(frame, jnp.ndarray):
            # Device-resident frame: must already be padded to the
            # encoder geometry; skips the host staging copy.
            if frame.shape != (b.pad_h, b.pad_w, 3):
                raise ValueError(
                    f"device frame must be pre-padded to {(b.pad_h, b.pad_w, 3)}")
        else:
            # donated staging lane: the upload lands in a recycled ring
            # slot and overlaps the in-flight frames' encode/fetch
            frame, slot = self._staging.stage(
                b._pad(np.asarray(frame, dtype=np.uint8)))
            stage_iv = (t0, time.monotonic())
            ticket = StagingTicket(self._staging, slot)
            try:
                return self._dispatch_staged(frame, ticket, t0, stage_iv)
            except Exception:
                # the slot must not leak busy; release via the ticket —
                # idempotent, so a harvest that also releases (when the
                # failure came after the in-flight item took ownership)
                # cannot double-free a re-staged slot
                ticket.release()
                raise
        return self._dispatch_staged(frame, ticket, t0, stage_iv)

    def _dispatch_staged(self, frame, ticket, t0, stage_iv=None) -> int:
        b = self.base
        td0 = time.monotonic()
        paint_candidate = b._paint_candidates().copy()
        # Optimistic mark: frames submitted while this one is in flight must
        # not re-trigger the same paint-over (a damaged stripe clears the
        # mark again at harvest in _decide_emits).
        b._painted |= paint_candidate
        qsel = jnp.asarray(paint_candidate.astype(np.int32))
        ahead = self._ready_watch.ahead
        with b.compile_watch.first_use("step"):
            packed, new_prev, yq, cbq, crq = b._step(
                frame, b._prev, b._qy, b._qc, qsel,
                b._wm_scaled, b._alpha_inv)
        b._prev = new_prev
        item = _InFlight(
            seq=self._seq, paint_candidate=paint_candidate,
            packed=packed, yq=yq, cbq=cbq, crq=crq, ticket=ticket,
        )
        item.ready = self._launched(packed, ahead)
        if stage_iv is not None:
            self._mark(item.trace, "stage", *stage_iv)
        td1 = time.monotonic()
        self._mark(item.trace, "dispatch", td0, td1)
        self._seq += 1
        self._inflight.append(item)
        self._unfetched.append(item)
        if len(self._unfetched) >= self.fetch_group:
            self._issue_fetch()
        # starting the group's fetch is part of the launch for the thread
        # (the frame's own ``dispatch`` stage ends at td1, as it always has)
        t_end = time.monotonic()
        self._mark(None, "dispatch", td1, t_end)
        self._record_dispatch((t_end - t0) * 1000.0)
        self._advance_ready()
        return item.seq

    def _issue_fetch(self) -> None:
        """Combine the pending frames' buffers into ONE device concat and
        start a single async host copy for the lot."""
        group_items, self._unfetched = self._unfetched, []
        if not group_items:
            return
        guess = self._guess
        stride = self._meta_words + guess
        slices = [it.packed[:stride] for it in group_items]
        arr = slices[0] if len(slices) == 1 else jnp.concatenate(slices)
        arr.copy_to_host_async()
        group = _FetchGroup(arr=arr, stride=stride)
        for i, it in enumerate(group_items):
            it.group = group
            it.group_index = i
            it.guess_words = guess
        self._note_inflight()

    # -- pipeline stages ---------------------------------------------------

    def _advance_ready(self) -> None:
        """Advance in-flight items in submission order (non-blocking).

        ``_decide_emits`` mutates shared damage/paint history, so the meta
        stage must run strictly in frame order: stop offering the meta stage
        to an item until every earlier item has completed it.
        """
        meta_ok = True
        for item in self._inflight:
            if not meta_ok:
                break
            self._advance(item, block=False)
            meta_ok = item.meta_done

    def _advance(self, item: _InFlight, block: bool) -> bool:
        """Move one item forward; returns True when fully harvestable."""
        b = self.base
        if not item.meta_done:
            if item.group is None:
                if not block:
                    return False
                self._issue_fetch()   # flush the partial group
            if not block and not item.group.arr.is_ready():
                return False
            if item.group.host is None:
                self._materialize(item.group)
            if item.group.fetch_iv is not None:
                item.trace["fetch_wait"] = item.group.fetch_iv
            stride = item.group.stride
            buf = item.group.host[item.group_index * stride:
                                  (item.group_index + 1) * stride]
            nbytes_np, base_np, ovf_np, damage_np = split_meta(
                buf[: self._meta_words], b.n_stripes)
            emit, is_paint = b._decide_emits(
                damage_np > b.damage_threshold, item.paint_candidate)
            item.emit, item.is_paint = emit, is_paint
            item.meta = (nbytes_np, base_np, ovf_np)
            item.meta_done = True
            total = b.total_packed_words(base_np, nbytes_np)
            if emit.any():
                if total <= item.guess_words:
                    item.words_np = buf[self._meta_words:]
                else:  # prediction miss: one more read for the full payload
                    bucket = b._packer.bucket_words(total)
                    item.refetch = item.packed[
                        self._meta_words: self._meta_words + bucket]
                    item.refetch.copy_to_host_async()
            # adapt: track the frame size plus one bucket of headroom
            target = b._packer.bucket_words(max(total * 2, 8192))
            self._guess = max(target, self._guess // 2)
            item.packed = None  # release our handle; refetch slice holds data
        if item.refetch is not None and item.words_np is None:
            if not block and not item.refetch.is_ready():
                return False
            tm0 = time.monotonic()
            item.words_np = np.asarray(item.refetch)
            tm1 = time.monotonic()
            # a prediction-miss second read extends the frame's fetch wait
            fw = item.trace.get("fetch_wait")
            item.trace["fetch_wait"] = (fw[0] if fw else tm0, tm1)
            self._mark(None, "fetch_wait", tm0, tm1)
            self.d2h_bytes_total += item.words_np.nbytes
        return True

    def _finish(self, item: _InFlight) -> List[StripeOutput]:
        b = self.base
        self.frames_completed += 1
        if item.ticket is not None:
            # harvested: the staged input's ring slot is donatable again
            item.ticket.release()
            item.ticket = None
        nbytes_np, base_np, ovf_np = item.meta
        emit, is_paint = item.emit, item.is_paint
        if not emit.any() or item.words_np is None:
            self._trace_store(item.seq, item.trace, item.ready)
            return []
        t0 = time.monotonic()
        scans = b._scans_from_packed(
            item.words_np, base_np, nbytes_np, ovf_np,
            emit, item.yq, item.cbq, item.crq)
        out = b._assemble(emit, is_paint, scans)
        t1 = time.monotonic()
        self._mark(item.trace, "pack", t0, t1)
        self._trace_store(item.seq, item.trace, item.ready)
        self.host_entropy_ms_total += (t1 - t0) * 1000.0
        self._publish_metrics()
        return out

    def _drain_one(self) -> Tuple[int, List[StripeOutput]]:
        item = self._inflight.popleft()
        try:
            self._advance(item, block=True)
        except Exception:
            # the item is already off the deque: a failed fetch must
            # still free its staging slot, or the ring stalls forever
            if item.ticket is not None:
                item.ticket.release()
                item.ticket = None
            raise
        return item.seq, self._finish(item)

    # -- public harvest ----------------------------------------------------

    def poll(self, flush_partial: bool = True, wait: bool = False
             ) -> List[Tuple[int, List[StripeOutput]]]:
        """Harvest all completed frames (in order; non-blocking unless
        ``wait``, which first blocks until the oldest frame is in: the
        async driver's move when captures queue behind a full pipe).

        ``flush_partial`` (default) issues any partially filled fetch
        group so frames are never stranded when submissions pause — the
        low-latency choice for live streaming. Throughput-oriented
        callers that poll after every submit pass False so groups only
        ship at ``fetch_group`` size (``flush()`` remains the deadline).

        Results accumulate in ``self._ready`` and are swapped out only
        at the end: a harvest raising mid-pass must not discard frames
        already completed this pass (they surface on the next call).
        """
        if self._unfetched and flush_partial:
            self._issue_fetch()
        if wait and self._inflight:
            self._ready.append(self._drain_one())
        self._advance_ready()
        while self._inflight and self._advance(self._inflight[0], block=False):
            item = self._inflight.popleft()
            self._ready.append((item.seq, self._finish(item)))
        out, self._ready = self._ready, []
        return out

    def flush(self) -> List[Tuple[int, List[StripeOutput]]]:
        """Drain the pipeline (blocking)."""
        while self._inflight:
            self._ready.append(self._drain_one())
        out, self._ready = self._ready, []
        return out

    def close(self) -> None:
        """Abandon in-flight work (display teardown / supervised restart):
        drop device handles and release every staging slot so a rebuilt
        pipeline never inherits a phantom-busy ring."""
        self._inflight.clear()
        self._unfetched.clear()
        self._ready.clear()
        self._trace_out.clear()
        self._ready_watch.stop()
        self._staging.release_all()


class ThreadedEncoderAdapter:
    """submit()/poll()/flush() facade over a synchronous ``encode_frame``
    encoder (the H.264 profiles), keeping the shared event loop free: one
    worker thread preserves frame order, a bounded queue drops frames
    under overload exactly like try_submit does."""

    def __init__(self, base, depth: int = 3,
                 wire_fullframe: bool = False, metrics=None) -> None:
        import concurrent.futures

        self.base = base
        self.depth = depth
        #: ship as one 0x00 full-frame packet instead of 0x04 stripes
        self.wire_fullframe = wire_fullframe
        #: observability Metrics (inc_frames_dropped / inc_encode_errors);
        #: the server attaches its instance after construction
        self.metrics = metrics
        #: called with the exception for every errored frame — the server
        #: routes this into the degradation ladder (ISSUE 2)
        self.on_error = None
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpuenc")
        self._pending: deque = deque()
        self._done: List = []
        self._seq = 0
        self.frames_completed = 0
        self.frames_dropped_total = 0
        self.encode_errors_total = 0
        #: flight-recorder intervals (the synchronous host encode is all
        #: "pack" — there is no separate device dispatch to attribute)
        self._trace_out: dict = {}

    def stats(self) -> dict:
        """Drop/error accounting plus the base encoder's entropy gauges
        (same shape as the pipelined encoders' stats for bench/health)."""
        n = max(1, self.frames_completed)
        return {
            "frames": self.frames_completed,
            "frames_dropped": self.frames_dropped_total,
            "encode_errors": self.encode_errors_total,
            "d2h_bytes_per_frame":
                getattr(self.base, "d2h_refetch_bytes_total", 0) / n,
            "host_entropy_ms_per_frame":
                getattr(self.base, "host_entropy_ms_total", 0.0) / n,
            "entropy": getattr(self.base, "entropy", None),
        }

    def try_submit(self, frame) -> Optional[int]:
        self._harvest()
        if len(self._pending) >= self.depth:
            self.frames_dropped_total += 1
            if self.metrics is not None:
                self.metrics.inc_frames_dropped()
            return None
        return self.submit(frame)

    def pop_trace(self, seq: int):
        """Stage intervals for a harvested frame (once; None if unknown)."""
        return self._trace_out.pop(seq, None)

    def compiling_for_s(self) -> float:
        watch = getattr(self.base, "compile_watch", None)
        return watch.compiling_for_s() if watch is not None else 0.0

    def _settle(self, seq: int, fut, out: List) -> None:
        """Resolve one finished encode future into ``out`` with full
        error accounting (shared by the poll and flush drains)."""
        try:
            stripes, iv = fut.result()
            out.append((seq, stripes))
            self._trace_out[seq] = {"pack": iv}
            while len(self._trace_out) > 4 * max(8, self.depth):
                self._trace_out.pop(next(iter(self._trace_out)))
            self.frames_completed += 1
        except Exception as exc:
            # encoder error: the frame is lost, but it must be COUNTED
            # (metrics + stats) and REPORTED (ladder hook), not just
            # logged — silent decay is what ISSUE 2 removes
            import logging

            self.encode_errors_total += 1
            if self.metrics is not None:
                self.metrics.inc_encode_errors()
            logging.getLogger(__name__).exception("encode failed")
            if self.on_error is not None:
                try:
                    self.on_error(exc)
                except Exception:
                    logging.getLogger(__name__).exception(
                        "encode on_error hook failed")

    def _harvest(self) -> None:
        while self._pending and self._pending[0][1].done():
            seq, fut = self._pending.popleft()
            self._settle(seq, fut, self._done)

    def submit(self, frame) -> int:
        # defensive crop: encoder dims can be tighter than the source's
        # (H.264 needs even dims); mismatch must not poison the worker
        h = getattr(self.base, "height", None)
        w = getattr(self.base, "width", None)
        if h is not None and frame.shape[0] >= h and frame.shape[1] >= w \
                and (frame.shape[0] != h or frame.shape[1] != w):
            frame = frame[:h, :w]
        seq = self._seq
        self._seq += 1
        self._pending.append(
            (seq, self._pool.submit(self._timed_encode, frame)))
        return seq

    def _timed_encode(self, frame):
        """Worker-side encode wrapped with its flight-recorder interval."""
        t0 = time.monotonic()
        out = self.base.encode_frame(frame)
        return out, (t0, time.monotonic())

    # control surface passthrough (PLI/viewer-join keyframes, rate control)
    def request_keyframe(self) -> None:
        rk = getattr(self.base, "request_keyframe", None)
        if rk is not None:
            rk()

    force_keyframe = request_keyframe

    @property
    def qp(self):
        return getattr(self.base, "qp", None)

    @qp.setter
    def qp(self, value):
        if hasattr(self.base, "qp"):
            self.base.qp = value

    def poll(self):
        self._harvest()
        out, self._done = self._done, []
        return out

    def flush(self):
        out, self._done = self._done, []
        while self._pending:
            seq, fut = self._pending.popleft()
            self._settle(seq, fut, out)
        return out

    def close(self) -> None:
        """Stop the worker and abandon queued frames (display teardown).

        An encode_frame ALREADY RUNNING cannot be interrupted — a truly
        hung native coder leaves its thread blocked past shutdown. The
        server bounds that exposure (DisplayState.wedge_faults caps
        rebuild cycles of a wedged bottom-rung encoder)."""
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._pending.clear()
        self._done.clear()
        self._trace_out.clear()


@dataclass
class _H264InFlight:
    seq: int
    pending: Any                     # h264._H264Pending: holds the fetch
    host: Optional[np.ndarray] = None
    ticket: Optional[StagingTicket] = None
    #: per-frame stage intervals for the flight recorder
    trace: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    #: when this frame's step was ready (and the step launched before it)
    ready: Optional[ReadyStamp] = None


class PipelinedH264Encoder(_PipelineTelemetry):
    """Depth-N pipelined wrapper around H264StripeEncoder.

    Every frame's host copy starts at its dispatch, right behind the
    frame's own step on the device queue: a P frame's fetch prefix
    (sized by the encoder for the content, h264._choose_prefix) or an
    IDR's flat16 levels. On a directly attached chip a read waits
    0.07 ms (PERF.md); nothing is concatenated across frames, so no
    transfer waits for the next frame's dispatch and no program's shape
    depends on which prefixes met. In steady state ``harvest`` issues no
    device program and waits for none.
    """

    def __init__(self, base, depth: int = 8, metrics=None) -> None:
        self.base = base
        self.depth = depth
        #: transfer accounting for the d2h_bytes_per_frame /
        #: host_entropy_ms_per_frame gauges (host-entropy time and
        #: refetch bytes accumulate on the base encoder in harvest)
        self.metrics = metrics
        self.d2h_bytes_total = 0
        self.frames_completed = 0
        self.frames_dropped_total = 0
        self._inflight: deque[_H264InFlight] = deque()
        self._ready: List[Tuple[int, list]] = []
        self._seq = 0
        #: donated H2D staging lane (ISSUE 12), sized so every in-flight
        #: frame can hold a slot without stalling the ring
        self._staging = StagingRing(depth=depth + 1)
        self._init_telemetry()

    @property
    def n_inflight(self) -> int:
        return len(self._inflight)

    @property
    def inflight_batches(self) -> int:
        """Frames dispatched whose host copy (a P frame's head, an IDR's
        flat16) is still outstanding (the ISSUE 12 gauge)."""
        return sum(1 for it in self._inflight if it.host is None)

    def stats(self) -> dict:
        """Per-frame transfer/host-entropy gauges over the run so far.
        D2H counts head fetches, IDR flat16 reads, and the base encoder's
        undershoot/overflow re-reads; entropy ms is the base harvest's
        host coding+glue wall time."""
        n = max(1, self.frames_completed)
        d2h = self.d2h_bytes_total \
            + getattr(self.base, "d2h_refetch_bytes_total", 0)
        ems = getattr(self.base, "host_entropy_ms_total", 0.0)
        return {
            "frames": self.frames_completed,
            "d2h_bytes_per_frame": d2h / n,
            "host_entropy_ms_per_frame": ems / n,
            "frames_dropped": self.frames_dropped_total,
            "entropy_errors": getattr(self.base, "entropy_errors_total", 0),
            "entropy": getattr(self.base, "entropy", None),
            "cavlc_frames": getattr(self.base, "cavlc_frames_total", 0),
            "cavlc_low_tier_frames": getattr(
                self.base, "cavlc_low_tier_frames_total", 0),
            "cavlc_payload_words": getattr(
                self.base, "cavlc_payload_words_total", 0),
            "cavlc_tier_words": getattr(
                self.base, "cavlc_tier_words_total", 0),
            "prefix_hit_frames": getattr(
                self.base, "prefix_hit_frames_total", 0),
            "staging_stalls": self._staging.stalls_total,
            **self._telemetry_stats(),
        }

    def _publish_metrics(self) -> None:
        if self.metrics is not None and self.frames_completed:
            st = self.stats()
            self.metrics.set_d2h_bytes_per_frame(st["d2h_bytes_per_frame"])
            self.metrics.set_host_entropy_ms_per_frame(
                st["host_entropy_ms_per_frame"])
            self.metrics.set_inflight_batches(st["inflight_batches"])
            self._publish_launch_idle(st)
            if st["cavlc_frames"]:
                self.metrics.set_cavlc_low_tier_share(
                    st["cavlc_low_tier_frames"] / st["cavlc_frames"])
                self.metrics.set_cavlc_tier_fill_share(
                    st["cavlc_payload_words"] / st["cavlc_tier_words"])
                self.metrics.set_fetch_prefix_hit_share(
                    st["prefix_hit_frames"] / st["cavlc_frames"])

    def request_keyframe(self) -> None:
        self.base.request_keyframe()

    force_keyframe = request_keyframe

    @property
    def qp(self):
        return self.base.qp

    @qp.setter
    def qp(self, value):
        self.base.qp = value

    def try_submit(self, frame) -> Optional[int]:
        if len(self._inflight) >= self.depth:
            self.frames_dropped_total += 1
            if self.metrics is not None:
                self.metrics.inc_frames_dropped()
            return None
        return self.submit(frame)

    def submit(self, frame) -> int:
        """Dispatch one frame; blocks (harvesting the oldest) if full."""
        while len(self._inflight) >= self.depth:
            self._ready.append(self._drain_one())
        ts0 = time.monotonic()
        slot = None
        if not isinstance(frame, jnp.ndarray):
            # host frames ride the donated staging ring; device-resident
            # frames pass through untouched
            frame, slot = self._staging.stage(
                np.asarray(frame, dtype=np.uint8))
        td0 = time.monotonic()
        ahead = self._ready_watch.ahead
        try:
            # the encoder starts the frame's own host copy (head, or an
            # IDR's flat16) behind the step it has just enqueued
            p = self.base.dispatch(frame)
        except Exception:
            # no ticket exists yet: free the staged slot here or it
            # leaks busy forever and the lane loses a buffer
            self._staging.release(slot)
            raise
        item = _H264InFlight(seq=self._seq, pending=p,
                             ticket=StagingTicket(self._staging, slot))
        # the buffer the P step wrote (an IDR's step writes its fetch),
        # not the prefix slice a program of its own cuts from it
        buf = getattr(p, "buf", None)
        item.ready = self._launched(
            buf if buf is not None else p.fetch, ahead)
        if slot is not None:
            self._mark(item.trace, "stage", ts0, td0)
        td1 = time.monotonic()
        self._mark(item.trace, "dispatch", td0, td1)
        self._seq += 1
        self._inflight.append(item)
        self._record_dispatch((td1 - ts0) * 1000.0)
        return item.seq

    def _advance(self, item: _H264InFlight, block: bool) -> bool:
        """Bring the frame's host copy in; True once it is there (blocks
        for it only where ``block``)."""
        arr = item.pending.fetch
        if not block and not arr.is_ready():
            return False
        if item.host is None:
            tm0 = time.monotonic()
            item.host = np.asarray(arr)
            tm1 = time.monotonic()
            self._mark(item.trace, "fetch_wait", tm0, tm1)
            self._record_fetch_wait((tm1 - tm0) * 1000.0)
            self.d2h_bytes_total += item.host.nbytes
        return True

    @staticmethod
    def _release_ticket(item) -> None:
        if item.ticket is not None:
            item.ticket.release()
            item.ticket = None

    def _harvest_item(self, item: _H264InFlight) -> Tuple[int, list]:
        t0 = time.monotonic()
        try:
            out = self.base.harvest(item.pending, host=item.host)
        finally:
            # the item is already off the deque: even a failed harvest
            # must free its staging slot, or the ring stalls forever
            self._release_ticket(item)
        self._mark(item.trace, "pack", t0, time.monotonic())
        self._trace_store(item.seq, item.trace, item.ready)
        self.frames_completed += 1
        return item.seq, out

    def _drain_one(self) -> Tuple[int, list]:
        # harvest() mutates per-stripe frame_num/static history, so frames
        # complete strictly in submission order (deque head first)
        item = self._inflight.popleft()
        try:
            self._advance(item, block=True)
        except Exception:
            self._release_ticket(item)
            raise
        seq_out = self._harvest_item(item)
        self._publish_metrics()
        return seq_out

    def poll(self, flush_partial: bool = True, wait: bool = False
             ) -> List[Tuple[int, list]]:
        """Harvest completed frames in order. ``wait`` first blocks until
        the oldest frame is in, as PipelinedJpegEncoder.poll does;
        ``flush_partial`` is that pipe's (every frame here has its own
        read, so nothing is ever held back for a group).

        Results accumulate in ``self._ready`` and are swapped out only at
        the end: a harvest raising mid-pass must not discard the frames
        already completed this pass (they surface on the next call)."""
        if wait and self._inflight:
            self._ready.append(self._drain_one())
        while self._inflight and self._advance(self._inflight[0],
                                               block=False):
            self._ready.append(self._harvest_item(self._inflight.popleft()))
        self._publish_metrics()
        out, self._ready = self._ready, []
        return out

    def flush(self) -> List[Tuple[int, list]]:
        while self._inflight:
            self._ready.append(self._drain_one())
        out, self._ready = self._ready, []
        return out

    def close(self) -> None:
        self._inflight.clear()
        self._ready.clear()
        self._trace_out.clear()
        self._ready_watch.stop()
        # a rebuilt pipeline must never inherit phantom-busy ring slots
        self._staging.release_all()
