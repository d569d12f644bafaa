"""Mesh encode coordinator: a dynamic, failure-isolated session scheduler.

This is the integration layer that makes BASELINE config 5 a *product*
path rather than a benchmark: the server's per-display capture loops keep
their shape (one asyncio task per display, reference selkies.py:2846-2904),
but instead of each owning a solo encoder pipeline they submit frames to a
per-session facade, and a single worker thread batches sessions into
sharded :class:`~selkies_tpu.parallel.mesh.MeshStripeEncoder` dispatches
over the ("session", "stripe") device mesh.

Scheduling model (ISSUE 14, docs/scaling.md). Sessions pack into **batch
lanes**: each lane owns one compiled SPMD encoder with a fixed number of
slots, its own bounded in-flight window, and its own fault accounting — a
lane is a fault domain, and a *slot* is the sub-domain one session rides.

* **Dynamic admission** — a join takes a free slot in any live lane; when
  every lane is full a new lane is built on demand, up to ``max_lanes``.
  A full scheduler is therefore a real capacity statement (the server's
  admission control turns it into queue/shed verdicts), not an artifact
  of a construction-time constant.
* **Rebalance on leave** — a lane with no sessions and an empty window is
  retired after a grace period, freeing its device arrays; the tick never
  dispatches an empty lane, so a freed lane shrinks the dispatched work
  instead of ticking dead slots. One healthy lane is kept warm to spare
  the next joiner a rebuild (unless it carries quarantined slots — then
  retiring it is how the poisoned fault domain gets recycled).
* **Slot health + quarantine + live migration** — per-slot error EWMAs
  (:class:`~selkies_tpu.robustness.SlotHealth`) accumulate from failed
  dispatch/harvest ticks and injected slot faults. A slot that keeps
  faulting is quarantined (never returns to the free list) and its
  session is **migrated in place** to a healthy slot — the facade stays
  the same object, the new slot gets a full state reset (zeroed prev
  planes + keyframe), and the capture loop is told via
  ``consume_migration()`` so it can ride the PR 2 reset path
  (PIPELINE_RESETTING + ``Supervisor.forgive``). Cohabiting sessions keep
  streaming throughout: a slot failure must never become a mesh failure.
* **Lane-contained errors** — a failing lane charges its own slots and
  backs off by itself (``skip_until``); other lanes' ticks proceed. The
  worker thread only sees ``mesh.tick_raise``-style whole-tick faults.

A tick encodes the newest submitted frame per attached session; sessions
without a new frame re-present their previous frame, which damage gating
suppresses on device — each dispatch stays dense and mesh-uniform (SPMD
needs every device to run the same program) while idle sessions cost no
wire bytes. Mesh batching uses the server-wide quality settings; per-client
encoder overrides are ignored in this mode (they would break SPMD
uniformity), which mirrors the shared-pipeline restriction the reference
has for shared displays.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..observability.device_probe import ReadyStamp, ReadyWatch
from ..robustness import SlotHealth, backoff_delay

logger = logging.getLogger("selkies_tpu.parallel")

#: seconds a failed lane build blocks further build attempts — a broken
#: device must not be re-probed on every join
LANE_BUILD_BLOCK_S = 30.0

#: process-global lane id counter: geometry buckets share one fault
#: injector, so a ``mesh.slot_raise=lane:slot`` arming must name exactly
#: one lane across ALL coordinators, not one per bucket
_lane_ids = itertools.count()

#: the worker thread's name, and the name of its track in the flight
#: recorder (the solo driver's is ``tpuenc-async``)
WORKER_THREAD = "mesh-encode"
#: the thread that stamps when each launched step's output is ready
READY_THREAD = "mesh-ready"


def _p50(samples, ndigits: int = 3) -> float:
    """Median of a small sample window (0.0 when empty). Shared with
    the bench reporters so every ``*_ms_p50`` surface agrees."""
    if not samples:
        return 0.0
    s = sorted(samples)
    return round(s[len(s) // 2], ndigits)


class MeshSessionFacade:
    """One session's encoder-shaped handle onto the coordinator.

    The facade survives migration: the coordinator rebinds the session to
    a new (lane, slot) underneath it, and the capture loop polls
    :meth:`consume_migration` to learn a rebind happened (so it can reset
    frame ids and notify the client)."""

    def __init__(self, coord: "MeshEncodeCoordinator", sid: int) -> None:
        self._coord = coord
        self.sid = sid
        self.closed = False
        #: see :meth:`try_submit`
        self.replaced_seq: Optional[int] = None
        self._base: Any = None

    @property
    def slot(self) -> Optional[int]:
        """Current slot index (None once released)."""
        return self._coord._slot_of(self.sid)

    @property
    def lane_id(self) -> Optional[int]:
        return self._coord._lane_of(self.sid)

    @property
    def recorder(self):
        """The FlightRecorder the lane's worker writes its track to. The
        server hands over the one it holds when a capture loop starts, as
        it does to the solo driver; the sessions of a coordinator share
        it."""
        return getattr(self._coord, "recorder", None)

    @recorder.setter
    def recorder(self, rec) -> None:
        self._coord.recorder = rec

    @property
    def base(self) -> Any:
        """The lane encoder this session rides (observability/
        device_phases.py asks it for ``lower_step``): the current lane's
        while attached, the last one's once released, None where the
        coordinator knows none."""
        encoder_of = getattr(self._coord, "_encoder_of", None)
        enc = encoder_of(self.sid) if encoder_of is not None else None
        if enc is not None:
            self._base = enc
        return self._base

    def try_submit(self, frame) -> Optional[int]:
        """The seq the frame will harvest under; None when it took the
        place of a frame still pending (the session's mailbox holds one):
        the OLDER frame is the one lost, and ``replaced_seq`` then says
        under which seq this one will harvest, so that the capture loop
        can hand the lost frame's place in its records to this one."""
        seq, replaced = self._coord._submit(self.sid, frame)
        self.replaced_seq = seq if replaced else None
        return None if replaced else seq

    submit = try_submit

    def poll(self) -> List[Tuple[int, list]]:
        return self._coord._poll(self.sid)

    def flush(self) -> List[Tuple[int, list]]:
        return self._coord._flush(self.sid)

    def force_keyframe(self) -> None:
        self._coord._force_keyframe(self.sid)

    def consume_migration(self) -> bool:
        """True once per quarantine migration since the last call — the
        capture loop's cue to reset frame ids (keyframe is already forced
        on the new slot by the coordinator)."""
        return self._coord._consume_migration(self.sid)

    def compiling_for_s(self) -> float:
        """Seconds this session's lane has been inside a program's
        first-use compile (0.0 otherwise, and for injected encoders that
        carry no runtime.CompileWatch)."""
        return self._coord._compiling_for_s(self.sid)

    def pop_trace(self, seq: int):
        """Flight-recorder stage intervals for a harvested frame: the
        solo driver's seven from ``submit_wait`` to ``pack``, tiling
        acceptance to the harvest's end, ``lane_step``, and the ready
        watch's three inside ``in_device`` + ``fetch_wait``
        (``_harvest_oldest`` says where each begins and ends, and what an
        injected encoder without the launch mark or the harvest's split
        keeps coarse; docs/observability.md)."""
        return self._coord._pop_trace(self.sid, seq)

    def stats(self) -> dict:
        """What the solo pipes count a launch, of this session's
        coordinator (its lanes share one worker and one ready watch)."""
        return self._coord.launch_stats()

    def close(self) -> None:
        if not self.closed:
            self.closed = True
            self._base = self.base     # for who asks once the lane is let go
            self._coord._release(self.sid)


class _Session:
    """Scheduler-side state of one attached session (slot-independent, so
    migration only touches the lane/slot binding)."""

    __slots__ = ("sid", "lane", "slot", "gen", "seq", "pending", "results",
                 "traces", "inflight", "staged", "want_key", "want_reset",
                 "migrations_pending", "coded_bytes_total", "closed",
                 "accepted_at")

    def __init__(self, sid: int, lane: "_Lane", slot: int) -> None:
        self.sid = sid
        self.lane = lane
        self.slot = slot
        #: bumped on migration: harvests tagged with an older generation
        #: are dropped, so in-flight results of the old binding (or a
        #: previous occupant of a reused slot) never reach this session
        self.gen = 0
        self.seq = 0
        self.pending: Any = None
        #: when ``pending`` was accepted into the mailbox (latest wins:
        #: a replacing capture brings its own time)
        self.accepted_at = 0.0
        self.results: List[Tuple[int, list]] = []
        #: seq -> stage intervals for the flight recorder (bounded)
        self.traces: Dict[int, dict] = {}
        #: frames of this session inside some lane's in-flight window
        self.inflight = 0
        #: of those, taken out of ``pending`` and not yet in the lane's
        #: ``inflight_q`` (the worker is making room or dispatching): they
        #: harvest before anything submitted now
        self.staged = 0
        self.want_key = False
        self.want_reset = False
        self.migrations_pending = 0
        self.coded_bytes_total = 0
        self.closed = False


class _Batch(list):
    """The ``(session, slot, generation)`` rows one tick took for one
    lane, and the clock readings its frames share: they ride with the
    rows through the in-flight window to the harvest, which writes them
    into each frame's trace."""

    __slots__ = ("t_taken", "accepted", "t_launch", "t_step_end", "ready")

    def __init__(self, t_taken: float) -> None:
        super().__init__()
        self.t_taken = t_taken
        #: sid -> when the capture taken was accepted into its mailbox
        self.accepted: Dict[int, float] = {}
        #: when ``lane.enc.dispatch`` launched its step (None where the
        #: encoder does not say)
        self.t_launch: Optional[float] = None
        #: the end of the lane's part of the tick that took the rows
        self.t_step_end: Optional[float] = None
        #: when the step's output was ready on the chips, and the step the
        #: worker launched before it (the ready watch's stamp)
        self.ready: Optional[ReadyStamp] = None


class _Lane:
    """One SPMD batch lane: a compiled mesh encoder, its slot table, its
    bounded in-flight window, and its fault accounting."""

    __slots__ = ("id", "enc", "n_slots", "free", "sessions", "health",
                 "slot_errors", "inflight_q", "error_streak", "skip_until",
                 "idle_since")

    def __init__(self, lane_id: int, enc, n_slots: int,
                 health: SlotHealth) -> None:
        self.id = lane_id
        self.enc = enc
        self.n_slots = n_slots
        self.free = list(range(n_slots))
        self.sessions: Dict[int, _Session] = {}   # slot -> session
        self.health = health
        #: frames lost to failed dispatch/harvest ticks, per slot (so a
        #: single noisy session is attributable)
        self.slot_errors = [0] * n_slots
        #: (pending, [(session, slot, gen)], dispatch_interval)
        self.inflight_q: deque = deque()
        #: consecutive failed ticks of THIS lane; drives the per-lane
        #: capped backoff so a sick lane never slows its neighbours
        self.error_streak = 0
        self.skip_until = 0.0
        self.idle_since: Optional[float] = None


class _LaneTickError(RuntimeError):
    """Internal: a lane's dispatch/harvest failed (already attributed)."""


class MeshEncodeCoordinator:
    """Owns the batch lanes, the session table, and the tick thread."""

    def __init__(
        self,
        mesh_spec: str,
        sessions_per_chip: int,
        width: int,
        height: int,
        settings=None,
        framerate: float = 60.0,
        stripe_h: int = 64,
        profile: str = "jpeg",
        max_inflight: int = 2,
        max_lanes: Optional[int] = None,
        slots_per_lane: Optional[int] = None,
        enc_factory: Optional[Callable[[int], Any]] = None,
        health_sick_errors: Optional[float] = None,
        health_window_s: Optional[float] = None,
        lane_retire_s: float = 5.0,
        sfe_shards: int = 1,
    ) -> None:
        self.profile = profile
        self.width, self.height = width, height
        self.framerate = float(framerate)
        #: split-frame encoding (ISSUE 15, docs/scaling.md): when > 1,
        #: every lane of this bucket is an SFE lane — one session slot
        #: spans this many chips, each encoding a stripe band of the
        #: same frame. The default factory decides from sfe_min_pixels;
        #: injected-encoder harnesses pass it explicitly.
        self.sfe_shards = max(1, int(sfe_shards))
        if enc_factory is not None:
            # injected lanes (tests, tools/swarm_run.py): no jax import,
            # capacity comes from the caller
            self.chips = max(1, self._chips_from_spec(mesh_spec))
            self.slots_per_lane = int(
                slots_per_lane or max(1, sessions_per_chip))
            self._enc_factory = enc_factory
        else:
            self._enc_factory = self._build_default_factory(
                mesh_spec, sessions_per_chip, width, height,
                settings, stripe_h, profile)
        if max_lanes is None and settings is not None:
            max_lanes = int(getattr(settings, "mesh_max_lanes", 4) or 4)
        self.max_lanes = max(1, int(max_lanes or 4))
        if health_sick_errors is None and settings is not None:
            health_sick_errors = float(
                getattr(settings, "slot_quarantine_errors", 3) or 3)
        if health_window_s is None and settings is not None:
            health_window_s = float(
                getattr(settings, "slot_health_window_s", 30) or 30)
        self._health_sick_errors = float(health_sick_errors or 3.0)
        self._health_window_s = float(health_window_s or 30.0)
        self.lane_retire_s = float(lane_retire_s)

        self._lock = threading.Lock()
        #: serializes lane BUILDS only: device allocation can take
        #: seconds and must never happen under the main lock (it would
        #: freeze every ticking lane and every facade poll/submit)
        self._build_lock = threading.Lock()
        self.lanes: List[_Lane] = []
        self._lane_build_block_until = 0.0
        #: sids currently blocked from migrating (nowhere healthy to
        #: go): membership makes migrations_blocked_total count blocked
        #: EVENTS, not retry ticks
        self._blocked_sids: set = set()
        self._sessions: Dict[int, _Session] = {}
        self._next_sid = 0
        #: fault-injection registry checked at the tick/slot sites
        #: (mesh.tick_raise / mesh.slot_raise); wired by the server
        self.faults = None
        #: the server's FlightRecorder, handed over through a session's
        #: facade as to the solo driver: the worker writes its states to
        #: the thread track there; without one nothing is written
        self.recorder = None
        #: when the worker last finished a tick that did work: where its
        #: ``sleep`` begins
        self._worked_until: Optional[float] = None
        #: stamps when each launched step's output is ready (thread
        #: ``mesh-ready``): a lane frame's ``device_wait``, ``device_run``
        #: and ``ready_wait``, and the two counts a launch
        self._ready_watch = ReadyWatch(READY_THREAD)

        #: bounded in-flight window PER LANE (ISSUE 12): up to
        #: ``max_inflight`` dispatched ticks ride the device at once —
        #: dispatch of tick N+1 overlaps the D2H fetch of tick N, drained
        #: oldest-first (per-stripe host state advances per tick)
        self.max_inflight = max(1, int(max_inflight))
        self.inflight_batches_max = 0
        self._kick = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # -- aggregate fault/scheduling accounting (health feeds + tests)
        self.tick_errors_total = 0
        self._consecutive_tick_failures = 0
        self.worker_restarts_total = 0
        self.slot_faults_total = 0
        self.quarantined_total = 0
        self.migrations_total = 0
        self.migrations_blocked_total = 0
        self.lanes_built_total = 0
        self.lanes_retired_total = 0
        #: recent harvest fetch/concat walls (ms) from the lane encoders'
        #: last_harvest_stages — the sfe_concat_ms observability feed
        self._fetch_ms_window: deque = deque(maxlen=128)
        self._concat_ms_window: deque = deque(maxlen=128)
        # first lane is built eagerly so construction failures surface at
        # coordinator-build time (the server scopes those per geometry)
        if self._build_lane() is None:
            raise RuntimeError("mesh lane construction failed")

    # -- construction helpers ----------------------------------------------

    @staticmethod
    def _chips_from_spec(spec: str) -> int:
        """Device count implied by a ``tpu_mesh`` spec string, computed
        textually so injected-encoder mode never imports jax. Malformed
        parts are a configuration error and REJECTED — a typo'd axis
        must not silently collapse a multi-chip slice to one chip."""
        chips = 1
        for part in str(spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, num = part.partition(":")
            if not sep or not name.strip():
                raise ValueError(f"malformed tpu_mesh part {part!r} "
                                 f"(want axis:size)")
            try:
                chips *= max(1, int(num))
            except ValueError:
                raise ValueError(
                    f"malformed tpu_mesh part {part!r}: size "
                    f"{num!r} is not an integer") from None
        return chips

    @staticmethod
    def _sfe_shard_count(total_chips: int, width: int, height: int,
                         settings) -> int:
        """Stripe shards one frame of this geometry should span: 1 below
        ``sfe_min_pixels`` (or on a single chip), else ``sfe_shards``
        (0 = every chip), clamped to the largest count that tiles the
        slice. Pure policy — unit-testable without devices."""
        sfe_min = int(getattr(settings, "sfe_min_pixels", 0) or 0) \
            if settings is not None else 0
        if not sfe_min or total_chips <= 1 or width * height < sfe_min:
            return 1
        want = int(getattr(settings, "sfe_shards", 0) or 0) \
            if settings is not None else 0
        shards = max(1, min(want or total_chips, total_chips))
        while total_chips % shards:    # largest count that tiles the slice
            shards -= 1
        return shards

    def _build_default_factory(self, mesh_spec, sessions_per_chip, width,
                               height, settings, stripe_h, profile):
        from .mesh import MeshStripeEncoder, parse_mesh_spec
        from .mesh_h264 import MeshH264Encoder

        mesh = parse_mesh_spec(mesh_spec)
        total = mesh.shape["session"] * mesh.shape["stripe"]
        shards = self._sfe_shard_count(total, width, height, settings)
        if shards > 1:
            # SFE lane kind (ISSUE 15): this geometry's frames are too
            # big for one chip — re-partition the slice stripe-major so
            # one session's stripe bands shard across `shards` chips
            # (H.264 stripes are independently decodable, so the shards
            # run shard-local device CAVLC and the host concatenates).
            import numpy as _np
            from jax.sharding import Mesh as _Mesh

            devs = _np.asarray(mesh.devices).reshape(-1)
            mesh = _Mesh(devs.reshape(total // shards, shards),
                         ("session", "stripe"))
            self.sfe_shards = shards
            logger.info(
                "SFE lane geometry for %dx%d (%s): %d stripe shards "
                "per frame, %d session slot(s) per lane axis",
                width, height, profile, shards, total // shards)
        # an operator-configured stripe axis (tpu_mesh "…,stripe:M") is
        # stripe sharding too: shard-keyed faults and SFE accounting
        # must see it even when sfe_min_pixels never fired
        self.sfe_shards = max(self.sfe_shards, mesh.shape["stripe"])
        self.chips = mesh.shape["session"] * mesh.shape["stripe"]
        self.slots_per_lane = (
            mesh.shape["session"] * max(1, sessions_per_chip))
        kwargs: Dict[str, Any] = {}
        if profile == "x264enc-striped":
            # H.264 stripes over the mesh; CRF
            # settings map onto the QP scale like the solo factory does
            if settings is not None:
                kwargs = dict(
                    qp=int(settings.h264_crf.default),
                    paint_over_qp=int(settings.h264_paintover_crf.default),
                    use_paint_over_quality=bool(
                        settings.use_paint_over_quality.value),
                    stripe_h=int(settings.tpu_stripe_height),
                )
            else:
                kwargs = dict(stripe_h=stripe_h)

            def factory(n: int):
                return MeshH264Encoder(mesh, n, width, height, **kwargs)
        else:
            if settings is not None:
                kwargs = dict(
                    quality=int(settings.jpeg_quality.default),
                    paintover_quality=int(
                        settings.paint_over_jpeg_quality.default),
                    use_paint_over_quality=bool(
                        settings.use_paint_over_quality.value),
                    stripe_h=int(settings.tpu_stripe_height),
                )
            else:
                kwargs = dict(stripe_h=stripe_h)

            def factory(n: int):
                return MeshStripeEncoder(mesh, n, width, height, **kwargs)
        return factory

    def _build_lane(self) -> Optional[_Lane]:
        """Build and publish one lane, holding the main lock only for
        the capacity check and the publish — the encoder construction
        (device allocation) runs outside it, so ticking lanes and facade
        polls never freeze behind a build. ``_build_lock`` serializes
        concurrent builders (two joins racing must not overshoot
        ``max_lanes``)."""
        with self._build_lock:
            with self._lock:
                if len(self.lanes) >= self.max_lanes:
                    return None
                if time.monotonic() < self._lane_build_block_until:
                    return None
            try:
                enc = self._enc_factory(self.slots_per_lane)
            except Exception:
                # a broken device tier must not be re-probed per join
                with self._lock:
                    self._lane_build_block_until = (
                        time.monotonic() + LANE_BUILD_BLOCK_S)
                logger.exception("mesh lane build failed; blocking "
                                 "builds for %.0fs", LANE_BUILD_BLOCK_S)
                return None
            lane = _Lane(next(_lane_ids), enc, self.slots_per_lane,
                         SlotHealth(self.slots_per_lane,
                                    sick_errors=self._health_sick_errors,
                                    window_s=self._health_window_s))
            with self._lock:
                self.lanes.append(lane)
                self.lanes_built_total += 1
            logger.info("mesh lane %d built (%d slots, %d lanes live)",
                        lane.id, lane.n_slots, len(self.lanes))
            return lane

    # -- session lifecycle (event-loop side) -------------------------------

    @property
    def active_sessions(self) -> int:
        """Currently attached sessions (live occupancy, not cumulative)."""
        with self._lock:
            return len(self._sessions)

    @property
    def n_sessions(self) -> int:
        """Batch width of one lane (compat: the pre-lane slot count)."""
        return self.slots_per_lane

    @property
    def _attached(self) -> Dict[int, _Session]:
        """Compat view for tests: sid -> session."""
        with self._lock:
            return dict(self._sessions)

    def _bind_free_slot_locked(self) -> Optional[int]:
        lane = next((ln for ln in self.lanes if ln.free), None)
        if lane is None:
            return None
        slot = lane.free.pop(0)
        sid = self._next_sid
        self._next_sid += 1
        sess = _Session(sid, lane, slot)
        lane.sessions[slot] = sess
        lane.idle_since = None
        self._sessions[sid] = sess
        # applied at tick time: the worker may be mid-dispatch and the
        # encoder's host state is not safe to touch from here. A new
        # occupant gets a full reset (zeroed prev planes), not just a
        # keyframe — stale pixels must not leak across occupants.
        sess.want_reset = True
        return sid

    def acquire(self, width: int, height: int) -> Optional[MeshSessionFacade]:
        """Attach a session; None when geometry differs or — after trying
        to grow a fresh lane — the scheduler is genuinely out of slots."""
        if (width, height) != (self.width, self.height):
            return None
        with self._lock:
            sid = self._bind_free_slot_locked()
        if sid is None:
            # grow on demand: the build runs outside the main lock, so
            # existing lanes keep ticking while the new one allocates
            self._build_lane()
            with self._lock:
                sid = self._bind_free_slot_locked()
        if sid is None:
            return None
        self._ensure_thread()
        return MeshSessionFacade(self, sid)

    def capacity(self) -> Dict[str, int]:
        """Live lane capacity for the server's admission verdicts."""
        with self._lock:
            free = sum(len(ln.free) for ln in self.lanes)
            quarantined = sum(len(ln.health.quarantined)
                              for ln in self.lanes)
            growable = ((self.max_lanes - len(self.lanes))
                        * self.slots_per_lane
                        if time.monotonic() >= self._lane_build_block_until
                        else 0)
            return {
                "slots_free": free,
                "growable_slots": growable,
                "slots_total": len(self.lanes) * self.slots_per_lane,
                "quarantined_slots": quarantined,
                "active_sessions": len(self._sessions),
                "lanes": len(self.lanes),
                # SFE lanes span several chips per session slot: the
                # admission verdict still thinks in slots (correct), but
                # capacity consumers must see what one slot costs
                "sfe_shards": self.sfe_shards,
                "chips_per_slot": self.sfe_shards,
            }

    def _release(self, sid: int) -> None:
        with self._lock:
            sess = self._sessions.pop(sid, None)
            if sess is None:
                return
            sess.closed = True
            sess.pending = None
            sess.results = []
            sess.traces = {}
            self._blocked_sids.discard(sid)
            lane = sess.lane
            if lane.sessions.get(sess.slot) is sess:
                lane.sessions.pop(sess.slot, None)
                # quarantined slots never return to service; the lane is
                # recycled wholesale once it drains
                if sess.slot not in lane.health.quarantined:
                    lane.free.append(sess.slot)

    def _slot_of(self, sid: int) -> Optional[int]:
        with self._lock:
            sess = self._sessions.get(sid)
            return sess.slot if sess is not None else None

    def _lane_of(self, sid: int) -> Optional[int]:
        with self._lock:
            sess = self._sessions.get(sid)
            return sess.lane.id if sess is not None else None

    def _encoder_of(self, sid: int):
        with self._lock:
            sess = self._sessions.get(sid)
            return sess.lane.enc if sess is not None else None

    def _compiling_for_s(self, sid: int) -> float:
        enc = self._encoder_of(sid)
        watch = getattr(enc, "compile_watch", None)
        return watch.compiling_for_s() if watch is not None else 0.0

    def _pop_trace(self, sid: int, seq: int):
        with self._lock:
            sess = self._sessions.get(sid)
            return sess.traces.pop(seq, None) if sess is not None else None

    def _consume_migration(self, sid: int) -> bool:
        with self._lock:
            sess = self._sessions.get(sid)
            if sess is not None and sess.migrations_pending:
                sess.migrations_pending = 0
                return True
            return False

    def stop(self) -> None:
        self._stop.set()
        self._kick.set()
        self._ready_watch.stop()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- facade surface ----------------------------------------------------

    def _submit(self, sid: int, frame) -> Tuple[Optional[int], bool]:
        """(seq the frame will harvest under, whether it replaced a frame
        that was still pending)."""
        with self._lock:
            sess = self._sessions.get(sid)
            if sess is None:
                return None, False
            dropped = sess.pending is not None
            sess.pending = frame
            sess.accepted_at = time.monotonic()
            # the seq THIS frame will harvest under: seq advances only at
            # harvest, so same-generation frames already in the in-flight
            # window come first — without the offset, overlapped steady
            # state would hand the in-flight frame's seq to every new
            # submit (trace correlation off by one)
            inflight = sum(
                1 for entry in sess.lane.inflight_q
                for s, _slot, g in entry[1] if s is sess and g == sess.gen)
            # ... and so does a frame the worker has taken and not yet
            # queued there: it may sit for a whole step, blocked making
            # room, and a submit in that time used to be handed its seq
            # (on the chip, the trace of nearly every lane frame)
            seq = sess.seq + inflight + sess.staged
        self._kick.set()
        return seq, dropped

    def _poll(self, sid: int) -> List[Tuple[int, list]]:
        with self._lock:
            sess = self._sessions.get(sid)
            if sess is None:
                return []
            out = sess.results
            if out:
                sess.results = []
            return out

    def _flush(self, sid: int) -> List[Tuple[int, list]]:
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            with self._lock:
                sess = self._sessions.get(sid)
                if sess is None or (sess.pending is None
                                    and sess.inflight == 0):
                    break
            time.sleep(0.005)
        return self._poll(sid)

    def _force_keyframe(self, sid: int) -> None:
        with self._lock:
            sess = self._sessions.get(sid)
            if sess is not None:
                sess.want_key = True
        self._kick.set()

    # -- worker ------------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            if self._thread is not None:
                # the previous worker died (tick exception storm or device
                # loss); account for the re-spawn so it is observable
                self.worker_restarts_total += 1
            self._stop.clear()
            # stop() ended it with the worker: a worker started again
            # gets its stamps again
            self._ready_watch.resume()
            self._thread = threading.Thread(
                target=self._run, name=WORKER_THREAD, daemon=True)
            self._thread.start()

    def _run(self) -> None:
        interval = 1.0 / max(1.0, self.framerate)
        next_tick = time.monotonic()
        while not self._stop.is_set():
            delay = next_tick - time.monotonic()
            if delay > 0:
                self._kick.wait(timeout=delay)
            self._kick.clear()
            now = time.monotonic()
            if now < next_tick:
                continue
            next_tick = max(next_tick + interval, now - interval)
            try:
                self._tick()
                self._consecutive_tick_failures = 0
            except Exception:
                # whole-tick failure (mesh.tick_raise / unexpected): lane
                # errors are contained per lane, so reaching here is rare;
                # back off with a capped exponential so a persistent fault
                # doesn't spin the worker at tick rate
                self.tick_errors_total += 1
                self._consecutive_tick_failures += 1
                logger.exception("mesh encode tick failed (streak %d)",
                                 self._consecutive_tick_failures)
                # interruptible: stop() must not wait out the backoff
                self._stop.wait(backoff_delay(
                    self._consecutive_tick_failures, 0.5, 5.0))

    def launch_stats(self) -> dict:
        """Integers with one writer each: read without the lock."""
        encs = [ln.enc for ln in self.lanes]      # H.264's has no whole yet
        return {
            **self._ready_watch.counts(),
            "host_fallback_stripes": sum(
                getattr(e, "host_fallback_stripes_total", 0) for e in encs),
            "stripes_emitted": sum(
                getattr(e, "stripes_emitted_total", 0) for e in encs),
        }

    def stats(self) -> dict:
        """Scheduler + per-slot fault accounting for health feeds/tests."""
        with self._lock:
            lane_detail = []
            for ln in self.lanes:
                lane_detail.append({
                    "id": ln.id,
                    "slots": ln.n_slots,
                    "free": len(ln.free),
                    "sessions": len(ln.sessions),
                    "slot_errors": list(ln.slot_errors),
                    "error_streak": ln.error_streak,
                    "inflight": len(ln.inflight_q),
                    "health": ln.health.state(),
                })
            return {
                "active_sessions": len(self._sessions),
                "lanes": len(self.lanes),
                "slots_per_lane": self.slots_per_lane,
                "capacity_slots": len(self.lanes) * self.slots_per_lane,
                "free_slots": sum(len(ln.free) for ln in self.lanes),
                "quarantined_slots": sum(
                    len(ln.health.quarantined) for ln in self.lanes),
                "tick_errors_total": self.tick_errors_total,
                "worker_restarts_total": self.worker_restarts_total,
                "slot_errors": [e for ln in self.lanes
                                for e in ln.slot_errors],
                "slot_faults_total": self.slot_faults_total,
                "quarantined_total": self.quarantined_total,
                "migrations_total": self.migrations_total,
                "migrations_blocked_total": self.migrations_blocked_total,
                "lanes_built_total": self.lanes_built_total,
                "lanes_retired_total": self.lanes_retired_total,
                "inflight_batches": sum(
                    len(ln.inflight_q) for ln in self.lanes),
                "inflight_batches_max": self.inflight_batches_max,
                "sfe_shards": self.sfe_shards,
                "sfe_fetch_ms_p50": _p50(self._fetch_ms_window),
                "sfe_concat_ms_p50": _p50(self._concat_ms_window),
                "lane_detail": lane_detail,
                **self.launch_stats(),
            }

    def verify_slot_accounting(self) -> List[str]:
        """Leak check for tests/harnesses: every slot of every lane must
        be exactly one of free / occupied / quarantined."""
        problems: List[str] = []
        with self._lock:
            for ln in self.lanes:
                occupied = set(ln.sessions)
                free = set(ln.free)
                quarantined = set(ln.health.quarantined)
                if len(ln.free) != len(free):
                    problems.append(f"lane {ln.id}: duplicate free slots")
                if free & occupied:
                    problems.append(
                        f"lane {ln.id}: slots both free and occupied: "
                        f"{sorted(free & occupied)}")
                if quarantined & free:
                    problems.append(
                        f"lane {ln.id}: quarantined slots back in the "
                        f"free list: {sorted(quarantined & free)}")
                accounted = free | occupied | quarantined
                missing = set(range(ln.n_slots)) - accounted
                if missing:
                    problems.append(
                        f"lane {ln.id}: leaked slots {sorted(missing)}")
            for sid, sess in self._sessions.items():
                if sess.lane.sessions.get(sess.slot) is not sess:
                    problems.append(f"session {sid}: dangling slot binding")
        return problems

    def _track(self, state: str, t0: float, t1: float) -> None:
        """One state of the worker, to the recorder's thread track, from
        clock readings the tick takes for its frames' stages anyway."""
        rec = self.recorder
        if rec is not None and t1 > t0:
            rec.thread_state(WORKER_THREAD, state, t0, t1)

    def _fetch_ready(self, lane: _Lane, pending) -> bool:
        ready = getattr(lane.enc, "fetch_ready", None)
        if ready is None:
            return True
        try:
            return bool(ready(pending))
        except Exception:
            return True

    def _harvest_oldest(self, lane: _Lane) -> None:
        """Harvest the head of a lane's in-flight window (dispatch order
        is mandatory: per-stripe host state advances per tick)."""
        pending, took, dispatch_iv = lane.inflight_q[0]
        t0 = time.monotonic()
        try:
            out, session_bytes = lane.enc.harvest(pending)
        except Exception:
            with self._lock:
                lane.inflight_q.popleft()
                for sess, slot, _gen in took:
                    lane.slot_errors[slot] += 1
                    lane.health.record_error(slot)
                    sess.inflight = max(0, sess.inflight - 1)
            raise
        # flight-recorder intervals, under the solo driver's names
        # (observability/tracing.py STAGES). They tile the frame's time
        # from its acceptance to the end of this harvest:
        #   submit_wait  accepted into the session's mailbox -> taken
        #   pipe_wait    taken -> lane.enc.dispatch entered (room-making)
        #   stage        dispatch entered -> the step launched
        #   dispatch     launched -> dispatch returned
        #   in_device    dispatch returned -> this harvest began
        #   fetch_wait   D2H materialization (last_harvest_stages, with
        #                per-shard attribution for SFE lanes)
        #   pack         host slice-concat / entropy glue
        # device_wait, device_run and ready_wait tile in_device +
        # fetch_wait by when the step's output was ready (the ready
        # watch's stamp; none where it has not landed),
        # and lane_step lies across them: the worker's occupied time in
        # the tick that took the frame (to this harvest's end where the
        # frame is harvested in that same tick). An encoder that does
        # not say when it launched (injected fakes) keeps stage inside
        # dispatch; one without the harvest's split keeps pack inside
        # fetch_wait.
        t1 = time.monotonic()
        harvest_ms = (t1 - t0) * 1000.0
        stages = getattr(lane.enc, "last_harvest_stages", None)
        has_split = isinstance(stages, dict) and "fetch_ms" in stages
        t_split = min(t1, t0 + float(stages["fetch_ms"]) / 1000.0) \
            if has_split else t1
        self._track("fetch_wait", t0, t_split)
        self._track("pack", t_split, t1)
        trace_iv = {"pipe_wait": (took.t_taken, dispatch_iv[0]),
                    "dispatch": dispatch_iv,
                    "in_device": (dispatch_iv[1], max(dispatch_iv[1], t0)),
                    "fetch_wait": (t0, t_split),
                    "lane_step": (took.t_taken, took.t_step_end or t1)}
        if took.t_launch is not None:
            trace_iv["stage"] = (dispatch_iv[0], took.t_launch)
            trace_iv["dispatch"] = (took.t_launch, dispatch_iv[1])
        if has_split:
            trace_iv["pack"] = (t_split, t1)
        trace_iv.update(self._ready_watch.stages(
            dispatch_iv[1], t_split, took.ready))
        # encoder-internal stripe-job failures (whole-frame containment
        # withheld the AU without raising) must charge the slot exactly
        # like a harvest raise or an injected fault — otherwise a sick
        # shard chip freezes its session forever while health records ok
        # and quarantine/migration never fire
        failed = getattr(lane.enc, "last_failed_sessions", None) \
            or frozenset()
        with self._lock:
            if has_split:
                # under the lock: stats() sorts these windows while the
                # worker appends — deques must not be mutated mid-iteration
                self._fetch_ms_window.append(float(stages["fetch_ms"]))
                self._concat_ms_window.append(
                    float(stages.get("concat_ms", 0.0)))
            lane.inflight_q.popleft()
            for sess, slot, gen in took:
                sess.inflight = max(0, sess.inflight - 1)
                if slot in failed:
                    lane.slot_errors[slot] += 1
                    lane.health.record_error(slot)
                else:
                    lane.health.record_ok(slot, harvest_ms)
                if sess.closed or sess.gen != gen:
                    # released or migrated mid-flight: the old binding's
                    # pixels must not reach the (re-homed) session
                    continue
                sess.coded_bytes_total += int(session_bytes[slot])
                seq = sess.seq
                sess.seq = seq + 1
                sess.results.append((seq, out[slot]))
                sess.traces[seq] = dict(trace_iv)
                sess.traces[seq]["submit_wait"] = (
                    took.accepted[sess.sid], took.t_taken)
                while len(sess.traces) > 32:
                    sess.traces.pop(next(iter(sess.traces)))

    def _unwind_took_locked(self, lane: _Lane, took) -> None:
        """A batch that never reached the in-flight window lost its
        frames: attribute per slot and release the inflight holds."""
        for sess, slot, _gen in took:
            lane.slot_errors[slot] += 1
            lane.health.record_error(slot)
            sess.inflight = max(0, sess.inflight - 1)
            sess.staged = max(0, sess.staged - 1)

    def _tick(self) -> None:
        """One scheduler tick: apply deferred resets, build each lane's
        batch (with slot-fault screening), dispatch/drain every lane's
        bounded in-flight window, then run the quarantine/migration pass.
        Lane failures are contained to the lane (its slots charged, its
        own backoff armed); only whole-tick faults propagate to _run."""
        faults = self.faults
        if faults is not None:
            faults.maybe_raise("mesh.tick_raise")
        now = time.monotonic()
        plans: List[Tuple[_Lane, list, _Batch]] = []
        with self._lock:
            self._retire_idle_lanes_locked(now)
            for sess in self._sessions.values():
                lane = sess.lane
                try:
                    if sess.want_reset:
                        # a new occupant / migration target gets zeroed
                        # prev planes AND a keyframe (reset implies it)
                        lane.enc.reset_session(sess.slot)
                    elif sess.want_key:
                        lane.enc.force_keyframe(sess.slot)
                except Exception:
                    # a broken lane must not take the whole tick down:
                    # charge the slot and let health/quarantine decide
                    lane.slot_errors[sess.slot] += 1
                    lane.health.record_error(sess.slot)
                    logger.exception("lane %d reset/keyframe failed for "
                                     "slot %d", lane.id, sess.slot)
                sess.want_reset = False
                sess.want_key = False
            # the take: one reading for every capture this tick takes out
            # of its mailbox (none can be accepted while the lock is held)
            t_taken = time.monotonic()
            for lane in self.lanes:
                if now < lane.skip_until:
                    continue
                frames = [None] * lane.n_slots
                took = _Batch(t_taken)
                for slot, sess in list(lane.sessions.items()):
                    if sess.pending is None:
                        continue
                    keys = ()
                    if faults is not None:
                        keys = [f"{lane.id}:{slot}", slot]
                        if self.sfe_shards > 1:
                            # an SFE slot answers to its shard identities
                            # too: a fault targeting ONE stripe shard of
                            # the frame still drops the WHOLE frame
                            # (whole-frame containment — a torn access
                            # unit is never an outcome) and charges this
                            # session's slot
                            for k in range(self.sfe_shards):
                                keys += [f"{lane.id}:{slot}:{k}",
                                         f"shard:{k}"]
                    if faults is not None and faults.should_fire_for(
                            "mesh.slot_raise", *keys):
                        # slot-scoped fault: charge THIS slot and drop its
                        # frame; cohabiting sessions' tick proceeds — a
                        # slot failure must never become a mesh failure
                        lane.slot_errors[slot] += 1
                        lane.health.record_error(slot)
                        self.slot_faults_total += 1
                        sess.pending = None
                        continue
                    frames[slot] = sess.pending
                    sess.pending = None
                    sess.inflight += 1
                    sess.staged += 1
                    took.append((sess, slot, sess.gen))
                    took.accepted[sess.sid] = sess.accepted_at
                if took or lane.inflight_q:
                    plans.append((lane, frames, took))
        if plans and self._worked_until is not None:
            self._track("sleep", self._worked_until, now)
        try:
            for lane, frames, took in plans:
                self._tick_lane(lane, frames, took)
            self._migrate_sick_sessions()
        finally:
            if plans:
                self._worked_until = time.monotonic()

    def _tick_lane(self, lane: _Lane, frames: list, took: _Batch) -> None:
        dispatched = False
        try:
            # make room FIRST: the window is a hard bound on dispatched-
            # unharvested ticks, so a full window blocks on the oldest
            # fetch BEFORE the new dispatch, never after
            while took and len(lane.inflight_q) >= self.max_inflight:
                self._harvest_oldest(lane)
            t_disp0 = time.monotonic()
            ahead = self._ready_watch.ahead
            pending = lane.enc.dispatch(frames) if took else None
            if pending is not None:
                t_disp1 = time.monotonic()
                step_out = getattr(pending, "step_out", None)
                if step_out is not None:
                    took.ready = self._ready_watch.launched(step_out, ahead)
                # where staging ended and the launch began, if the
                # encoder says (as last_harvest_stages says the harvest's
                # split): an injected fake without it keeps one dispatch
                launch = getattr(lane.enc, "last_launch_at", None)
                if launch is not None and t_disp0 <= launch <= t_disp1:
                    took.t_launch = launch
                    self._track("stage", t_disp0, launch)
                    self._track("dispatch", launch, t_disp1)
                else:
                    self._track("dispatch", t_disp0, t_disp1)
                with self._lock:
                    lane.inflight_q.append(
                        (pending, took, (t_disp0, t_disp1)))
                    for sess, _slot, _gen in took:
                        sess.staged = max(0, sess.staged - 1)
                    depth = sum(len(ln.inflight_q) for ln in self.lanes)
                    self.inflight_batches_max = max(
                        self.inflight_batches_max, depth)
                dispatched = True
            elif took:
                # an encoder that swallowed a batch without a pending must
                # not strand the inflight holds (facade.flush would block
                # on them for its full timeout)
                with self._lock:
                    for sess, _slot, _gen in took:
                        sess.inflight = max(0, sess.inflight - 1)
                        sess.staged = max(0, sess.staged - 1)
                dispatched = True
            # opportunistic drain: only fetches that already landed are
            # taken here, so this tick's dispatch is never stalled by a
            # slow transfer (the window cap above is the blocking site)
            while lane.inflight_q and self._fetch_ready(
                    lane, lane.inflight_q[0][0]):
                self._harvest_oldest(lane)
        except Exception:
            # lane-contained failure: charge the batch that was lost, arm
            # this lane's own backoff, and keep every other lane ticking
            with self._lock:
                if took and not dispatched:
                    self._unwind_took_locked(lane, took)
                lane.error_streak += 1
                lane.skip_until = time.monotonic() + backoff_delay(
                    lane.error_streak, 0.5, 5.0)
            self.tick_errors_total += 1
            logger.exception("mesh lane %d tick failed (streak %d)",
                             lane.id, lane.error_streak)
        else:
            lane.error_streak = 0
        if took:
            # lane_step of the frames this tick took: the take to here
            took.t_step_end = time.monotonic()

    def _retire_idle_lanes_locked(self, now: float) -> None:
        """Rebalance on leave: a drained lane is retired after a grace
        period so its device arrays are freed — except the last healthy
        lane, which stays warm for the next joiner. A drained lane with
        quarantined slots is always retired: that is how a poisoned
        fault domain gets recycled into a fresh one."""
        if self.lane_retire_s < 0:
            return
        for lane in list(self.lanes):
            if lane.sessions or lane.inflight_q:
                lane.idle_since = None
                continue
            if lane.idle_since is None:
                lane.idle_since = now
                continue
            if now - lane.idle_since < self.lane_retire_s:
                continue
            if len(self.lanes) == 1 and not lane.health.quarantined:
                continue
            self.lanes.remove(lane)
            self.lanes_retired_total += 1
            logger.info("mesh lane %d retired (%d lanes live, %d slots "
                        "quarantined)", lane.id, len(self.lanes),
                        len(lane.health.quarantined))

    # -- quarantine + live migration ---------------------------------------

    def _migrate_sick_sessions(self) -> None:
        """Quarantine slots whose error EWMA crossed the threshold and
        re-home their sessions onto healthy slots, preferring a different
        lane (the whole lane may be the sick domain). The facade is
        untouched: only the binding moves, the new slot gets a full reset,
        and the capture loop learns via ``consume_migration()``.

        When no free slot exists anywhere, ONE lane build is attempted
        (outside the main lock — the build blocks only this tick thread,
        which already pays first-dispatch compiles by design, never the
        facades) and the pass retries. Still nowhere to go after that:
        the session keeps serving on the sick slot — degraded beats dead
        — counted once per blocked episode in ``migrations_blocked_total``
        and retried every tick while the EWMA keeps the slot flagged."""
        for attempt in (0, 1):
            with self._lock:
                blocked: List[_Session] = []
                for sess in list(self._sessions.values()):
                    if not sess.lane.health.is_sick(sess.slot):
                        continue
                    dest = self._find_migration_slot_locked(sess.lane)
                    if dest is None:
                        blocked.append(sess)
                        continue
                    self._do_migrate_locked(sess, *dest)
            if not blocked:
                return
            if attempt == 0 and self._build_lane() is not None:
                continue            # retry against the fresh lane
            with self._lock:
                for sess in blocked:
                    if sess.sid not in self._blocked_sids:
                        self._blocked_sids.add(sess.sid)
                        self.migrations_blocked_total += 1
            return

    def _do_migrate_locked(self, sess: _Session, dest_lane: _Lane,
                           dest_slot: int) -> None:
        old_lane, old_slot = sess.lane, sess.slot
        old_lane.health.quarantine(old_slot)
        old_lane.sessions.pop(old_slot, None)
        self.quarantined_total += 1
        dest_lane.sessions[dest_slot] = sess
        dest_lane.idle_since = None
        sess.lane, sess.slot = dest_lane, dest_slot
        sess.gen += 1              # drop the old binding's in-flights
        sess.pending = None        # staged for a dead slot
        sess.want_reset = True
        sess.migrations_pending += 1
        self.migrations_total += 1
        self._blocked_sids.discard(sess.sid)
        logger.warning(
            "session %d migrated off sick slot %d/lane %d -> "
            "slot %d/lane %d (slot quarantined)",
            sess.sid, old_slot, old_lane.id, dest_slot, dest_lane.id)

    def _find_migration_slot_locked(
            self, avoid: _Lane) -> Optional[Tuple[_Lane, int]]:
        candidates = [ln for ln in self.lanes
                      if ln is not avoid and ln.free]
        if not candidates and avoid.free:
            # same lane, different slot: weaker isolation, still a new
            # fault domain at slot granularity
            candidates = [avoid]
        if not candidates:
            return None
        lane = min(candidates, key=lambda ln: ln.error_streak)
        return lane, lane.free.pop(0)
