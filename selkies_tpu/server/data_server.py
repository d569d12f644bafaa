"""The WebSocket data/control server.

Behavioral counterpart of the reference's ``DataStreamingServer``
(selkies.py:803-2964): one asyncio server owning the client registry,
settings negotiation, per-display capture/encode pipelines, the frame-ID
backpressure gate, file upload, and the periodic stats feed. The media path
differs by design: instead of pixelflux C++ threads pushing encoded stripes
through a queue, each display runs an asyncio capture loop that submits raw
frames to the pipelined TPU encoder and broadcasts the harvested stripes.

Concurrency model (same invariant as the reference, SURVEY.md §5): a single
asyncio loop owns all mutable state; the TPU pipeline is driven with
non-blocking submits/polls from that loop.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Set, Tuple

try:
    # at module load, not at the first stats tick: compiling psutil's
    # source there held the event loop for 60-85 ms, five seconds after
    # the first client joined (the stall watch's stack, PERF.md PR 25)
    import psutil
except ImportError:             # the stats fall back to the load average
    psutil = None

from ..protocol.wire import (
    FrameId,
    ProtocolError,
    pack_full_frame,
    pack_h264_stripe,
    pack_jpeg_stripe,
    pack_system_health,
    parse_text_message,
    unpack_client_binary,
)
from ..observability.tracing import FlightRecorder
from ..robustness import (
    FAILED,
    UPLOAD_VERB_COST,
    BoundedSendQueue,
    ConnectionGuard,
    DegradationLadder,
    EncoderFault,
    FaultInjector,
    Supervisor,
    backoff_delay,
    classify_verb,
    parse_limit_spec,
)
from ..runtime import COMPILE_GRACE_S
from ..settings import SETTING_DEFINITIONS, Settings
from .backpressure import CHECK_INTERVAL_S, BackpressureState

logger = logging.getLogger("selkies_tpu.server")

STATS_INTERVAL_S = 5.0
UPLOAD_DIR_ENV = "SELKIES_UPLOAD_DIR"

#: largest accepted client display dimension: an unbounded resize request
#: is a memory bomb (the capture source allocates width*height*3 per
#: frame); 8192 covers 8K while keeping one frame under ~200 MB
MAX_DISPLAY_DIM = 8192

#: bounded mesh geometry-bucket count: each bucket's lanes hold device
#: prev planes for all their slots. Joins past the cap are served by
#: solo pipelines — the admission verdict and the acquire-time fallback
#: must agree on this number, or verdicts shed clients solo could serve.
MESH_BUCKET_CAP = 4

#: floor of the capture loop's wedge deadline (it is four watchdog
#: deadlines otherwise): a pipeline that neither accepts nor harvests for
#: this long, and is not compiling, is dead
WEDGE_MIN_S = 30.0


def _clamp_dim(v: int) -> int:
    """Clamp a client-requested display dimension to [16, MAX] and even."""
    return min(MAX_DISPLAY_DIM, max(16, int(v) & ~1))


async def _ready_before(ready: asyncio.Event, deadline: float) -> bool:
    """Sleep until ``deadline`` (``time.monotonic``) or until ``ready`` is
    set, whichever comes first; True where it was ``ready``, which is
    cleared. Always yields to the loop once, as ``asyncio.sleep(0)``."""
    delay = deadline - time.monotonic()
    if delay <= 0.0:
        await asyncio.sleep(0)
        return False
    try:
        await asyncio.wait_for(ready.wait(), delay)
    except asyncio.TimeoutError:
        return False
    ready.clear()
    return True


def _ws_broadcast(targets, message) -> None:
    """Fan one message out to many clients.

    Real websockets go through ``websockets.broadcast`` (non-blocking,
    drops slow consumers at the transport layer). Targets exposing a
    synchronous ``send_nowait`` are served directly instead — that keeps
    the whole data plane drivable by in-process fakes on hosts without the
    websockets package (fault-injection tests, tools/chaos_run.py) and
    open to alternative transports."""
    real = []
    for t in targets:
        fn = getattr(t, "send_nowait", None)
        if fn is not None:
            try:
                fn(message)
            except Exception:
                logger.debug("send_nowait target failed", exc_info=True)
        else:
            real.append(t)
    if real:
        import websockets

        websockets.broadcast(real, message)


class _TracedChunk:
    """A media chunk carrying its frame's flight-recorder trace through
    the owner's send queue: only the LAST stripe of a frame rides traced
    (the frame is decodable when that stripe lands), so queue/send/ack
    measure the whole frame without N-stripe double counting."""

    __slots__ = ("payload", "trace", "t_offer")

    def __init__(self, payload, trace, t_offer: float) -> None:
        self.payload = payload
        self.trace = trace
        self.t_offer = t_offer

    def __len__(self) -> int:       # byte accounting parity with bytes
        return len(self.payload)


class _ClientSendQueue:
    """Asyncio drainer around a :class:`BoundedSendQueue` for one client.

    The fan-out path offers into the bounded queue (synchronous, never
    blocks the capture loop); this drainer task awaits the transport's
    real ``send`` so per-client flow control backs up into the queue —
    where drop-oldest-video and the eviction verdict live — instead of
    into the shared event loop.

    Flight-recorder duty (ISSUE 13): a :class:`_TracedChunk` passing
    through here closes the frame's ``queue`` and ``send`` stages and
    registers the span for ACK correlation; every way a traced chunk can
    die (drop-oldest overflow, a raising transport send, queue teardown)
    lands a terminal ``dropped@`` mark instead of leaking the span."""

    def __init__(self, ws, q: BoundedSendQueue, on_evict,
                 recorder: Optional[FlightRecorder] = None) -> None:
        self.ws = ws
        self.q = q
        self.evicted = False
        self._on_evict = on_evict
        self._recorder = recorder
        # drop-oldest may discard a traced chunk: its span must close
        q.on_drop = self._on_video_dropped
        self._wake = asyncio.Event()
        self.task = asyncio.create_task(self._drain())

    def _on_video_dropped(self, message) -> None:
        if isinstance(message, _TracedChunk) and self._recorder is not None:
            self._recorder.drop(message.trace, "queue")

    def offer(self, message, control: bool) -> None:
        self.q.offer(message, control=control)
        self._wake.set()
        if not self.evicted and self.q.should_evict:
            self.evicted = True
            self._on_evict(self)

    def offer_traced(self, payload, trace) -> None:
        """Queue the frame's last stripe with its trace attached (the
        queue stage opens now; the drainer closes it at pop time)."""
        self.offer(_TracedChunk(payload, trace, time.monotonic()),
                   control=False)

    async def _send_one(self, message) -> None:
        if not isinstance(message, _TracedChunk):
            await self.ws.send(message)
            return
        tr = message.trace
        now = time.monotonic()
        tr.mark("queue", message.t_offer, now)
        # register for ACK correlation BEFORE the await: under write
        # backpressure the payload can reach the client (and its ACK the
        # reader task) while this coroutine is still suspended in send —
        # exactly the frames glass_to_glass_ms exists to observe. An ack
        # racing the send closes the span from the queue-exit mark; the
        # RTT then includes the transport write, which is honest.
        if self._recorder is not None:
            self._recorder.sent(tr)
        try:
            await self.ws.send(message.payload)
        except BaseException:
            # transport death / cancellation mid-send: terminal mark,
            # then let the existing error handling decide the session
            if self._recorder is not None and tr.terminal is None:
                self._recorder.drop(tr, "send")
            raise
        if tr.terminal is None:
            tr.mark("send", now, time.monotonic())

    async def _drain(self) -> None:
        try:
            while True:
                await self._wake.wait()
                self._wake.clear()
                while True:
                    message = self.q.pop()
                    if message is None:
                        break
                    await self._send_one(message)
        except asyncio.CancelledError:
            raise
        except Exception:
            # the connection died mid-send; ws_handler's cleanup owns the
            # socket, the drainer just stops
            logger.debug("send-queue drain ended", exc_info=True)

    def close(self) -> None:
        if self.task is not None and not self.task.done():
            self.task.cancel()
        # spans queued behind the cancellation point must still close
        while True:
            message = self.q.pop()
            if message is None:
                break
            self._on_video_dropped(message)


def upload_dir() -> str:
    """The file-manager root (uploads land here; /files serves it) —
    reference FILE_MANAGER_PATH, selkies.py:98-103."""
    d = os.environ.get(UPLOAD_DIR_ENV) or os.path.join(
        os.path.expanduser("~"), "Desktop")
    os.makedirs(d, exist_ok=True)
    return d


def default_encoder_factory(
    width: int, height: int, settings: Settings,
    overrides: Optional[Dict[str, Any]] = None,
):
    """Encoder-profile selection (parity: the reference's encoder enum,
    settings.py 'encoder' / pixelflux output_mode): ``jpeg`` is the
    device-entropy striped pipeline; ``x264enc-striped``/``x264enc`` are
    the TPU H.264 profiles (striped / one full-frame stripe). CRF settings
    map onto the QP scale (both 0-51).

    The degradation ladder (docs/robustness.md) rides the ``tpu_entropy``
    override: ``host`` builds the encoder with host-side entropy coding;
    the ladder's last rung additionally forces ``encoder=jpeg``. Entropy is
    fixed at construction (the device programs are compiled per tier), so a
    rung change takes effect as a supervised pipeline restart.

    Device-entropy tiers ride the async pipeline driver (ISSUE 12,
    docs/pipeline.md): a dedicated thread keeps >=2 frames in flight —
    dispatch of frame N+1 overlapped with frame N's D2H fetch — so the
    capture loop's submit/poll never touch the device and the served
    encode latency tracks the chip, not the dispatch/fetch floor. Host
    rungs keep the threaded adapter (their encode is synchronous by
    construction)."""
    from ..encoder.async_driver import AsyncEncodeDriver
    from ..encoder.jpeg import JpegStripeEncoder
    from ..encoder.pipeline import (PipelinedH264Encoder,
                                    PipelinedJpegEncoder,
                                    ThreadedEncoderAdapter)

    ov = overrides or {}
    profile = ov.get("encoder", settings.encoder)
    #: the ladder's rung: "host" only through its override
    entropy = ov.get("tpu_entropy") or "device"
    if profile in ("x264enc", "x264enc-striped"):
        from ..encoder.h264 import H264StripeEncoder

        if str(settings.watermark_path):
            logger.warning(
                "watermark is implemented in the JPEG profile only; the "
                "H.264 profiles ignore watermark_path for now")
        crf = int(ov.get("h264_crf", settings.h264_crf.default))
        paint_crf = int(ov.get("h264_paintover_crf",
                               settings.h264_paintover_crf.default))
        even_w, even_h = width - width % 2, height - height % 2
        base = H264StripeEncoder(
            even_w, even_h,
            stripe_height=int(settings.tpu_stripe_height),
            qp=crf, paint_over_qp=paint_crf,
            fullframe=(profile == "x264enc"),
            entropy=entropy,
        )
        if base.entropy != "device":
            # host-entropy rung: harvest is CPU-bound host CAVLC, the
            # threaded adapter's one worker is the right shape for it
            return ThreadedEncoderAdapter(
                base, depth=3, wire_fullframe=(profile == "x264enc"))
        return AsyncEncodeDriver(
            PipelinedH264Encoder(base, depth=4),
            wire_fullframe=(profile == "x264enc"))
    base = JpegStripeEncoder(
        width,
        height,
        stripe_height=settings.tpu_stripe_height,
        quality=ov.get("jpeg_quality", settings.jpeg_quality.default),
        paintover_quality=ov.get(
            "paint_over_jpeg_quality",
            settings.paint_over_jpeg_quality.default),
        use_paint_over_quality=ov.get(
            "use_paint_over_quality",
            settings.use_paint_over_quality.value),
        entropy=entropy,
        watermark_path=str(settings.watermark_path),
        watermark_location=int(settings.watermark_location),
    )
    if base.entropy != "device":
        # degraded rung: host entropy coding can't ride the device-packed
        # pipeline, so the synchronous encode_frame path runs off-loop in
        # the threaded adapter instead
        return ThreadedEncoderAdapter(base, depth=3)
    return AsyncEncodeDriver(
        PipelinedJpegEncoder(base, depth=4, fetch_group=2))


def default_source_factory(width: int, height: int, fps: float,
                           x: int = 0, y: int = 0):
    from ..capture.x11 import X11Source
    from ..capture.synthetic import SyntheticSource

    if X11Source.available():
        return X11Source(width, height, fps, x=x, y=y)
    return SyntheticSource(width, height, fps, pattern="desktop")


@dataclass
class DisplayState:
    display_id: str
    ws: Any = None
    width: int = 1024
    height: int = 768
    #: framebuffer offset of this display (set by _apply_x11_layout)
    x: int = 0
    y: int = 0
    bp: BackpressureState = field(default_factory=BackpressureState)
    #: serializes start/stop/reconfigure (they await mid-flight, so two
    #: concurrent calls could otherwise both pass the is-running guard and
    #: spawn duplicate capture loops)
    lock: asyncio.Lock = field(default_factory=asyncio.Lock)
    capture_task: Optional[asyncio.Task] = None
    backpressure_task: Optional[asyncio.Task] = None
    #: supervisors owning the two loops above (ISSUE 2): crash restarts
    #: with bounded backoff, frame-deadline watchdog, restart budget
    supervisor: Optional[Supervisor] = None
    bp_supervisor: Optional[Supervisor] = None
    #: encoder degradation state (device -> host -> jpeg); persists across
    #: supervised restarts and reconfigures — it is display health, not
    #: pipeline state
    ladder: DegradationLadder = field(default_factory=DegradationLadder)
    #: sticky terminal marker: the capture supervisor exhausted its restart
    #: budget and the pipeline was torn down (cleared by an explicit
    #: START_VIDEO / reconfigure restart)
    failed: bool = False
    #: wedge faults at the bottom rung (nowhere left to degrade): each
    #: restart of a hung encoder can abandon a blocked worker thread, so
    #: these are bounded — a few strikes and the display goes terminal
    wedge_faults: int = 0
    video_active: bool = True
    #: clamped per-client setting overrides from the SETTINGS handshake
    overrides: Dict[str, Any] = field(default_factory=dict)
    #: live encoder of the running capture loop (keyframe kicks)
    encoder: Any = None
    #: (w, h, x, y) the running pipeline was started with — scoped
    #: reconfiguration restarts only displays whose geometry changed
    running_geom: Optional[Tuple[int, int, int, int]] = None
    #: (overrides, framerate) snapshot at pipeline start: a SETTINGS
    #: change with unchanged geometry must still rebuild the encoder
    running_config: Optional[Tuple[Dict[str, Any], float]] = None


@dataclass
class _Upload:
    path: str
    rel_path: str  # as the client named it; echoed back in errors
    fobj: Any
    received: int = 0
    size: int = 0


class DataStreamingServer:
    def __init__(
        self,
        settings: Settings,
        app=None,
        encoder_factory: Callable = default_encoder_factory,
        source_factory: Callable = default_source_factory,
        input_handler=None,
        host: str = "0.0.0.0",
    ) -> None:
        self.settings = settings
        self.app = app
        self.input_handler = input_handler
        self.encoder_factory = encoder_factory
        self.source_factory = source_factory
        self.host = host
        self.port = settings.port

        self.clients: Set[Any] = set()
        self.display_clients: Dict[str, DisplayState] = {}
        self._uploads: Dict[Any, _Upload] = {}
        self._stats_task: Optional[asyncio.Task] = None
        self._server = None
        self._stop_event: Optional[asyncio.Event] = None
        self.bytes_sent = 0
        self.metrics = None         # wired by main() when prometheus is up
        self.audio_pipeline = None  # wired by main() when audio is enabled
        self.warmup = None          # main.WarmUp: boot compile + its outcome
        self._audio_wanted = True   # cleared by STOP_AUDIO until re-requested
        self._last_layout = None    # last xrandr-applied Layout (dedup)
        #: mesh-batched encode (tpu_mesh setting, BASELINE configs 4/5):
        #: one coordinator per display geometry, lazily built — a
        #: mismatched-resolution join gets its own bucket instead of a
        #: silent solo fallback
        self.mesh_coordinators: Dict[Tuple[int, int, str], Any] = {}
        #: coordinator constructor override (tests / tools/swarm_run.py):
        #: same signature as MeshEncodeCoordinator — lets harnesses run
        #: the real scheduler over injected (device-free) encoders
        self.coordinator_factory: Optional[Callable] = None
        #: geometries whose coordinator construction failed — scoped per
        #: geometry so one bad bucket (e.g. a transient OOM at 4K) does
        #: not disable mesh batching for healthy buckets
        self._mesh_failed_geoms: Set[Tuple[int, int, str]] = set()
        #: counters surfaced in the stats JSON so mesh fallbacks are
        #: observable, not silent
        self.mesh_stats = {"bucketed": 0, "solo_fallback": 0}
        #: fault-injection registry for this server (docs/robustness.md):
        #: armed from the tpu_faults setting / SELKIES_TPU_FAULTS env and
        #: checked at the real capture/encode/fetch/ws call sites
        self.faults = FaultInjector(str(getattr(settings, "tpu_faults", "")
                                        or ""))
        #: frame flight recorder (ISSUE 13, docs/observability.md): every
        #: served frame's capture→ack stage timeline, exported via the
        #: metrics endpoint (/debug/trace), the system_health feed, and
        #: the per-stage Prometheus histograms. Always on — marking a
        #: trace is a few dict stores per frame.
        self.recorder = FlightRecorder(capacity=4096)
        #: device probes started by server.main.serve (one per device):
        #: the stats tick reads their last memory sample instead of
        #: calling the device from the event loop
        self.device_probes: list = []
        #: fire-and-forget helpers (ws.drop closes, failed-display
        #: teardown) — referenced so they are neither GC'd mid-flight nor
        #: left to warn "exception was never retrieved"
        self._bg_tasks: Set[asyncio.Task] = set()
        # --- wire-edge hardening (ISSUE 3, docs/hardening.md) ---
        #: per-class rate limits; a bad rate_limits spec fails construction
        #: loudly, like a bad fault spec
        self._limits = parse_limit_spec(
            str(getattr(settings, "rate_limits", "") or ""))
        #: per-connection protocol armor (error budget + class buckets)
        self._guards: Dict[Any, ConnectionGuard] = {}
        #: per-client bounded send queues wrapped around the fan-out path
        self._send_queues: Dict[Any, _ClientSendQueue] = {}
        #: local mirrors of the edge metrics so behavior is assertable
        #: without prometheus (rate_limited is per message class)
        self.edge_stats: Dict[str, Any] = {
            "protocol_errors": 0,
            "rate_limited": {},
            "upload_paced": 0,
            "sessions_rejected": 0,
            "sessions_queued": 0,
            "slow_client_evictions": 0,
            "reconfigure_runs": 0,
            "reconfigure_coalesced": 0,
        }
        #: debounced/serialized display reconfiguration: a resize storm
        #: coalesces into one stop-the-world reconfigure, not one per message
        self._reconfig_task: Optional[asyncio.Task] = None
        self._reconfig_dirty = False
        #: admission-control load shedding (driven by sustained encoder
        #: drops observed in the stats loop)
        self._load_shedding = False
        self._shed_strikes = 0
        self._last_dropped_total = 0

    @property
    def mesh_coordinator(self):
        """First (primary-geometry) coordinator — back-compat accessor."""
        return next(iter(self.mesh_coordinators.values()), None)

    # ------------------------------------------------------------------
    # broadcast primitives

    def broadcast(self, message) -> None:
        if self.clients:
            self._fanout(self.clients, message)
            if isinstance(message, (bytes, bytearray)):
                self.bytes_sent += len(message) * len(self.clients)

    def _fanout(self, targets, message) -> None:
        """Fan one message out through the per-client bounded send queues
        (docs/hardening.md): text is control (never dropped), binary media
        is droppable — a slow consumer converges to the live edge of the
        stream or is evicted, and never stalls the capture loop. Targets
        without a queue (added outside ws_handler, or mid-handshake) get
        the direct transport broadcast."""
        control = isinstance(message, str)
        direct = []
        for t in targets:
            cq = self._send_queues.get(t)
            if cq is None:
                direct.append(t)
            elif not cq.evicted:
                cq.offer(message, control)
        if direct:
            _ws_broadcast(direct, message)

    def _evict_slow_client(self, cq: _ClientSendQueue) -> None:
        """Sustained send-queue overflow: this consumer is not keeping up
        and dropping video no longer helps — close its one socket (with a
        best-effort KILL) so its backlog stops costing memory."""
        self.edge_stats["slow_client_evictions"] += 1
        if self.metrics is not None:
            self.metrics.inc_slow_client_eviction()
        logger.warning(
            "evicting slow consumer: queue depth %d, %d video drops",
            len(cq.q), cq.q.dropped_video_total)
        cq.close()   # the drainer may be wedged inside a stalled send
        ws = cq.ws

        async def _kill():
            try:
                await asyncio.wait_for(ws.send("KILL slow_consumer"), 1.0)
            except Exception:
                pass
            await ws.close()

        self._spawn_background(_kill(), "evict-slow-client")

    def _viewers_of(self, display_id: str) -> Set[Any]:
        """Primary-display media is fanned out to every client (sharing
        modes); secondary displays go only to their owning client."""
        if display_id == "primary":
            return set(self.clients)
        st = self.display_clients.get(display_id)
        return {st.ws} if st and st.ws else set()

    # ------------------------------------------------------------------
    # lifecycle

    #: bind-retry policy: capped exponential backoff with jitter, then a
    #: hard error — an occupied port must fail loudly, not retry at a
    #: fixed 1 Hz forever (class attributes so tests can shrink them)
    BIND_MAX_ATTEMPTS = 8
    BIND_BASE_DELAY_S = 0.5
    BIND_MAX_DELAY_S = 10.0

    async def run_server(self) -> None:
        """Serve until stop() — with crash-restart supervision like the
        reference's run loop (selkies.py:2453-2510)."""
        import websockets.asyncio.server as ws_server

        self._stop_event = asyncio.Event()
        bind_attempts = 0
        # transport-level armor: an unbounded max_size lets one client
        # frame buffer arbitrary memory before any handler runs
        cap_mb = int(getattr(self.settings, "max_ws_message_mb", 0))
        max_size = cap_mb * 1024 * 1024 if cap_mb > 0 else None
        while not self._stop_event.is_set():
            try:
                async with ws_server.serve(
                    self.ws_handler, self.host, self.port,
                    compression=None, max_size=max_size,
                ) as server:
                    self._server = server
                    bind_attempts = 0
                    logger.info("data server listening on %s:%d", self.host, self.port)
                    await self._stop_event.wait()
            except OSError as e:
                bind_attempts += 1
                if bind_attempts >= self.BIND_MAX_ATTEMPTS:
                    raise RuntimeError(
                        f"data server could not bind {self.host}:{self.port}"
                        f" after {bind_attempts} attempts: {e}") from e
                delay = backoff_delay(bind_attempts, self.BIND_BASE_DELAY_S,
                                      self.BIND_MAX_DELAY_S, jitter=0.25)
                logger.error("server bind failed (%s); retry %d/%d in %.1fs",
                             e, bind_attempts, self.BIND_MAX_ATTEMPTS, delay)
                await asyncio.sleep(delay)

    async def stop(self) -> None:
        if self._reconfig_task is not None and not self._reconfig_task.done():
            self._reconfig_task.cancel()
        for cq in list(self._send_queues.values()):
            cq.close()
        self._send_queues.clear()
        for st in list(self.display_clients.values()):
            await self._stop_display(st)
        for coord in self.mesh_coordinators.values():
            coord.stop()
        self.mesh_coordinators.clear()
        if self.audio_pipeline is not None:
            await self.audio_pipeline.stop()
            self.audio_pipeline.close()
        if self._stats_task:
            self._stats_task.cancel()
        if self._stop_event:
            self._stop_event.set()

    # ------------------------------------------------------------------
    # connection handling

    async def _admit(self, websocket) -> bool:
        """Admission control at accept time (docs/hardening.md): a full or
        load-shedding server rejects the connection gracefully — a wire
        KILL the client UI can show — instead of degrading every session."""
        maxc = int(getattr(self.settings, "max_clients", 0) or 0)
        full = bool(maxc and len(self.clients) >= maxc)
        if not full and not self._load_shedding:
            return True
        self.edge_stats["sessions_rejected"] += 1
        if self.metrics is not None:
            self.metrics.inc_sessions_rejected()
        logger.warning("connection rejected: %s",
                       "server_full" if full else "load_shedding")
        try:
            await websocket.send("KILL server_full")
        except Exception:
            pass
        try:
            await websocket.close()
        except Exception:
            pass
        return False

    # -- display-plane admission: scheduler verdicts (docs/scaling.md) --

    def _mesh_profile_of(self, overrides: Dict[str, Any]) -> str:
        return str(overrides.get("encoder", self.settings.encoder))

    def _display_admission_verdict(self, width: int, height: int,
                                   overrides: Dict[str, Any]) -> str:
        """``admit`` / ``queue`` / ``shed`` for a NEW display join.

        The flat ``max_displays`` cap is the hard backstop; below it the
        verdict comes from live lane capacity: a join whose geometry
        bucket has a free or growable slot is admitted, a join into a
        momentarily-full scheduler queues (leave/resize churn frees slots
        within the queue window), and a genuinely full scheduler sheds.
        Displays the mesh cannot serve (solo-only profiles, watermark,
        failed geometries) are admitted toward their solo pipelines, and
        ``mesh_overflow_solo`` restores the pre-scheduler overflow-to-solo
        behavior wholesale."""
        if self._load_shedding:
            return "shed"
        maxd = int(getattr(self.settings, "max_displays", 0) or 0)
        if maxd and len(self.display_clients) >= maxd:
            return "shed"
        if not str(self.settings.tpu_mesh) or \
                bool(getattr(self.settings, "mesh_overflow_solo", False)):
            return "admit"
        profile = self._mesh_profile_of(overrides)
        if profile not in ("jpeg", "x264enc-striped") or \
                str(self.settings.watermark_path):
            return "admit"          # solo-served by design, not overflow
        geom = (_clamp_dim(width), _clamp_dim(height), profile)
        coord = self.mesh_coordinators.get(geom)
        if coord is None:
            if geom in self._mesh_failed_geoms:
                return "admit"      # this geometry runs solo (scoped)
            # below the bucket cap a fresh bucket can be built; past it
            # the acquire path serves the join with a solo encoder by
            # design — admit toward that, never queue on a condition
            # that cannot resolve (buckets are not retired)
            return "admit"
        try:
            cap = coord.capacity()
        except Exception:
            return "admit"
        if cap["slots_free"] + cap["growable_slots"] > 0:
            return "admit"
        return "queue"

    async def _await_display_admission(self, width: int, height: int,
                                       overrides: Dict[str, Any]) -> str:
        """Hold a queued join for up to ``admission_queue_ms`` waiting for
        a scheduler slot to free (leave/resize churn), then resolve to
        admit or shed. Bounded by construction — a queued client is never
        parked forever."""
        self.edge_stats["sessions_queued"] += 1
        if self.metrics is not None:
            self.metrics.inc_sessions_queued()
        wait_ms = int(getattr(self.settings, "admission_queue_ms", 0) or 0)
        deadline = time.monotonic() + wait_ms / 1000.0
        while True:
            verdict = self._display_admission_verdict(
                width, height, overrides)
            if verdict != "queue":
                return verdict
            if time.monotonic() >= deadline:
                return "shed"
            await asyncio.sleep(0.025)

    def scheduler_stats(self) -> Optional[Dict[str, int]]:
        """Aggregate live lane capacity across geometry buckets (None
        when mesh batching is off) — the admission verdicts' input,
        surfaced for the stats feed and harnesses."""
        if not str(self.settings.tpu_mesh):
            return None
        agg = {"slots_free": 0, "growable_slots": 0, "slots_total": 0,
               "quarantined_slots": 0, "active_sessions": 0, "lanes": 0}
        for coord in self.mesh_coordinators.values():
            try:
                cap = coord.capacity()
            except Exception:
                continue
            for k in agg:
                agg[k] += int(cap.get(k, 0))
        return agg

    async def ws_handler(self, websocket) -> None:
        if not await self._admit(websocket):
            return
        self._guards[websocket] = ConnectionGuard(
            limits=self._limits,
            error_budget=int(getattr(self.settings,
                                     "protocol_error_budget", 25)))
        self.clients.add(websocket)
        if self.metrics is not None:
            self.metrics.set_clients(len(self.clients))
        # late-joining viewer (sharing modes): damage gating means static
        # content would never reach it — force the next frame to be a full
        # refresh / IDR on the primary stream
        primary = self.display_clients.get("primary")
        if primary is not None and primary.encoder is not None:
            kick = getattr(primary.encoder, "force_keyframe", None) \
                or getattr(primary.encoder, "request_keyframe", None)
            if kick is not None:
                kick()
        try:
            if (self.audio_pipeline is not None and self._audio_wanted
                    and not self.audio_pipeline.running):
                await self.audio_pipeline.start()
            await websocket.send("MODE websockets")
            if self.app and self.app.last_cursor_sent:
                await websocket.send(
                    "cursor," + json.dumps(self.app.last_cursor_sent))
            await websocket.send(json.dumps(self.settings.schema_payload()))
            # handshake done: fan-out to this client now rides its bounded
            # send queue (slow-consumer isolation + eviction)
            self._send_queues[websocket] = _ClientSendQueue(
                websocket,
                BoundedSendQueue(
                    max_video=int(self.settings.max_send_queue),
                    evict_after_s=float(int(
                        self.settings.slow_client_evict_s))),
                on_evict=self._evict_slow_client,
                recorder=self.recorder)
            if self._stats_task is None or self._stats_task.done():
                self._stats_task = asyncio.create_task(self._stats_loop())
            async for message in websocket:
                # Per-message exception boundary: a malformed or
                # handler-crashing message is dropped and charged against
                # this connection's error budget — it must never end the
                # async-for loop (= the whole session) the way a transport
                # error does, and never touch other clients' sessions.
                try:
                    if isinstance(message, (bytes, bytearray)):
                        await self._handle_binary(websocket, message)
                    else:
                        await self._handle_text(websocket, message)
                except Exception as e:
                    if (isinstance(e, ConnectionError)
                            or type(e).__name__.startswith(
                                "ConnectionClosed")):
                        # a handler failing to SEND to a dead peer is
                        # transport death, not client hostility: end the
                        # session (pre-boundary behavior) instead of
                        # polluting protocol_errors_total / the budget
                        raise
                    self.edge_stats["protocol_errors"] += 1
                    if self.metrics is not None:
                        self.metrics.inc_protocol_errors()
                    logger.debug("protocol error (dropped message): %r", e)
                    guard = self._guards.get(websocket)
                    if guard is not None and guard.record_error():
                        logger.warning(
                            "error budget exhausted after %d protocol "
                            "errors; killing abusive client",
                            guard.errors_total)
                        try:
                            await websocket.send("KILL protocol_abuse")
                        except Exception:
                            pass
                        await websocket.close()
                        break
        except Exception as e:  # connection errors end the session
            logger.debug("ws session ended: %r", e)
        finally:
            self.clients.discard(websocket)
            self._guards.pop(websocket, None)
            cq = self._send_queues.pop(websocket, None)
            if cq is not None:
                cq.close()
            if self.metrics is not None:
                self.metrics.set_clients(len(self.clients))
            up = self._uploads.pop(websocket, None)
            if up is not None:
                # never leak the fd or the partial file of an interrupted
                # upload (satellite: upload fd leak on disconnect)
                self._abort_upload(up)
                logger.info("upload aborted by disconnect: %s (%d/%d bytes)",
                            up.path, up.received, up.size)
            dropped = False
            for st in list(self.display_clients.values()):
                if st.ws is websocket:
                    # deregister FIRST: a concurrent reconfigure worker
                    # must see the display as gone before our stop lands,
                    # or it can restart a zombie pipeline that holds its
                    # scheduler slot forever (found by tools/swarm_run.py)
                    del self.display_clients[st.display_id]
                    await self._stop_display(st)
                    dropped = True
            if dropped and self.display_clients:
                # surviving displays reflow into a smaller framebuffer
                self._schedule_reconfigure()
            if (not self.clients and self.audio_pipeline is not None
                    and self.audio_pipeline.running):
                await self.audio_pipeline.stop()

    # ------------------------------------------------------------------
    # text protocol

    def _count_rate_limited(self, cls: str) -> None:
        counts = self.edge_stats["rate_limited"]
        counts[cls] = counts.get(cls, 0) + 1
        if self.metrics is not None:
            self.metrics.inc_rate_limited(cls)

    def _count_upload_paced(self) -> None:
        # pacing ACCEPTS the message after a sleep: a separate series so
        # a fast healthy upload never reads as "dropped by rate limiting"
        self.edge_stats["upload_paced"] += 1
        if self.metrics is not None:
            self.metrics.inc_upload_paced()

    async def _handle_text(self, websocket, message: str) -> None:
        msg = parse_text_message(message)   # ProtocolError → boundary
        verb = msg.verb

        guard = self._guards.get(websocket)
        if guard is not None:
            cls = classify_verb(verb)
            if cls == "upload":
                # stateful upload verbs are paced like upload bytes, never
                # dropped — a dropped FILE_UPLOAD_END leaves the fd open
                # and splices the next file into it
                wait = guard.throttle("upload", UPLOAD_VERB_COST)
                if wait > 0:
                    self._count_upload_paced()
                    await asyncio.sleep(wait)
            elif not guard.allow(cls):
                self._count_rate_limited(cls)
                logger.debug("rate-limited %s message %r", cls, verb[:32])
                return

        if verb == "SETTINGS":
            await self._on_settings(websocket, msg.json_body or "{}")
        elif verb == "CLIENT_FRAME_ACK":
            # Only the display's OWNER acks: a shared-mode viewer (or a
            # hostile client) feeding random ids into the primary's
            # backpressure state would wedge the gate for everyone.
            st = self._display_of(websocket)
            if st and st.ws is websocket and msg.args:
                try:
                    fid = int(msg.args[0])
                except ValueError:
                    pass
                else:
                    st.bp.on_client_ack(fid)
                    # the ACK closes the frame's flight span with the
                    # true network round trip (send end → ack arrival)
                    self.recorder.ack(st.display_id, fid)
        elif verb == "r" and len(msg.args) >= 1:
            await self._on_resize(websocket, msg.args)
        elif verb == "START_VIDEO":
            st = self._display_of(websocket)
            if st and st.ws is websocket:
                st.video_active = True
                await self._start_display(st)
                # through the send queue, like PIPELINE_RESETTING: the
                # reply must not overtake media already queued behind it
                self._fanout({websocket}, "VIDEO_STARTED")
        elif verb == "STOP_VIDEO":
            st = self._display_of(websocket)
            if st and st.ws is websocket:
                st.video_active = False
                await self._stop_display(st)
                self._fanout({websocket}, "VIDEO_STOPPED")
        elif verb == "START_AUDIO":
            self._audio_wanted = True
            if self.audio_pipeline is not None:
                await self.audio_pipeline.start()
                self.broadcast("AUDIO_STARTED")
        elif verb == "STOP_AUDIO":
            self._audio_wanted = False
            if self.audio_pipeline is not None:
                await self.audio_pipeline.stop()
                self.broadcast("AUDIO_STOPPED")
        elif verb == "FILE_UPLOAD_START":
            await self._on_upload_start(websocket, msg.args)
        elif verb == "FILE_UPLOAD_END":
            up = self._uploads.pop(websocket, None)
            if up:
                up.fobj.close()
                if up.size and up.received < up.size:
                    # a short upload is a broken file: remove it and tell
                    # the client rather than leaving truncated data behind
                    logger.warning("short upload removed: %s (%d/%d bytes)",
                                   up.path, up.received, up.size)
                    try:
                        os.unlink(up.path)
                    except OSError:
                        pass
                    await websocket.send(
                        f"FILE_UPLOAD_ERROR:{up.rel_path}:"
                        f"short upload ({up.received}/{up.size} bytes)")
                else:
                    logger.info("upload finished: %s (%d bytes)",
                                up.path, up.received)
        elif verb == "FILE_UPLOAD_ERROR":
            up = self._uploads.pop(websocket, None)
            if up:
                self._abort_upload(up)
        elif verb == "s" and msg.args:
            # scale request (reference "s,<scale>"): HiDPI factor → Xft DPI
            try:
                scale = min(4.0, max(0.5, float(msg.args[0])))
                await self._apply_dpi(int(round(96 * scale)))
            except ValueError:
                pass
        elif verb == "SET_NATIVE_CURSOR_RENDERING" and msg.args:
            # client renders the cursor itself (CSS) vs composited frames;
            # re-send the last cursor so the toggle takes effect immediately
            if self.app is not None and self.app.last_cursor_sent:
                try:
                    await websocket.send(
                        "cursor," + json.dumps(self.app.last_cursor_sent))
                except Exception:
                    pass
        elif verb == "cmd":
            if self.settings.command_enabled.value and msg.args:
                await self._run_command(msg.args[0])
        else:
            # Everything else is input-plane grammar; forward whole messages
            # like the reference ws_handler does for non-prefixed text.
            if verb == "_f":
                st = self._display_of(websocket)
                if st and st.ws is websocket and msg.args:
                    try:
                        fps = float(msg.args[0])
                        st.bp.on_client_fps(fps)
                        if self.metrics is not None:
                            self.metrics.set_fps(fps)
                    except ValueError:
                        pass
            elif verb == "_l" and msg.args and self.metrics is not None:
                try:
                    self.metrics.set_latency(float(msg.args[0]))
                except ValueError:
                    pass
            if self.input_handler is not None:
                await self.input_handler.on_message(
                    message, self._display_id_of(websocket))
            else:
                logger.debug("unhandled message verb %r", verb)

    # ------------------------------------------------------------------
    # binary protocol (client → server)

    async def _handle_binary(self, websocket, data: bytes) -> None:
        if not data:
            raise ProtocolError("empty binary frame")
        guard = self._guards.get(websocket)
        t = data[0]
        if t == 0x01:  # file chunk
            if guard is not None:
                # uploads are PACED, not dropped (a dropped chunk corrupts
                # the file): sleeping here stops reading the socket, which
                # backpressures the sender through TCP. Charged BEFORE the
                # open-upload check so orphan 0x01 floods (no
                # FILE_UPLOAD_START) are metered like any other bytes
                # instead of being a free unmetered lane.
                wait = guard.throttle("upload", len(data))
                if wait > 0:
                    self._count_upload_paced()
                    await asyncio.sleep(wait)
            up = self._uploads.get(websocket)
            if up:
                # Absolute cap holds even when the client declares size 0
                # (or lies): declared size is a courtesy check, the cap is
                # the actual hardening.
                cap = self.settings.max_upload_mb * 1024 * 1024
                limit = min(up.size, cap) if up.size else cap
                if limit and up.received + len(data) - 1 > limit:
                    self._uploads.pop(websocket, None)
                    self._abort_upload(up)
                    await websocket.send(
                        f"FILE_UPLOAD_ERROR:{up.rel_path}:"
                        "exceeded size limit")
                    return
                up.fobj.write(data[1:])
                up.received += len(data) - 1
        elif t == 0x02:  # microphone PCM
            cap = int(getattr(self.settings, "max_mic_chunk_kb", 0)) * 1024
            if cap and len(data) - 1 > cap:
                # file chunks have max_upload_mb; mic bytes get their own
                # cap before they reach the audio pipeline's resampler
                raise ProtocolError(
                    f"mic chunk of {len(data) - 1} bytes exceeds "
                    f"{cap}-byte cap")
            if guard is not None and not guard.allow("mic", len(data)):
                self._count_rate_limited("mic")
                return
            if self.audio_pipeline is not None:
                await self.audio_pipeline.on_mic_data(data[1:])
        else:
            # the canonical demux raises the precise rejection (wrong-
            # direction 0x00/0x03/0x04 vs unknown) — one trust boundary,
            # not two that drift
            unpack_client_binary(data)
            raise ProtocolError(f"unroutable client binary type 0x{t:02x}")

    def _abort_upload(self, up: _Upload) -> None:
        """Close the fd and remove the partial file of a dead upload."""
        try:
            up.fobj.close()
        except Exception:
            pass
        try:
            os.unlink(up.path)
        except OSError:
            pass

    # ------------------------------------------------------------------
    # settings negotiation

    async def _on_settings(self, websocket, body: str) -> None:
        try:
            requested = json.loads(body)
        except json.JSONDecodeError:
            logger.warning("bad SETTINGS payload")
            return
        display_id = str(requested.get("displayId", "primary"))

        if display_id != "primary" and not self.settings.second_screen.value:
            await websocket.send("KILL Second screens are disabled on this server.")
            await websocket.close()
            return

        # Parse/clamp every client value BEFORE touching any state: a
        # garbage value must cost only itself (ignored + logged), never
        # leave a half-registered zombie display holding a max_displays
        # slot or a live display with partially-applied settings.
        known = {s.name for s in SETTING_DEFINITIONS}
        applied: Dict[str, Any] = {}
        width = height = None
        for key, value in requested.items():
            if key in ("displayId",):
                continue
            try:
                if key == "initialClientWidth":
                    width = _clamp_dim(value)
                elif key == "initialClientHeight":
                    height = _clamp_dim(value)
                elif key in known:
                    applied[key] = self.settings.clamp_client_value(
                        key, value)
            except (TypeError, ValueError):
                logger.warning("ignoring bad client setting %s=%r",
                               key, value)

        st = self.display_clients.get(display_id)
        if st and st.ws is not None and st.ws is not websocket:
            # superseded client for this display: kill the old one
            try:
                await st.ws.send("KILL Display taken over by another client.")
                await st.ws.close()
            except Exception:
                pass
        if st is None:
            # admission control on the display plane (docs/scaling.md):
            # each display is a capture+encode pipeline, far heavier than
            # a viewer — the verdict comes from live scheduler lane
            # capacity (admit / queue / shed), with max_displays as the
            # hard backstop above it
            verdict = self._display_admission_verdict(
                width or 1024, height or 768, applied)
            if verdict == "queue":
                verdict = await self._await_display_admission(
                    width or 1024, height or 768, applied)
            if verdict != "admit":
                self.edge_stats["sessions_rejected"] += 1
                if self.metrics is not None:
                    self.metrics.inc_sessions_rejected()
                logger.warning(
                    "display %s rejected (%s): %d displays live",
                    display_id, verdict, len(self.display_clients))
                await websocket.send("KILL server_full")
                await websocket.close()
                return
            # the queue wait yields the loop: another handshake may have
            # registered this display meanwhile — adopt it (superseding
            # its client, same as the pre-wait path), don't clobber
            st = self.display_clients.get(display_id)
            if st is not None and st.ws is not None \
                    and st.ws is not websocket:
                try:
                    await st.ws.send(
                        "KILL Display taken over by another client.")
                    await st.ws.close()
                except Exception:
                    pass
            if st is None:
                st = DisplayState(display_id=display_id)
                self.display_clients[display_id] = st
        st.ws = websocket
        if width is not None:
            st.width = width
        if height is not None:
            st.height = height
        st.overrides.update(applied)
        if "framerate" in applied:
            st.bp.framerate = float(applied["framerate"])
        logger.info("client settings for %s: %s", display_id, applied)

        if "scaling_dpi" in applied:
            await self._apply_dpi(int(applied["scaling_dpi"]))
        self._schedule_reconfigure()

    async def _apply_dpi(self, dpi: int) -> None:
        from ..display import DpiManager

        try:
            await asyncio.to_thread(DpiManager().set_dpi, dpi)
        except ValueError as e:
            logger.warning("dpi rejected: %s", e)

    async def _on_resize(self, websocket, args) -> None:
        if self.settings.is_manual_resolution_mode.value:
            return
        try:
            res = args[0]
            display_id = args[1] if len(args) > 1 else "primary"
            w, h = (int(v) for v in res.split("x"))
        except (ValueError, IndexError):
            return
        st = self.display_clients.get(display_id)
        if not st or st.ws is not websocket:
            # resizing is owner-only: a viewer must not be able to force
            # stop-the-world reconfigurations of someone else's display
            return
        st.width, st.height = _clamp_dim(w), _clamp_dim(h)
        self._schedule_reconfigure()
        self.broadcast(json.dumps({
            "type": "stream_resolution",
            "width": st.width,
            "height": st.height,
        }))

    def _schedule_reconfigure(self) -> None:
        """Debounce/coalesce display reconfiguration behind one serialized
        worker task: ``_reconfigure_displays`` stops and restarts EVERY
        capture pipeline, so a client spamming ``r,<WxH>`` must cost one
        reconfiguration per storm, not one per message."""
        self._reconfig_dirty = True
        if self._reconfig_task is None or self._reconfig_task.done():
            self._reconfig_task = asyncio.create_task(
                self._reconfigure_worker())
        else:
            self.edge_stats["reconfigure_coalesced"] += 1
            if self.metrics is not None:
                self.metrics.inc_reconfigure_coalesced()

    async def _reconfigure_worker(self) -> None:
        try:
            debounce = max(0, int(getattr(self.settings,
                                          "resize_debounce_ms", 0))) / 1000.0
            while self._reconfig_dirty:
                if debounce:
                    # absorb the rest of the storm before doing the work;
                    # requests landing mid-run re-arm the dirty flag and
                    # get one more (batched) pass
                    await asyncio.sleep(debounce)
                self._reconfig_dirty = False
                self.edge_stats["reconfigure_runs"] += 1
                await self._reconfigure_displays()
        except asyncio.CancelledError:
            raise
        except Exception:
            # a failed reconfigure must not take the worker down with an
            # unretrieved exception; the next request starts a fresh one
            logger.exception("display reconfiguration failed")

    async def _reconfigure_displays(self) -> None:
        """Display-plane reconfiguration (reference reconfigure_displays
        selkies.py:2616): stop captures, re-arrange the X screen, then
        restart active pipelines with their new geometry/offsets.

        With a real X server every capture stops FIRST so no XGetImage
        ever races a shrinking root window. Without one (synthetic
        capture: tests, the swarm churn harness) the restart is SCOPED to
        displays whose geometry or offset actually changed — under
        join/leave/resize churn at hundreds of sessions, a stop-the-world
        restart per event would itself be the outage (docs/scaling.md)."""
        scoped = True
        try:
            from ..display import xrandr_available

            scoped = not xrandr_available()
        except Exception:
            pass
        if not scoped:
            for st in list(self.display_clients.values()):
                await self._stop_display(st)
        await self._apply_x11_layout()
        for st in list(self.display_clients.values()):
            if not (st.video_active and st.ws is not None):
                continue
            # running_geom/_config are what the live pipeline was STARTED
            # with; st.width/height/overrides already carry the request.
            # Offset-only shifts (every join reflows the framebuffer
            # layout) don't restart in scoped mode: without xrandr there
            # is no shared root window whose regions could go stale, and
            # restarting N-1 healthy streams per join is the exact
            # stop-the-world cost this path exists to avoid. A SETTINGS
            # change (quality/framerate/encoder overrides) DOES restart —
            # the encoder is built from that snapshot.
            changed = (st.running_geom is None
                       or st.running_geom[:2] != (st.width, st.height)
                       or st.running_config != (st.overrides,
                                                st.bp.framerate))
            running = st.capture_task is not None \
                and not st.capture_task.done()
            if scoped and running and not changed:
                continue        # untouched display keeps streaming
            if scoped and running:
                await self._stop_display(st)
            await self._start_display(st)

    async def _apply_x11_layout(self) -> None:
        """Arrange the client displays into one framebuffer and mirror it
        onto the real X screen (xrandr modes, --fb, --setmonitor).  Always
        updates per-display capture offsets; the xrandr half is skipped on
        hosts without it (synthetic capture) or when the layout is unchanged
        since the last apply."""
        from ..display import (XrandrManager, compute_layout,
                               xrandr_available)

        if not self.display_clients:
            return
        displays = {d: (st.width, st.height)
                    for d, st in self.display_clients.items()}
        primary = self.display_clients.get("primary")
        position = ((primary.overrides.get("second_screen_position")
                     if primary else None)
                    or self.settings.second_screen_position)
        try:
            layout = compute_layout(displays, position)
        except ValueError as e:
            logger.warning("layout rejected: %s", e)
            return
        for p in layout.placements:
            stp = self.display_clients.get(p.display_id)
            if stp:
                stp.x, stp.y = p.x, p.y
        if not xrandr_available() or layout == self._last_layout:
            return
        try:
            mgr = XrandrManager()
            if len(layout.placements) == 1:
                p = layout.placements[0]
                await asyncio.to_thread(mgr.resize, p.width, p.height)
            else:
                await asyncio.to_thread(mgr.apply_layout, layout)
            self._last_layout = layout
        except Exception as e:
            logger.warning("x11 layout apply failed: %s", e)

    # ------------------------------------------------------------------
    # frame-id reset protocol

    async def _reset_frame_ids_and_notify(self, st: DisplayState) -> None:
        st.bp.reset()
        # ids restart at 1: frames sent under the old numbering will
        # never be ACKed — close their spans instead of leaking them
        self.recorder.drop_awaiting(st.display_id, "reset")
        message = f"PIPELINE_RESETTING {st.display_id}"
        if st.display_id == "primary":
            self.broadcast(message)
        elif st.ws:
            try:
                # ride the same per-client queue as the media so the reset
                # keeps its FIFO position relative to queued frames
                self._fanout({st.ws}, message)
            except Exception:
                # a dead secondary socket must not crash the (supervised)
                # restart that is trying to recover its display
                logger.debug("reset notify failed for %s", st.display_id)

    # ------------------------------------------------------------------
    # capture / encode pipeline per display

    async def reconfigure_display(self, st: DisplayState) -> None:
        async with st.lock:
            await self._stop_display_locked(st)
            if st.video_active:
                await self._start_display_locked(st)

    async def _start_display(self, st: DisplayState) -> None:
        async with st.lock:
            await self._start_display_locked(st)

    async def _stop_display(self, st: DisplayState) -> None:
        async with st.lock:
            await self._stop_display_locked(st)

    async def _start_display_locked(self, st: DisplayState) -> None:
        if self.display_clients.get(st.display_id) is not st:
            # the display was deregistered (client disconnect) while a
            # reconfigure/START_VIDEO raced toward this start: a pipeline
            # started now would be a zombie nobody stops — leaked capture
            # loop, leaked scheduler slot, leaked spans
            return
        if st.capture_task and not st.capture_task.done():
            return
        # A failed/finished supervisor may leave a live backpressure task
        # behind; tear both down so restarts never leak a ticking loop.
        await self._stop_display_locked(st)
        st.failed = False          # an explicit restart clears the marker
        st.wedge_faults = 0
        st.ladder.fail_threshold = max(
            1, int(self.settings.ladder_fail_threshold))
        st.ladder.probe_after_s = int(self.settings.ladder_probe_ms) / 1000.0
        fps = st.bp.framerate or 60.0
        wd_frames = int(self.settings.watchdog_frames)
        watchdog_s = (max(0.5, wd_frames / max(1.0, fps))
                      if wd_frames > 0 else None)
        max_restarts = int(self.settings.supervisor_max_restarts)
        window_s = float(int(self.settings.supervisor_restart_window_s))
        st.supervisor = Supervisor(
            f"capture:{st.display_id}",
            lambda: self._capture_loop(st),
            max_restarts=max_restarts,
            restart_window_s=window_s,
            watchdog_timeout_s=watchdog_s,
            on_event=lambda kind, info:
                self._on_supervisor_event(st, kind, info),
        )
        st.bp_supervisor = Supervisor(
            f"backpressure:{st.display_id}",
            lambda: self._backpressure_loop(st),
            max_restarts=max_restarts,
            restart_window_s=window_s,
            on_event=lambda kind, info:
                self._on_supervisor_event(st, kind, info),
        )
        st.capture_task = asyncio.create_task(st.supervisor.run())
        st.backpressure_task = asyncio.create_task(st.bp_supervisor.run())
        st.running_geom = (st.width, st.height, st.x, st.y)
        st.running_config = (dict(st.overrides), st.bp.framerate)

    async def _stop_display_locked(self, st: DisplayState) -> None:
        """Exception-safe teardown: cancel BOTH tasks even if the first
        cancellation raises, and always close the encoder adapter so worker
        threads never leak across reconfigures."""
        for attr in ("capture_task", "backpressure_task"):
            task = getattr(st, attr)
            if task and not task.done():
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                except Exception:
                    logger.exception("%s teardown for %s raised",
                                     attr, st.display_id)
            setattr(st, attr, None)
        st.supervisor = None
        st.bp_supervisor = None
        st.running_geom = None
        st.running_config = None
        # a stopped display's un-ACKed frames will never resolve
        self.recorder.drop_awaiting(st.display_id, "stop")
        encoder, st.encoder = st.encoder, None
        if encoder is not None:
            close = getattr(encoder, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    logger.exception("encoder close for %s raised",
                                     st.display_id)

    async def _capture_loop(self, st: DisplayState) -> None:
        """Source frames → pipelined TPU encode → stripe broadcast.

        One *supervised* run (st.supervisor owns restarts): exceptions
        propagate to the supervisor instead of being swallowed here, with
        encoder-path failures wrapped in :class:`EncoderFault` so they step
        the degradation ladder. The loop returns cleanly when the ladder
        rung changes under it — the supervisor then restarts it, which
        rebuilds the encoder at the new rung.
        """
        sup = st.supervisor
        faults = self.faults
        fps = st.bp.framerate or 60.0
        rung = st.ladder.rung
        # The capture loop numbers frames from 1 again on EVERY (re)start —
        # supervised crash restarts included — so the client and the
        # backpressure gate must drop their old frame-id horizon; otherwise
        # desync = (1 - old_ack) mod 2^16 reads as a huge lag and wedges the
        # gate closed (reference resets likewise, selkies.py:1119-1146).
        await self._reset_frame_ids_and_notify(st)
        encoder = None
        if rung == "device":
            encoder = self._acquire_mesh_encoder(st, fps)
        if encoder is None:
            overrides = dict(st.overrides)
            if rung == "host":
                overrides["tpu_entropy"] = "host"
            elif rung == "jpeg":
                overrides["encoder"] = "jpeg"
                overrides["tpu_entropy"] = "host"
            try:
                try:
                    encoder = self.encoder_factory(
                        st.width, st.height, self.settings, overrides)
                except TypeError:  # factory without overrides support
                    encoder = self.encoder_factory(
                        st.width, st.height, self.settings)
            except Exception as e:
                # construction-time device sickness must step the ladder
                # like any other encoder failure — otherwise a broken
                # device tier is retried forever and never degrades
                raise EncoderFault(
                    f"encoder construction failed: {e!r}") from e
        if getattr(encoder, "metrics", False) is None:
            encoder.metrics = self.metrics
        if hasattr(encoder, "on_error"):
            # encode errors harvested off-loop (worker thread futures) feed
            # the same ladder as loop-crashing EncoderFaults
            encoder.on_error = lambda exc: st.ladder.record_failure()
        #: set (from the encoder's thread) when a finished frame lies in
        #: the encoder: the loop then harvests it now, between two ticks,
        #: where it would have lain until the next one. An encoder without
        #: the hook is harvested at the ticks alone
        ready = asyncio.Event()
        if hasattr(encoder, "on_ready"):
            wake = asyncio.get_running_loop().call_soon_threadsafe
            encoder.on_ready = lambda: wake(ready.set)
        if getattr(encoder, "faults", False) is None:
            # the async driver checks fetch.hang at ITS harvest site, so
            # one SELKIES_TPU_FAULTS entry can wedge either side of the
            # D2H path (tools/chaos_run.py arms it for both)
            encoder.faults = faults
        st.encoder = encoder
        source = None
        #: the recorder the server holds NOW (a harness may have swapped
        #: the one build() made): the encoder's driver thread writes its
        #: timeline there, and this loop's spans between submit and
        #: harvest live in its table
        recorder = self.recorder
        if getattr(encoder, "recorder", False) is None:
            encoder.recorder = recorder
        pending = recorder.pending()
        try:
            if sup is not None:
                sup.beat()   # encoder construction counts as progress
            try:
                source = self.source_factory(st.width, st.height, fps,
                                             x=st.x, y=st.y)
            except TypeError:  # factory without offset support (tests)
                source = self.source_factory(st.width, st.height, fps)
            source.start()
            frame_id = 0
            interval = 1.0 / fps
            next_tick = time.monotonic()
            #: ticks whose harvest surfaced encoder errors without the
            #: ladder stepping (i.e. at the bottom rung) — after the
            #: ladder's own threshold, force a supervised rebuild rather
            #: than streaming nothing forever
            error_ticks = 0
            #: frames with stripes that left at a ready cue since the last
            #: tick (the tick's ladder and wedge bookkeeping counts them)
            emitted_on_ready = 0
            #: a pipeline that stops ACCEPTING submits and harvesting
            #: anything is wedged even though the loop itself still ticks
            #: (e.g. a dead mesh worker); a first-use jit compile is told
            #: apart by THIS encoder's own signal (runtime.CompileWatch)
            wedge_s = None
            if sup is not None and sup.watchdog_timeout_s is not None:
                wedge_s = max(4.0 * sup.watchdog_timeout_s, WEDGE_MIN_S)
            accepted_at = time.monotonic()
            logger.info("capture loop started for %s (%dx%d@%g, rung=%s)",
                        st.display_id, st.width, st.height, fps, rung)
            consume_migration = getattr(encoder, "consume_migration", None)
            compiling_for_s = getattr(encoder, "compiling_for_s",
                                      lambda: 0.0)
            while True:
                if sup is not None:
                    sup.beat()
                faults.maybe_raise("capture.raise")
                await faults.maybe_hang("capture.stall")
                if consume_migration is not None and consume_migration():
                    # the scheduler live-migrated this session off a
                    # quarantined slot (docs/scaling.md): same recovery
                    # grammar as a supervised restart — frame ids restart
                    # with PIPELINE_RESETTING, the new slot's reset forces
                    # a keyframe, and the restart budget is forgiven (the
                    # scheduler absorbed the fault; the session is healthy)
                    logger.warning("display %s migrated to a healthy "
                                   "lane; resetting frame ids",
                                   st.display_id)
                    frame_id = 0
                    await self._reset_frame_ids_and_notify(st)
                    if sup is not None:
                        sup.forgive()
                    self._broadcast_health()
                # clean-probe evidence for the ladder: the tick must have
                # actually exercised the encoder (submit or delivery) AND
                # harvested no new errors (on_error bumps failures_total
                # from inside try_submit/poll for the threaded adapter)
                failures_before = st.ladder.failures_total
                progressed = False
                accepted = True     # "no submit attempted" is not a wedge
                if st.bp.send_enabled:
                    t_cap0 = time.monotonic()
                    frame = source.next_frame()
                    t_cap1 = time.monotonic()
                    if frame is not None:
                        # open this frame's flight span: (display, frame)
                        # context threaded capture → ... → client ACK
                        tr = recorder.begin(st.display_id, t=t_cap0)
                        tr.mark("capture", t_cap0, t_cap1)
                        # never block the shared event loop: drop when full
                        try_submit = getattr(encoder, "try_submit", None)
                        seq = None
                        try:
                            faults.maybe_raise("encode.raise")
                            if try_submit is not None:
                                # None = dropped (pipeline full): fine in
                                # bursts, but sustained non-acceptance with
                                # no harvests below means a wedged pipeline
                                seq = try_submit(frame)
                                accepted = seq is not None
                            else:
                                seq = encoder.submit(frame)
                        except Exception as e:
                            recorder.drop(tr, "submit")
                            raise EncoderFault(
                                f"encoder submit failed: {e!r}") from e
                        if not accepted:
                            # backpressure at the edge: a dropped frame
                            # closes terminally, it never leaks a span. A
                            # mesh lane's mailbox kept THIS frame and lost
                            # the one it had pending: the facade says
                            # under which seq, and the spans swap places
                            pending.refuse(
                                tr, getattr(encoder, "replaced_seq", None))
                        else:
                            pending.add(seq, tr)
                        progressed = True
                await faults.maybe_hang("fetch.hang")
                ready.clear()       # this poll takes what it was set for
                frame_id, emitted = self._harvest_and_emit(
                    st, encoder, recorder, pending, frame_id)
                if sup is not None:
                    # submit/poll can legitimately block the loop for one
                    # long stretch (first-use jit compile); beating after
                    # them keeps that from reading as a stall
                    sup.beat()
                # frames that left at a ready cue since the last tick are
                # this tick's progress as much as those it took itself
                emitted += emitted_on_ready
                emitted_on_ready = 0
                if emitted:
                    progressed = True
                    accepted = True
                now = time.monotonic()
                if accepted or 0.0 < compiling_for_s() < COMPILE_GRACE_S:
                    # this display's encoder is inside a program's first
                    # call — an XLA compile (minutes for the 1080p H.264
                    # P step), not a wedge: the clock restarts when it
                    # ends. Past the grace it reads as a wedge after all.
                    accepted_at = now
                elif wedge_s is not None and now - accepted_at > wedge_s:
                    # loop ticks, nothing moves: dead mesh worker / wedged
                    # pipeline — force_step tells the event handler to step
                    # the ladder immediately (one accounting site; a
                    # consecutive count would be reset by each restart's
                    # first accepted submit and never escalate)
                    raise EncoderFault(
                        f"pipeline wedged: no accepted submits or harvests "
                        f"for {now - accepted_at:.1f}s", force_step=True)
                if st.ladder.failures_total > failures_before:
                    # errors surfaced off-loop this tick (threaded-adapter
                    # harvest); if the ladder can no longer step down, a
                    # persistently sick bottom rung must still force a
                    # supervised rebuild instead of streaming nothing
                    error_ticks += 1
                    if (error_ticks >= st.ladder.fail_threshold
                            and st.ladder.rung == rung):
                        raise EncoderFault(
                            f"persistent encode errors at rung {rung} "
                            f"({error_ticks} consecutive error ticks)")
                elif progressed:
                    error_ticks = 0
                    if st.ladder.record_success():
                        logger.info("display %s probed back up to rung %s",
                                    st.display_id, st.ladder.rung)
                if st.ladder.rung != rung:
                    # rung changed under us (off-loop step-down via
                    # on_error, or the probe above): exit cleanly; the
                    # supervisor restarts with the new rung's encoder
                    self._broadcast_health()
                    return
                if st.ws is not None and faults.should_fire("ws.drop"):
                    self._spawn_background(st.ws.close(),
                                           f"ws.drop:{st.display_id}")
                next_tick += interval
                if next_tick - time.monotonic() < -1.0:
                    next_tick = time.monotonic()    # fell badly behind
                # sleep to the tick; a ready cue on the way there is a
                # finished frame: it leaves now, and the sleep goes on to
                # the SAME tick (captures stay on their grid)
                while await _ready_before(ready, next_tick):
                    frame_id, emitted = self._harvest_and_emit(
                        st, encoder, recorder, pending, frame_id,
                        on_ready=True)
                    emitted_on_ready += emitted
        finally:
            if source is not None:
                try:
                    source.stop()
                except Exception:
                    logger.exception("source stop for %s raised",
                                     st.display_id)
            # frames in flight inside the (about to be closed) encoder
            # are abandoned with it: close their spans terminally so a
            # supervised restart never leaks open spans
            pending.drop_all("restart")
            st.encoder = None
            close = getattr(encoder, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    logger.exception("encoder close for %s raised",
                                     st.display_id)

    def _harvest_and_emit(self, st: DisplayState, encoder, recorder,
                          pending, frame_id: int, *,
                          on_ready: bool = False) -> Tuple[int, int]:
        """Take what the encoder has finished and send it: the capture
        loop's one ``poll()`` site, run at every tick and, ``on_ready``,
        when the encoder's ready cue wakes the loop between two ticks.
        Frames leave in harvest order under consecutive frame ids. Returns
        the last frame id issued and how many frames carried stripes."""
        try:
            harvested = encoder.poll()
        except Exception as e:
            raise EncoderFault(
                f"encoder poll failed: {e!r}") from e
        if not harvested:
            return frame_id, 0
        t_harvest = time.monotonic()
        for counter in (self.metrics, encoder):
            count = getattr(counter, "count_harvests", None)
            if count is not None:
                count(len(harvested), on_ready)
        pop_trace = getattr(encoder, "pop_trace", None)
        emitted = 0
        for _seq, stripes in harvested:
            tr = pending.take(_seq)
            if tr is not None:
                # fold in the encoder-side stage intervals
                # (submit_wait ... pack) harvested with the frame
                if pop_trace is not None:
                    try:
                        tr.merge(pop_trace(_seq))
                    except Exception:
                        logger.debug("pop_trace failed", exc_info=True)
                packed = tr.spans.get("pack")
                if packed is not None:
                    # packed on the driver's thread -> taken by this
                    # loop's poll()
                    tr.mark("harvest_wait", packed[1],
                            max(packed[1], t_harvest))
            if not stripes:
                # damage gating emitted nothing: a coalesced frame,
                # closed (not dropped, not acked)
                if tr is not None:
                    recorder.finish_empty(tr)
                continue
            emitted += 1
            frame_id = FrameId.next(frame_id)
            viewers = self._viewers_of(st.display_id)
            try:
                self._emit_frame(st, encoder, frame_id, stripes,
                                 viewers, tr)
            except BaseException:
                if tr is not None and tr.terminal is None:
                    recorder.drop(tr, "send")
                raise
            st.bp.on_frame_sent(frame_id)
        return frame_id, emitted

    def _emit_frame(self, st: DisplayState, encoder, frame_id: int,
                    stripes, viewers, tr) -> None:
        """Wire-pack and fan out one harvested frame.

        Flight recorder: the LAST stripe of a traced frame rides the
        owner's send queue with the trace attached (the frame is
        decodable when that stripe lands), closing queue/send there and
        registering the span for CLIENT_FRAME_ACK correlation; every
        no-delivery path (no viewers, evicted owner, ownerless display)
        closes the span terminally instead of leaking it."""
        recorder = self.recorder
        owner = st.ws
        owner_cq = self._send_queues.get(owner) if owner is not None else None
        if tr is not None:
            tr.frame_id = frame_id
        n = len(stripes)
        for i, s in enumerate(stripes):
            chunk = self._pack_stripe(frame_id, s, encoder)
            if not viewers:
                continue
            traced_here = (tr is not None and i == n - 1
                           and owner is not None and owner in viewers)
            if traced_here:
                others = viewers - {owner}
                if others:
                    self._fanout(others, chunk)
                if owner_cq is not None and not owner_cq.evicted:
                    owner_cq.offer_traced(chunk, tr)
                elif owner_cq is not None:
                    # evicted mid-kill: the frame will never reach the
                    # owner, so its span ends at the queue
                    recorder.drop(tr, "queue")
                else:
                    # no send queue (client registered outside
                    # ws_handler): direct synchronous fan-out — queue
                    # dwell is zero by construction
                    t0 = time.monotonic()
                    _ws_broadcast({owner}, chunk)
                    t1 = time.monotonic()
                    tr.mark("queue", t0, t0)
                    tr.mark("send", t0, t1)
                    recorder.sent(tr)
            else:
                self._fanout(viewers, chunk)
            self.bytes_sent += len(chunk) * len(viewers)
        if tr is not None and tr.terminal is None and not (
                viewers and owner is not None and owner in viewers):
            # encoded, but nobody to ack it (no clients / viewer-only
            # fan-out): close terminally rather than waiting on an ACK
            # that cannot come
            recorder.drop(tr, "send")

    @staticmethod
    def _pack_stripe(frame_id: int, s, encoder) -> bytes:
        """Wire-pack one encoded stripe by profile: JPEG stripes → 0x03,
        striped H.264 → 0x04, full-frame H.264 → 0x00 (the client's three
        decode paths). The fullframe routing is an explicit encoder flag
        set at construction — a short display can legitimately have one
        stripe in striped mode and must still ship 0x04."""
        if hasattr(s, "annexb"):
            if getattr(encoder, "wire_fullframe", False):
                return pack_full_frame(frame_id, s.annexb, s.is_key)
            return pack_h264_stripe(
                frame_id, s.y_start, s.width, s.height, s.annexb, s.is_key)
        return pack_jpeg_stripe(frame_id, s.y_start, s.jpeg)

    def _acquire_mesh_encoder(self, st: DisplayState, fps: float):
        """Session facade onto the mesh coordinator when ``tpu_mesh`` is
        configured (BASELINE config 5); None → solo encoder pipeline.

        Mesh batching covers the JPEG and striped-H.264 profiles with
        server-wide quality settings (SPMD uniformity); the full-frame
        x264enc profile, mismatched geometry, or slot exhaustion fall
        back to a solo encoder per display. Buckets are keyed by
        (geometry, profile) — the SPMD program is profile-specific.
        """
        spec = str(self.settings.tpu_mesh)
        if not spec:
            return None
        profile = st.overrides.get("encoder", self.settings.encoder)
        if profile not in ("jpeg", "x264enc-striped"):
            return None
        if str(self.settings.watermark_path):
            # the mesh encoder has no watermark stage yet; a configured
            # watermark must not silently vanish — keep the solo pipeline
            logger.warning(
                "tpu_mesh ignored for %s: watermark_path requires the solo "
                "JPEG pipeline", st.display_id)
            return None
        geom = (st.width, st.height, profile)
        if geom in self._mesh_failed_geoms:
            self.mesh_stats["solo_fallback"] += 1
            return None
        coord = self.mesh_coordinators.get(geom)
        if coord is None:
            if len(self.mesh_coordinators) >= MESH_BUCKET_CAP:
                self.mesh_stats["solo_fallback"] += 1
                logger.warning(
                    "mesh batching: bucket limit reached; %s at %dx%d "
                    "uses a solo encoder", st.display_id, *geom[:2])
                return None
            try:
                from ..parallel.coordinator import MeshEncodeCoordinator

                factory = self.coordinator_factory or MeshEncodeCoordinator
                coord = factory(
                    spec, int(self.settings.tpu_sessions_per_chip),
                    st.width, st.height, settings=self.settings,
                    framerate=fps, profile=profile)
                # mesh fault points (mesh.tick_raise / mesh.slot_raise)
                # check the server's injector at the coordinator's sites
                coord.faults = self.faults
                self.mesh_coordinators[geom] = coord
                sfe_n = int(getattr(coord, "sfe_shards", 1) or 1)
                logger.info(
                    "mesh batching: %s → %s session slots/lane (max %s "
                    "lanes) at %dx%d (bucket %d)%s", spec,
                    getattr(coord, "slots_per_lane", "?"),
                    getattr(coord, "max_lanes", "?"), st.width, st.height,
                    len(self.mesh_coordinators),
                    f" — SFE lanes, {sfe_n} stripe shards/frame"
                    if sfe_n > 1 else "")
            except Exception:
                logger.exception(
                    "mesh coordinator for %dx%d (%s) unavailable; that "
                    "geometry uses solo encoders", *geom)
                self._mesh_failed_geoms.add(geom)
                self.mesh_stats["solo_fallback"] += 1
                return None
        facade = coord.acquire(st.width, st.height)
        if facade is None:
            # races the admission verdict lost (two joins for the last
            # slot) land here: serve them solo rather than dropping a
            # session the front door already admitted
            self.mesh_stats["solo_fallback"] += 1
            logger.warning(
                "mesh batching: no slot for %s at %dx%d; solo encoder",
                st.display_id, st.width, st.height)
        else:
            self.mesh_stats["bucketed"] += 1
        return facade

    async def _backpressure_loop(self, st: DisplayState) -> None:
        sup = st.bp_supervisor
        while True:
            await asyncio.sleep(CHECK_INTERVAL_S)
            if sup is not None:
                sup.beat()
            st.bp.evaluate()

    # ------------------------------------------------------------------
    # supervision events + health feed (ISSUE 2)

    def _spawn_background(self, coro, name: str) -> None:
        """Run a fire-and-forget coroutine with a held reference and
        logged (not warned-at-GC) exceptions."""
        async def runner():
            try:
                await coro
            except Exception:
                logger.debug("background task %s failed", name,
                             exc_info=True)
        task = asyncio.create_task(runner())
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)

    def _on_supervisor_event(self, st: DisplayState, kind: str,
                             info: Any) -> None:
        """Metrics + ladder + health fan-out for supervisor lifecycle
        events (runs on the event loop; must never raise)."""
        if kind == "failure" and isinstance(info, EncoderFault):
            force_step = getattr(info, "force_step", False)
            stepped = (st.ladder.force_step_down() if force_step
                       else st.ladder.record_failure())
            if stepped:
                st.wedge_faults = 0
                logger.warning("display %s degraded to rung %s",
                               st.display_id, st.ladder.rung)
                if st.supervisor is not None:
                    # the ladder absorbed this failure streak; judge the
                    # new rung against a fresh budget, or probe cycles
                    # would terminally fail a healthy degraded display
                    st.supervisor.forgive()
            elif force_step:
                # wedged with nowhere left to degrade: each rebuild of a
                # hung encoder may abandon a blocked worker thread, so
                # bound the cycle instead of leaking threads forever
                st.wedge_faults += 1
                if st.wedge_faults >= 3:
                    logger.error(
                        "display %s wedged %d times at the bottom rung; "
                        "marking failed", st.display_id, st.wedge_faults)
                    kind = "failed"
        if self.metrics is not None:
            if kind == "restart":
                self.metrics.inc_supervisor_restart()
            elif kind == "watchdog":
                self.metrics.inc_watchdog_restart()
        if kind == "failed":
            # a terminally failed capture pipeline must not leave its
            # sibling backpressure loop ticking forever; tear the display
            # down from OUTSIDE the supervisor task that emitted the event
            # (stopping it inline would await the task we are inside of)
            st.failed = True
            self._spawn_background(self._teardown_failed_display(st),
                                   f"teardown-failed:{st.display_id}")
        self._broadcast_health()

    async def _teardown_failed_display(self, st: DisplayState) -> None:
        async with st.lock:
            if not st.failed:
                # an explicit START_VIDEO/reconfigure restarted the display
                # before this queued teardown ran — it is healthy again and
                # must not be torn back down
                return
            await self._stop_display_locked(st)

    def _failed_displays(self) -> int:
        return sum(1 for d in self.display_clients.values()
                   if d.failed or (d.supervisor is not None
                                   and d.supervisor.state == FAILED))

    def _health_payload(self) -> str:
        """The ``system,health`` wire message: per-display supervision,
        watchdog, and degradation-ladder state."""
        displays: Dict[str, Any] = {}
        for did, st in self.display_clients.items():
            sup = st.supervisor.stats() if st.supervisor is not None else {}
            d: Dict[str, Any] = {
                "rung": st.ladder.rung,
                "ladder": st.ladder.state(),
                "failed": st.failed,
                "supervisor": sup.get("state",
                                      "failed" if st.failed else "idle"),
                "restarts": sup.get("restarts_total", 0),
                "failures": sup.get("failures_total", 0),
                "watchdog_restarts": sup.get("watchdog_restarts_total", 0),
            }
            enc = st.encoder
            if enc is not None and hasattr(enc, "stats"):
                try:
                    est = enc.stats()
                except Exception:
                    est = {}
                d["frames_dropped"] = est.get("frames_dropped", 0)
                d["encode_errors"] = est.get("encode_errors", 0)
            # flight-recorder stage breakdown (ISSUE 13): where each
            # frame's time went, pushed so the client stats overlay can
            # show it without scraping Prometheus
            try:
                summ = self.recorder.summary(did, last_s=60.0)
            except Exception:
                summ = {}
            if summ.get("stages"):
                d["stages"] = {
                    stage: {"p50_ms": v["p50_ms"], "p95_ms": v["p95_ms"]}
                    for stage, v in summ["stages"].items()}
                for k in ("glass_to_glass_p50_ms", "encode_only_p50_ms"):
                    if k in summ:
                        d[k] = summ[k]
            displays[did] = d
        # session-scheduler slot health (ISSUE 14, docs/scaling.md): the
        # per-slot fault domains lived only in coordinator stats() before
        # — a quarantined slot or a live migration must reach the client
        # overlay and the dashboard, not just a debugger
        mesh: Dict[str, Any] = {}
        for (w, h, profile), coord in list(self.mesh_coordinators.items()):
            try:
                cs = coord.stats()
            except Exception:
                continue
            mesh[f"{w}x{h}/{profile}"] = {
                "active_sessions": cs.get("active_sessions", 0),
                "lanes": cs.get("lanes", 0),
                "capacity_slots": cs.get("capacity_slots", 0),
                "free_slots": cs.get("free_slots", 0),
                "quarantined_slots": cs.get("quarantined_slots", 0),
                "slot_errors": cs.get("slot_errors", []),
                "tick_errors_total": cs.get("tick_errors_total", 0),
                "worker_restarts_total":
                    cs.get("worker_restarts_total", 0),
                "inflight_batches": cs.get("inflight_batches", 0),
                "migrations_total": cs.get("migrations_total", 0),
                # SFE lanes (ISSUE 15): chips one frame spans, and the
                # host-side slice-concat share of the harvest wall
                "sfe_shards": cs.get("sfe_shards", 1),
                "sfe_concat_ms_p50": cs.get("sfe_concat_ms_p50", 0.0),
                "lane_detail": cs.get("lane_detail", []),
            }
        return pack_system_health(displays, mesh=mesh or None)

    def _publish_health_metrics(self) -> None:
        """Recompute the health gauges from current state — recovery and
        display removal must clear them, not only events raise them."""
        if self.metrics is None:
            return
        levels = [d.ladder.level for d in self.display_clients.values()]
        self.metrics.set_degradation_rung(max(levels) if levels else 0)
        self.metrics.set_failed_displays(self._failed_displays())

    def _update_load_shed(self) -> None:
        """Admission-control load shedding (stats-tick cadence): when the
        encode pipelines report sustained frame drops — the device can no
        longer keep up with the admitted load — stop admitting NEW
        connections until the drop rate recovers. Existing sessions keep
        their backpressure/degradation machinery; shedding only protects
        them from additional load."""
        threshold = int(getattr(self.settings, "shed_drop_threshold", 0) or 0)
        if threshold <= 0:
            self._load_shedding = False
            return
        total = 0
        for st in self.display_clients.values():
            enc = st.encoder
            if enc is not None and hasattr(enc, "stats"):
                try:
                    total += int(enc.stats().get("frames_dropped", 0))
                except Exception:
                    pass
        delta = total - self._last_dropped_total
        if delta < 0:
            # a supervised restart replaced an encoder (its cumulative
            # counter restarted from zero) — exactly when overload churn
            # is likely; the new encoder's drops are all new drops, so
            # count the post-reset total rather than resetting the strikes
            delta = total
        self._last_dropped_total = total
        if delta >= threshold:
            self._shed_strikes += 1
        else:
            self._shed_strikes = 0
        shedding = self._shed_strikes >= 2
        if shedding != self._load_shedding:
            logger.warning(
                "load shedding %s (%d frames dropped this tick, "
                "threshold %d)",
                "engaged" if shedding else "released", delta, threshold)
        self._load_shedding = shedding

    def _broadcast_health(self) -> None:
        try:
            self._publish_health_metrics()
            self.broadcast(self._health_payload())
        except Exception:
            logger.exception("health broadcast failed")

    async def set_framerate(self, fps: float) -> None:
        """Apply a new target framerate to every active display.

        Wire-level parity with the reference ``_arg_fps`` path
        (input_handler.py:1662 → app.set_fps → pipeline restart).
        """
        fps = float(self.settings.framerate.clamp(int(fps)))
        for st in list(self.display_clients.values()):
            st.bp.framerate = fps
            if st.capture_task is not None and not st.capture_task.done():
                await self.reconfigure_display(st)

    # ------------------------------------------------------------------
    # file upload (path-sanitized, reference selkies.py:1843-1952)

    def _upload_dir(self) -> str:
        return upload_dir()

    async def _on_upload_start(self, websocket, args) -> None:
        if "upload" not in self.settings.file_transfers:
            await websocket.send("FILE_UPLOAD_ERROR:GENERAL:uploads disabled")
            return
        try:
            rel_path = args[0]
            size = int(args[1]) if len(args) > 1 and args[1] else 0
        except (ValueError, IndexError):
            await websocket.send("FILE_UPLOAD_ERROR:GENERAL:bad upload header")
            return
        root = os.path.realpath(self._upload_dir())
        norm = os.path.normpath(rel_path)
        if norm.startswith(("/", "\\")) or ".." in norm.split(os.sep) \
                or any(ord(c) < 0x20 or c in '"\x7f' for c in norm):
            # control characters / quotes in names would otherwise reach
            # the /files listing + Content-Disposition planes
            await websocket.send(f"FILE_UPLOAD_ERROR:{rel_path}:invalid path")
            return
        target = os.path.realpath(os.path.join(root, norm))
        if not target.startswith(root + os.sep):
            await websocket.send(f"FILE_UPLOAD_ERROR:{rel_path}:invalid path")
            return
        os.makedirs(os.path.dirname(target), exist_ok=True)
        old = self._uploads.pop(websocket, None)
        if old:
            # superseded mid-flight: remove the truncated partial too, or
            # the /files listing serves it as if complete
            self._abort_upload(old)
        self._uploads[websocket] = _Upload(
            path=target, rel_path=rel_path, fobj=open(target, "wb"), size=size)
        logger.info("upload started: %s (%d bytes)", target, size)

    # ------------------------------------------------------------------
    # command execution

    async def _run_command(self, command: str) -> None:
        logger.info("exec: %s", command)
        try:
            await asyncio.create_subprocess_shell(
                command,
                stdout=asyncio.subprocess.DEVNULL,
                stderr=asyncio.subprocess.DEVNULL,
            )
        except OSError as e:
            logger.warning("command failed to spawn: %s", e)

    # ------------------------------------------------------------------
    # stats feed (reference selkies.py:2966-3083)

    async def _stats_loop(self) -> None:
        prev_bytes = 0
        while True:
            await asyncio.sleep(STATS_INTERVAL_S)
            try:
                self._update_load_shed()
                # flight-recorder upkeep: late metrics attachment and the
                # expiry sweep (clients that never ACK must not pin open
                # spans forever)
                self.recorder.metrics = self.metrics
                self.recorder.expire()
                if self.metrics is not None:
                    self.metrics.set_trace_open_spans(
                        self.recorder.open_spans())
                if self.metrics is not None:
                    # aggregated ONCE per tick here, not per display loop
                    self.metrics.set_backpressured(sum(
                        1 for d in self.display_clients.values()
                        if not d.bp.send_enabled))
                    self.metrics.set_send_queue_depth(max(
                        (len(cq.q) for cq in self._send_queues.values()),
                        default=0))
                    self._publish_health_metrics()
                stats = self._collect_system_stats()
                self.broadcast(json.dumps(stats))
                net = {
                    "type": "network_stats",
                    "bytes_sent_delta": self.bytes_sent - prev_bytes,
                    "interval_s": STATS_INTERVAL_S,
                }
                if self.mesh_coordinators or self.mesh_stats["solo_fallback"]:
                    # mesh fallbacks must be observable, not silent.
                    # "bucketed" is a cumulative acquisition counter (it
                    # never decrements on release), so surface it under a
                    # _total name and report live occupancy separately.
                    net["mesh_buckets"] = len(self.mesh_coordinators)
                    net["mesh_acquisitions_total"] = \
                        self.mesh_stats["bucketed"]
                    net["mesh_sessions"] = sum(
                        coord.active_sessions
                        for coord in self.mesh_coordinators.values())
                    net["mesh_solo_fallbacks"] = \
                        self.mesh_stats["solo_fallback"]
                    # per-shard fault accounting (ISSUE 2): failed ticks
                    # and worker re-spawns are health, not noise
                    net["mesh_tick_errors"] = sum(
                        coord.tick_errors_total
                        for coord in self.mesh_coordinators.values())
                    net["mesh_worker_restarts"] = sum(
                        coord.worker_restarts_total
                        for coord in self.mesh_coordinators.values())
                    # scheduler health (ISSUE 14): lane capacity feeds the
                    # admission verdicts; quarantines/migrations say the
                    # fault-domain machinery is actually firing
                    sched = self.scheduler_stats()
                    if sched is not None:
                        net["mesh_lanes"] = sched["lanes"]
                        net["mesh_slots_free"] = sched["slots_free"]
                        net["mesh_quarantined_slots"] = \
                            sched["quarantined_slots"]
                    net["mesh_migrations_total"] = sum(
                        getattr(coord, "migrations_total", 0)
                        for coord in self.mesh_coordinators.values())
                    # one stats() snapshot per coordinator per tick (it
                    # takes the scheduler lock): SFE + gauges share it
                    coord_stats = [c.stats() for c in
                                   self.mesh_coordinators.values()]
                    # SFE lanes (ISSUE 15): shard count + slice-concat
                    # wall ride the stats feed and the gauges
                    sfe_stats = [cs for cs in coord_stats
                                 if cs.get("sfe_shards", 1) > 1]
                    if sfe_stats:
                        net["mesh_sfe_shards"] = max(
                            cs["sfe_shards"] for cs in sfe_stats)
                        net["mesh_sfe_concat_ms_p50"] = max(
                            cs.get("sfe_concat_ms_p50", 0.0)
                            for cs in sfe_stats)
                    if self.metrics is not None:
                        self.metrics.set_mesh_health(
                            active_sessions=net["mesh_sessions"],
                            lanes=net.get("mesh_lanes", 0),
                            inflight=sum(
                                cs.get("inflight_batches", 0)
                                for cs in coord_stats),
                            slot_errors=sum(
                                sum(cs.get("slot_errors", []))
                                for cs in coord_stats),
                            tick_errors=net["mesh_tick_errors"],
                            worker_restarts=net["mesh_worker_restarts"],
                            quarantined=net.get(
                                "mesh_quarantined_slots", 0),
                            migrations=net["mesh_migrations_total"])
                        self.metrics.set_sfe_health(
                            shards=net.get("mesh_sfe_shards", 0),
                            concat_ms_p50=net.get(
                                "mesh_sfe_concat_ms_p50", 0.0))
                edge = self.edge_stats
                if (edge["protocol_errors"] or edge["rate_limited"]
                        or edge["sessions_rejected"]
                        or edge["sessions_queued"]
                        or edge["slow_client_evictions"]):
                    # hostile-client activity rides the stats feed so a
                    # dashboardless operator still sees it
                    net["edge"] = {
                        "protocol_errors": edge["protocol_errors"],
                        "rate_limited": dict(edge["rate_limited"]),
                        "sessions_rejected": edge["sessions_rejected"],
                        "sessions_queued": edge["sessions_queued"],
                        "slow_client_evictions":
                            edge["slow_client_evictions"],
                        "load_shedding": self._load_shedding,
                    }
                prev_bytes = self.bytes_sent
                self.broadcast(json.dumps(net))
                if self.display_clients:
                    self._broadcast_health()
                tpu = self._collect_tpu_stats()
                if tpu:
                    self.broadcast(json.dumps(tpu))
            except Exception:
                logger.exception("stats loop error")

    def _collect_system_stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"type": "system_stats"}
        if psutil is not None:
            out["cpu_percent"] = psutil.cpu_percent()
            mem = psutil.virtual_memory()
            out["mem_total"] = mem.total
            out["mem_used"] = mem.used
        else:
            la1, _, _ = os.getloadavg()
            out["load_1m"] = la1
        return out

    def _collect_tpu_stats(self) -> Optional[Dict[str, Any]]:
        """TPU occupancy takes the role of the reference's gpu_stats loop
        (GPUtil, selkies.py:2988)."""
        try:
            import jax

            devs = jax.devices()
        except Exception:
            return None
        # no device call on the event loop: the device probe's thread
        # samples memory_stats() once a second (it can wait behind the
        # queued steps), and this tick reads the last sample
        probes = self.device_probes
        stats = probes[0].memory if probes else None
        out = {"type": "gpu_stats", "device_count": len(devs),
               "platform": devs[0].platform if devs else "none"}
        if stats:
            out["bytes_in_use"] = stats.get("bytes_in_use", 0)
            out["bytes_limit"] = stats.get("bytes_limit", 0)
        return out

    # ------------------------------------------------------------------
    # helpers

    def _display_of(self, websocket) -> Optional[DisplayState]:
        for st in self.display_clients.values():
            if st.ws is websocket:
                return st
        # viewers (shared mode) ride the primary display
        return self.display_clients.get("primary")

    def _display_id_of(self, websocket) -> str:
        st = self._display_of(websocket)
        return st.display_id if st else "primary"
