#!/usr/bin/env python3
"""Chip smoke: the served encode path, on the TPU, through the real server.

One process — the only one that touches JAX — does, in order, and exits
non-zero at the first thing that fails:

  default (one chip):
    1. refuses anything but a TPU (no CPU branch, no JAX_PLATFORMS here);
    2. requires the native coders built from the tracked ``.cpp`` files;
    3. JPEG profile: boots the server the way ``selkies-tpu`` does
       (``server.main``'s ``build`` + ``serve``, default encoder + source
       factories, the synthetic 1920x1080 "desktop" source for X11), drives
       it with a real ``websockets`` client that ACKs every frame and
       PIL-decodes every 0x03 stripe, and holds the reassembled frame to
       a PSNR bound against the source frame;
    4. ``x264enc-striped`` profile: same client, every 0x04 stripe goes
       through the libavcodec ``ConformanceDecoder`` in emission order;
       after the stream has drained, each stripe's last decoded picture
       must equal the encoder's ``stripe_ref`` bit for bit;
    5. after each profile: ladder still at the device rung, no host
       fallback stripes, no supervisor restarts, no open spans.

  ``--chips 4`` (run by the builder, never by the driver): ONLY the path
  across chips and what it is compared with — a ``tpu_mesh=session:4``
  lane serving four 1080p displays and a ``session:1,stripe:4`` SFE lane
  serving one 3840x2160 ``x264enc-striped`` display, each replayed call
  for call through a one-device mesh in this process and compared byte
  for byte.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import io
import json
import os
import socket
import sys
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))

#: PSNR floor for the JPEG canvas against the synthetic "desktop" source
#: at the default quality (40; paint-over re-sends idle stripes at 90):
#: the pattern is a smooth wallpaper plus flat windows with hard 2-px
#: borders and a saturated moving block, whose 4:2:0 chroma edges cost
#: far more than smooth content does (the >35 dB rule of thumb is for
#: smooth content at q80). The CPU rehearsal at 1920x1080 reads 40.6-40.8 dB.
JPEG_PSNR_MIN_DB = 38.0
#: luma PSNR floor for the H.264 canvas (qp 26): a sanity bound only —
#: the binding check is bit-exactness against the encoder's references
H264_PSNR_MIN_DB = 33.0
#: frames each client receives per phase, by --chips (with 4, every
#: dispatch's frames are kept in memory for the one-device replay)
FRAMES = {1: 36, 4: 12}
#: seconds allowed for one cold-compile stall (boot warm-up, first stripe)
COMPILE_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    pass


def log(*parts: Any) -> None:
    print("[smoke]", *parts, flush=True)


def check(cond: Any, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def psnr(a, b) -> float:
    import numpy as np

    mse = float(np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2))
    return 99.0 if mse == 0 else 10.0 * np.log10(255.0 ** 2 / mse)


class CacheEvents:
    """Counts JAX's persistent-compile-cache hits and misses, and keeps
    the name and seconds of every XLA compile (or cache load) of a
    second or more — "cold compile seconds", program by program."""

    def __init__(self) -> None:
        import logging
        import re

        import jax

        self.hits = self.misses = 0
        self.compiles: List[Tuple[str, float]] = []
        self._seen = 0
        jax.monitoring.register_event_listener(self._on_event)
        pat = re.compile(r"Finished XLA compilation of (.+) in ([0-9.]+) sec")
        outer = self

        class Handler(logging.Handler):
            def emit(self, record) -> None:
                m = pat.match(record.getMessage())
                if m and float(m.group(2)) >= 1.0:
                    outer.compiles.append((m.group(1), float(m.group(2))))

        lg = logging.getLogger("jax._src.dispatch")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(Handler())
        lg.propagate = False     # DEBUG chatter stays out of stderr

    def new_compiles(self) -> str:
        """Compiles of >= 1 s since the last call, as one line."""
        new, self._seen = self.compiles[self._seen:], len(self.compiles)
        return ", ".join(f"{n} {s:.1f}s" for n, s in new) or "none"

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.hits, self.misses


class SourceOracle:
    """The default headless source is ``SyntheticSource(w, h, fps,
    pattern="desktop")`` — deterministic in its frame index. The capture
    loop may skip indices (a full pipeline drops the frame), so the
    client finds WHICH source frame its canvas shows by matching 8x
    subsampled thumbnails, then compares at full size."""

    def __init__(self, width: int, height: int) -> None:
        self.width, self.height = width, height

    def _make(self):
        from selkies_tpu.capture.synthetic import SyntheticSource

        return SyntheticSource(self.width, self.height, pattern="desktop")

    def frame_shown(self, canvas, lo: int, hi: int):
        """(psnr_db, index, frame) of the source frame nearest ``canvas``
        (RGB, or a 2-D luma plane) among indices [lo, hi)."""
        import numpy as np

        thumb = canvas[::8, ::8].astype(np.float32)
        lum = np.array([0.299, 0.587, 0.114], np.float32)
        src = self._make()
        src.seek(lo)
        errs = []
        for _ in range(lo, hi):
            t = src.next_frame()[::8, ::8].astype(np.float32)
            errs.append(float(np.abs(
                thumb - (t @ lum if thumb.ndim == 2 else t)).sum()))
        # thumbnails shortlist, full size decides (the block's path
        # nearly revisits places, and a thumbnail cannot tell those apart)
        best = (-1.0, 0, None)
        for k in np.argsort(errs)[:4]:
            src.seek(lo + int(k))
            frame = src.next_frame()
            db = psnr(canvas, luma_of(frame) if canvas.ndim == 2 else frame)
            if db > best[0]:
                best = (db, lo + int(k), frame)
        return best

    def frame_shown_since(self, canvas, fps: float, t_from: float,
                          t_to: float, floor_db: float):
        """The capture loop ticks at ``fps`` from the display's start, so
        a frame captured between ``t_from`` and ``t_to`` seconds after it
        has an index near ``fps * t``. Search that window (with slack for
        a loop that resynchronized); only if nothing there clears
        ``floor_db`` scan every index before giving the verdict."""
        lo = max(0, int(fps * t_from) - 600)
        hi = int(fps * t_to) + 120
        best = self.frame_shown(canvas, lo, hi)
        if best[0] < floor_db and lo > 0:
            best = max(best, self.frame_shown(canvas, 0, lo),
                       key=lambda b: b[0])
        return best


def luma_of(rgb):
    """Y plane by the repo's own colour transform (ops/color.py)."""
    import jax.numpy as jnp
    import numpy as np

    from selkies_tpu.ops.color import rgb_to_ycbcr

    y = rgb_to_ycbcr(jnp.asarray(rgb))[0]
    return np.clip(np.rint(np.asarray(y)), 0, 255).astype(np.uint8)


# ---------------------------------------------------------------------------
# server boot / shutdown — the way ``selkies-tpu`` does it


class Booted(NamedTuple):
    task: Any
    server: Any
    port: int


async def boot_server(extra_env: Dict[str, str],
                      coordinator_factory: Optional[Callable] = None
                      ) -> Booted:
    """``server.main._amain`` in its two halves, so the script holds the
    server it drives: ``build`` (runtime policy, app + data server, boot
    warm-up), then ``serve`` as a task."""
    from selkies_tpu.server.main import build, serve
    from selkies_tpu.settings import Settings

    env = {"SELKIES_PORT": str(free_port()),
           "SELKIES_WEB_PORT": str(free_port()),
           "SELKIES_METRICS_PORT": str(free_port())}
    env.update(extra_env)
    server = build(Settings(argv=[], env=env))
    server.host = "127.0.0.1"
    if coordinator_factory is not None:
        # the server's own harness hook (tests, tools/swarm_run.py)
        server.coordinator_factory = coordinator_factory
    task = asyncio.create_task(serve(server))
    deadline = time.monotonic() + 60
    while True:
        if task.done():
            task.result()
            raise SmokeFailure("server exited during boot")
        if server._server is not None:
            return Booted(task, server, int(env["SELKIES_PORT"]))
        check(time.monotonic() < deadline, "server did not listen in 60 s")
        await asyncio.sleep(0.05)


async def wait_warmup(server, timeout_s: float) -> float:
    """Block until the boot compile ends; a device failure there is the
    smoke's failure (server/main.py WarmUp keeps the outcome)."""
    ok = await asyncio.to_thread(server.warmup.done.wait, timeout_s)
    check(ok, f"boot warm-up did not finish in {timeout_s:.0f} s")
    check(server.warmup.error is None,
          f"boot warm-up failed: {server.warmup.error!r}")
    return server.warmup.seconds


async def shutdown(b: Booted) -> None:
    b.task.cancel()
    try:
        await asyncio.wait_for(b.task, 60)
    except asyncio.CancelledError:
        pass
    check(b.server.recorder.open_spans() == 0,
          f"{b.server.recorder.open_spans()} flight-recorder spans left "
          "open after stop")


def assert_healthy(server, display_id: str = "primary") -> Dict[str, Any]:
    """Zero ladder steps, zero restarts, zero encode errors."""
    d = json.loads(server._health_payload())["displays"][display_id]
    check(d["rung"] == "device", f"degradation ladder stepped: {d['rung']}")
    check(not d["failed"], "display marked failed")
    for k in ("restarts", "failures", "watchdog_restarts"):
        check(d.get(k, 0) == 0, f"supervisor {k} = {d.get(k)}")
    check(d.get("encode_errors", 0) == 0,
          f"encode_errors = {d.get('encode_errors')}")
    return d


# ---------------------------------------------------------------------------
# the client: a real websockets peer standing in for the browser


class Client:
    """SETTINGS handshake, receive stripes, ACK completed frames."""

    def __init__(self, port: int, display_id: str, width: int, height: int,
                 on_stripe: Callable[[int, Any], None]) -> None:
        self.port, self.display_id = port, display_id
        self.width, self.height = width, height
        self.on_stripe = on_stripe
        self.acking = True
        self.frames_done = 0          # completed frames (id changed after)
        self.stripes = 0
        self.bytes = 0
        self.first_stripe_s: Optional[float] = None
        self.last_binary = time.monotonic()
        self._cur: Optional[int] = None
        self.on_frame_done: Optional[Callable[[int], None]] = None
        self.ws = None

    async def connect(self) -> None:
        import websockets

        self.ws = await websockets.connect(
            f"ws://127.0.0.1:{self.port}", max_size=None,
            compression=None)
        check(await self.ws.recv() == "MODE websockets", "no MODE line")
        schema = json.loads(await self.ws.recv())
        check(schema.get("type") == "server_settings", "no server_settings")
        self.t0 = time.monotonic()
        await self.ws.send("SETTINGS," + json.dumps({
            "displayId": self.display_id,
            "initialClientWidth": self.width,
            "initialClientHeight": self.height}))

    async def _ack(self, frame_id: int) -> None:
        if self.acking:
            await self.ws.send(f"CLIENT_FRAME_ACK {frame_id}")

    async def pump(self, until: Callable[[], bool], timeout_s: float,
                   recv_timeout_s: float = 1.0) -> None:
        """Receive until ``until()`` holds (checked after every message
        and every quiet ``recv_timeout_s``)."""
        from selkies_tpu.protocol import unpack_binary
        from selkies_tpu.protocol.wire import VideoStripe

        deadline = time.monotonic() + timeout_s
        while not until():
            check(time.monotonic() < deadline,
                  f"{self.display_id}: timed out after {timeout_s:.0f} s "
                  f"({self.frames_done} frames, {self.stripes} stripes)")
            try:
                m = await asyncio.wait_for(self.ws.recv(), recv_timeout_s)
            except asyncio.TimeoutError:
                continue
            if not isinstance(m, bytes):
                check(not str(m).startswith("KILL"), f"server said {m!r}")
                continue
            f = unpack_binary(m)
            if not isinstance(f, VideoStripe):
                continue
            now = time.monotonic()
            if self.first_stripe_s is None:
                self.first_stripe_s = now - self.t0
            self.last_binary = now
            if self._cur is not None and f.frame_id != self._cur:
                done = self._cur
                self.frames_done += 1
                if self.on_frame_done is not None:
                    self.on_frame_done(done)
                await self._ack(done)
            self._cur = f.frame_id
            self.stripes += 1
            self.bytes += len(f.payload)
            self.on_stripe(m[0], f)

    async def close(self) -> None:
        if self.ws is not None:
            await self.ws.close()


# ---------------------------------------------------------------------------
# one chip: the two served profiles


async def serve_jpeg(width: int, height: int, frames: int,
                     cache: CacheEvents, first_timeout_s: float) -> None:
    import numpy as np
    from PIL import Image

    h0, m0 = cache.snapshot()
    b = await boot_server({})
    log(f"jpeg: server up on :{b.port}, encoder={b.server.settings.encoder}")
    cold = await wait_warmup(b.server, first_timeout_s)
    log(f"jpeg: cold compile (boot warm-up, 1920x1080 default encoder) "
        f"{cold:.1f} s")

    canvas = np.zeros((height, width, 3), np.uint8)
    oracle = SourceOracle(width, height)
    worst = [99.0]
    seen_types = set()

    def on_stripe(type_byte: int, f) -> None:
        seen_types.add(type_byte)
        img = Image.open(io.BytesIO(f.payload))
        img.load()                                  # full entropy decode
        arr = np.asarray(img.convert("RGB"))
        rows = min(arr.shape[0], height - f.y_start)
        canvas[f.y_start:f.y_start + rows] = arr[:rows, :width]

    client = Client(b.port, "primary", width, height, on_stripe)
    fps = 60.0        # the server's default framerate; SETTINGS asks none

    def on_frame_done(_fid: int) -> None:
        # the canvas now shows exactly one source frame: which, how well?
        if client.frames_done in (1, frames // 2, frames):
            now = time.monotonic() - client.t0
            db, t, _src = oracle.frame_shown_since(
                canvas, fps, now, now, JPEG_PSNR_MIN_DB)
            worst[0] = min(worst[0], db)
            log(f"jpeg: frame {client.frames_done} shows source frame {t}: "
                f"PSNR {db:.2f} dB")

    client.on_frame_done = on_frame_done
    try:
        await client.connect()
        await client.pump(lambda: client.frames_done >= frames,
                          first_timeout_s + 120)
        check(seen_types == {0x03}, f"wire types {seen_types}, want 0x03")
        check(worst[0] >= JPEG_PSNR_MIN_DB,
              f"JPEG canvas PSNR {worst[0]:.2f} < {JPEG_PSNR_MIN_DB} dB")
        st = b.server.display_clients["primary"]
        est = st.encoder.stats()
        check(est.get("host_fallback_stripes", 0) == 0,
              f"host_fallback_stripes = {est.get('host_fallback_stripes')}")
        assert_healthy(b.server)
        log(f"jpeg: {client.frames_done} frames, {client.stripes} stripes "
            f"(all PIL-decoded), {client.bytes} bytes; first stripe "
            f"{client.first_stripe_s:.2f} s after SETTINGS; "
            f"host_fallback_stripes=0, rung=device, restarts=0")
    finally:
        await client.close()
        await shutdown(b)
    h1, m1 = cache.snapshot()
    log(f"jpeg: compile cache hits={h1 - h0} misses={m1 - m0}; "
        "open spans after stop = 0")
    log(f"jpeg: XLA compiles/loads >= 1 s: {cache.new_compiles()}")


async def serve_h264(width: int, height: int, frames: int,
                     cache: CacheEvents, first_timeout_s: float) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from selkies_tpu.encoder import conformance
    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.runtime import pallas_interpret

    h0, m0 = cache.snapshot()
    b = await boot_server({"SELKIES_ENCODER": "x264enc-striped"})
    log(f"h264: server up on :{b.port}, encoder={b.server.settings.encoder}")
    cold_idr = await wait_warmup(b.server, first_timeout_s)
    log(f"h264: cold compile (boot warm-up: IDR + P steps) "
        f"{cold_idr:.1f} s")

    decoders: Dict[int, Any] = {}
    last: Dict[int, Any] = {}
    seen_types = set()
    p_first = [None]

    def on_stripe(type_byte: int, f) -> None:
        seen_types.add(type_byte)
        dec = decoders.get(f.y_start)
        if dec is None:
            dec = decoders[f.y_start] = conformance.ConformanceDecoder(
                "h264", max_dim=max(2048, width))
        if not f.is_key and p_first[0] is None:
            p_first[0] = time.monotonic() - client.t0
        out = dec.decode(f.payload)                 # emission order
        if out is not None:
            last[f.y_start] = out

    client = Client(b.port, "primary", width, height, on_stripe)
    try:
        await client.connect()
        # the first P frame sits behind the P step's cold compile
        await client.pump(lambda: client.frames_done >= frames,
                          first_timeout_s + 120)
        log(f"h264: first stripe {client.first_stripe_s:.2f} s, first P "
            f"stripe {p_first[0]:.1f} s after SETTINGS")
        st = b.server.display_clients["primary"]
        # drain: a client that stops ACKing closes the server's send gate
        # (backpressure.py: 4 s stall / 2 s desync); the capture loop then
        # submits nothing new but keeps delivering what is in flight
        client.acking = False
        t_stop = time.monotonic()

        def drained() -> bool:
            return (not st.bp.send_enabled
                    and time.monotonic() - client.last_binary > 2.0)

        await client.pump(drained, 60, recv_timeout_s=0.25)
        log(f"h264: send gate closed and stream drained "
            f"{time.monotonic() - t_stop:.1f} s after the last ACK")

        check(seen_types == {0x04}, f"wire types {seen_types}, want 0x04")
        driver = st.encoder
        base = driver.pipe.base       # H264StripeEncoder behind the driver
        check(base.entropy == "device", f"entropy tier {base.entropy}")
        mismatched = []
        canvas_y = np.zeros((height, width), np.uint8)
        for i, stripe in enumerate(base.stripes):
            dec = decoders.get(stripe.y0)
            check(dec is not None, f"stripe y={stripe.y0} never served")
            tail = dec.flush()
            if tail:
                last[stripe.y0] = tail[-1]
            dy, du, dv = last[stripe.y0]
            ry, rcb, rcr = base.stripe_ref(i)
            same = (np.array_equal(dy, ry[:dy.shape[0], :dy.shape[1]])
                    and np.array_equal(du, rcb[:du.shape[0], :du.shape[1]])
                    and np.array_equal(dv, rcr[:dv.shape[0], :dv.shape[1]]))
            if not same:
                mismatched.append(stripe.y0)
            rows = min(dy.shape[0], height - stripe.y0)
            canvas_y[stripe.y0:stripe.y0 + rows] = dy[:rows, :width]
        check(not mismatched,
              f"decoded picture != encoder stripe_ref at y={mismatched}")
        log(f"h264: {len(base.stripes)} stripes: last decoded picture == "
            "encoder stripe_ref, bit for bit (Y, Cb, Cr) — check used: "
            "bit-exact, not the PSNR stand-in")

        best_db, best_t, _src = SourceOracle(width, height).frame_shown_since(
            canvas_y, float(st.bp.framerate or 60.0),
            t_stop - client.t0, time.monotonic() - client.t0,
            H264_PSNR_MIN_DB)
        log(f"h264: canvas shows source frame {best_t}: luma PSNR "
            f"{best_db:.2f} dB")
        check(best_db >= H264_PSNR_MIN_DB,
              f"H.264 luma PSNR {best_db:.2f} < {H264_PSNR_MIN_DB} dB")

        pst = driver.pipe.stats()
        check(pst.get("entropy_errors", 0) == 0,
              f"entropy_errors = {pst.get('entropy_errors')}")
        check(pst.get("frames_dropped", 0) == 0,
              f"frames_dropped = {pst.get('frames_dropped')}")
        assert_healthy(b.server)

        # which ME backend ran, and is the compiled kernel in the step?
        me = dev.ME
        check(not pallas_interpret(), "Pallas interpreter mode is on")
        S, sh = base.n_stripes, base.stripe_h
        u8 = jnp.uint8

        def sds(*shape, dtype=u8):
            return jax.ShapeDtypeStruct(shape, dtype)

        y = sds(base.pad_h, base.pad_w)
        c = sds(base.pad_h // 2, base.pad_w // 2)
        i32 = sds(dtype=jnp.int32)
        text = dev.encode_frame_p_cavlc_rgb.lower(
            sds(base.pad_h, base.pad_w, 3), y, c, c, y, c, c,
            sds(S, dtype=jnp.int32), i32, i32,
            pad_h=base.pad_h, pad_w=base.pad_w, n_stripes=S, sh=sh,
            search=base.search, max_stripe_bytes=base._cavlc_msb,
            me=me).as_text()
        check("tpu_custom_call" in text,
              "no tpu_custom_call in the served P step: the Pallas kernel "
              "is not compiled into it")
        log(f"h264: ME backend = {me}; tpu_custom_call present in the "
            f"served P step ({base.pad_h}x{base.pad_w}); P-step programs "
            f"compiled this run: "
            f"{dev.encode_frame_p_cavlc_rgb._cache_size()}")
        log(f"h264: {client.frames_done} frames, {client.stripes} stripes "
            f"(all libavcodec-decoded), {client.bytes} bytes; "
            f"entropy=device, frames_dropped=0, rung=device, restarts=0")
    finally:
        await client.close()
        await shutdown(b)
        for dec in decoders.values():
            dec.close()
    h1, m1 = cache.snapshot()
    log(f"h264: compile cache hits={h1 - h0} misses={m1 - m0}; "
        "open spans after stop = 0")
    log(f"h264: XLA compiles/loads >= 1 s: {cache.new_compiles()}")


async def one_chip(cache: CacheEvents) -> None:
    await serve_jpeg(1920, 1080, FRAMES[1], cache, COMPILE_TIMEOUT_S)
    await serve_h264(1920, 1080, FRAMES[1], cache, COMPILE_TIMEOUT_S)


# ---------------------------------------------------------------------------
# four chips: the lanes, each against a one-device mesh


class RecordingEncoder:
    """Transparent proxy around a lane's mesh encoder: forwards every
    attribute, and writes down each state-changing call — with a copy of
    its frames — so the same calls can be replayed on a reference."""

    def __init__(self, enc, calls: list) -> None:
        self.__dict__["_enc"] = enc
        self.__dict__["_calls"] = calls
        self.__dict__["_live"] = {}     # keeps pendings (and ids) alive
        self.__dict__["device_sets"] = []

    def __getattr__(self, name):
        return getattr(self._enc, name)

    def __setattr__(self, name, value):
        setattr(self._enc, name, value)

    def dispatch(self, frames):
        import jax
        import numpy as np

        rec = [None if f is None else np.array(f, copy=True)
               for f in frames]
        p = self._enc.dispatch(frames)
        self._live[id(p)] = p
        devs = set()
        for v in vars(p).values():
            if isinstance(v, jax.Array):
                devs |= set(v.sharding.device_set)
        self.device_sets.append(devs)
        self._calls.append(("dispatch", id(p), rec))
        return p

    def harvest(self, p):
        out = self._enc.harvest(p)
        self._calls.append(("harvest", id(p), out))
        return out

    def force_keyframe(self, session):
        self._calls.append(("force_keyframe", session, None))
        return self._enc.force_keyframe(session)

    def reset_session(self, session):
        self._calls.append(("reset_session", session, None))
        return self._enc.reset_session(session)


def recording_coordinator(record: Dict[str, Any]):
    """A MeshEncodeCoordinator (handed to the server through its
    ``coordinator_factory`` hook) whose lanes are RecordingEncoders, and
    which also keeps the SAME default factory built for a one-device
    mesh — the reference the lanes are compared with."""
    from selkies_tpu.parallel.coordinator import MeshEncodeCoordinator

    class Recording(MeshEncodeCoordinator):
        def _build_default_factory(self, mesh_spec, *rest):
            real = super()._build_default_factory(mesh_spec, *rest)
            keep = (self.chips, self.slots_per_lane, self.sfe_shards)
            record["reference_factory"] = super()._build_default_factory(
                "session:1,stripe:1", *rest)
            self.chips, self.slots_per_lane, self.sfe_shards = keep

            def factory(n: int):
                calls: list = []
                enc = RecordingEncoder(real(n), calls)
                record.setdefault("lanes", []).append((n, enc, calls))
                return enc
            return factory

    def make(*a, **kw):
        coord = Recording(*a, **kw)
        record["coord"] = coord
        return coord
    return make


def _lane_programs(enc) -> List[Tuple[str, Callable[[], Any]]]:
    """(label, compile thunk) for every step program ``enc`` — a
    MeshStripeEncoder or MeshH264Encoder — will ask for, lowered with the
    arguments its own ``dispatch`` passes."""
    import jax
    import jax.numpy as jnp

    n, S = enc.n_sessions, enc.n_stripes
    frames = jax.device_put(
        jnp.zeros((n, enc.pad_h, enc.pad_w, 3), jnp.uint8),
        enc._frame_sharding)
    devs = len(enc.mesh.devices.flat)
    if hasattr(enc, "_step_for"):                       # H.264 lanes
        mask = jax.device_put(jnp.zeros((n, S), jnp.int32),
                              enc._plane_sharding)
        args = (frames, enc._prev_y, enc._prev_cb, enc._prev_cr,
                enc._ref_y, enc._ref_cb, enc._ref_cr, mask, mask,
                jnp.int32(enc.qp), jnp.int32(enc.paint_over_qp))
        return [(f"h264 {enc.width}x{enc.height} on {devs} dev "
                 f"with_idr={w}",
                 lambda w=w: enc._step_for(w).lower(*args).compile())
                for w in (True, False)]
    qsel = jax.device_put(jnp.zeros((n, S), jnp.int32), enc._qsel_sharding)
    args = (frames, enc._prev, enc._qy, enc._qc, qsel)
    return [(f"jpeg {enc.width}x{enc.height} x{n} on {devs} dev",
             lambda: enc._step.lower(*args).compile())]


def precompile_lanes(name: str, env: Dict[str, str], width: int,
                     height: int) -> None:
    """Fill the persistent compile cache with the lane's step programs
    AND the one-device reference's, all at once: XLA compiles run in
    parallel threads (the device-CAVLC H.264 step takes ~5 min each, four
    of them back to back would spend the four chips' time waiting). The
    server's lane and the replay then load them from the cache."""
    import threading

    from selkies_tpu.settings import Settings

    settings = Settings(argv=[], env=env)
    record: Dict[str, Any] = {}
    coord = recording_coordinator(record)(
        str(settings.tpu_mesh), int(settings.tpu_sessions_per_chip),
        width, height, settings=settings, profile=str(settings.encoder))
    try:
        n, lane_enc, _calls = record["lanes"][0]
        ref_enc = record["reference_factory"](n)
        jobs = _lane_programs(lane_enc._enc) + _lane_programs(ref_enc)
        took: Dict[str, Any] = {}

        def run(label, thunk):
            t0 = time.monotonic()
            try:
                thunk()
                took[label] = time.monotonic() - t0
            except BaseException as e:
                took[label] = e

        t0 = time.monotonic()
        threads = [threading.Thread(target=run, args=j) for j in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for label, res in took.items():
            check(not isinstance(res, BaseException),
                  f"{name}: compile of {label} failed: {res!r}")
            log(f"{name}: cold compile {label}: {res:.1f} s")
        log(f"{name}: {len(jobs)} programs compiled in parallel in "
            f"{time.monotonic() - t0:.1f} s wall")
    finally:
        coord.stop()


def stripe_bytes(s) -> bytes:
    return s.annexb if hasattr(s, "annexb") else s.jpeg


def replay_and_compare(name: str, record: Dict[str, Any], height: int,
                       state_attrs: Tuple[str, ...]) -> None:
    """Replay every recorded call on the one-device reference and compare
    each harvest byte for byte; assert the lane really spanned 4 chips."""
    import jax

    check(len(record.get("lanes", [])) == 1,
          f"{name}: expected one lane, got {len(record.get('lanes', []))}")
    n, enc, calls = record["lanes"][0]
    real = enc._enc
    lane_devs = {d for d in real.mesh.devices.flat}
    check(len(lane_devs) == 4, f"{name}: lane mesh has {len(lane_devs)} "
          "devices")
    for attr in state_attrs:
        arr = getattr(real, attr)
        devs = {s.device for s in arr.addressable_shards}
        check(len(devs) == 4, f"{name}: state {attr} has shards on "
              f"{len(devs)} devices")
    check(enc.device_sets and all(len(d) == 4 for d in enc.device_sets),
          f"{name}: a dispatch's outputs did not span 4 devices")
    log(f"{name}: lane state {state_attrs} and every dispatch's outputs "
        f"have shards on 4 distinct devices "
        f"{sorted(d.id for d in lane_devs)}")

    t0 = time.monotonic()
    ref = record["reference_factory"](n)
    ref_devs = {d for d in ref.mesh.devices.flat}
    check(len(ref_devs) == 1, f"{name}: reference mesh is not one device")
    pend: Dict[int, Any] = {}
    n_harvest = n_stripes = n_bytes = 0
    for kind, key, payload in calls:
        if kind == "dispatch":
            pend[key] = ref.dispatch(payload)
        elif kind == "harvest":
            got, got_sb = payload
            want, _ = ref.harvest(pend.pop(key))
            for sess, (g, w_) in enumerate(zip(got, want)):
                g = [s for s in g if s.y_start < height]
                w_ = [s for s in w_ if s.y_start < height]
                check([s.y_start for s in g] == [s.y_start for s in w_],
                      f"{name}: harvest {n_harvest} session {sess}: stripe "
                      f"sets differ")
                for a, b_ in zip(g, w_):
                    x, y = stripe_bytes(a), stripe_bytes(b_)
                    k = next((i for i, (p, q) in enumerate(zip(x, y))
                              if p != q), min(len(x), len(y)))
                    check(x == y,
                          f"{name}: harvest {n_harvest} session {sess} "
                          f"stripe y={a.y_start}: bytes differ at {k} "
                          f"(lane {len(x)} B, one-device {len(y)} B)")
                    n_stripes += 1
                    n_bytes += len(stripe_bytes(a))
            n_harvest += 1
        else:
            getattr(ref, kind)(key)
    # the server stopped with dispatches in flight, so the replay ends
    # with them too: consume them. A process that exits while a host copy
    # still waits on its computation crashes in the TPU client's teardown
    # (SIGSEGV in CopyToLiteralAsync: exit 139 after the success line).
    for p in pend.values():
        ref.harvest(p)
    check(n_stripes > 0, f"{name}: nothing was compared")
    log(f"{name}: {n_harvest} harvests, {n_stripes} stripes, {n_bytes} "
        f"bytes byte-identical to the one-device mesh "
        f"(device {sorted(d.id for d in ref_devs)}; replay took "
        f"{time.monotonic() - t0:.1f} s incl. its compile)")
    record.clear()
    jax.clear_caches()


def served_was_harvested(name: str, record: Dict[str, Any],
                         got: Dict[str, List[bytes]]) -> None:
    """Every stripe a client received is one the recorded lane produced:
    the bytes the comparison below vouches for are the bytes that were
    served. (Displays restart — and change slots — whenever a display
    joins, so this is membership, not a per-slot sequence.)"""
    _n, _enc, calls = record["lanes"][0]
    produced = {stripe_bytes(s) for kind, _k, out in calls
                if kind == "harvest" for sess in out[0] for s in sess}
    for did, payloads in got.items():
        stray = sum(1 for p in payloads if p not in produced)
        check(payloads and not stray,
              f"{name}: display {did} received {len(payloads)} stripes, "
              f"{stray} of them not produced by the recorded lane")
    log(f"{name}: all {sum(len(v) for v in got.values())} stripes the "
        f"clients received were produced by the recorded lane")


async def serve_lane(name: str, env: Dict[str, str],
                     displays: List[str], width: int, height: int,
                     frames: int, want_type: int, first_timeout_s: float,
                     state_attrs: Tuple[str, ...],
                     cache: CacheEvents) -> None:
    await asyncio.to_thread(precompile_lanes, name, env, width, height)
    record: Dict[str, Any] = {}
    h0, m0 = cache.snapshot()
    b = await boot_server(
        env, coordinator_factory=recording_coordinator(record))
    log(f"{name}: server up on :{b.port}, tpu_mesh="
        f"{b.server.settings.tpu_mesh}, encoder={b.server.settings.encoder}")
    await wait_warmup(b.server, first_timeout_s)  # a no-op under tpu_mesh
    got: Dict[str, List[bytes]] = {d: [] for d in displays}
    types = set()
    clients: Dict[str, Client] = {}
    for did in displays:
        def on_stripe(type_byte, f, did=did):
            types.add(type_byte)
            got[did].append(f.payload)
        clients[did] = Client(b.port, did, width, height, on_stripe)
    try:
        for c in clients.values():
            await c.connect()
        await asyncio.gather(*[
            c.pump(lambda c=c: c.frames_done >= frames,
                   first_timeout_s + 300) for c in clients.values()])
        check(types == {want_type}, f"{name}: wire types {types}")
        check(b.server.mesh_coordinators, f"{name}: no mesh coordinator — "
              "the displays were served solo")
        for did in displays:
            enc = b.server.display_clients[did].encoder
            check(hasattr(enc, "slot") and enc.slot is not None,
                  f"{name}: display {did} is not on a mesh lane")
            assert_healthy(b.server, did)
        coord = record["coord"]
        cst = coord.stats()
        log(f"{name}: {len(displays)} display(s) x {frames} frames served; "
            f"lanes={len(coord.lanes)} chips={coord.chips} "
            f"sfe_shards={coord.sfe_shards} "
            f"slot_faults={cst.get('slot_faults_total', 0)} "
            f"migrations={cst.get('migrations_total', 0)}")
        check(coord.slot_faults_total == 0 and coord.migrations_total == 0
              and coord.tick_errors_total == 0,
              f"{name}: lane faults during the run: {cst}")
        served_was_harvested(name, record, got)
    finally:
        for c in clients.values():
            await c.close()
        await shutdown(b)
    replay_and_compare(name, record, height, state_attrs)
    h1, m1 = cache.snapshot()
    log(f"{name}: compile cache while serving and replaying: "
        f"hits={h1 - h0} misses={m1 - m0}")
    log(f"{name}: XLA compiles/loads >= 1 s: {cache.new_compiles()}")


async def four_chips(cache: CacheEvents) -> None:
    await serve_lane(
        "session-lane", {"SELKIES_TPU_MESH": "session:4",
                         "SELKIES_TPU_SESSIONS_PER_CHIP": "1",
                         "SELKIES_SECOND_SCREEN": "true",
                         "SELKIES_MAX_DISPLAYS": "0"},
        ["primary", "d1", "d2", "d3"], 1920, 1080,
        FRAMES[4], 0x03, COMPILE_TIMEOUT_S, ("_prev",), cache)
    await serve_lane(
        "sfe-lane", {"SELKIES_TPU_MESH": "session:1,stripe:4",
                     "SELKIES_TPU_SESSIONS_PER_CHIP": "1",
                     "SELKIES_ENCODER": "x264enc-striped"},
        ["primary"], 3840, 2160, FRAMES[4], 0x04,
        COMPILE_TIMEOUT_S, ("_ref_y", "_ref_cb", "_prev_y"), cache)


# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip lanes and their "
                         "one-device comparison (builder-run)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU — jax reports platform="
              f"{devs[0].platform!r}; refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax reports {len(devs)}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    import jaxlib

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu} "
        f"python {sys.version.split()[0]}")
    log(f"device: {json.dumps(device)}")

    from selkies_tpu import native
    from selkies_tpu.runtime import CACHE_ENV, enable_compile_cache
    from selkies_tpu.server.data_server import UPLOAD_DIR_ENV

    # everything the server writes stays inside the checkout
    os.environ.setdefault(UPLOAD_DIR_ENV,
                          os.path.join(REPO, "chiprun_out", "uploads"))
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} "
        f"({'from ' + CACHE_ENV if os.environ.get(CACHE_ENV) else 'in-repo default'}"
        f", {n_cached} entries at start)")
    cache = CacheEvents()

    t0 = time.monotonic()
    try:
        # a failed native build is a failure here, not a slower fallback
        native.require("entropy", "cavlc", "conformance")
        log("native coders built from tracked sources: entropy, cavlc, "
            "conformance")
        if args.chips == 4:
            asyncio.run(four_chips(cache))
        else:
            asyncio.run(one_chip(cache))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    hits, misses = cache.snapshot()
    log(f"done in {time.monotonic() - t0:.1f} s; compile cache "
        f"hits={hits} misses={misses}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
