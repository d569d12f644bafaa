"""Server entry point wiring (reference: selkies.py:3133-3307 main())."""

from __future__ import annotations

import asyncio
import functools
import gc
import logging
import os
import threading
import time
from typing import Optional

from ..runtime import (COMPILE_GRACE_S, INTERPRET_ENV,
                       enable_compile_cache)
from ..settings import Settings
from .app import StreamingApp
from .data_server import DataStreamingServer

logger = logging.getLogger("selkies_tpu")


def run(settings: Settings) -> int:
    logging.basicConfig(
        level=logging.DEBUG if settings.debug.value else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    return asyncio.run(_amain(settings)) or 0


async def _amain(settings: Settings) -> int:
    return await serve(build(settings))


class WarmUp:
    """Background compile of the default encoder geometry (keyframe AND
    inter-frame programs) so the first client doesn't pay the jit stall.

    The outcome is kept, not swallowed: ``done`` is set when the thread
    ends, ``error`` holds what the first device compile raised (logged at
    ERROR — a device that fails at boot is not a debug-level event) and
    ``seconds`` how long the cold compile took."""

    def __init__(self, settings: Settings) -> None:
        self.settings = settings
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.seconds = 0.0
        if str(settings.tpu_mesh):
            # displays ride mesh lanes, whose programs depend on the
            # joining geometry; compiling the solo encoder here would
            # spend minutes (and device memory) on a program that at
            # most an overflow display ever runs
            logger.info("tpu_mesh set: solo encoder warm-up skipped")
            self.done.set()
            return
        threading.Thread(target=self._work, name="tpuenc-warmup",
                         daemon=True).start()

    def _work(self) -> None:
        t0 = time.monotonic()
        try:
            from ..capture.synthetic import SyntheticSource
            from ..encoder.async_driver import AsyncEncodeDriver
            from .data_server import default_encoder_factory

            enc = default_encoder_factory(1920, 1080, self.settings)
            flush = enc.flush     # the threaded adapter's blocks to the end
            if isinstance(enc, AsyncEncodeDriver):
                # the async driver's gives up after 60 s by default —
                # shorter than the compile it waits for
                flush = functools.partial(enc.flush,
                                          timeout=COMPILE_GRACE_S)
            # three frames of moving content: the first compiles the
            # keyframe program, the second the inter-frame program and,
            # with it, the slice program of every fetch-prefix tier the
            # content can select later (h264.dispatch) — a stream's
            # second frame must not be where a minutes-long compile
            # lands, nor its first busy frame where a small one does
            src = SyntheticSource(1920, 1080, pattern="desktop")
            for _ in range(3):
                enc.submit(src.next_frame())
                flush()
            close = getattr(enc, "close", None)
            if close:
                close()
            # what boot made (modules, traced programs, tables) lives as
            # long as the process: out of the collector's sight, so that a
            # full collection in a served stream walks the stream's own
            # objects only (one held every thread for 54 ms, PERF.md PR 25)
            gc.collect()
            gc.freeze()
            logger.info("encoder warm-up done")
        except BaseException as e:
            self.error = e
            logger.exception("encoder warm-up FAILED: the default 1080p "
                             "encoder does not run on this device")
        finally:
            self.seconds = time.monotonic() - t0
            self.done.set()


def build(settings: Settings) -> DataStreamingServer:
    """Everything boot decides before a socket opens: the runtime policy
    (interpreter flag, compile cache), the app + data server pair, and
    the background warm-up compile (kept on ``server.warmup``)."""
    if settings.tpu_interpret.value:
        os.environ[INTERPRET_ENV] = "true"
    cache_dir = enable_compile_cache()   # raises: no quiet boot without it
    logger.info("XLA compile cache: %s", cache_dir)
    app = StreamingApp(settings)
    server = DataStreamingServer(settings, app=app)
    app.data_server = server
    server.warmup = WarmUp(settings)
    return server


def _start_watchers(server: DataStreamingServer) -> list:
    """The stall watch, and one device probe per device the server
    encodes on (every local device under ``tpu_mesh``, else the default
    one). Never fatal: a server without them serves all the same."""
    out: list = []
    try:
        import jax

        from ..observability.device_probe import DeviceProbe
        from ..observability.stall_watch import StallWatch

        out.append(StallWatch(
            lambda: server.recorder, loop=asyncio.get_running_loop(),
            capture_stacks=bool(server.settings.stall_stacks.value)).start())
        devices = jax.local_devices()
        if not str(getattr(server.settings, "tpu_mesh", "") or ""):
            devices = devices[:1]
        server.device_probes = [
            DeviceProbe(d, lambda: server.recorder,
                        lambda: server.metrics).start() for d in devices]
        out.extend(server.device_probes)
    except Exception:
        logging.getLogger("selkies_tpu").exception(
            "observability threads not started")
    return out


async def serve(server: DataStreamingServer) -> int:
    """Bring up every plane around a built server and serve until
    cancelled."""
    settings, app = server.settings, server.app

    if settings.audio_enabled.value:
        try:
            from ..audio import AudioCaptureSettings, AudioPipeline, opus_available

            if opus_available():
                server.audio_pipeline = AudioPipeline(server, AudioCaptureSettings(
                    device_name=settings.audio_device_name,
                    opus_bitrate=int(settings.audio_bitrate),
                    use_silence_gate=True))
            else:
                logging.getLogger("selkies_tpu").warning(
                    "audio disabled: libopus unavailable")
        except Exception:
            logging.getLogger("selkies_tpu").exception("audio init failed")

    input_handler = None
    cursor_monitor = None
    try:
        from ..input import InputHandler, open_clipboard_backend, open_x11_backend
        from ..input.cursor import CursorMonitor, open_cursor_source

        input_handler = InputHandler(
            backend=open_x11_backend(),
            clipboard=open_clipboard_backend(),
            data_server=server,
            enable_clipboard=(
                "true" if settings.clipboard_enabled.value else "false"),
            enable_binary_clipboard=settings.enable_binary_clipboard.value,
        )
        def _on_set_fps(fps: int) -> None:
            app.set_framerate(fps)
            asyncio.get_running_loop().create_task(server.set_framerate(fps))

        input_handler.on_set_fps = _on_set_fps
        server.input_handler = input_handler
        cursor_monitor = CursorMonitor(open_cursor_source(), app.send_cursor)
    except Exception as e:  # no X display etc. — stream-only mode
        logging.getLogger("selkies_tpu").warning("input plane disabled: %s", e)

    # observability threads (docs/observability.md). Each looks the
    # recorder up on the server when it writes: a harness may swap the one
    # build() made before calling serve()
    watchers = _start_watchers(server)
    for probe in server.device_probes:
        # the probe's one-add program compiles here, before a client can
        # join (milliseconds; never persisted, so never a cache miss)
        await asyncio.to_thread(probe.ready.wait, 30.0)

    tasks = [asyncio.create_task(server.run_server())]

    # HTTP side: serve the bundled web client + /turn + signaling on the
    # web port (reference: signalling_web.py serves gst-web on 8080)
    web_server = None
    try:
        from ..rtc import SignalingServer
        from . import bundled_web_root

        web_root = bundled_web_root()
        if web_root is not None:
            files_root = None
            if "download" in settings.file_transfers:
                from .data_server import upload_dir

                files_root = upload_dir()
            web_server = SignalingServer(
                addr="0.0.0.0", port=int(settings.web_port),
                web_root=web_root,
                files_root=files_root,
                turn_shared_secret=str(settings.turn_shared_secret),
                turn_host=str(settings.turn_host),
                turn_port=str(settings.turn_port),
            )

            async def _run_web(ws=web_server):
                # a busy web port must not take the media plane down
                try:
                    await ws.run()
                except OSError as e:
                    logging.getLogger("selkies_tpu").error(
                        "web server bind failed (%s); client serving "
                        "disabled", e)

            tasks.append(asyncio.create_task(_run_web()))
        else:
            logging.getLogger("selkies_tpu").warning(
                "web client assets not bundled; HTTP serving disabled")
    except Exception:
        logging.getLogger("selkies_tpu").exception("web server init failed")

    metrics = None
    try:
        from ..observability import Metrics

        if int(settings.metrics_port) > 0:
            metrics = Metrics(port=int(settings.metrics_port))
            # observability surface (docs/observability.md): the flight
            # recorder backs /debug/trace; the jax.profiler hook is
            # opt-in. start_http is non-fatal on a busy port.
            metrics.recorder = server.recorder
            metrics.jax_trace_enabled = bool(
                settings.jax_trace_enabled.value)
            metrics.start_http()
            server.metrics = metrics
            server.recorder.metrics = metrics
    except Exception as e:
        logging.getLogger("selkies_tpu").warning("metrics disabled: %s", e)

    if input_handler is not None:
        tasks.append(asyncio.create_task(input_handler.run_clipboard_poll()))
    if cursor_monitor is not None:
        tasks.append(asyncio.create_task(cursor_monitor.run()))
    try:
        await asyncio.gather(*tasks)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        for w in watchers:
            w.stop()
        server.device_probes = []
        if web_server is not None:
            await web_server.stop()
        if cursor_monitor is not None:
            cursor_monitor.stop()
            cursor_monitor.source.close()
        if input_handler is not None:
            try:
                await input_handler.close()
            except Exception:
                logging.getLogger("selkies_tpu").exception(
                    "input plane shutdown failed")
        await server.stop()
    return 0
