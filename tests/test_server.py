"""End-to-end server tests over a real WebSocket, with a fake encoder
(no TPU/jit) standing in for the tpuenc pipeline."""

import asyncio
import json
import os

import numpy as np
import pytest
import websockets

from selkies_tpu.encoder.jpeg import StripeOutput
from selkies_tpu.protocol import unpack_binary, VideoStripe
from selkies_tpu.server.app import StreamingApp
from selkies_tpu.server.data_server import DataStreamingServer
from selkies_tpu.settings import Settings


class FakeEncoder:
    """Pipelined-encoder lookalike: every submitted frame yields one stripe."""

    def __init__(self):
        self.submitted = 0
        self._ready = []

    def submit(self, frame):
        self.submitted += 1
        self._ready.append(
            (self.submitted,
             [StripeOutput(y_start=0, height=64,
                           jpeg=b"\xff\xd8FAKE%d" % self.submitted + b"\xff\xd9",
                           is_paintover=False)]))

    def poll(self):
        out, self._ready = self._ready, []
        return out

    def flush(self):
        return self.poll()


class FakeSource:
    def __init__(self, width, height, fps):
        self.width, self.height, self.fps = width, height, fps

    def start(self):
        pass

    def stop(self):
        pass

    def next_frame(self):
        return np.zeros((self.height, self.width, 3), np.uint8)


@pytest.fixture
def anyio_backend():
    return "asyncio"


def make_server(tmp_path, **settings_env):
    env = {"SELKIES_PORT": "0"}
    env.update(settings_env)
    settings = Settings(argv=[], env=env)
    app = StreamingApp(settings)
    encoders = []

    def encoder_factory(w, h, s):
        enc = FakeEncoder()
        encoders.append(enc)
        return enc

    server = DataStreamingServer(
        settings, app=app,
        encoder_factory=encoder_factory,
        source_factory=lambda w, h, fps: FakeSource(w, h, fps),
        host="127.0.0.1",
    )
    app.data_server = server
    os.environ["SELKIES_UPLOAD_DIR"] = str(tmp_path / "uploads")
    return server, app, encoders


async def start_on_free_port(server):
    import websockets.asyncio.server as ws_server

    server._stop_event = asyncio.Event()
    srv = await ws_server.serve(
        server.ws_handler, "127.0.0.1", 0, compression=None, max_size=None)
    server._server = srv
    port = srv.sockets[0].getsockname()[1]
    return srv, port


async def handshake(ws):
    assert await ws.recv() == "MODE websockets"
    schema = json.loads(await ws.recv())
    assert schema["type"] == "server_settings"
    return schema


@pytest.mark.anyio
async def test_handshake_and_video_flow(tmp_path):
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            schema = await handshake(ws)
            assert "encoder" in schema["settings"]

            await ws.send('SETTINGS,' + json.dumps({
                "displayId": "primary",
                "initialClientWidth": 320,
                "initialClientHeight": 240,
                "framerate": 30,
            }))
            # PIPELINE_RESETTING broadcast then binary stripes (stats JSON
            # may interleave)
            while True:
                reset = await asyncio.wait_for(ws.recv(), 5)
                if reset == "PIPELINE_RESETTING primary":
                    break
            while True:
                frame = await asyncio.wait_for(ws.recv(), 5)
                if isinstance(frame, bytes):
                    break
            f = unpack_binary(frame)
            assert isinstance(f, VideoStripe)
            assert f.payload.startswith(b"\xff\xd8FAKE")
            assert f.frame_id == 1

            # ACK flows into backpressure state
            await ws.send(f"CLIENT_FRAME_ACK {f.frame_id}")
            await asyncio.sleep(0.1)
            st = server.display_clients["primary"]
            assert st.bp.acknowledged_frame_id == f.frame_id
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_stop_start_video(tmp_path):
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,{"displayId": "primary"}')
            await asyncio.wait_for(ws.recv(), 5)  # PIPELINE_RESETTING

            await ws.send("STOP_VIDEO")
            # drain until VIDEO_STOPPED
            while True:
                m = await asyncio.wait_for(ws.recv(), 5)
                if m == "VIDEO_STOPPED":
                    break
            st = server.display_clients["primary"]
            assert st.capture_task is None

            await ws.send("START_VIDEO")
            while True:
                m = await asyncio.wait_for(ws.recv(), 5)
                if m == "VIDEO_STARTED":
                    break
            assert st.capture_task is not None
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_second_screen_disabled_kills_client(tmp_path):
    server, app, encoders = make_server(
        tmp_path, SELKIES_SECOND_SCREEN="false")
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,{"displayId": "display2"}')
            while True:
                msg = await asyncio.wait_for(ws.recv(), 5)
                if isinstance(msg, str) and msg.startswith("KILL"):
                    break
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_file_upload_and_path_traversal(tmp_path):
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send("FILE_UPLOAD_START:sub/ok.txt:11")
            await ws.send(b"\x01hello")
            await ws.send(b"\x01 world")
            await ws.send("FILE_UPLOAD_END:sub/ok.txt")
            await asyncio.sleep(0.2)
            target = tmp_path / "uploads" / "sub" / "ok.txt"
            assert target.read_bytes() == b"hello world"

            await ws.send("FILE_UPLOAD_START:../evil.txt:4")
            msg = await asyncio.wait_for(ws.recv(), 5)
            assert msg.startswith("FILE_UPLOAD_ERROR")
            assert not (tmp_path / "evil.txt").exists()
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_resize_broadcasts_resolution(tmp_path):
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,{"displayId": "primary"}')
            await asyncio.wait_for(ws.recv(), 5)
            await ws.send("r,1280x720,primary")
            while True:
                m = await asyncio.wait_for(ws.recv(), 5)
                if isinstance(m, str) and m.startswith("{"):
                    d = json.loads(m)
                    if d.get("type") == "stream_resolution":
                        assert (d["width"], d["height"]) == (1280, 720)
                        break
            assert server.display_clients["primary"].width == 1280
    finally:
        await server.stop()
        srv.close()


def test_backpressure_state_logic():
    from selkies_tpu.server.backpressure import BackpressureState

    bp = BackpressureState(framerate=60)
    t = 1000.0
    bp.reset(now=t)
    # healthy: acked close behind sent
    for i in range(1, 100):
        bp.on_frame_sent(i, now=t + i * 0.016)
    bp.on_client_ack(95, now=t + 99 * 0.016)
    assert bp.evaluate(now=t + 99 * 0.016) is True

    # desync beyond 2s of frames → gate closes
    bp2 = BackpressureState(framerate=60)
    bp2.reset(now=t)
    for i in range(1, 300):
        bp2.on_frame_sent(i, now=t + i * 0.016)
    bp2.on_client_ack(10, now=t + 1.0)
    assert bp2.evaluate(now=t + 5.0) is False  # 289 frames > 120 allowed

    # stall: no ACK for > 4s
    bp3 = BackpressureState(framerate=60)
    bp3.reset(now=t)
    bp3.on_frame_sent(1, now=t)
    bp3.on_client_ack(1, now=t)
    assert bp3.evaluate(now=t + 0.1) is True
    assert bp3.evaluate(now=t + 4.5) is False

    # legitimate wrap: sender wrapped past 65535, client still far behind —
    # modular desync sees the true 5539-frame gap and keeps the gate closed
    # (the reference's abs() heuristic would wrongly treat this as an anomaly)
    bp4 = BackpressureState(framerate=60)
    bp4.reset(now=t)
    bp4.on_frame_sent(3, now=t)
    bp4.on_client_ack(60000, now=t)
    assert bp4.evaluate(now=t + 1) is False

    # true anomaly: client ACKs an id "ahead" of the sender → reset posture
    bp5 = BackpressureState(framerate=60)
    bp5.reset(now=t)
    bp5.on_frame_sent(5, now=t)
    bp5.on_client_ack(10, now=t)
    assert bp5.evaluate(now=t + 1) is True


@pytest.mark.anyio
async def test_settings_overrides_reach_encoder_factory(tmp_path):
    settings = Settings(argv=[], env={})
    seen = {}

    def factory(w, h, s, overrides=None):
        seen.update(overrides or {})
        return FakeEncoder()

    server = DataStreamingServer(
        settings, app=None, encoder_factory=factory,
        source_factory=lambda w, h, fps: FakeSource(w, h, fps),
        host="127.0.0.1")
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,' + json.dumps(
                {"displayId": "primary", "jpeg_quality": 77,
                 "framerate": 24}))
            await asyncio.sleep(0.3)
            assert seen.get("jpeg_quality") == 77
            st = server.display_clients["primary"]
            assert st.bp.framerate == 24.0
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_upload_exceeding_declared_size_rejected(tmp_path):
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send("FILE_UPLOAD_START:big.bin:4")
            await ws.send(b"\x01" + b"x" * 100)
            msg = await asyncio.wait_for(ws.recv(), 5)
            assert msg.startswith("FILE_UPLOAD_ERROR")
            assert not (tmp_path / "uploads" / "big.bin").exists()
            # further chunks are ignored, session stays alive
            await ws.send(b"\x01more")
            await ws.send("r,bogus")  # malformed resize is tolerated too
            await ws.send("CLIENT_FRAME_ACK notanint")
            pong = await ws.ping()
            await asyncio.wait_for(pong, 5)  # socket still open, not torn down
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_resize_resets_frame_ids(tmp_path):
    """A capture restart renumbers frames from 1, so the server must emit
    PIPELINE_RESETTING (else the backpressure gate wedges on stale ACKs)."""
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,{"displayId": "primary"}')
            await asyncio.wait_for(ws.recv(), 5)
            st = server.display_clients["primary"]
            st.bp.on_frame_sent(40000)
            st.bp.on_client_ack(40000)
            await ws.send("r,1280x720,primary")
            saw_reset = False
            for _ in range(20):
                m = await asyncio.wait_for(ws.recv(), 5)
                if isinstance(m, str) and m.startswith("PIPELINE_RESETTING"):
                    saw_reset = True
                    break
            assert saw_reset
            # restarted loop renumbers from 1 — the stale 40000 horizon is gone
            assert st.bp.last_sent_frame_id < 100
            assert st.bp.send_enabled
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_reconnect_resyncs_frame_ids_and_keyframe(tmp_path):
    """Satellite (ISSUE 2): client disconnect mid-stream then reconnect
    exercises _reset_frame_ids_and_notify — frame IDs restart at 1, the
    rebuilt encoder leads with a keyframe, and the reset precedes media."""
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,' + json.dumps({
                "displayId": "primary", "initialClientWidth": 320,
                "initialClientHeight": 240, "framerate": 30}))
            seen = 0
            while seen < 3:
                m = await asyncio.wait_for(ws.recv(), 5)
                if isinstance(m, bytes):
                    seen += 1
        # socket closed: the handler tears the display down
        for _ in range(100):
            if "primary" not in server.display_clients:
                break
            await asyncio.sleep(0.02)
        assert "primary" not in server.display_clients
        n_enc = len(encoders)

        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws2:
            await handshake(ws2)
            await ws2.send('SETTINGS,' + json.dumps({
                "displayId": "primary", "initialClientWidth": 320,
                "initialClientHeight": 240, "framerate": 30}))
            saw_reset = False
            frame = None
            while frame is None:
                m = await asyncio.wait_for(ws2.recv(), 5)
                if isinstance(m, str) and m.startswith("PIPELINE_RESETTING"):
                    saw_reset = True
                elif isinstance(m, bytes):
                    frame = m
            assert saw_reset, "media arrived before PIPELINE_RESETTING"
            f = unpack_binary(frame)
            assert isinstance(f, VideoStripe)
            assert f.frame_id == 1
            assert f.is_key
            assert len(encoders) > n_enc       # rebuilt, not reused
            st = server.display_clients["primary"]
            assert st.bp.last_sent_frame_id < 100
            assert st.bp.send_enabled
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_multi_display_layout_drives_xrandr(tmp_path, monkeypatch):
    """Two displays attach → the server computes the extended layout, sets
    capture offsets, and (with xrandr 'available') issues the monitor
    grammar; secondary disconnect reflows back to a single display."""
    import selkies_tpu.display as disp_pkg
    import selkies_tpu.display.xrandr as xr_mod

    calls = []

    class FakeXrandr:
        def __init__(self, *a, **k):
            pass

        def resize(self, w, h, refresh=60.0, output=None):
            calls.append(("resize", w, h))
            return f"{w}x{h}"

        def apply_layout(self, layout, refresh=60.0):
            calls.append(("layout", layout.fb_width, layout.fb_height,
                          tuple((p.display_id, p.x, p.y)
                                for p in layout.placements)))

    monkeypatch.setattr(disp_pkg, "xrandr_available", lambda: True)
    monkeypatch.setattr(disp_pkg, "XrandrManager", FakeXrandr)

    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}/") as ws1:
            await handshake(ws1)
            await ws1.send("SETTINGS," + json.dumps(
                {"displayId": "primary", "initialClientWidth": 1920,
                 "initialClientHeight": 1080}))
            await asyncio.sleep(0.3)
            assert ("resize", 1920, 1080) in calls

            async with websockets.connect(f"ws://127.0.0.1:{port}/") as ws2:
                await handshake(ws2)
                await ws2.send("SETTINGS," + json.dumps(
                    {"displayId": "display2", "initialClientWidth": 1280,
                     "initialClientHeight": 720}))
                await asyncio.sleep(0.3)
                layouts = [c for c in calls if c[0] == "layout"]
                assert layouts, calls
                _, fbw, fbh, placements = layouts[-1]
                assert (fbw, fbh) == (3200, 1080)
                assert ("display2", 1920, 0) in placements
                # capture offsets landed on the display state
                st2 = server.display_clients["display2"]
                assert (st2.x, st2.y) == (1920, 0)

            # secondary gone → reflow to single display
            await asyncio.sleep(0.4)
            assert ("resize", 1920, 1080) in calls[-2:] or \
                ("resize", 1920, 1080) in calls
            assert "display2" not in server.display_clients
    finally:
        srv.close()
        await srv.wait_closed()
        await server.stop()


@pytest.mark.anyio
async def test_layout_dedup_skips_repeat_xrandr(tmp_path, monkeypatch):
    import selkies_tpu.display as disp_pkg

    calls = []

    class FakeXrandr:
        def __init__(self, *a, **k):
            pass

        def resize(self, w, h, refresh=60.0, output=None):
            calls.append((w, h))
            return f"{w}x{h}"

        def apply_layout(self, layout, refresh=60.0):
            calls.append(("multi",))

    monkeypatch.setattr(disp_pkg, "xrandr_available", lambda: True)
    monkeypatch.setattr(disp_pkg, "XrandrManager", FakeXrandr)

    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}/") as ws:
            await handshake(ws)
            await ws.send("SETTINGS," + json.dumps(
                {"displayId": "primary", "initialClientWidth": 1024,
                 "initialClientHeight": 768}))
            await asyncio.sleep(0.3)
            n_after_settings = len(calls)
            # same-geometry settings again → no new xrandr traffic
            await ws.send("SETTINGS," + json.dumps(
                {"displayId": "primary", "initialClientWidth": 1024,
                 "initialClientHeight": 768}))
            await asyncio.sleep(0.3)
            assert len(calls) == n_after_settings
            # a real resize does reach xrandr
            await ws.send("r,800x600")
            await asyncio.sleep(0.3)
            assert calls[-1] == (800, 600)
    finally:
        srv.close()
        await srv.wait_closed()
        await server.stop()


@pytest.mark.anyio
async def test_h264_encoder_selection(tmp_path):
    """Client requesting x264enc-striped gets 0x04 frames; x264enc (full
    frame) gets 0x00 — through the real TPU-profile H.264 encoder on CPU."""
    env = {"SELKIES_PORT": "0"}
    settings = Settings(argv=[], env=env)
    app = StreamingApp(settings)
    server = DataStreamingServer(
        settings, app=app,
        source_factory=lambda w, h, fps, **kw: FakeSource(w, h, fps),
        host="127.0.0.1",
    )
    app.data_server = server
    srv, port = await start_on_free_port(server)
    try:
        for encoder, expect_type in (("x264enc-striped", 0x04),
                                     ("x264enc", 0x00)):
            async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
                await handshake(ws)
                await ws.send("SETTINGS," + json.dumps({
                    "initialClientWidth": 64, "initialClientHeight": 64,
                    "encoder": encoder, "framerate": 20}))
                got = None
                for _ in range(300):
                    msg = await asyncio.wait_for(ws.recv(), 10)
                    if isinstance(msg, bytes) and msg and \
                            msg[0] == expect_type:
                        got = msg
                        break
                assert got is not None, f"no 0x{expect_type:02x} frames"
                if expect_type == 0x04:
                    from selkies_tpu.protocol import unpack_binary
                    f = unpack_binary(got)
                    assert f.payload.startswith(b"\x00\x00\x00\x01")
                    assert f.width and f.height
    finally:
        srv.close()
        await server.stop()


@pytest.mark.anyio
async def test_viewer_join_forces_keyframe(tmp_path):
    """A second (sharing) client connecting must kick a full refresh on the
    primary stream — damage gating would otherwise leave it black."""
    server, app, encoders = make_server(tmp_path)
    srv, port = await start_on_free_port(server)
    kicked = []
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as host_ws:
            await handshake(host_ws)
            await host_ws.send("SETTINGS," + json.dumps({"framerate": 30}))
            for _ in range(100):
                if encoders:
                    break
                await asyncio.sleep(0.02)
            assert encoders
            encoders[0].force_keyframe = lambda: kicked.append(True)
            async with websockets.connect(
                    f"ws://127.0.0.1:{port}") as viewer_ws:
                await handshake(viewer_ws)   # viewer never sends SETTINGS
                await asyncio.sleep(0.1)
            assert kicked, "viewer join did not force a keyframe"
    finally:
        srv.close()
        await server.stop()


@pytest.mark.anyio
async def test_mesh_batched_sessions_serve_wire_stripes(tmp_path):
    """BASELINE config 5 as a product path: with tpu_mesh configured, two
    displays' capture loops feed ONE sharded mesh dispatch (CPU mesh here)
    and both websockets receive wire-ready 0x03 JPEG stripes."""
    import io
    from PIL import Image

    server, app, encoders = make_server(
        tmp_path,
        SELKIES_TPU_MESH="session:2,stripe:2",
        SELKIES_TPU_SESSIONS_PER_CHIP="1",
    )
    srv, port = await start_on_free_port(server)

    async def collect_stripes(ws, want):
        got = []
        while len(got) < want:
            m = await asyncio.wait_for(ws.recv(), 30)
            if isinstance(m, bytes):
                f = unpack_binary(m)
                if isinstance(f, VideoStripe):
                    got.append(f)
        return got

    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws1, \
                websockets.connect(f"ws://127.0.0.1:{port}") as ws2:
            await handshake(ws1)
            await handshake(ws2)
            await ws1.send('SETTINGS,' + json.dumps({
                "displayId": "primary",
                "initialClientWidth": 320, "initialClientHeight": 240}))
            await ws2.send('SETTINGS,' + json.dumps({
                "displayId": "display2",
                "initialClientWidth": 320, "initialClientHeight": 240}))

            # primary fans out to all clients; display2 only to its owner —
            # ws2 must see both streams' stripes, ws1 the primary's
            s1 = await collect_stripes(ws1, 2)
            s2 = await collect_stripes(ws2, 2)

            # both displays ride the mesh coordinator, not solo encoders
            assert server.mesh_coordinator is not None
            assert len(server.mesh_coordinator._attached) == 2
            assert encoders == []   # solo factory never invoked

        for f in s1 + s2:
            assert f.payload.startswith(b"\xff\xd8")
            assert f.payload.endswith(b"\xff\xd9")
            img = Image.open(io.BytesIO(f.payload))
            assert img.size[0] == 320
    finally:
        await server.stop()
        srv.close()
        assert server.mesh_coordinator is None or \
            not server.mesh_coordinator._thread


@pytest.mark.anyio
async def test_mesh_stripe_axis_single_session_config4(tmp_path):
    """BASELINE config 4 as a product path: ONE display whose stripes
    shard across the mesh's "stripe" axis (single-session shape, no
    session batching) — the 4K-on-v5e-4 layout, scaled down to the CPU
    test mesh. The display must ride the mesh coordinator, and the wire
    stripes must decode."""
    import io
    from PIL import Image

    server, app, encoders = make_server(
        tmp_path,
        SELKIES_TPU_MESH="stripe:4",
        SELKIES_TPU_SESSIONS_PER_CHIP="1",
    )
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,' + json.dumps({
                "displayId": "primary",
                # 512 rows = 8 stripes of 64: divisible across stripe:4
                "initialClientWidth": 320, "initialClientHeight": 512}))
            got = []
            while len(got) < 3:
                m = await asyncio.wait_for(ws.recv(), 30)
                if isinstance(m, bytes):
                    f = unpack_binary(m)
                    if isinstance(f, VideoStripe):
                        got.append(f)
            assert server.mesh_coordinator is not None
            assert server.mesh_coordinator.n_sessions == 1
            assert len(server.mesh_coordinator._attached) == 1
            assert encoders == []      # solo factory never invoked
        for f in got:
            assert f.payload.startswith(b"\xff\xd8")
            img = Image.open(io.BytesIO(f.payload))
            assert img.size[0] == 320
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_mesh_geometry_buckets(tmp_path):
    """A join at a different resolution gets its own mesh bucket instead
    of silently falling back to a solo encoder; the
    fallback/bucket counters ride the stats feed."""
    server, app, encoders = make_server(
        tmp_path,
        SELKIES_TPU_MESH="session:2",
        SELKIES_TPU_SESSIONS_PER_CHIP="1",
    )
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws1, \
                websockets.connect(f"ws://127.0.0.1:{port}") as ws2:
            await handshake(ws1)
            await handshake(ws2)
            await ws1.send('SETTINGS,' + json.dumps({
                "displayId": "primary",
                "initialClientWidth": 320, "initialClientHeight": 240}))
            await ws2.send('SETTINGS,' + json.dumps({
                "displayId": "display2",
                "initialClientWidth": 256, "initialClientHeight": 128}))

            async def first_stripe(ws):
                while True:
                    m = await asyncio.wait_for(ws.recv(), 30)
                    if isinstance(m, bytes):
                        f = unpack_binary(m)
                        if isinstance(f, VideoStripe):
                            return f
            await first_stripe(ws1)
            await first_stripe(ws2)
            assert len(server.mesh_coordinators) == 2   # two buckets
            assert server.mesh_stats["bucketed"] == 2
            assert server.mesh_stats["solo_fallback"] == 0
            assert encoders == []                        # no solo encoder
    finally:
        await server.stop()
        srv.close()


@pytest.mark.anyio
async def test_mesh_h264_display_serves_wire_stripes(tmp_path):
    """An H.264 display rides the tpu_mesh coordinator
    — the wire carries 0x04 striped Annex-B that the conformance oracle
    decodes, with no solo-encoder fallback."""
    from selkies_tpu.encoder import conformance

    server, app, encoders = make_server(
        tmp_path,
        SELKIES_TPU_MESH="session:2,stripe:2",
        SELKIES_TPU_SESSIONS_PER_CHIP="1",
        SELKIES_ENCODER="x264enc-striped",
    )
    srv, port = await start_on_free_port(server)
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await handshake(ws)
            await ws.send('SETTINGS,' + json.dumps({
                "displayId": "primary",
                "initialClientWidth": 320, "initialClientHeight": 256}))
            got = []
            # the first stripe sits behind a cold CPU compile of the mesh
            # program: ~60 s alone, several times that when six workers
            # compile at once; later stripes keep the short wait
            wait = 420
            while len(got) < 4:
                m = await asyncio.wait_for(ws.recv(), wait)
                if isinstance(m, bytes):
                    f = unpack_binary(m)
                    if isinstance(f, VideoStripe):
                        got.append((m[0], f))
                        wait = 60
            assert server.mesh_coordinator is not None
            assert server.mesh_coordinator.profile == "x264enc-striped"
            assert len(server.mesh_coordinator._attached) == 1
            assert encoders == []          # solo factory never invoked
    finally:
        await server.stop()
        srv.close()

    for prefix_byte, f in got:
        assert prefix_byte == 0x04        # striped H.264, not JPEG
        assert f.payload.startswith(b"\x00\x00\x00\x01")
    # first stripe sequence decodes in the libavcodec oracle
    if conformance.ConformanceDecoder is not None:
        try:
            dec = conformance.ConformanceDecoder("h264", max_dim=512)
        except RuntimeError:
            return
        y0 = got[0][1].y_start
        n_dec = 0
        for _, f in got:
            if f.y_start != y0:
                continue
            out = dec.decode(f.payload)
            if out is not None:
                n_dec += 1
        n_dec += len(dec.flush())
        dec.close()
        assert n_dec >= 1
