"""The REFERENCE web client as the compatibility oracle.

SURVEY §7 step 1 kept the wire grammar byte-identical with the
reference precisely so its client could certify this server. This test
executes the reference's real selkies-core.js (4.2k LoC, unmodified
except its two ES-module imports) under tools/minijs, bridges its
WebSocket to a live DataStreamingServer with the real JPEG encode
pipeline, and asserts the whole contract at once:

  * the reference client accepts our MODE/server_settings handshake
    and emits its SETTINGS payload, which our server parses;
  * our binary 0x03 stripes reach its ImageDecoder with decodable
    JPEG bytes at the right stripe offsets;
  * its CLIENT_FRAME_ACK heartbeat drives our backpressure state.

One green run certifies the entire wire surface against the client a
reference user actually runs. Reference: selkies-core.js:2720-2990.
"""

import asyncio
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from reference_env import (REFERENCE_CORE, fire_dom_ready,  # noqa: E402
                           make_reference_env)

pytestmark = pytest.mark.skipif(
    not os.path.isfile(REFERENCE_CORE),
    reason="reference checkout not mounted")


@pytest.mark.anyio
async def test_reference_client_negotiates_decodes_and_acks(tmp_path):
    import websockets
    import websockets.asyncio.server as ws_server

    from selkies_tpu.capture.synthetic import SyntheticSource
    from selkies_tpu.server.app import StreamingApp
    from selkies_tpu.server.data_server import (DataStreamingServer,
                                                default_encoder_factory)
    from selkies_tpu.settings import Settings

    # Single-value enum override = the reference's documented "lock the
    # choice" semantics (reference settings.py:29-31): the schema's allowed
    # list becomes ["jpeg"], so the client's sanitize pass switches its
    # stored x264enc default to jpeg and tells the server — the flow a
    # jpeg-only deployment exercises.
    settings = Settings(argv=[], env={"SELKIES_PORT": "0",
                                      "SELKIES_ENCODER": "jpeg"})
    app = StreamingApp(settings)
    server = DataStreamingServer(
        settings, app=app,
        source_factory=lambda w, h, fps, x=0, y=0: SyntheticSource(
            w, h, fps, pattern="scroll"),
        encoder_factory=default_encoder_factory,
        host="127.0.0.1")
    app.data_server = server
    server._stop_event = asyncio.Event()
    srv = await ws_server.serve(server.ws_handler, "127.0.0.1", 0,
                                compression=None, max_size=None)
    port = srv.sockets[0].getsockname()[1]

    # the reference client boots at DOMContentLoaded and opens its
    # socket; bridge that fake socket to the real server
    env = make_reference_env()
    fire_dom_ready(env)
    assert env.sockets, "reference client opened no websocket"
    fake_ws = env.sockets[0]
    assert fake_ws.url.endswith("/websockets")

    real_ws = await websockets.connect(
        f"ws://127.0.0.1:{port}/websockets", max_size=None)
    fake_ws.server_open()
    sent_idx = 0
    text_log = []

    async def pump():
        nonlocal sent_idx
        while True:
            while sent_idx < len(fake_ws.sent):
                m = fake_ws.sent[sent_idx]
                sent_idx += 1
                if isinstance(m, str):
                    text_log.append(m)
                await real_ws.send(m)
            env.interp.fire_timers(1)      # ACK heartbeat interval
            await asyncio.sleep(0.01)

    pump_task = asyncio.create_task(pump())

    async def feed():
        async for msg in real_ws:
            if isinstance(msg, bytes):
                fake_ws.server_binary(msg)
            else:
                fake_ws.server_text(msg)

    feed_task = asyncio.create_task(feed())

    def check_bridge():
        # a minijs gap inside a handler must fail the test loudly, not
        # decay into a timeout
        for t in (pump_task, feed_task):
            if t.done() and not t.cancelled() and t.exception():
                raise t.exception()

    try:
        # 1. the reference client's SETTINGS handshake parsed server-side
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            check_bridge()
            if server.display_clients:
                break
            await asyncio.sleep(0.05)
        assert server.display_clients, \
            f"server never registered the client; sent={text_log[:3]}"
        settings_msgs = [m for m in text_log if m.startswith("SETTINGS,")]
        assert settings_msgs, text_log[:5]
        payload = json.loads(settings_msgs[0].split(",", 1)[1])
        assert "initialClientWidth" in payload
        # the locked enum actually drove the client off its x264enc
        # default: its sanitize pass reported the switch
        assert any('"encoder": "jpeg"' in m or "'encoder': 'jpeg'" in m
                   or '"encoder":"jpeg"' in m for m in text_log), \
            "client never adopted the server-locked jpeg encoder"

        # 2. our 0x03 stripes reach its ImageDecoder as decodable JPEG
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            check_bridge()
            if len(env.image_decoders) >= 6:
                break
            await asyncio.sleep(0.05)
        assert len(env.image_decoders) >= 6, \
            "reference client decoded no JPEG stripes"
        import io
        from PIL import Image
        for dec in env.image_decoders[:6]:
            assert dec.type == "image/jpeg"
            img = Image.open(io.BytesIO(dec.data))
            img.load()                    # PIL = independent decode proof

        # 3. its CLIENT_FRAME_ACK heartbeat reached our backpressure gate
        deadline = time.monotonic() + 30
        acked = 0
        while time.monotonic() < deadline:
            check_bridge()
            st = next(iter(server.display_clients.values()))
            acked = st.bp.acknowledged_frame_id
            if acked > 0:
                break
            await asyncio.sleep(0.05)
        assert acked > 0, "no CLIENT_FRAME_ACK processed by the server"
        assert any(m.startswith("CLIENT_FRAME_ACK") for m in text_log)
    finally:
        pump_task.cancel()
        feed_task.cancel()
        await real_ws.close()
        await server.stop()
        srv.close()
