"""Execute the real browser WebRTC peer (web/webrtc.js) in CI.

the from-scratch WebRTC stack had no
browser-side consumer. These tests run the actual shipped webrtc.js
under tools/minijs with RTCPeerConnection/fetch stubs and drive the
full signaling → SDP answer → ICE → data-channel input flow — the same
certification style test_web_client_exec.py gives the WebSocket client.

Reference counterpart: addons/gst-web/src/webrtc.js:42-790 +
signaling.js:36-320.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from web_stubs import BrowserEnv, install_webrtc_stubs  # noqa: E402
from tools.minijs import (  # noqa: E402
    NativeFunction, UNDEF, JSObject, to_str)


@pytest.fixture(scope="module")
def client_env():
    env = BrowserEnv(files=())
    install_webrtc_stubs(env)
    env.load("webrtc.js")
    env.load("input.js")
    return env


@pytest.fixture()
def env(client_env):
    client_env.sockets.clear()
    client_env.peer_connections.clear()
    client_env.fetch_calls.clear()
    client_env.interp.timer_map.clear()
    client_env.document.listeners.clear()
    return client_env


def make_client(env, **extra):
    video = env.document.createElement("video")
    statuses = []
    clips = []
    props = {
        "signalingUrl": "ws://testhost:8080/ws",
        "video": video,
        "onStatus": NativeFunction(
            lambda t, a, i: (statuses.append(to_str(a[0])), UNDEF)[1]),
        "onClipboard": NativeFunction(
            lambda t, a, i: (clips.append(to_str(a[0])), UNDEF)[1]),
    }
    props.update(extra)
    client = env.construct(env.exports["SelkiesWebRTCClient"],
                           [JSObject(props)])
    env.call(env.get(client, "connect"), [], this=client)
    ws = env.sockets[-1]
    ws.server_open()
    return client, ws, video, statuses, clips


def offer_json():
    return json.dumps({"sdp": {"type": "offer",
                               "sdp": "v=0\r\ns=fake-offer\r\n"}})


def test_hello_registration_and_turn_fetch(env):
    """connect() fetches /turn for the RTC config and registers as the
    numbered peer with base64 metadata (signaling.py HELLO grammar)."""
    client, ws, video, statuses, _ = make_client(env)
    assert any(u.endswith("/turn") for u in env.fetch_calls)
    assert len(ws.sent) == 1
    toks = ws.sent[0].split()
    assert toks[0] == "HELLO" and toks[1] == "1"
    import base64
    meta = json.loads(base64.b64decode(toks[2]))
    assert "res" in meta and "scale" in meta
    ws.server_text("HELLO")
    assert statuses[-1] == "registered"


def test_offer_produces_answer_with_negotiated_pc(env):
    client, ws, video, statuses, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    assert len(env.peer_connections) == 1
    pc = env.peer_connections[0]
    assert to_str(env.get(pc.remoteDescription, "type")) == "offer"
    # the answer went back over signaling as {"sdp": {...}}
    answers = [m for m in ws.sent[1:] if "answer" in m]
    assert answers, ws.sent
    data = json.loads(answers[0])
    assert data["sdp"]["type"] == "answer"
    assert statuses[-1] == "negotiated"
    # the fetched TURN config reached the RTCPeerConnection ctor
    ice = env.get(pc.config, "iceServers")
    assert ice is not UNDEF


def test_ice_trickles_both_ways(env):
    client, ws, video, _, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    # remote ICE → addIceCandidate
    ws.server_text(json.dumps(
        {"ice": {"candidate": "candidate:1 1 udp 1 10.0.0.1 4000 typ host",
                 "sdpMLineIndex": 0}}))
    assert len(pc.added_ice) == 1
    # local ICE → signaling {"ice": ...}
    pc.fire_local_ice("candidate:9 1 udp 1 10.0.0.2 4001 typ host")
    sent_ice = [m for m in ws.sent if '"ice"' in m]
    assert sent_ice
    assert "10.0.0.2" in json.loads(sent_ice[-1])["ice"]["candidate"]


def test_track_attaches_to_video(env):
    client, ws, video, _, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    stream = JSObject({"id": "remote-stream"})
    pc.server_track(stream)
    assert env.get(video, "srcObject") is stream


def test_input_channel_queues_until_open_then_flows(env):
    client, ws, video, statuses, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    # input sent before the channel opens is queued, not lost
    env.call(env.get(client, "send"), ["kd,65"], this=client)
    ch = pc.server_datachannel("input")
    assert ch.sent == []
    ch.server_open()
    assert ch.sent == ["kd,65"]
    assert statuses[-1] == "input-ready"
    env.call(env.get(client, "send"), ["ku,65"], this=client)
    assert ch.sent == ["kd,65", "ku,65"]


def test_selkies_input_drives_the_data_channel(env):
    """The full input plane (web/input.js) plugs into the WebRTC client
    unchanged — keydown on the video element reaches the data channel
    as the same wire verb WebSocket mode uses."""
    client, ws, video, _, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    ch = pc.server_datachannel("input")
    ch.server_open()
    inp = env.construct(env.exports["SelkiesInput"], [client, video])
    env.call(env.get(inp, "attach"), [], this=inp)
    env.fire(env.window, "keydown", env.make_event(
        "keydown", key="a", code="KeyA", target=video))
    assert any(m.startswith("kd,97") for m in ch.sent), ch.sent


def test_clipboard_control_object_from_server(env):
    import base64
    client, ws, video, _, clips = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    ch = pc.server_datachannel("input")
    ch.server_open()
    payload = base64.b64encode("héllo".encode()).decode()
    ch.server_message(json.dumps({"type": "clipboard", "data": payload}))
    assert clips == ["héllo"]


def test_connection_state_reaches_status(env):
    client, ws, video, statuses, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    pc.set_connection_state("connected")
    assert statuses[-1] == "connected"
    pc.set_connection_state("failed")
    assert statuses[-1] == "disconnected"


def test_already_open_channel_flushes_queue(env):
    """A remotely-announced channel can arrive with readyState already
    'open' (spec browsers fire no open event on the receiving side) —
    queued input must flush immediately (code-review r3)."""
    client, ws, video, statuses, _ = make_client(env)
    ws.server_text("HELLO")
    ws.server_text(offer_json())
    pc = env.peer_connections[0]
    env.call(env.get(client, "send"), ["kd,65"], this=client)
    from web_stubs import FakeRTCDataChannel
    ch = FakeRTCDataChannel(env, "input")
    ch.readyState = "open"               # arrives pre-opened
    if pc.ondatachannel not in (None,):
        env.call(pc.ondatachannel, [JSObject({"channel": ch})])
    assert ch.sent == ["kd,65"]
    assert statuses[-1] == "input-ready"
