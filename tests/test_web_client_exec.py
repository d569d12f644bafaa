"""EXECUTED web-client tests: the real web/*.js running under the
tools/minijs interpreter against browser stubs (tests/web_stubs.py).

This supersedes the regex contract checks in test_web_client.py for
logic coverage: demux, ACK
wraparound, decoder pools, input mapping, IME fallback, trackpad
scrolling, and the schema-driven dashboard all run for real here.
"""

import os
import struct
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from web_stubs import BrowserEnv, FakeWebSocket  # noqa: E402
from tools.minijs import (  # noqa: E402
    UNDEF, JSArray, JSObject, to_num, to_str)


# ------------------------------------------------------------ fixtures


def make_client(env, **opt_props):
    canvas = env.document.createElement("canvas")
    canvas.width, canvas.height = 1920.0, 1080.0
    props = {"canvas": canvas, "url": "ws://test/websockets"}
    props.update(opt_props)
    client = env.construct(env.exports["SelkiesClient"], [JSObject(props)])
    env.call(env.get(client, "connect"), [])
    ws = env.sockets[-1]
    ws.server_open()
    return client, ws, canvas


def jpeg_stripe(frame_id, y_start, payload=b"\xff\xd8flat\xff\xd9"):
    return bytes([3, 0]) + struct.pack(">HH", frame_id, y_start) + payload


@pytest.fixture(scope="module")
def client_env():
    return BrowserEnv(files=("selkies-client.js",))


@pytest.fixture()
def env(client_env):
    # fresh per-test state on a shared parsed environment
    client_env.sockets.clear()
    client_env.video_decoders.clear()
    client_env.audio_decoders.clear()
    client_env.bitmaps.clear()
    client_env.interp.timer_map.clear()
    client_env.document.listeners.clear()
    client_env.wake_locks.clear()
    return client_env


# ----------------------------------------------------------- handshake


def test_settings_handshake_and_server_push(env):
    client, ws, canvas = make_client(env)
    texts = ws.texts()
    assert texts and texts[0].startswith("SETTINGS,")
    assert '"encoder": "jpeg"' in texts[0]

    pushed = []
    obj = JSObject({})
    env.interp.globals.declare("__push", env.interp.py_to_js(None))

    # capture server_settings callback
    def on_settings(this, args, interp):
        pushed.append(args[0])
        return UNDEF
    from tools.minijs import NativeFunction
    env.interp.set_prop(client, "onServerSettings",
                        NativeFunction(on_settings))
    ws.server_text('{"type": "server_settings", '
                   '"settings": {"framerate": {"value": 60}}}')
    assert pushed and isinstance(pushed[0], JSObject)
    assert "framerate" in pushed[0].props


def test_viewer_mode_does_not_claim_display(env):
    client, ws, canvas = make_client(env, claimDisplay=False)
    assert not any(t.startswith("SETTINGS,") for t in ws.texts())


# --------------------------------------------------------------- demux


def test_jpeg_stripe_decodes_and_paints_at_y(env):
    client, ws, canvas = make_client(env)
    ws.server_binary(jpeg_stripe(7, 128))
    env.interp.run_microtasks()
    ctx = canvas.getContext("2d")
    assert ctx.draw_calls[-1][1:] == (0.0, 128.0)
    assert to_num(env.get(client, "lastFrameId")) == 7.0
    assert env.bitmaps[-1].closed          # bitmap released after paint


def test_stale_stripe_not_painted_over_newer(env):
    client, ws, canvas = make_client(env)
    ctx = canvas.getContext("2d")
    n0 = len(ctx.draw_calls)
    ws.server_binary(jpeg_stripe(100, 64))
    env.interp.run_microtasks()
    ws.server_binary(jpeg_stripe(99, 64))   # older frame for the same band
    env.interp.run_microtasks()
    assert len(ctx.draw_calls) == n0 + 1    # second stripe dropped
    ws.server_binary(jpeg_stripe(101, 64))
    env.interp.run_microtasks()
    assert len(ctx.draw_calls) == n0 + 2


def test_ack_only_advances_forward_with_wraparound(env):
    client, ws, canvas = make_client(env)
    ws.server_binary(jpeg_stripe(0xFFFE, 0))
    env.interp.run_microtasks()
    assert to_num(env.get(client, "lastFrameId")) == float(0xFFFE)
    # wraparound: 3 is "newer" than 0xFFFE mod 2^16
    ws.server_binary(jpeg_stripe(3, 64))
    env.interp.run_microtasks()
    assert to_num(env.get(client, "lastFrameId")) == 3.0
    # stale late stripe on another band must NOT regress the ACK id
    ws.server_binary(jpeg_stripe(0xFFFF, 128))
    env.interp.run_microtasks()
    assert to_num(env.get(client, "lastFrameId")) == 3.0
    # the ACK timer ships the held id
    env.interp.fire_timers(1)
    assert "CLIENT_FRAME_ACK 3" in ws.texts()


def test_full_frame_h264_waits_for_keyframe(env):
    client, ws, canvas = make_client(env)
    delta = bytes([0, 0]) + struct.pack(">H", 5) + b"\x00\x00\x00\x01\x41dd"
    ws.server_binary(delta)
    assert not env.video_decoders          # no decoder until a keyframe
    key = bytes([0, 1]) + struct.pack(">H", 6) + b"\x00\x00\x00\x01\x67kk"
    ws.server_binary(key)
    assert env.video_decoders
    dec = env.video_decoders[-1]
    assert [c.type for c in dec.chunks] == ["key"]
    assert dec.chunks[0].data == b"\x00\x00\x00\x01\x67kk"
    ws.server_binary(bytes([0, 0]) + struct.pack(">H", 7) + b"dd2")
    assert [c.type for c in dec.chunks] == ["key", "delta"]
    # decode error → decoders reset, next delta ignored until key
    dec.fail_next = True
    ws.server_binary(bytes([0, 0]) + struct.pack(">H", 8) + b"dd3")
    assert dec.state == "closed"
    assert env.get(client, "videoDecoder") is not dec


def test_striped_h264_per_stripe_decoder_pool(env):
    client, ws, canvas = make_client(env)

    def stripe(fid, y, key, payload):
        return bytes([4, 1 if key else 0]) + struct.pack(
            ">HH", fid, y) + b"\x00" * 4 + payload

    ws.server_binary(stripe(1, 0, True, b"s0"))
    ws.server_binary(stripe(1, 64, True, b"s1"))
    decs = env.get(client, "stripeDecoders")
    assert len(decs) == 2                  # one decoder per band
    # delta for an unknown band is ignored (no decoder without a key)
    ws.server_binary(stripe(2, 128, False, b"s2"))
    assert len(decs) == 2
    # decode error evicts that band's decoder only
    band0 = decs[0.0].props["dec"]
    band0.fail_next = True
    ws.server_binary(stripe(3, 0, False, b"s3"))
    assert len(decs) == 1


def test_audio_chunks_reach_worklet_ring(env):
    client, ws, canvas = make_client(env)
    ws.server_binary(bytes([1, 0]) + b"OPUSDATA")
    env.interp.run_microtasks()
    assert env.audio_decoders, "AudioDecoder never constructed"
    assert env.audio_decoders[-1].chunks[-1].data == b"OPUSDATA"
    assert env.worklet_nodes, "AudioWorklet ring not built"
    msg = env.worklet_nodes[-1].port.messages[-1]
    ch0 = msg.props["ch0"]
    assert ch0.length == 960               # one 20 ms frame landed


def test_pipeline_reset_clears_ack_and_decoders(env):
    client, ws, canvas = make_client(env)
    ws.server_binary(jpeg_stripe(50, 0))
    env.interp.run_microtasks()
    key = bytes([0, 1]) + struct.pack(">H", 51) + b"kf"
    ws.server_binary(key)
    dec = env.video_decoders[-1]
    ws.server_text("PIPELINE_RESETTING")
    assert to_num(env.get(client, "lastFrameId")) == -1.0
    assert dec.state == "closed"


def test_kill_supersedes_session(env):
    client, ws, canvas = make_client(env)
    statuses = []
    from tools.minijs import NativeFunction
    env.interp.set_prop(client, "onStatus", NativeFunction(
        lambda t, a, i: (statuses.append(to_str(a[0])), UNDEF)[1]))
    ws.server_text("KILL")
    assert "superseded" in statuses
    assert ws.readyState == FakeWebSocket.CLOSED


def test_clipboard_roundtrip_utf8(env):
    client, ws, canvas = make_client(env)
    got = []
    from tools.minijs import NativeFunction
    env.interp.set_prop(client, "onClipboard", NativeFunction(
        lambda t, a, i: (got.append(to_str(a[0])), UNDEF)[1]))
    import base64
    text = "héllo → wörld"
    ws.server_text("clipboard," +
                   base64.b64encode(text.encode("utf-8")).decode())
    assert got == [text]
    env.call(env.get(client, "sendClipboard"), [text])
    sent = [t for t in ws.texts() if t.startswith("cw,")][-1]
    assert base64.b64decode(sent[3:]).decode("utf-8") == text


def test_stream_resolution_resizes_canvas(env):
    client, ws, canvas = make_client(env)
    ws.server_text('{"type": "stream_resolution", '
                   '"width": 2560, "height": 1440}')
    assert canvas.width == 2560.0 and canvas.height == 1440.0


def test_stats_report_fps_accounting(env):
    client, ws, canvas = make_client(env)
    stats = []
    from tools.minijs import NativeFunction
    env.interp.set_prop(client, "onStats", NativeFunction(
        lambda t, a, i: (stats.append(a[0]), UNDEF)[1]))
    for fid in range(3):
        ws.server_binary(jpeg_stripe(fid, 0))
        env.interp.run_microtasks()
    env.interp.now_ms += 1000.0
    env.call(env.get(client, "_reportStats"), [], this=client)
    assert stats and to_str(stats[-1].props["type"]) == "client_stats"
    assert abs(to_num(stats[-1].props["fps"]) - 3.0) < 0.2
    assert any(t.startswith("_f ") for t in ws.texts())


# ----------------------------------------------------------- input.js


@pytest.fixture(scope="module")
def input_env():
    return BrowserEnv(files=("input.js",))


def make_input(ienv):
    from tools.minijs import NativeFunction
    sent = []
    client = JSObject({"send": NativeFunction(
        lambda t, a, i: (sent.append(to_str(a[0])), UNDEF)[1], "send")})
    el = ienv.document.createElement("canvas")
    el.width, el.height = 1920.0, 1080.0
    inp = ienv.construct(ienv.exports["SelkiesInput"], [client, el])
    ienv.call(ienv.get(inp, "attach"), [])
    return inp, el, sent


def key_ev(ienv, key, code="", **kw):
    return ienv.make_event("keydown", key=key, code=code,
                           keyCode=kw.pop("keyCode", 0), **kw)


def test_eventkeysym_mapping(input_env):
    ienv = input_env
    ks = ienv.exports["eventKeysym"]
    assert to_num(ienv.call(ks, [key_ev(ienv, "a")])) == 97.0
    assert to_num(ienv.call(ks, [key_ev(ienv, "é")])) == 233.0  # latin-1
    # X11 unicode rule above latin-1
    assert to_num(ienv.call(ks, [key_ev(ienv, "あ")])) == 0x01000000 + 0x3042
    assert to_num(ienv.call(ks, [key_ev(ienv, "Enter")])) == 0xFF0D
    # ev.code beats ev.key for keypad distinction
    assert to_num(ienv.call(ks, [key_ev(ienv, "7", "Numpad7")])) == 0xFFB7
    assert ienv.call(ks, [key_ev(ienv, "SomeUnknownKey")]) is None


def test_keydown_sends_keysym_and_window_blur_releases(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    for fn in ienv.window.listeners["keydown"]:
        ienv.call(fn, [key_ev(ienv, "a")])
    assert sent[-1] == "kd,97"
    for fn in ienv.window.listeners["keyup"]:
        ienv.call(fn, [ienv.make_event("keyup", key="a", code="",
                                       keyCode=0)])
    assert sent[-1] == "ku,97"
    for fn in ienv.window.listeners["blur"]:
        ienv.call(fn, [ienv.make_event("blur")])
    assert sent[-1] == "kr"


def test_composition_end_sends_atomic_text(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    proxy = ienv.get(inp, "_imeProxy")
    ienv.fire(proxy, "compositionstart", ienv.make_event(
        "compositionstart"))
    # keydown during composition must NOT emit keysyms
    n0 = len(sent)
    for fn in ienv.window.listeners["keydown"]:
        ienv.call(fn, [ienv.make_event("keydown", key="Process",
                                       keyCode=229, isComposing=True)])
    assert len(sent) == n0
    ienv.fire(proxy, "compositionend", ienv.make_event(
        "compositionend", data="日本語"))
    assert sent[-1] == "co,end,日本語"


def test_osk_char_after_enter_not_swallowed(input_env):
    """Regression: a preventDefault'ed Enter used to latch _sentKey and
    swallow the next on-screen-keyboard character."""
    ienv = input_env
    inp, el, sent = make_input(ienv)
    proxy = ienv.get(inp, "_imeProxy")
    # OSK Enter: a real key event, handled
    for fn in ienv.window.listeners["keydown"]:
        ienv.call(fn, [key_ev(ienv, "Enter")])
    assert sent[-1] == "kd,65293"
    # OSK 'a': keydown is Unidentified (ignored), text arrives via input
    for fn in ienv.window.listeners["keydown"]:
        ienv.call(fn, [key_ev(ienv, "Unidentified")])
    ienv.fire(proxy, "input", ienv.make_event(
        "input", inputType="insertText", data="a"))
    assert sent[-1] == "co,end,a", "first OSK char after Enter swallowed"


def test_mouse_move_and_buttons(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    ienv.fire(el, "mousedown", ienv.make_event(
        "mousedown", button=0.0, clientX=10.0, clientY=20.0))
    assert sent[-1].startswith("m,") and ",1,0" in sent[-1]
    ienv.fire(el, "mouseup", ienv.make_event(
        "mouseup", button=0.0, clientX=10.0, clientY=20.0))
    assert ",0,0" in sent[-1]


def test_wheel_scroll_bits(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    ienv.fire(el, "wheel", ienv.make_event(
        "wheel", deltaY=-120.0, clientX=0.0, clientY=0.0))
    assert ",8," in sent[-1]     # scroll-up bit
    ienv.fire(el, "wheel", ienv.make_event(
        "wheel", deltaY=120.0, clientX=0.0, clientY=0.0))
    assert ",16," in sent[-1]    # scroll-down bit


def touch_ev(ienv, type_, touches, changed=None):
    mk = lambda pts: JSArray([JSObject({
        "clientX": float(x), "clientY": float(y)}) for x, y in pts])
    return ienv.make_event(type_, touches=mk(touches),
                           changedTouches=mk(changed or touches))


def test_trackpad_two_finger_scroll_sends_press_release_pairs(input_env):
    """Regression: a held scroll bit latched server-side after one notch;
    each notch must be a press/release pair."""
    ienv = input_env
    inp, el, sent = make_input(ienv)
    ienv.call(ienv.get(inp, "toggleTrackpadMode"), [], this=inp)
    ienv.fire(el, "touchstart", touch_ev(
        ienv, "touchstart", [(100, 100), (120, 100)]))
    n0 = len(sent)
    ienv.fire(el, "touchmove", touch_ev(
        ienv, "touchmove", [(100, 145), (120, 145)]))   # 45px → 2 notches
    new = sent[n0:]
    assert new == ["m2,0,0,8,1", "m2,0,0,0,0",
                   "m2,0,0,8,1", "m2,0,0,0,0"]
    ienv.call(ienv.get(inp, "toggleTrackpadMode"), [], this=inp)


def test_trackpad_tap_clicks_and_two_finger_tap_right_clicks(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    ienv.call(ienv.get(inp, "toggleTrackpadMode"), [], this=inp)
    # single tap
    ienv.fire(el, "touchstart", touch_ev(ienv, "touchstart", [(50, 50)]))
    ienv.fire(el, "touchend", touch_ev(ienv, "touchend", [], [(50, 50)]))
    assert sent[-2:] == ["m2,0,0,1,0", "m2,0,0,0,0"]
    # two-finger tap → right click
    ienv.fire(el, "touchstart", touch_ev(
        ienv, "touchstart", [(50, 50), (70, 50)]))
    ienv.fire(el, "touchend", touch_ev(ienv, "touchend", [], [(50, 50)]))
    assert sent[-2:] == ["m2,0,0,4,0", "m2,0,0,0,0"]
    ienv.call(ienv.get(inp, "toggleTrackpadMode"), [], this=inp)


def test_gamepad_connect_and_poll(input_env):
    ienv = input_env
    inp, el, sent = make_input(ienv)
    from tools.minijs import NativeFunction
    pad = JSObject({
        "index": 0.0, "id": "X360 pad",
        "axes": JSArray([0.0, 0.0]),
        "buttons": JSArray([JSObject({"value": 0.0}),
                            JSObject({"value": 0.0})]),
    })
    ienv.gamepads = JSArray([pad])
    for fn in ienv.window.listeners["gamepadconnected"]:
        ienv.call(fn, [JSObject({"gamepad": pad})])
    assert any(t.startswith("js,c,0,") and t.endswith(",2,2")
               for t in sent)
    # press a button and move an axis, then poll
    pad.props["buttons"].elems[1].props["value"] = 1.0
    pad.props["axes"].elems[0] = 0.5
    ienv.call(ienv.get(inp, "_pollGamepads"), [], this=inp)
    assert "js,b,0,1,1.000" in sent
    assert "js,a,0,0,0.500" in sent


# -------------------------------------------------------- dashboard.js


@pytest.fixture(scope="module")
def dash_env():
    return BrowserEnv(files=("selkies-client.js", "input.js",
                             "touch-gamepad.js", "dashboard.js"))


SCHEMA = ('{"type": "server_settings", "settings": {'
          '"encoder": {"value": "jpeg", "allowed": ["jpeg", "x264enc"]},'
          '"framerate": {"value": 60, "min": 8, "max": 120},'
          '"jpeg_quality": {"value": 40, "min": 1, "max": 100},'
          '"audio_enabled": {"value": true},'
          '"use_cpu": {"value": false, "locked": true},'
          '"ui_title": {"value": "My Desk"},'
          '"file_transfers": {"value": ["upload", "download"]},'
          '"clipboard_enabled": {"value": true},'
          '"gamepad_enabled": {"value": true},'
          '"custom_knob": {"value": 3, "min": 0, "max": 9}'
          "}}")


def make_dashboard(denv, mode="full"):
    denv.sockets.clear()
    denv.local_storage.clear()
    root = denv.document.createElement("div")
    canvas = denv.document.createElement("canvas")
    canvas.width, canvas.height = 1920.0, 1080.0
    dash = denv.construct(denv.exports["SelkiesDashboard"], [JSObject({
        "root": root, "canvas": canvas, "wsUrl": "ws://t/ws",
        "mode": mode})])
    # click Connect
    btns = root.find_all(lambda e: e.tagName == "BUTTON"
                         and e.textContent == "Connect")
    denv.fire(btns[0], "click")
    ws = denv.sockets[-1]
    ws.server_open()
    return dash, root, canvas, ws


def test_dashboard_renders_schema_sections(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    widgets = dash_env.get(dash, "widgets")
    assert "framerate" in widgets and "encoder" in widgets
    assert "custom_knob" in widgets           # unknown → Advanced section
    # locked bool renders disabled
    assert widgets["use_cpu"].disabled is True
    # enum select carries the allowed values as options
    enc = widgets["encoder"]
    opts = [to_str(o.attrs.get("value")) for o in enc.children.elems]
    assert opts == ["jpeg", "x264enc"]
    # ui_title applied
    assert dash_env.get(dash, "titleEl").textContent == "My Desk"


def test_dashboard_checkbox_pushes_clamped_settings(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    widgets = dash_env.get(dash, "widgets")
    box = widgets["audio_enabled"]
    box.checked = False
    dash_env.fire(box, "change", dash_env.make_event(
        "change", target=box))
    # START/STOP_AUDIO immediate + debounced SETTINGS push
    assert "STOP_AUDIO" in ws.texts()
    dash_env.interp.fire_timers(1)
    pushes = [t for t in ws.texts() if t.startswith("SETTINGS,")]
    assert '"audio_enabled": false' in pushes[-1]
    # override persisted to localStorage
    assert '"audio_enabled": false' in \
        dash_env.local_storage["selkies_settings"]


def test_dashboard_number_input_clamps_to_schema_range(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    widgets = dash_env.get(dash, "widgets")
    fr = widgets["framerate"]
    fr.value = "500"                           # out of range
    dash_env.fire(fr, "change", dash_env.make_event("change", target=fr))
    assert to_num(fr.value) == 120.0           # clamped to schema max
    dash_env.interp.fire_timers(1)
    pushes = [t for t in ws.texts() if t.startswith("SETTINGS,")]
    assert '"framerate": 120' in pushes[-1]


def test_dashboard_stats_render(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    ws.server_text('{"type": "system_stats", "cpu_percent": 31,'
                   ' "mem_percent": 40}')
    ws.server_text('{"type": "gpu_stats", "utilization": 77}')
    stats_el = dash_env.get(dash, "statsEl")
    assert "31%" in stats_el.textContent
    assert "77%" in stats_el.textContent


def test_dashboard_stage_breakdown_render(dash_env):
    # ISSUE 13: the flight-recorder stage block riding system_health is
    # rendered in the stats overlay (where each frame's time went)
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    ws.server_text(
        '{"type": "system_health", "displays": {"primary": {'
        '"rung": "device", "glass_to_glass_p50_ms": 42.5,'
        ' "stages": {"capture": {"p50_ms": 1.3, "p95_ms": 3.0},'
        ' "ack": {"p50_ms": 12.0, "p95_ms": 30.0}}}}}')
    stats_el = dash_env.get(dash, "statsEl")
    assert "g2g 42.5 ms" in stats_el.textContent
    assert "capture 1.3" in stats_el.textContent
    assert "ack 12.0" in stats_el.textContent


def test_dashboard_sharing_links_and_copy(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    dash_env.clipboard_writes.clear()
    ws.server_text(SCHEMA)
    host = dash_env.get(dash, "settingsHost")
    rows = host.find_all(lambda e: "share-row" in (e.className or ""))
    labels = [r.children.elems[0].textContent for r in rows]
    assert labels == ["View only", "Player 2", "Player 3", "Player 4"]
    copy_btn = rows[1].children.elems[1]
    dash_env.fire(copy_btn, "click", dash_env.make_event(
        "click", target=copy_btn))
    assert dash_env.clipboard_writes[-1].endswith("#player2")


def test_dashboard_files_modal_toggle(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env)
    ws.server_text(SCHEMA)
    host = dash_env.get(dash, "settingsHost")
    dl = host.find_all(lambda e: e.tagName == "BUTTON"
                       and e.textContent == "Download files")
    assert dl, "download button missing though file_transfers allows it"
    dash_env.fire(dl[0], "click")
    modal = dash_env.get(dash, "_filesModal")
    assert modal is not None and modal is not UNDEF
    iframes = modal.find_all(lambda e: e.tagName == "IFRAME")
    assert iframes and iframes[0].attrs.get("src") == "./files/"
    dash_env.fire(dl[0], "click")              # toggle off
    assert dash_env.get(dash, "_filesModal") is None


def test_dashboard_player_mode_is_gamepad_only(dash_env):
    dash, root, canvas, ws = make_dashboard(dash_env, mode="player2")
    # gamepad-only client never claims the display
    assert not any(t.startswith("SETTINGS,") for t in ws.texts())
    inp = dash_env.get(dash, "input")
    assert to_num(dash_env.get(inp, "gamepadIndexOffset")) == 1.0


# ----------------------------------------------------- touch-gamepad.js


def test_touch_gamepad_patches_getgamepads(dash_env):
    denv = dash_env
    tg = denv.interp.globals.lookup("TouchGamepad")
    denv.call(denv.get(tg, "enable"), [])
    pads = denv.call(denv.interp.globals.lookup("navigator").props[
        "getGamepads"], [])
    virt = pads.elems[3]
    assert virt is not None and virt is not UNDEF
    assert "Touch Gamepad" in to_str(denv.get(virt, "id"))
    # stick touch drives axes on the virtual pad
    overlay = denv.document.body.children.elems[-1]
    w, h = 1920.0, 1080.0
    ev = denv.make_event(
        "touchstart",
        changedTouches=JSArray([JSObject({
            "identifier": 1.0,
            "clientX": 0.18 * w + 50.0, "clientY": 0.72 * h})]))
    denv.fire(overlay, "touchstart", ev)
    axes = denv.get(virt, "axes")
    assert to_num(axes.elems[0]) > 0.3         # pushed right
    denv.call(denv.get(tg, "disable"), [])
    pads2 = denv.call(denv.interp.globals.lookup("navigator").props[
        "getGamepads"], [])
    assert pads2 is denv.gamepads              # native restored


def test_wake_lock_lifecycle(env):
    client, ws, canvas = make_client(env)
    env.interp.run_microtasks()
    assert env.wake_locks, "wake lock not requested on connect"
    lock = env.wake_locks[-1]
    # tab hidden → UA releases; on return to foreground, re-acquire
    env.document.visibilityState = "visible"
    n0 = len(env.wake_locks)
    for fn in env.document.listeners.get("visibilitychange", []):
        env.call(fn, [env.make_event("visibilitychange")])
    assert len(env.wake_locks) == n0 + 1
    env.call(env.get(client, "disconnect"), [])
    assert env.wake_locks[-1].props["released"] is True


def test_upload_file_chunks_and_frames(env):
    """uploadFile: START/chunk/END protocol with 0x01-framed binary and
    the bufferedAmount backpressure loop."""
    client, ws, canvas = make_client(env)
    from web_stubs import FakeBlobSlice
    from tools.minijs import NativeFunction

    data = bytes(range(256)) * 1200          # 300 KB → 2 chunks @ 256 KB

    class FakeFile:
        name = "report.pdf"
        size = float(len(data))

        def slice(self, a, b):
            return FakeBlobSlice(env, data[int(to_num(a)):int(to_num(b))])

    env.call(env.get(client, "uploadFile"), [FakeFile()], this=client)
    texts = ws.texts()
    assert f"FILE_UPLOAD_START:report.pdf:{len(data)}" in texts
    assert "FILE_UPLOAD_END:report.pdf" in texts
    bins = [b for b in ws.sent if isinstance(b, bytes) and b[:1] == b"\x01"]
    assert len(bins) == 2                    # 256 KB + 44 KB
    assert b"".join(b[1:] for b in bins) == data
