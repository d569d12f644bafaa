"""Input plane tests: message grammar, injection semantics, gamepad protocol.

Drives the full InputHandler logic against fake backends — the reference has
no automated tests here (SURVEY.md §4); this suite covers the grammar of
input_handler.py:1507-1697 behaviorally.
"""

import asyncio
import base64
import struct

import pytest

from selkies_tpu.input import (FakeX11Backend, InputHandler, MemoryClipboard,
                               keysym_to_char, keysym_to_name)
from selkies_tpu.input.cursor import (CursorImage, cursor_to_msg,
                                      encode_png_rgba)
from selkies_tpu.input.gamepad import (ABS_HAT0Y, ABS_RZ, ABS_X, AXIS_MAX,
                                       BTN_A, CONFIG_STRUCT_SIZE, EV_ABS,
                                       EV_KEY, EV_SYN, GamepadManager,
                                       GamepadMapper, VirtualGamepad,
                                       XPAD_MODEL, pack_config)


def run(coro):
    return asyncio.get_event_loop_policy().new_event_loop().run_until_complete(coro)


def make_handler(**kw):
    backend = FakeX11Backend()
    clip = MemoryClipboard()
    h = InputHandler(backend=backend, clipboard=clip, **kw)
    return h, backend, clip


# ---------------------------------------------------------------------------
# keysyms


def test_keysym_names():
    assert keysym_to_name(0xFF0D) == "Return"
    assert keysym_to_name(0xFFE1) == "Shift_L"
    assert keysym_to_name(0xFFBE) == "F1"
    assert keysym_to_name(0xFFC8) == "F11"
    assert keysym_to_name(0x20) == "space"
    assert keysym_to_name(0x61) == "a"
    assert keysym_to_name(0x01000394) == "U0394"  # unicode Δ
    assert keysym_to_char(0x01000394) == "Δ"
    assert keysym_to_char(0x41) == "A"
    assert keysym_to_char(0xFF0D) is None


# ---------------------------------------------------------------------------
# keyboard grammar


def test_alpha_key_press_release():
    h, be, _ = make_handler()
    run(h.on_message("kd,97"))   # 'a'
    assert ("key", 97, True) in be.events
    run(h.on_message("ku,97"))
    assert ("key", 97, False) in be.events


def test_non_alpha_printable_typed_atomically():
    h, be, _ = make_handler()
    run(h.on_message("kd,33"))   # '!'
    assert ("type", "!") in be.events
    # matching keyup must be swallowed (no stray key event)
    run(h.on_message("ku,33"))
    assert not any(e[0] == "key" for e in be.events)


def test_modifier_tracking_disables_atomic_typing():
    h, be, _ = make_handler()
    run(h.on_message("kd,65507"))  # Control_L (0xFFE3)
    run(h.on_message("kd,33"))     # '!' while ctrl held → real key event
    assert ("key", 33, True) in be.events
    assert not any(e[0] == "type" for e in be.events)
    run(h.on_message("ku,65507"))
    assert 0xFFE3 not in h.active_modifiers


def test_keyboard_reset_releases_pressed():
    h, be, _ = make_handler()
    run(h.on_message("kd,97"))
    run(h.on_message("kd,65507"))
    run(h.on_message("kr"))
    assert ("key", 97, False) in be.events
    assert ("key", 65507, False) in be.events
    assert not h.pressed_keysyms and not h.active_modifiers


def test_atomic_type_verb():
    h, be, _ = make_handler()
    run(h.on_message("co,end,hello, world"))
    assert ("type", "hello, world") in be.events


# ---------------------------------------------------------------------------
# mouse grammar


def test_mouse_move_and_click():
    h, be, _ = make_handler()
    run(h.on_message("m,100,200,1,0"))
    assert ("move", 100, 200) in be.events
    assert ("button", 1, True) in be.events
    run(h.on_message("m,100,200,0,0"))
    assert ("button", 1, False) in be.events


def test_mouse_relative():
    h, be, _ = make_handler()
    run(h.on_message("m2,5,-3,0,0"))
    assert ("rel", 5, -3) in be.events


def test_scroll_up_with_magnitude():
    h, be, _ = make_handler()
    run(h.on_message("m,0,0,8,3"))  # bit 3 + magnitude → 3× button-4 click
    ups = [e for e in be.events if e == ("button", 4, True)]
    assert len(ups) == 3


def test_back_synthesizes_alt_left():
    h, be, _ = make_handler()
    run(h.on_message("m,0,0,8,0"))  # bit 3, no magnitude → Alt+Left
    assert ("key", 0xFFE9, True) in be.events
    assert ("key", 0xFF51, True) in be.events
    assert ("key", 0xFFE9, False) in be.events


def test_display_offset_applied():
    class FakeServer:
        display_layouts = {"display2": {"x": 1920, "y": 0}}

    h, be, _ = make_handler(data_server=FakeServer())
    run(h.on_message("m,10,20,0,0", "display2"))
    assert ("move", 1930, 20) in be.events


# ---------------------------------------------------------------------------
# clipboard grammar


def test_clipboard_write_read_roundtrip():
    h, _, clip = make_handler()
    payload = base64.b64encode("héllo".encode()).decode()
    run(h.on_message(f"cw,{payload}"))
    assert clip.data == "héllo".encode()

    got = []

    async def capture(data, mime):
        got.append((data, mime))

    h.on_clipboard_read = capture
    run(h.on_message("cr"))
    assert got == [("héllo".encode(), "text/plain")]


def test_clipboard_disabled_drops_write():
    h, _, clip = make_handler(enable_clipboard="out")
    payload = base64.b64encode(b"x").decode()
    run(h.on_message(f"cw,{payload}"))
    assert clip.data == b""


def test_multipart_clipboard():
    h, _, clip = make_handler()

    async def scenario():
        data = b"A" * 1000
        await h.on_message(f"cws,{len(data)}")
        half = base64.b64encode(data[:500]).decode()
        rest = base64.b64encode(data[500:]).decode()
        await h.on_message(f"cwd,{half}")
        await h.on_message(f"cwd,{rest}")
        await h.on_message("cwe")

    run(scenario())
    assert clip.data == b"A" * 1000


def test_multipart_size_mismatch_rejected():
    h, _, clip = make_handler()

    async def scenario():
        await h.on_message("cws,999")
        await h.on_message(f"cwd,{base64.b64encode(b'short').decode()}")
        await h.on_message("cwe")

    run(scenario())
    assert clip.data == b""


def test_binary_clipboard():
    h, _, clip = make_handler(enable_binary_clipboard=True)
    png = b"\x89PNG fake"
    payload = base64.b64encode(png).decode()
    run(h.on_message(f"cb,image/png,{payload}"))
    assert clip.data == png and clip.mime_type == "image/png"


# ---------------------------------------------------------------------------
# callbacks


def test_bitrate_fps_latency_callbacks():
    h, _, _ = make_handler()
    seen = {}
    h.on_video_bitrate = lambda v: seen.setdefault("vb", v)
    h.on_audio_bitrate = lambda v: seen.setdefault("ab", v)
    h.on_set_fps = lambda v: seen.setdefault("fps", v)
    h.on_client_fps = lambda v: seen.setdefault("_f", v)
    h.on_client_latency = lambda v: seen.setdefault("_l", v)
    for m in ("vb,4000", "ab,128", "_arg_fps,30", "_f,59", "_l,12"):
        run(h.on_message(m))
    assert seen == {"vb": 4000, "ab": 128, "fps": 30, "_f": 59, "_l": 12}


def test_arg_resize_parses_even_alignment():
    h, _, _ = make_handler()
    seen = {}
    h.on_set_enable_resize = lambda e, r: seen.update(enabled=e, res=r)
    run(h.on_message("_arg_resize,true,1921x1079"))
    assert seen == {"enabled": True, "res": "1922x1080"}


def test_malformed_messages_do_not_raise():
    h, _, _ = make_handler()
    for m in ("kd", "kd,notanint", "m,1,2", "js,b", "cw,!!!notb64",
              "_arg_fps,x", "zzz,1"):
        run(h.on_message(m))


# ---------------------------------------------------------------------------
# gamepad protocol


def test_config_struct_layout():
    blob = pack_config(XPAD_MODEL)
    assert len(blob) == CONFIG_STRUCT_SIZE == 1360
    name = blob[:255].split(b"\0")[0].decode()
    assert name == "Microsoft X-Box 360 pad"
    vendor, product, version, nbtn, nax = struct.unpack_from("=5H", blob, 256)
    assert (vendor, product, version) == (0x045E, 0x028E, 0x0114)
    assert nbtn == 11 and nax == 8
    btn_map = struct.unpack_from("=512H", blob, 266)
    assert btn_map[0] == BTN_A
    axes_map = struct.unpack_from("=64B", blob, 1290)
    assert axes_map[0] == ABS_X


def test_mapper_buttons_axes_triggers_dpad():
    m = GamepadMapper()
    ev = m.map_button(0, 1.0)              # A button
    assert ev.is_button and ev.evdev_code == BTN_A and ev.value_evdev == 1
    ev = m.map_button(7, 1.0)              # right trigger → ABS_RZ
    assert not ev.is_button and ev.evdev_code == ABS_RZ
    assert ev.value_evdev == AXIS_MAX
    ev = m.map_button(12, 1.0)             # dpad up → HAT0Y = -1
    assert ev.evdev_code == ABS_HAT0Y and ev.value_evdev == -1
    assert ev.value_js == -AXIS_MAX        # js hats scale to full range
    ev = m.map_axis(0, -1.0)               # left stick X full left
    assert ev.evdev_code == ABS_X and ev.value_evdev == -AXIS_MAX
    ev = m.map_axis(1, 0.0)
    assert abs(ev.value_evdev) <= 1        # centered
    assert m.map_button(99, 1.0) is None


def test_gamepad_socket_end_to_end(tmp_path):
    async def scenario():
        pad = VirtualGamepad(0, socket_dir=str(tmp_path))
        await pad.start()
        # --- js client
        r, w = await asyncio.open_unix_connection(pad.js_path)
        cfg = await r.readexactly(CONFIG_STRUCT_SIZE)
        assert cfg[:8] == b"Microsof"
        w.write(bytes([8]))  # 64-bit arch
        await w.drain()
        # --- evdev client
        r2, w2 = await asyncio.open_unix_connection(pad.ev_path)
        await r2.readexactly(CONFIG_STRUCT_SIZE)
        w2.write(bytes([8]))
        await w2.drain()
        await asyncio.sleep(0.05)

        pad.send_button(0, 1.0)  # A down
        js_ev = await asyncio.wait_for(r.readexactly(8), timeout=2)
        ts, value, ev_type, number = struct.unpack("=IhBB", js_ev)
        assert (value, ev_type, number) == (1, 0x01, 0)

        ev_pair = await asyncio.wait_for(r2.readexactly(48), timeout=2)
        sec, usec, t, code, val = struct.unpack_from("=qqHHi", ev_pair, 0)
        assert (t, code, val) == (EV_KEY, BTN_A, 1)
        sec, usec, t, code, val = struct.unpack_from("=qqHHi", ev_pair, 24)
        assert (t, code) == (EV_SYN, 0)

        w.close()
        w2.close()
        await pad.stop()

    run(scenario())


def test_gamepad_manager_via_grammar(tmp_path):
    async def scenario():
        mgr = GamepadManager(socket_dir=str(tmp_path))
        h = InputHandler(backend=FakeX11Backend(), gamepads=mgr)
        name = base64.b64encode(b"Test Pad").decode()
        await h.on_message(f"js,c,0,{name},4,17")
        assert 0 in mgr.pads and mgr.pads[0].running
        # connect a client and exercise b/a events through the grammar
        pad = mgr.pads[0]
        r, w = await asyncio.open_unix_connection(pad.js_path)
        await r.readexactly(CONFIG_STRUCT_SIZE)
        w.write(bytes([8]))
        await w.drain()
        await asyncio.sleep(0.05)
        await h.on_message("js,a,0,0,0.5")
        ev = await asyncio.wait_for(r.readexactly(8), timeout=2)
        _, value, ev_type, number = struct.unpack("=IhBB", ev)
        assert ev_type == 0x02 and number == 0 and value > 0
        await h.on_message("js,d,0")
        assert not pad.running
        w.close()
        await mgr.close()

    run(scenario())


def test_out_of_range_gamepad_index(tmp_path):
    async def scenario():
        mgr = GamepadManager(num_slots=2, socket_dir=str(tmp_path))
        h = InputHandler(backend=FakeX11Backend(), gamepads=mgr)
        await h.on_message("js,c,7,{},4,17")
        assert not mgr.pads
        await mgr.close()

    run(scenario())


# ---------------------------------------------------------------------------
# cursor


def test_cursor_to_msg_crops_and_encodes():
    # 8×8 transparent image with an opaque 2×2 block at (3,2)
    import numpy as np
    img = np.zeros((8, 8, 4), np.uint8)
    img[2:4, 3:5] = [255, 0, 0, 255]
    cur = CursorImage(8, 8, xhot=4, yhot=3, serial=7, rgba=img.tobytes())
    msg = cursor_to_msg(cur)
    assert msg["width"] == 2 and msg["height"] == 2
    assert msg["hotx"] == 1 and msg["hoty"] == 1
    assert msg["handle"] == 7
    png = base64.b64decode(msg["curdata"])
    assert png[:8] == b"\x89PNG\r\n\x1a\n"


def test_cursor_size_cap():
    import numpy as np
    img = np.full((128, 128, 4), 255, np.uint8)
    cur = CursorImage(128, 128, 64, 64, 1, img.tobytes())
    msg = cursor_to_msg(cur, size_cap=64)
    assert max(msg["width"], msg["height"]) == 64


def test_empty_cursor():
    msg = cursor_to_msg(None)
    assert msg["curdata"] == "" and msg["width"] == 0
    import numpy as np
    img = np.zeros((4, 4, 4), np.uint8)  # fully transparent
    msg = cursor_to_msg(CursorImage(4, 4, 0, 0, 3, img.tobytes()))
    assert msg["curdata"] == "" and msg["handle"] == 3


def test_png_encoder_valid():
    import zlib
    png = encode_png_rgba(bytes(range(16)) * 4, 4, 4)
    assert png[:8] == b"\x89PNG\r\n\x1a\n"
    # IDAT decompresses to 4 rows × (1 filter + 16 pixel bytes)
    idat_off = png.index(b"IDAT") + 4
    idat_len = struct.unpack(">I", png[idat_off - 8:idat_off - 4])[0]
    raw = zlib.decompress(png[idat_off:idat_off + idat_len])
    assert len(raw) == 4 * (1 + 16)


# ---------------------------------------------------------------------------
# international / IME coverage


def test_cyrillic_keysym_reaches_backend():
    h, be, _ = make_handler()
    zhe = 0x01000000 | ord("Ж")      # client unicode rule for non-latin keys
    run(h.on_message(f"kd,{zhe}"))
    run(h.on_message(f"ku,{zhe}"))
    # printable non-latin: atomically typed (stuck-modifier-safe), exactly
    # like latin printables — never silently dropped
    assert ("type", "Ж") in be.events or ("key", zhe, True) in be.events


def test_cjk_ime_composition_types_atomically():
    h, be, _ = make_handler()
    run(h.on_message("co,end,こんにちは世界"))
    assert ("type", "こんにちは世界") in be.events


def test_dead_key_composed_character():
    h, be, _ = make_handler()
    run(h.on_message("co,end,é"))    # dead-acute + e composed client-side
    assert ("type", "é") in be.events


def test_xf86_media_keysym_not_dropped():
    from selkies_tpu.input.keysyms import keysym_to_name

    h, be, _ = make_handler()
    vol_up = 0x1008ff13              # XF86AudioRaiseVolume
    run(h.on_message(f"kd,{vol_up}"))
    assert ("key", vol_up, True) in be.events
    assert keysym_to_name(vol_up) is not None


def test_keypad_keysyms_roundtrip():
    h, be, _ = make_handler()
    run(h.on_message("kd,65421"))    # KP_Enter 0xff8d
    assert ("key", 0xff8d, True) in be.events
