"""Supervision, degradation-ladder, and fault-injection tests (ISSUE 2).

The end-to-end tests drive ``DataStreamingServer.ws_handler`` with
in-process fake websockets: the server's fan-out path duck-types on
``send_nowait`` (data_server._ws_broadcast), so the full
capture → encode → transport pipeline — supervisor restarts, watchdog,
ladder transitions, health broadcasts — runs without the ``websockets``
package or any network, and faults are injected deterministically through
``server.faults`` at the real call sites.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from selkies_tpu.encoder.jpeg import StripeOutput
from selkies_tpu.observability.metrics import HAVE_PROM, Metrics
from selkies_tpu.protocol import VideoStripe, unpack_binary
from selkies_tpu.robustness import (FAILED, DegradationLadder, EncoderFault,
                                    FaultInjected, FaultInjector,
                                    InProcessClient, Supervisor)
from selkies_tpu.server.app import StreamingApp
from selkies_tpu.server import data_server
from selkies_tpu.server.data_server import DataStreamingServer, DisplayState
from selkies_tpu.settings import Settings


@pytest.fixture
def anyio_backend():
    return "asyncio"


# ---------------------------------------------------------------------------
# in-process fakes


#: the canonical in-process websocket stand-in lives with the robustness
#: package so the chaos harness and these tests share one surface
FakeWs = InProcessClient


class FakeEncoder:
    """Pipelined-encoder lookalike; records the overrides it was built
    with so rung switches are observable."""

    def __init__(self, overrides=None):
        ov = overrides or {}
        self.entropy = ov.get("tpu_entropy", "device")
        self.profile = ov.get("encoder", "")
        self.submitted = 0
        self.closed = False
        self._ready = []

    def submit(self, frame):
        self.submitted += 1
        self._ready.append(
            (self.submitted,
             [StripeOutput(y_start=0, height=64,
                           jpeg=b"\xff\xd8FAKE%d" % self.submitted
                           + b"\xff\xd9",
                           is_paintover=False)]))

    def poll(self):
        out, self._ready = self._ready, []
        return out

    def flush(self):
        return self.poll()

    def close(self):
        self.closed = True


class FakeSource:
    def __init__(self, width, height, fps):
        self.width, self.height, self.fps = width, height, fps

    def start(self):
        pass

    def stop(self):
        pass

    def next_frame(self):
        return np.zeros((self.height, self.width, 3), np.uint8)


def make_server(**settings_env):
    env = {"SELKIES_PORT": "0", "SELKIES_AUDIO_ENABLED": "false"}
    env.update(settings_env)
    settings = Settings(argv=[], env=env)
    app = StreamingApp(settings)
    encoders = []

    def encoder_factory(w, h, s, overrides=None):
        enc = FakeEncoder(overrides)
        encoders.append(enc)
        return enc

    server = DataStreamingServer(
        settings, app=app,
        encoder_factory=encoder_factory,
        source_factory=lambda w, h, fps, **kw: FakeSource(w, h, fps),
        host="127.0.0.1",
    )
    app.data_server = server
    return server, encoders


async def wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return False


async def open_client(server, settings_body=None):
    ws = FakeWs()
    task = asyncio.create_task(server.ws_handler(ws))
    assert await wait_until(lambda: len(ws.sent) >= 2, timeout=5.0)
    assert ws.sent[0] == "MODE websockets"
    if settings_body is not None:
        ws.feed("SETTINGS," + json.dumps(settings_body))
    return ws, task


async def close_client(ws, task):
    await ws.close()
    try:
        await asyncio.wait_for(task, 5.0)
    except asyncio.TimeoutError:
        task.cancel()


# ---------------------------------------------------------------------------
# fault injector


def test_fault_injector_grammar():
    f = FaultInjector("capture.raise*2,fetch.hang=1.5,ws.drop")
    assert set(f.armed) == {"capture.raise", "fetch.hang", "ws.drop"}
    # counts decrement and the point disarms at zero
    assert f.should_fire("capture.raise")
    assert f.should_fire("capture.raise")
    assert not f.should_fire("capture.raise")
    assert f.fired["capture.raise"] == 2
    # an unarmed point is free
    assert not f.should_fire("encode.raise")
    with pytest.raises(FaultInjected):
        f.arm("ws.drop")
        f.maybe_raise("ws.drop")
    with pytest.raises(ValueError):
        f.arm("no.such.point")
    with pytest.raises(ValueError):
        FaultInjector("what even is this*")
    f.reset()
    assert f.armed == () and f.fired == {}


@pytest.mark.anyio
async def test_fault_injector_hang_is_cancellable():
    f = FaultInjector("capture.stall=30")
    t = asyncio.ensure_future(f.maybe_hang("capture.stall"))
    await asyncio.sleep(0.05)
    assert not t.done()          # hanging
    t.cancel()
    with pytest.raises(asyncio.CancelledError):
        await t
    # disarmed after firing once
    await asyncio.wait_for(f.maybe_hang("capture.stall"), 1.0)


# ---------------------------------------------------------------------------
# supervisor


@pytest.mark.anyio
async def test_supervisor_restarts_crashing_task_then_runs():
    crashes = []
    ran = asyncio.Event()

    async def child():
        if len(crashes) < 2:
            crashes.append(1)
            raise RuntimeError("boom")
        ran.set()
        await asyncio.sleep(3600)

    events = []
    sup = Supervisor("t", child, base_delay_s=0.01, max_delay_s=0.05,
                     max_restarts=5,
                     on_event=lambda k, i: events.append(k))
    task = asyncio.create_task(sup.run())
    await asyncio.wait_for(ran.wait(), 5.0)
    assert sup.failures_total == 2
    assert sup.restarts_total >= 2
    assert sup.state == "running"
    assert events.count("failure") == 2
    task.cancel()
    await asyncio.gather(task, return_exceptions=True)
    assert sup.state == "stopped"


@pytest.mark.anyio
async def test_supervisor_budget_exhaustion_is_terminal():
    async def child():
        raise RuntimeError("always")

    events = []
    sup = Supervisor("t", child, base_delay_s=0.005, max_delay_s=0.01,
                     max_restarts=3, restart_window_s=30.0,
                     on_event=lambda k, i: events.append(k))
    await asyncio.wait_for(sup.run(), 10.0)   # returns (terminal), no raise
    assert sup.state == FAILED
    assert sup.failures_total == 4            # budget 3 + the final straw
    assert "failed" in events


@pytest.mark.anyio
async def test_supervisor_watchdog_restarts_stalled_child():
    recovered = asyncio.Event()
    runs = []

    async def child():
        runs.append(1)
        if len(runs) == 1:
            await asyncio.sleep(3600)   # stalls without ever beating
        while True:
            sup.beat()
            recovered.set()
            await asyncio.sleep(0.01)

    sup = Supervisor("t", child, base_delay_s=0.01,
                     watchdog_timeout_s=0.2, max_restarts=5)
    task = asyncio.create_task(sup.run())
    await asyncio.wait_for(recovered.wait(), 5.0)
    assert sup.watchdog_restarts_total == 1
    assert sup.failures_total == 0
    task.cancel()
    await asyncio.gather(task, return_exceptions=True)


# ---------------------------------------------------------------------------
# degradation ladder


def test_ladder_steps_down_and_probes_up():
    now = [0.0]
    ladder = DegradationLadder(fail_threshold=2, probe_after_s=5.0,
                               clock=lambda: now[0])
    assert ladder.rung == "device"
    assert not ladder.record_failure()
    assert ladder.record_failure()             # 2 consecutive -> step down
    assert ladder.rung == "host"
    # success resets the consecutive count; no probe before the window
    assert not ladder.record_success()
    now[0] = 3.0
    ladder.record_failure()                    # 1 of 2: no step
    assert ladder.rung == "host"
    ladder.record_failure()
    assert ladder.rung == "jpeg"               # bottom rung
    ladder.record_failure()
    ladder.record_failure()
    assert ladder.rung == "jpeg"               # clamped
    now[0] = 10.0
    assert ladder.record_success()             # clean probe window -> up
    assert ladder.rung == "host"
    now[0] = 16.0
    assert ladder.record_success()
    assert ladder.rung == "device"
    assert ladder.transitions == [
        "device->host", "host->jpeg", "jpeg->host", "host->device"]
    assert ladder.failures_total == 6
    # single-shot overwhelming evidence (wedge) bypasses the threshold
    assert ladder.force_step_down()
    assert ladder.rung == "host"
    ladder.force_step_down()
    assert not ladder.force_step_down()        # bottom rung: no step
    assert ladder.rung == "jpeg"


def test_backoff_delay_formula():
    from selkies_tpu.robustness import backoff_delay

    assert backoff_delay(1, 0.5, 10.0) == 0.5
    assert backoff_delay(3, 0.5, 10.0) == 2.0
    assert backoff_delay(50, 0.5, 10.0) == 10.0          # capped, no overflow
    d = backoff_delay(1, 1.0, 10.0, jitter=0.5)
    assert 1.0 <= d <= 1.5


# ---------------------------------------------------------------------------
# bind backoff (satellite: run_server retry policy)


@pytest.mark.anyio
async def test_run_server_bind_backoff_gives_up(monkeypatch):
    import sys
    import types

    calls = []

    def serve(*a, **k):
        calls.append(1)
        raise OSError(98, "address in use")

    ws = types.ModuleType("websockets")
    ws_asyncio = types.ModuleType("websockets.asyncio")
    ws_server = types.ModuleType("websockets.asyncio.server")
    ws_server.serve = serve
    ws.asyncio = ws_asyncio
    ws_asyncio.server = ws_server
    monkeypatch.setitem(sys.modules, "websockets", ws)
    monkeypatch.setitem(sys.modules, "websockets.asyncio", ws_asyncio)
    monkeypatch.setitem(sys.modules, "websockets.asyncio.server", ws_server)

    server, _ = make_server()
    server.BIND_MAX_ATTEMPTS = 3
    server.BIND_BASE_DELAY_S = 0.01
    server.BIND_MAX_DELAY_S = 0.02
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="could not bind"):
        await asyncio.wait_for(server.run_server(), 10.0)
    assert len(calls) == 3
    assert time.monotonic() - t0 < 5.0         # capped, not 1s-per-retry


# ---------------------------------------------------------------------------
# encoder adapter accounting (satellite: _harvest counts, not just logs)


def test_threaded_adapter_counts_errors_and_drops():
    import threading

    from selkies_tpu.encoder.pipeline import ThreadedEncoderAdapter

    gate = threading.Event()

    class FlakyBase:
        def __init__(self):
            self.calls = 0

        def encode_frame(self, frame):
            gate.wait(5.0)
            self.calls += 1
            if self.calls % 2:
                raise RuntimeError("entropy exploded")
            return ["stripe"]

    seen_errors = []
    adapter = ThreadedEncoderAdapter(FlakyBase(), depth=2)
    adapter.on_error = seen_errors.append
    frame = np.zeros((16, 16, 3), np.uint8)
    assert adapter.try_submit(frame) is not None
    assert adapter.try_submit(frame) is not None
    assert adapter.try_submit(frame) is None    # full -> counted drop
    assert adapter.frames_dropped_total == 1
    gate.set()
    deadline = time.monotonic() + 5.0
    got = []
    while time.monotonic() < deadline and len(got) < 1:
        got.extend(adapter.poll())
        time.sleep(0.01)
    assert adapter.encode_errors_total == 1
    assert len(seen_errors) == 1
    st = adapter.stats()
    assert st["encode_errors"] == 1
    assert st["frames_dropped"] == 1
    assert st["frames"] == 1
    # the flush drain counts errors identically to poll (no silent path)
    assert adapter.submit(frame) is not None   # call 3: raises
    assert adapter.submit(frame) is not None   # call 4: ok
    flushed = adapter.flush()
    assert adapter.encode_errors_total == 2
    assert len(seen_errors) == 2
    assert len(flushed) == 1
    adapter.close()


# ---------------------------------------------------------------------------
# teardown safety (satellite: _stop_display_locked exception-safe)


@pytest.mark.anyio
async def test_stop_display_teardown_is_exception_safe():
    server, _ = make_server()
    st = DisplayState(display_id="primary")

    async def bad_cleanup():
        try:
            await asyncio.sleep(3600)
        except asyncio.CancelledError:
            raise RuntimeError("cleanup raised instead of cancelling")

    async def good_loop():
        await asyncio.sleep(3600)

    closed = []

    class Enc:
        def close(self):
            closed.append(True)
            raise RuntimeError("close also raised")

    st.capture_task = asyncio.create_task(bad_cleanup())
    st.backpressure_task = asyncio.create_task(good_loop())
    st.encoder = Enc()
    await asyncio.sleep(0.05)
    await asyncio.wait_for(server._stop_display(st), 5.0)
    # the first task's RuntimeError did not abort the teardown
    assert st.capture_task is None
    assert st.backpressure_task is None
    assert st.encoder is None
    assert closed == [True]


# ---------------------------------------------------------------------------
# mesh coordinator per-shard accounting


def test_mesh_tick_failure_attributes_slots_and_unblocks_flush():
    """A failed lane dispatch charges the slots that were in that tick
    and releases their in-flight holds — a stranded hold would block
    facade.flush for its full timeout (ISSUE 14: failures are contained
    to the lane; the worker thread never sees them)."""
    from selkies_tpu.parallel.coordinator import MeshEncodeCoordinator

    class BadEnc:
        n_sessions = 2

        def reset_session(self, s):
            pass

        def force_keyframe(self, s):
            pass

        def dispatch(self, frames):
            raise RuntimeError("device gone")

    coord = MeshEncodeCoordinator(
        "session:1", 2, 64, 48, enc_factory=lambda n: BadEnc(),
        slots_per_lane=2, max_lanes=1, framerate=60.0,
        health_sick_errors=100)
    coord.stop()                    # drive the tick by hand
    fa = coord.acquire(64, 48)
    fb = coord.acquire(64, 48)
    coord.stop()
    fa.try_submit("frame0")
    fb.try_submit("frame1")
    coord._tick()                   # contained: does NOT raise
    st_stats = coord.stats()
    assert st_stats["slot_errors"] == [1, 1]
    assert st_stats["tick_errors_total"] == 1
    # the holds were released: flush returns immediately, no wedge
    t0 = time.monotonic()
    assert fa.flush() == []
    assert time.monotonic() - t0 < 1.0
    assert coord.verify_slot_accounting() == []
    coord.stop()


# ---------------------------------------------------------------------------
# acceptance (a): capture-loop crash restarts; websocket session survives


@pytest.mark.anyio
async def test_capture_crash_restarts_without_killing_session():
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="10",
        SELKIES_WATCHDOG_FRAMES="0",
    )
    server.faults.arm("capture.raise", times=2)
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        st_ok = await wait_until(
            lambda: "primary" in server.display_clients
            and server.display_clients["primary"].supervisor is not None
            and server.display_clients["primary"]
                .supervisor.failures_total >= 2)
        assert st_ok
        st = server.display_clients["primary"]
        # recovery: frames flow after the crashes, on the SAME websocket
        n0 = len(ws.binary())
        assert await wait_until(lambda: len(ws.binary()) > n0 + 2)
        assert not ws.closed
        assert st.supervisor.state in ("running", "backoff")
        assert st.supervisor.failures_total == 2
        assert len(encoders) >= 3               # one encoder per (re)start
        assert server.faults.fired["capture.raise"] == 2
        # frame ids were resynchronized on each restart
        first = unpack_binary(ws.binary()[0])
        assert isinstance(first, VideoStripe) and first.frame_id == 1
        assert any("PIPELINE_RESETTING" in t for t in ws.texts())
        # supervision events rode the system,health feed
        healths = [t for t in ws.texts()
                   if isinstance(t, str) and '"system_health"' in t]
        assert healths
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# acceptance (b): repeated device failures degrade to host, then recover


@pytest.mark.anyio
async def test_ladder_degrades_to_host_and_recovers_to_device():
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="20",
        SELKIES_WATCHDOG_FRAMES="0",
        SELKIES_LADDER_FAIL_THRESHOLD="3",
        SELKIES_LADDER_PROBE_MS="300",
    )
    server.faults.arm("encode.raise", times=3)
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        st_ok = await wait_until(lambda: "primary" in server.display_clients)
        assert st_ok
        st = server.display_clients["primary"]
        # three injected device-entropy failures step the ladder down …
        assert await wait_until(
            lambda: any(e.entropy == "host" for e in encoders))
        host_at = next(i for i, e in enumerate(encoders)
                       if e.entropy == "host")
        assert "device->host" in st.ladder.transitions
        # … and a clean probe window steps it back up: a LATER encoder is
        # built at device entropy again
        assert await wait_until(
            lambda: any(e.entropy == "device"
                        for e in encoders[host_at + 1:]))
        assert "host->device" in st.ladder.transitions
        assert st.ladder.rung == "device"
        assert st.ladder.failures_total == 3
        # the rung transitions were visible on the wire
        rungs = []
        for t in ws.texts():
            if '"system_health"' in t:
                payload = json.loads(t)
                rungs.append(payload["displays"]["primary"]["rung"])
        assert "host" in rungs and "device" in rungs
        # and frames flow again at the recovered rung
        n0 = len(ws.binary())
        assert await wait_until(lambda: len(ws.binary()) > n0 + 2)
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# acceptance (c): a stalled fetch trips the watchdog


@pytest.mark.anyio
async def test_stalled_fetch_trips_watchdog():
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="10",
        SELKIES_WATCHDOG_FRAMES="30",     # 30/60fps -> 0.5s deadline
    )
    if HAVE_PROM:
        server.metrics = Metrics(port=0)
    server.faults.arm("fetch.hang", times=1)
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(
            lambda: "primary" in server.display_clients
            and server.display_clients["primary"].supervisor is not None
            and server.display_clients["primary"]
                .supervisor.watchdog_restarts_total >= 1,
            timeout=15.0)
        st = server.display_clients["primary"]
        assert st.supervisor.failures_total == 0   # a stall, not a crash
        # the restarted pipeline streams again
        n0 = len(ws.binary())
        assert await wait_until(lambda: len(ws.binary()) > n0 + 2)
        if HAVE_PROM:
            text = server.metrics.render().decode()
            assert "watchdog_restarts_total 1.0" in text
        # watchdog restarts ride the health feed too
        assert any('"watchdog_restarts": 1' in t or
                   '"watchdog_restarts": 2' in t for t in ws.texts())
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# a first-use compile is not a wedge — for the grace, and for its display


class QuietEncoder(FakeEncoder):
    """A driver whose thread sits inside one long dispatch: accepts
    nothing, harvests nothing. ``compiling`` makes it say, through the
    encoder's own signal (runtime.CompileWatch via the wrappers), that
    the dispatch is a program's first use."""

    def __init__(self, overrides=None, compiling=False):
        super().__init__(overrides)
        self.compiling = compiling
        self.born = time.monotonic()

    def try_submit(self, frame):
        return None

    def compiling_for_s(self):
        return time.monotonic() - self.born if self.compiling else 0.0


@pytest.mark.anyio
@pytest.mark.parametrize("compiling, grace_s, steps", [
    (True, 60.0, False),    # compiling past the wedge deadline: spared
    (True, 1.0, True),      # ... past the grace too: a wedge after all
    (False, 60.0, True),    # quiet and NOT compiling: a wedge, as before
])
async def test_wedge_detector_spares_only_its_own_compiling_encoder(
        monkeypatch, compiling, grace_s, steps):
    monkeypatch.setattr(data_server, "WEDGE_MIN_S", 0.0)
    monkeypatch.setattr(data_server, "COMPILE_GRACE_S", grace_s)
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="20",
        SELKIES_WATCHDOG_FRAMES="30",     # 0.5 s watchdog -> 2 s wedge
    )

    def encoder_factory(w, h, s, overrides=None):
        # only the device rung is quiet; what the ladder steps down to
        # (and any other display's encoder) streams
        if (overrides or {}).get("tpu_entropy", "device") == "device":
            enc = QuietEncoder(overrides, compiling=compiling)
        else:
            enc = FakeEncoder(overrides)
        encoders.append(enc)
        return enc

    server.encoder_factory = encoder_factory
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(lambda: "primary" in server.display_clients)
        st = server.display_clients["primary"]
        stepped = await wait_until(
            lambda: "device->host" in st.ladder.transitions, timeout=4.5)
        assert stepped is steps
        if not steps:
            assert st.ladder.rung == "device" and len(encoders) == 1
            assert st.supervisor.failures_total == 0
            assert encoders[0].compiling_for_s() > 2.0  # past the deadline
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# satellite: reconnect/resync path


@pytest.mark.anyio
async def test_reconnect_resyncs_frame_ids_with_keyframe():
    server, encoders = make_server()
    ws1, task1 = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(lambda: len(ws1.binary()) >= 3)
        ids = [unpack_binary(m).frame_id for m in ws1.binary()[:3]]
        assert ids == [1, 2, 3]
        n_enc = len(encoders)
        # disconnect mid-stream: the handler tears the display down
        await close_client(ws1, task1)
        assert await wait_until(
            lambda: "primary" not in server.display_clients)

        # reconnect: new handshake, new SETTINGS
        ws2, task2 = await open_client(server, {
            "displayId": "primary", "initialClientWidth": 320,
            "initialClientHeight": 240, "framerate": 60})
        try:
            assert await wait_until(lambda: len(ws2.binary()) >= 1)
            # PIPELINE_RESETTING preceded the media
            reset_i = next(i for i, m in enumerate(ws2.sent)
                           if isinstance(m, str)
                           and m.startswith("PIPELINE_RESETTING"))
            frame_i = next(i for i, m in enumerate(ws2.sent)
                           if isinstance(m, (bytes, bytearray)))
            assert reset_i < frame_i
            # frame ids restarted at 1 (ACK horizon reset), fresh encoder
            # means the first frame is a keyframe
            f = unpack_binary(ws2.binary()[0])
            assert f.frame_id == 1
            assert f.is_key
            assert len(encoders) > n_enc       # rebuilt, not reused
            st = server.display_clients["primary"]
            assert st.bp.last_sent_frame_id < 100
            assert st.bp.send_enabled
        finally:
            await close_client(ws2, task2)
    finally:
        await server.stop()


# ---------------------------------------------------------------------------
# ladder step-downs forgive the restart budget (degrading != dying)


@pytest.mark.anyio
async def test_ladder_stepdowns_do_not_exhaust_restart_budget():
    """6 encoder faults with a budget of 3: each ladder step-down resets
    the budget, so the display walks device→host→jpeg instead of dying."""
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="3",
        SELKIES_SUPERVISOR_RESTART_WINDOW_S="60",
        SELKIES_WATCHDOG_FRAMES="0",
        SELKIES_LADDER_FAIL_THRESHOLD="2",
        SELKIES_LADDER_PROBE_MS="600000",   # no probe-up during the test
    )
    server.faults.arm("encode.raise", times=6)
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(lambda: "primary" in server.display_clients)
        st = server.display_clients["primary"]
        assert await wait_until(lambda: st.ladder.rung == "jpeg")
        assert st.ladder.transitions == ["device->host", "host->jpeg"]
        assert not st.failed
        assert st.supervisor is not None and st.supervisor.state != FAILED
        # the bottom-rung encoder streams (profile forced to jpeg)
        assert await wait_until(
            lambda: any(e.profile == "jpeg" and e.submitted > 0
                        for e in encoders))
        n0 = len(ws.binary())
        assert await wait_until(lambda: len(ws.binary()) > n0 + 2)
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# bottom rung + persistent off-loop errors: rebuild, then terminal failure
# (a display streaming nothing must never read as healthy forever)


@pytest.mark.anyio
async def test_bottom_rung_persistent_errors_walk_ladder_then_fail():
    settings = Settings(argv=[], env={
        "SELKIES_PORT": "0", "SELKIES_AUDIO_ENABLED": "false",
        "SELKIES_SUPERVISOR_MAX_RESTARTS": "2",
        "SELKIES_WATCHDOG_FRAMES": "0",
        "SELKIES_LADDER_FAIL_THRESHOLD": "2",
        "SELKIES_LADDER_PROBE_MS": "600000",
    })
    app = StreamingApp(settings)
    built = []

    class SickEncoder:
        """Every harvested frame errors (reported via on_error, like the
        threaded adapter) and nothing is ever delivered."""

        def __init__(self):
            self.on_error = None

        def try_submit(self, frame):
            return 1

        def poll(self):
            if self.on_error is not None:
                self.on_error(RuntimeError("sick"))
            return []

        def flush(self):
            return []

        def close(self):
            pass

    def factory(w, h, s, overrides=None):
        built.append(dict(overrides or {}))
        return SickEncoder()

    server = DataStreamingServer(
        settings, app=app, encoder_factory=factory,
        source_factory=lambda w, h, fps, **kw: FakeSource(w, h, fps),
        host="127.0.0.1")
    app.data_server = server
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(lambda: "primary" in server.display_clients)
        st = server.display_clients["primary"]
        # off-loop errors walk the whole ladder down …
        assert await wait_until(lambda: st.ladder.rung == "jpeg")
        assert st.ladder.transitions[:2] == ["device->host", "host->jpeg"]
        assert await wait_until(
            lambda: any(o.get("tpu_entropy") == "host" for o in built))
        assert await wait_until(
            lambda: any(o.get("encoder") == "jpeg" for o in built))
        # … and at the bottom rung, persistent errors force supervised
        # rebuilds until the budget marks the display terminally failed
        # instead of streaming nothing forever with a "running" state
        assert await wait_until(lambda: st.failed, timeout=20.0)
        assert not ws.closed
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# terminal failure: budget exhaustion tears the display down, sticky marker


@pytest.mark.anyio
async def test_restart_budget_exhaustion_fails_display_and_tears_down():
    server, encoders = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="2",
        SELKIES_SUPERVISOR_RESTART_WINDOW_S="60",
        SELKIES_WATCHDOG_FRAMES="0",
    )
    server.faults.arm("capture.raise", times=50)   # crash every run
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(
            lambda: "primary" in server.display_clients
            and server.display_clients["primary"].failed)
        st = server.display_clients["primary"]
        # the sibling backpressure loop must not tick forever for a dead
        # pipeline — the failed event tears the whole display down
        assert await wait_until(lambda: st.capture_task is None
                                and st.backpressure_task is None)
        assert server._failed_displays() == 1
        assert not ws.closed       # the websocket session itself survives
        assert any('"failed": true' in t for t in ws.texts()
                   if '"system_health"' in t)
        # an explicit START_VIDEO clears the marker and recovers
        server.faults.disarm()
        ws.feed("START_VIDEO")
        assert await wait_until(
            lambda: not st.failed and st.capture_task is not None)
        n0 = len(ws.binary())
        assert await wait_until(lambda: len(ws.binary()) > n0)
        assert server._failed_displays() == 0
    finally:
        await close_client(ws, task)
        await server.stop()


# ---------------------------------------------------------------------------
# chaos (slow): random fault storm over the REAL encoder factory


@pytest.mark.slow
@pytest.mark.anyio
async def test_chaos_session_survives_fault_storm():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.chaos_run import chaos_session

    report = await chaos_session(duration_s=5.0, seed=1)
    assert report["alive"], report
    assert report["injected"], report
    assert report["failed_displays"] == 0
    assert (report["restarts"] + report["watchdog_restarts"]
            + report["reconnects"]) >= 1, report
    assert report["frames_delivered"] > 0


# ---------------------------------------------------------------------------
# ws.drop fault: client churn mid-stream leaves the server healthy


@pytest.mark.anyio
async def test_ws_drop_fault_closes_client_server_survives():
    server, encoders = make_server()
    server.faults.arm("ws.drop", times=1)
    ws, task = await open_client(server, {
        "displayId": "primary", "initialClientWidth": 320,
        "initialClientHeight": 240, "framerate": 60})
    try:
        assert await wait_until(lambda: ws.closed, timeout=10.0)
        await asyncio.wait_for(task, 5.0)       # handler exited cleanly
        assert await wait_until(
            lambda: "primary" not in server.display_clients)
        # a new client gets a fresh, working session
        ws2, task2 = await open_client(server, {
            "displayId": "primary", "initialClientWidth": 320,
            "initialClientHeight": 240, "framerate": 60})
        try:
            assert await wait_until(lambda: len(ws2.binary()) >= 2)
        finally:
            await close_client(ws2, task2)
    finally:
        await close_client(ws, task)
        await server.stop()
