"""Metrics + flight-recorder tests (ISSUE 13).

Covers the recorder's span-leak invariant (every opened span reaches a
terminal mark — including under SELKIES_TPU_FAULTS chaos), the
trace-event export golden shape, ACK-RTT correctness through the real
ws_handler with the fake-websocket InProcessClient, the stage breakdown
riding system_health, and the hardened metrics HTTP endpoint
(/healthz, /debug/trace, non-fatal bind failure)."""

import asyncio
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from selkies_tpu.encoder.jpeg import StripeOutput
from selkies_tpu.observability import STAGES, FlightRecorder, Metrics
from selkies_tpu.protocol import VideoStripe, unpack_binary
from selkies_tpu.robustness import InProcessClient
from selkies_tpu.server.app import StreamingApp
from selkies_tpu.server.data_server import DataStreamingServer
from selkies_tpu.settings import Settings


@pytest.fixture
def anyio_backend():
    return "asyncio"


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_render():
    m = Metrics(port=0)
    m.set_fps(60.0)
    m.set_latency(12.5)
    m.set_device_queue_delay(0, 45.0)
    m.set_clients(3)
    m.set_backpressured(1)
    text = m.render().decode()
    assert "fps 60.0" in text
    assert "latency 12.5" in text
    assert 'device_queue_delay_ms{device="0"} 45.0' in text
    assert "connected_clients 3.0" in text
    # the two exporters nothing in the served program fed are gone
    assert "webrtc_statistics" not in text
    assert "tpuenc_encode_ms" not in text and "tpuenc_frame_bytes" not in text


def test_metrics_d2h_and_host_entropy_gauges():
    """ISSUE 1 satellite: the bottleneck gauges the pipelined encoders
    record must render."""
    m = Metrics(port=0)
    m.set_d2h_bytes_per_frame(12700.0)
    m.set_host_entropy_ms_per_frame(0.4)
    m.set_cavlc_low_tier_share(0.75)
    m.set_cavlc_tier_fill_share(0.25)
    m.set_fetch_prefix_hit_share(0.5)
    text = m.render().decode()
    assert "tpuenc_d2h_bytes_per_frame 12700.0" in text
    assert "tpuenc_host_entropy_ms_per_frame 0.4" in text
    assert "tpuenc_cavlc_low_tier_share 0.75" in text
    assert "tpuenc_cavlc_tier_fill_share 0.25" in text
    assert "tpuenc_fetch_prefix_hit_share 0.5" in text


def test_metrics_stage_series_render():
    """ISSUE 13: the flight-recorder series render with their labels."""
    m = Metrics(port=0)
    m.observe_stage("primary", "dispatch", 4.0)
    m.observe_glass_to_glass("primary", 42.0)
    m.observe_encode_only("primary", 17.0)
    m.set_trace_open_spans(3)
    m.inc_trace_dropped("queue")
    text = m.render().decode()
    assert 'frame_stage_ms_bucket{display="primary"' in text \
        or 'frame_stage_ms_bucket{' in text
    assert 'glass_to_glass_ms_count{display="primary"}' in text
    assert 'encode_only_ms_count{display="primary"}' in text
    assert "trace_open_spans 3.0" in text
    assert 'trace_dropped_total{stage="queue"}' in text


# ---------------------------------------------------------------------------
# flight recorder core


def test_recorder_span_lifecycle_and_summary():
    clock = [0.0]
    rec = FlightRecorder(capacity=32, clock=lambda: clock[0])
    tr = rec.begin("primary", t=0.0)
    tr.mark("capture", 0.0, 0.001)
    tr.mark("dispatch", 0.001, 0.005)
    tr.mark("pack", 0.006, 0.007)
    tr.frame_id = 1
    rec.sent(tr)
    tr.mark("send", 0.008, 0.009)
    assert rec.open_spans() == 1
    clock[0] = 0.025
    out = rec.ack("primary", 1)
    assert out is tr
    assert tr.terminal == "acked"
    assert rec.open_spans() == 0
    s = rec.summary("primary")
    assert s["frames"] == 1 and s["acked"] == 1
    assert s["stages"]["dispatch"]["p50_ms"] == pytest.approx(4.0)
    # ack = send end (0.009) -> ack arrival (0.025) = 16 ms: true RTT
    assert s["stages"]["ack"]["p50_ms"] == pytest.approx(16.0)
    assert s["glass_to_glass_p50_ms"] == pytest.approx(25.0)
    # encode_only: dispatch start (0.001) -> pack end (0.007)
    assert s["encode_only_p50_ms"] == pytest.approx(6.0)


def test_recorder_terminal_marks_and_ring_bound():
    rec = FlightRecorder(capacity=16, clock=lambda: 0.0)
    # dropped frames get dropped@<stage>, empties close quietly
    t1 = rec.begin("a", t=0.0)
    rec.drop(t1, "submit")
    assert t1.terminal == "dropped@submit"
    t2 = rec.begin("a", t=0.0)
    rec.finish_empty(t2)
    assert t2.terminal == "empty"
    # double-close is idempotent
    rec.drop(t2, "send")
    assert t2.terminal == "empty"
    assert rec.open_spans() == 0
    # ring stays bounded
    for i in range(100):
        tr = rec.begin("a", t=float(i))
        rec.drop(tr, "submit")
    assert rec.open_spans() == 0
    assert rec.summary()["frames"] <= 16


def test_recorder_expiry_and_wire_id_collision():
    clock = [0.0]
    rec = FlightRecorder(capacity=32, clock=lambda: clock[0])
    stale = rec.begin("a")
    stale.mark("send", 0.0, 0.001)
    stale.frame_id = 9
    rec.sent(stale)
    # same wire id re-registered (2^16 wrap): the stale span must close
    fresh = rec.begin("a")
    fresh.frame_id = 9
    rec.sent(fresh)
    assert stale.terminal == "expired@send"
    clock[0] = 100.0
    assert rec.expire() == 1                  # fresh span aged out
    assert rec.open_spans() == 0
    assert fresh.terminal.startswith("expired@")


def test_recorder_trace_event_export_golden():
    """Deterministic clock -> exact Chrome trace-event shape (the
    contract Perfetto and tools/trace_report.py consume)."""
    rec = FlightRecorder(capacity=8, clock=lambda: 0.0)
    tr = rec.begin("primary", t=0.0)
    tr.mark("capture", 0.0, 0.002)
    tr.mark("send", 0.004, 0.0045)
    tr.frame_id = 3
    rec.sent(tr)
    rec.ack("primary", 3, t=0.01)
    data = rec.export_trace_events()
    assert data["displayTimeUnit"] == "ms"
    assert data["otherData"]["open_spans"] == 0
    xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in data["traceEvents"] if e["ph"] == "M"]
    assert metas == [{
        "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
        "args": {"name": "display:primary"},
    }]
    assert [e["name"] for e in xs] == ["capture", "send", "ack"]
    cap = xs[0]
    assert cap == {
        "name": "capture", "cat": "frame", "ph": "X", "pid": 1,
        "tid": 3 % 64 + 1, "ts": 0.0, "dur": 2000.0,
        "args": {"frame_id": 3, "display": "primary",
                 "terminal": "acked", "span": 1},
    }
    # every event is valid for the trace_report consumer too
    from tools.trace_report import build_frames, render

    frames = build_frames(data)
    assert len(frames) == 1
    assert frames[0]["terminal"] == "acked"
    text = render(data, top=3)
    assert "glass-to-glass" in text and "capture" in text


def test_trace_report_does_not_merge_unsent_drops():
    """Dropped-before-wire frames share frame_id -1 and recycle tids mod
    64: the per-span token must keep them distinct in trace_report."""
    from tools.trace_report import build_frames

    rec = FlightRecorder(capacity=256, clock=lambda: 0.0)
    for i in range(130):                      # > 2 full tid cycles
        tr = rec.begin("a", t=float(i))
        tr.mark("capture", float(i), float(i) + 0.001)
        rec.drop(tr, "submit")
    frames = build_frames(rec.export_trace_events())
    assert len(frames) == 130
    assert all(f["total_ms"] == pytest.approx(1.0) for f in frames)


def test_mesh_submit_seq_accounts_for_inflight_window():
    """Regression (review finding): with frames in the in-flight window,
    _submit must return the seq the NEW frame will harvest under — not
    the in-flight frame's — or trace correlation shifts off by one in
    mesh steady state. Stale-generation entries (a migrated binding's
    leftovers) must NOT count: their harvests are dropped, not
    delivered."""
    from selkies_tpu.parallel.coordinator import MeshEncodeCoordinator
    from selkies_tpu.robustness import FakeMeshEncoder

    coord = MeshEncodeCoordinator(
        "session:1", 1, 64, 48, enc_factory=lambda n: FakeMeshEncoder(n),
        slots_per_lane=1, max_lanes=1)
    coord.stop()                       # no ticking: window driven by hand
    facade = coord.acquire(64, 48)
    coord.stop()
    with coord._lock:
        sess = coord._sessions[facade.sid]
        sess.seq = 5
        sess.lane.inflight_q.append(
            (object(), [(sess, 0, sess.gen)], (0.0, 0.0)))      # counts
        sess.lane.inflight_q.append(
            (object(), [(sess, 0, sess.gen - 1)], (0.0, 0.0)))  # stale
    assert facade.try_submit("frame") == 6    # 5 + 1 in-flight (live gen)
    # a second submit before the tick replaces the pending frame: drop
    assert facade.try_submit("frame2") is None
    # ... and says under which seq the replacing frame will harvest
    assert facade.replaced_seq == 6


# ---------------------------------------------------------------------------
# served path: ACK-RTT + span closure through the real ws_handler


class FakeEncoder:
    """Minimal pipelined-encoder lookalike whose submit returns no seq —
    exercising the capture loop's FIFO trace correlation."""

    def __init__(self):
        self.submitted = 0
        self._ready = []
        self.closed = False

    def submit(self, frame):
        self.submitted += 1
        self._ready.append(
            (self.submitted,
             [StripeOutput(y_start=0, height=64,
                           jpeg=b"\xff\xd8FAKE\xff\xd9",
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
        self.width, self.height = width, height

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
    server = DataStreamingServer(
        settings, app=app,
        encoder_factory=lambda w, h, s, overrides=None: FakeEncoder(),
        source_factory=lambda w, h, fps, **kw: FakeSource(w, h, fps),
        host="127.0.0.1",
    )
    app.data_server = server
    return server


async def wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(interval)
    return False


async def open_client(server, settings_body):
    ws = InProcessClient()
    task = asyncio.create_task(server.ws_handler(ws))
    assert await wait_until(lambda: len(ws.sent) >= 2, timeout=5.0)
    ws.feed("SETTINGS," + json.dumps(settings_body))
    return ws, task


async def close_client(ws, task):
    await ws.close()
    try:
        await asyncio.wait_for(task, 5.0)
    except asyncio.TimeoutError:
        task.cancel()


SETTINGS_BODY = {"displayId": "primary", "initialClientWidth": 320,
                 "initialClientHeight": 240, "framerate": 60}


@pytest.mark.anyio
async def test_ack_rtt_closes_spans_through_real_handler():
    server = make_server()
    ws, task = await open_client(server, SETTINGS_BODY)
    try:
        assert await wait_until(lambda: len(ws.binary()) >= 3)
        # ack every delivered frame like the browser client does
        acked = set()
        for raw in list(ws.binary()):
            f = unpack_binary(bytes(raw))
            if isinstance(f, VideoStripe) and f.frame_id not in acked:
                acked.add(f.frame_id)
                ws.feed(f"CLIENT_FRAME_ACK {f.frame_id}")
        assert await wait_until(
            lambda: server.recorder.acked_total >= len(acked))
        summ = server.recorder.summary("primary")
        st = summ["stages"]
        # the full wire half of the path was measured per frame
        for stage in ("capture", "queue", "send", "ack"):
            assert stage in st, f"missing stage {stage}: {st.keys()}"
            assert st[stage]["p50_ms"] >= 0.0
        assert "glass_to_glass_p50_ms" in summ
        # ack RTT is bounded by the observed end-to-end wall
        assert st["ack"]["p50_ms"] <= summ["glass_to_glass_p95_ms"]
    finally:
        await close_client(ws, task)
        await server.stop()
    assert server.recorder.open_spans() == 0


@pytest.mark.anyio
@pytest.mark.parametrize("fault", ["capture.raise", "encode.raise",
                                   "fetch.hang", "ws.drop"])
async def test_chaos_faults_leave_no_open_spans(fault):
    """ISSUE 13 acceptance: each fault class produces terminal marks,
    never recorder growth (capture.raise -> restart drops; encode.raise
    -> dropped@submit; fetch.hang -> watchdog restart; ws.drop ->
    send/queue/reset drops)."""
    server = make_server(
        SELKIES_SUPERVISOR_MAX_RESTARTS="50",
        SELKIES_WATCHDOG_FRAMES="30",
    )
    ws, task = await open_client(server, SETTINGS_BODY)
    try:
        assert await wait_until(lambda: len(ws.binary()) >= 2)
        server.faults.arm(fault, times=2,
                          arg="0.3" if fault == "fetch.hang" else None)
        await asyncio.sleep(0.5)
        assert await wait_until(
            lambda: server.faults.fired.get(fault, 0) >= 1)
    finally:
        await close_client(ws, task)
        await server.stop()
    rec = server.recorder
    assert rec.open_spans() == 0, (
        f"{fault}: {rec.open_spans()} spans leaked")
    assert rec.closed_total > 0
    if fault in ("capture.raise", "encode.raise"):
        # the fault cost frames, and each loss carries a terminal mark
        terminals = {t.terminal
                     for t in rec._completed() if t.terminal}
        assert any(term.startswith("dropped@") for term in terminals), \
            terminals


@pytest.mark.anyio
async def test_health_payload_carries_stage_breakdown():
    server = make_server()
    ws, task = await open_client(server, SETTINGS_BODY)
    try:
        assert await wait_until(lambda: len(ws.binary()) >= 2)
        for raw in list(ws.binary())[:3]:
            f = unpack_binary(bytes(raw))
            if isinstance(f, VideoStripe):
                ws.feed(f"CLIENT_FRAME_ACK {f.frame_id}")
        assert await wait_until(lambda: server.recorder.closed_total >= 1)
        payload = json.loads(server._health_payload())
        d = payload["displays"]["primary"]
        assert "stages" in d
        assert "capture" in d["stages"]
        assert {"p50_ms", "p95_ms"} <= set(d["stages"]["capture"])
    finally:
        await close_client(ws, task)
        await server.stop()


@pytest.mark.anyio
async def test_ack_racing_transport_send_still_closes_span():
    """Regression (review finding): under write backpressure the client
    can ACK while the drainer is still suspended in ws.send — the span
    must already be registered for correlation, not expire later."""
    from selkies_tpu.robustness import BoundedSendQueue
    from selkies_tpu.server.data_server import _ClientSendQueue

    rec = FlightRecorder(capacity=16)
    gate = asyncio.Event()
    sent_payloads = []

    class SlowWs:
        async def send(self, payload):
            sent_payloads.append(payload)
            await gate.wait()          # transport backpressure

    cq = _ClientSendQueue(SlowWs(), BoundedSendQueue(max_video=8),
                          on_evict=lambda c: None, recorder=rec)
    try:
        tr = rec.begin("primary")
        tr.mark("capture", tr.t0, tr.t0 + 0.001)
        tr.frame_id = 7
        cq.offer_traced(b"\x03payload", tr)
        # the payload reached the transport but send has not returned
        assert await wait_until(lambda: len(sent_payloads) == 1)
        out = rec.ack("primary", 7)    # ACK lands mid-send
        assert out is tr and tr.terminal == "acked"
        gate.set()                     # transport drains afterwards
        await asyncio.sleep(0.05)
        assert rec.open_spans() == 0
        assert rec.acked_total == 1 and rec.expired_total == 0
    finally:
        cq.close()


# ---------------------------------------------------------------------------
# metrics HTTP endpoint hardening


def test_http_endpoint_healthz_trace_and_nonfatal_bind():
    m = Metrics(port=0)
    rec = FlightRecorder(capacity=8)
    tr = rec.begin("primary")
    tr.mark("capture", tr.t0, tr.t0 + 0.001)
    rec.drop(tr, "submit")
    m.recorder = rec
    assert m.start_http() is True
    try:
        base = f"http://127.0.0.1:{m.http_port}"
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert r.status == 200 and r.read() == b"ok\n"
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            assert b"trace_open_spans" in r.read()
        with urllib.request.urlopen(base + "/debug/trace?s=9999",
                                    timeout=5) as r:
            data = json.loads(r.read())
            assert data["displayTimeUnit"] == "ms"
            assert any(e.get("ph") == "X" for e in data["traceEvents"])
        # jax tracing is opt-in: 403 until the setting enables it
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/debug/jax-trace", timeout=5)
        assert exc.value.code == 403
        # a second server on the same port must NOT raise — bind
        # failure logs and disables (the data server stays up)
        m2 = Metrics(port=m.http_port)
        assert m2.start_http() is False
    finally:
        m.stop_http()


def test_stage_names_stable():
    """The stage glossary is a wire/bench/docs contract: the eight work
    stages, the four waits between them (in path order), the ready
    watch's three inside ``in_device`` + ``fetch_wait``, and a mesh lane's
    ``lane_step``, which lies across its frame's others."""
    assert STAGES == ("capture", "submit_wait", "pipe_wait", "stage",
                      "dispatch", "in_device", "device_wait", "device_run",
                      "ready_wait", "fetch_wait", "pack", "lane_step",
                      "harvest_wait", "queue", "send", "ack")
