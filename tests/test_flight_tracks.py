"""The flight recorder's other rings and what writes them (ISSUE 25):

* a frame's flight through ``AsyncEncodeDriver`` has marks with no hole,
  with room in the pipe and with the pipe full; behind a full pipe the
  driver's mailbox keeps the newest capture, and the span that rides
  with a delivered frame is the one its own capture opened (ISSUE 43);
  where the step does not fit the tick a frame waits in the mailbox for
  the chip's queue and under a step on the chip (ISSUE 47);
* the driver thread's track never overlaps itself and covers the loop;
* the device probe writes clock pairs, from a thread of its own;
* the stall watch tells a blocked loop from a kept interpreter lock;
* ``PendingSpans`` (the capture loop's table) and the mesh lane's capture
  mark: through a ``session:N`` lane every delivered frame's capture mark
  brackets the source call whose content the frame shows.
"""

import asyncio
import io
import json
import threading
import time
from collections import deque

import numpy as np
import pytest
import websockets

from selkies_tpu.encoder.async_driver import AsyncEncodeDriver
from selkies_tpu.encoder.jpeg import StripeOutput
from selkies_tpu.encoder.pipeline import _PipelineTelemetry
from selkies_tpu.observability import STAGES, FlightRecorder
from selkies_tpu.observability.device_probe import DeviceProbe
from selkies_tpu.observability.stall_watch import StallWatch
from selkies_tpu.protocol import VideoStripe, unpack_binary
from selkies_tpu.server.app import StreamingApp
from selkies_tpu.server.data_server import DataStreamingServer
from selkies_tpu.settings import Settings


@pytest.fixture
def anyio_backend():
    return "asyncio"


class StepOut:
    """A pretend step's output, for the ready watch to block for."""

    def __init__(self, ready_at):
        self.ready_at = ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        return self


class FakePipe(_PipelineTelemetry):
    """A pipelined encoder with a pretend device: one step at a time,
    ``step_s`` each; ``submit`` blocks draining the oldest when ``depth``
    frames are in flight, as the real pipes do. A stripe's payload says
    which picture it was made from (the frame's first byte). ``watched``:
    each step's output goes to the ready watch, as the real pipes' does,
    so that ``has_room`` counts the steps unfinished."""

    def __init__(self, depth=4, step_s=0.004, stage_s=0.001, watched=False):
        self.depth, self.step_s, self.stage_s = depth, step_s, stage_s
        self.watched = watched
        self.metrics = None
        self.d2h_bytes_total = 0
        self._inflight = deque()        # [seq, trace, ready_at, level]
        self._ready = []
        self._seq = 0
        self._device_free_at = 0.0
        self._init_telemetry()

    inflight_batches = 0

    @property
    def n_inflight(self):
        return len(self._inflight)

    def stats(self):
        return {"frames": self._seq}

    def compiling_for_s(self):
        return 0.0

    def submit(self, frame):
        while len(self._inflight) >= self.depth:
            self._ready.append(self._drain_one(block=True))
        trace = {}
        t0 = time.monotonic()
        time.sleep(self.stage_s)
        t1 = time.monotonic()
        self._mark(trace, "stage", t0, t1)
        ahead = self._ready_watch.ahead
        self._device_free_at = max(self._device_free_at, t1) + self.step_s
        stamp = self._launched(StepOut(self._device_free_at), ahead) \
            if self.watched else None
        t2 = time.monotonic()
        self._mark(trace, "dispatch", t1, t2)
        seq, self._seq = self._seq, self._seq + 1
        self._inflight.append([seq, trace, self._device_free_at,
                               int(np.asarray(frame).flat[0]), stamp])
        return seq

    def _drain_one(self, block):
        seq, trace, ready_at, level, stamp = self._inflight[0]
        t0 = time.monotonic()
        if t0 < ready_at:
            if not block:
                return None
            time.sleep(ready_at - t0)
        self._inflight.popleft()
        t1 = time.monotonic()
        self._mark(trace, "fetch_wait", t0, t1)
        time.sleep(0.0005)
        self._mark(trace, "pack", t1, time.monotonic())
        self._trace_store(seq, trace, stamp)
        return seq, [StripeOutput(y_start=0, height=64,
                                  jpeg=b"\xff\xd8L%d\xff\xd9" % level,
                                  is_paintover=False)]

    def poll(self, flush_partial=True, wait=False):
        if wait and self._inflight:
            self._ready.append(self._drain_one(block=True))
        while self._inflight:
            got = self._drain_one(block=False)
            if got is None:
                break
            self._ready.append(got)
        out, self._ready = self._ready, []
        return out

    def flush(self):
        while self._inflight:
            self._ready.append(self._drain_one(block=True))
        out, self._ready = self._ready, []
        return out

    def close(self):
        self._inflight.clear()
        self._ready_watch.stop()


class NumberedSource:
    """Frames that say in their pixels which call made them: a flat grey
    of level ``8 * (n % 32)``; the calls are logged with their times."""

    def __init__(self, width, height, fps, log):
        self.width, self.height, self.log = width, height, log
        self.n = 0

    def start(self):
        pass

    def stop(self):
        pass

    def next_frame(self):
        t0 = time.monotonic()
        self.n += 1
        frame = np.full((self.height, self.width, 3), 8 * (self.n % 32),
                        np.uint8)
        self.log.append((self.n, t0, time.monotonic()))
        return frame


def make_server(tmp_path, monkeypatch, encoder_factory, log, **env):
    monkeypatch.setenv("SELKIES_UPLOAD_DIR", str(tmp_path / "uploads"))
    settings = Settings(argv=[], env=dict({"SELKIES_PORT": "0"}, **env))
    app = StreamingApp(settings)
    kw = {} if encoder_factory is None else {
        "encoder_factory": encoder_factory}
    server = DataStreamingServer(
        settings, app=app, host="127.0.0.1",
        source_factory=lambda w, h, fps, **_kw: NumberedSource(w, h, fps, log),
        **kw)
    app.data_server = server
    return server


async def serve_frames(server, seconds, fps=30, size=(320, 240)):
    """Join as the owning client, ACK every frame, and return the frames
    received: {frame_id: [payload, ...]}."""
    import websockets.asyncio.server as ws_server

    server._stop_event = asyncio.Event()
    srv = await ws_server.serve(server.ws_handler, "127.0.0.1", 0,
                                compression=None, max_size=None)
    server._server = srv
    port = srv.sockets[0].getsockname()[1]
    frames = {}
    try:
        async with websockets.connect(f"ws://127.0.0.1:{port}",
                                      max_size=None) as ws:
            assert await ws.recv() == "MODE websockets"
            await ws.recv()
            await ws.send("SETTINGS," + json.dumps({
                "displayId": "primary", "framerate": fps,
                "initialClientWidth": size[0],
                "initialClientHeight": size[1]}))
            t_end = None
            while t_end is None or time.monotonic() < t_end:
                try:
                    m = await asyncio.wait_for(ws.recv(), 120)
                except (asyncio.TimeoutError, websockets.ConnectionClosed):
                    break           # (a loaded machine: evicted as slow)
                if not isinstance(m, bytes):
                    continue
                f = unpack_binary(m)
                if isinstance(f, VideoStripe):
                    if t_end is None:       # the clock starts at the first
                        t_end = time.monotonic() + seconds
                    frames.setdefault(f.frame_id, []).append(f.payload)
                    try:
                        await ws.send(f"CLIENT_FRAME_ACK {f.frame_id}")
                    except websockets.ConnectionClosed:
                        break
            await asyncio.sleep(0.2)
    finally:
        await server.stop()
        srv.close()
    return frames


#: stages that are no part of the path: a lane's ``lane_step`` lies across
#: it, the ready watch's three inside ``in_device`` + ``fetch_wait``
OFF_PATH = ("lane_step", "device_wait", "device_run", "ready_wait")


def holes_ms(tr):
    """Gaps between a frame's consecutive stages, capture to send."""
    path = [s for s in STAGES[:STAGES.index("send") + 1]
            if s in tr.spans and s not in OFF_PATH]
    return [(a, b, (tr.spans[b][0] - tr.spans[a][1]) * 1000.0)
            for a, b in zip(path, path[1:])]


@pytest.mark.anyio
@pytest.mark.parametrize("full", [False, True], ids=["room", "pipe-full"])
async def test_a_frames_marks_run_from_capture_to_send_without_a_hole(
        tmp_path, monkeypatch, full):
    step_s = 0.06 if full else 0.004      # 30 fps in: 33 ms a frame
    pipes = []

    def factory(w, h, settings, overrides=None):
        pipes.append(FakePipe(depth=2 if full else 4, step_s=step_s))
        return AsyncEncodeDriver(pipes[-1])

    server = make_server(tmp_path, monkeypatch, factory, [])
    rec = server.recorder = FlightRecorder(capacity=4096)   # as a harness does
    await serve_frames(server, 1.5)
    done = [t for t in rec._completed() if t.terminal == "acked"]
    assert len(done) >= (10 if full else 25)
    # (lane_step is a mesh lane's, the ready watch's three split two of
    # these: no part of the path)
    want = [s for s in STAGES[:STAGES.index("send") + 1]
            if s not in OFF_PATH]
    whole = [t for t in done if all(s in t.spans for s in want)]
    assert len(whole) >= 0.9 * len(done), (
        [sorted(set(want) - set(t.spans)) for t in done][:5])
    clean = [t for t in whole if max(h for _a, _b, h in holes_ms(t)) <= 2.0
             and min(h for _a, _b, h in holes_ms(t)) >= -2.0]
    assert len(clean) >= 0.9 * len(whole), [holes_ms(t) for t in whole][:3]
    # the stages add up to the frame's capture-to-send time
    for t in clean:
        total = (t.spans["send"][1] - t.spans["capture"][0]) * 1000.0
        parts = sum(t.duration_ms(s) for s in want)
        assert parts == pytest.approx(total, abs=2.0 * len(want))
    waits = sorted(t.duration_ms("submit_wait") + t.duration_ms("pipe_wait")
                   for t in clean)
    if full:
        # one capture waits for a slot, and a newer one takes its place:
        # the survivor has waited a tick (33 ms) at most, where the
        # oldest of a queue of two waited two steps (120 ms)
        assert waits[len(waits) // 2] < 45.0
        assert rec.dropped_total > 0          # the older captures are lost
    else:
        assert waits[len(waits) // 2] < 5.0
    assert rec.open_spans() == 0


@pytest.mark.anyio
async def test_above_the_knee_a_frame_waits_in_the_mailbox_not_on_the_chip(
        tmp_path, monkeypatch):
    """ISSUE 47, through the capture loop: a step of 60 ms against a tick
    of 33 and a pipe of depth 4. A capture is launched when at most the
    running step is unfinished, so a delivered frame's ``device_wait`` is
    the rest of that step (under one step; behind three it was over two)
    and its wait for that moment lies in ``submit_wait``, where a newer
    capture takes its place: a tick at most."""
    step_ms, pipes = 60.0, []

    def factory(w, h, settings, overrides=None):
        pipes.append(FakePipe(depth=4, step_s=step_ms / 1000.0,
                              watched=True))
        return AsyncEncodeDriver(pipes[-1])

    server = make_server(tmp_path, monkeypatch, factory, [])
    rec = server.recorder = FlightRecorder(capacity=4096)
    await serve_frames(server, 2.0)
    done = [t for t in rec._completed()
            if t.terminal == "acked" and "device_wait" in t.spans]
    assert len(done) >= 15

    def p50(stage):
        v = sorted(t.duration_ms(stage) for t in done)
        return v[len(v) // 2]

    assert p50("device_wait") < step_ms
    assert p50("device_run") == pytest.approx(step_ms, abs=10.0)
    assert p50("in_device") + p50("fetch_wait") < 2 * step_ms + 10.0
    assert p50("submit_wait") < 33.4 + 15.0
    assert rec.dropped_total > 0              # the older captures are lost
    st = pipes[0]._telemetry_stats()
    assert st["launches_held_for_chip"] >= 0.8 * st["launches"] > 0
    assert rec.open_spans() == 0


def test_the_thread_track_never_overlaps_and_covers_a_busy_second():
    rec = FlightRecorder(capacity=4096)
    pipe = FakePipe(depth=2, step_s=0.012)
    drv = AsyncEncodeDriver(pipe)
    drv.recorder = rec          # the way the server hands it over
    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.2:
            drv.try_submit(np.zeros((8, 8, 3), np.uint8))
            drv.poll()
            time.sleep(0.005)
        t1 = time.monotonic()
        drv.flush()
        time.sleep(0.3)             # nothing to do: the thread sleeps
    finally:
        drv.close()
    assert "sleep" in {r[1] for r in rec.thread_track("tpuenc-async", t1)}
    rows = rec.thread_track("tpuenc-async", t0 + 0.1, t1 - 0.1)
    assert len(rows) > 50
    assert {r[1] for r in rows} >= {"stage", "dispatch", "fetch_wait",
                                    "pack", "emit"}
    for a, b in zip(rows, rows[1:]):
        assert a[3] <= b[2] + 1e-6, (a, b)         # never overlaps itself
    covered = sum(min(r[3], t1 - 0.1) - max(r[2], t0 + 0.1) for r in rows)
    assert covered >= 0.95 * (t1 - t0 - 0.2)
    # and it is exported as one more row of /debug/trace
    ev = rec.export_trace_events()["traceEvents"]
    assert any(e.get("cat") == "thread" and e["name"] == "fetch_wait"
               for e in ev)
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "tpuenc-async" for e in ev)


def test_marks_of_one_state_a_beat_apart_are_one_interval():
    rec = FlightRecorder()
    rec.thread_state("t", "sleep", 1.0, 1.002)
    rec.thread_state("t", "sleep", 1.00205, 1.004)     # 50 us later
    rec.thread_state("t", "pack", 1.004, 1.005)
    rec.thread_state("t", "sleep", 1.006, 1.008)       # after a hole
    assert rec.thread_track("t") == [
        ("t", "sleep", 1.0, 1.004), ("t", "pack", 1.004, 1.005),
        ("t", "sleep", 1.006, 1.008)]
    assert rec.thread_track("t", 1.0045, 1.0055) == [
        ("t", "pack", 1.004, 1.005)]


def test_the_probe_writes_clock_pairs_from_a_thread_of_its_own(monkeypatch):
    import jax

    rec = FlightRecorder()
    writers = set()

    def get_recorder():
        writers.add(threading.current_thread().name)
        return rec

    class Gauge:
        seen = []

        def set_device_queue_delay(self, dev, ms):
            self.seen.append((dev, ms))

    monkeypatch.setattr(DeviceProbe, "INTERVAL_S", 0.02)
    pipe = FakePipe()
    drv = AsyncEncodeDriver(pipe)
    drv.recorder = rec
    gauge = Gauge()
    probe = DeviceProbe(jax.devices()[0], get_recorder, lambda: gauge).start()
    try:
        assert probe.ready.wait(60) and probe.error is None
        t0 = time.monotonic()
        while len(rec.clock_pairs()) < 5 and time.monotonic() - t0 < 20:
            drv.try_submit(np.zeros((8, 8, 3), np.uint8))
            drv.poll()
            time.sleep(0.01)
    finally:
        probe.stop()
        drv.close()
        probe.join(5)
    pairs = rec.clock_pairs()
    assert len(pairs) >= 5
    assert all(dev == jax.devices()[0].id and a <= b for dev, a, b in pairs)
    # nothing of the probe runs on the driver thread, or on this one
    assert writers == {probe.thread_name}
    assert probe.thread_name not in {r[0] for r in rec.thread_track()}
    assert Gauge.seen and probe.last_delay_ms is not None
    assert probe.memory is None or isinstance(probe.memory, dict)


@pytest.mark.anyio
@pytest.mark.parametrize("kind", ["loop", "interpreter"])
async def test_the_stall_watch_tells_a_blocked_loop_from_a_kept_lock(kind):
    rec = FlightRecorder()
    watch = StallWatch(lambda: rec, loop=asyncio.get_running_loop()).start()
    big = list(np.random.default_rng(0).random(3_000_000))
    try:
        await asyncio.sleep(0.1)
        t0 = time.monotonic()
        if kind == "loop":
            time.sleep(0.06)      # gives the lock up: threads run, we do not
        else:
            # one C call that keeps the interpreter's lock (zlib and
            # time.sleep give it up; sorting floats does not), long enough
            # on any machine
            while time.monotonic() - t0 < 0.08:
                big.sort()
                big.reverse()
        t1 = time.monotonic()
        await asyncio.sleep(0.1)
    finally:
        watch.stop()
        watch.join(2)
    hit = [(k, a, b) for k, a, b in rec.stalls() if b > t0 and a < t1]
    assert any(k == kind and b - a >= 0.04 for k, a, b in hit), rec.stalls()
    if kind == "interpreter":
        # the loop stood still too, but that is not the loop's doing
        assert not any(k == "loop" for k, _a, _b in hit), hit
    assert rec.stalls(t1 + 1.0) == []
    ev = rec.export_trace_events()["traceEvents"]
    assert any(e.get("cat") == "thread" and e["name"] == kind for e in ev)


def test_stack_capture_keeps_every_threads_stack_beside_the_stall():
    rec = FlightRecorder()
    watch = StallWatch(lambda: rec, capture_stacks=True).start()
    big = list(np.random.default_rng(1).random(3_000_000))
    try:
        time.sleep(0.05)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.12:
            big.sort()
            big.reverse()
        time.sleep(0.05)
    finally:
        watch.stop()
        watch.join(2)
    kept = [rec.stall_stack(k, a) for k, a, _b in rec.stalls()
            if k == "interpreter"]
    assert any(s and "test_flight_tracks.py" in s for s in kept), kept


def test_pending_spans_close_what_they_lose_and_swap_on_a_mailbox():
    rec = FlightRecorder()
    pend = rec.pending()
    a, b, c = (rec.begin("d") for _ in range(3))
    pend.add(7, a)
    # a queueing encoder refused b: dropped at submit
    pend.refuse(b)
    assert b.terminal == "dropped@submit" and len(pend) == 1
    # a mailbox encoder kept c in a's place: a is the frame that was lost
    pend.refuse(c, replaced_seq=7)
    assert a.terminal == "dropped@submit" and c.terminal is None
    assert pend.take(7) is c and pend.take(7) is None
    # no seq: first in, first out; what is left when the encoder goes closes
    d, e = rec.begin("d"), rec.begin("d")
    pend.add(None, d)
    pend.add(None, e)
    assert pend.take(3) is d
    pend.drop_all("restart")
    assert e.terminal == "dropped@restart"
    rec.finish_empty(c)
    rec.finish_empty(d)
    assert rec.open_spans() == 0
    # capped: a pipeline that never harvests cannot grow the table
    many = [rec.begin("d") for _ in range(pend.CAP + 5)]
    for i, tr in enumerate(many):
        pend.add(i, tr)
    assert len(pend) == pend.CAP
    assert many[0].terminal == "dropped@submit"
    pend.drop_all("restart")
    assert rec.open_spans() == 0


@pytest.mark.anyio
async def test_through_a_lane_the_capture_mark_is_the_frames_own(
        tmp_path, monkeypatch):
    """``session:2`` on the CPU's virtual devices, the default (mesh)
    encoders: the lane holds one pending frame per session and replaces it
    when the next capture comes first. The span that rides with a delivered
    frame has to be the one its own capture opened."""
    from PIL import Image

    log = []
    server = make_server(tmp_path, monkeypatch, None, log,
                         SELKIES_TPU_MESH="session:2",
                         SELKIES_TPU_SESSIONS_PER_CHIP="1")
    rec = server.recorder
    frames = await serve_frames(server, 2.5, fps=120)
    assert server.mesh_stats["solo_fallback"] == 0
    assert len(frames) >= 10
    by_id = {}
    for tr in rec._completed():
        if tr.frame_id >= 0:
            by_id.setdefault(tr.frame_id, []).append(tr)
    checked = 0
    for fid, payloads in frames.items():
        level = float(np.asarray(Image.open(io.BytesIO(payloads[0]))
                                 .convert("L")).mean())
        shown = int(round(level / 8.0)) % 32
        assert len(by_id.get(fid, [])) == 1, fid      # ids did not wrap
        cap = by_id[fid][0].spans["capture"]
        calls = [n for n, a, b in log if cap[0] <= a and b <= cap[1]]
        assert len(calls) == 1, (fid, calls)
        assert calls[0] % 32 == shown, (fid, calls[0], shown)
        checked += 1
    assert checked >= 10
    # the lane did replace pending frames in this run: each one lost closed
    # its own span at submit, none rode on with another frame's picture
    lost = [t for t in rec._completed() if t.terminal == "dropped@submit"]
    assert lost, "no pending frame was replaced: the test did not bite"


#: the driver's stages of a frame, in the order they tile acceptance to
#: ``pack``'s end
DRIVER_STAGES = tuple(
    s for s in STAGES[STAGES.index("submit_wait"):STAGES.index("pack") + 1]
    if s not in OFF_PATH)


@pytest.mark.anyio
async def test_through_the_solo_driver_the_capture_mark_is_the_frames_own(
        tmp_path, monkeypatch):
    """The solo twin of the lane test above (ISSUE 43): a real
    ``AsyncEncodeDriver`` over a pipe that is held full (a step of 50 ms
    against a tick of 8.3), through ``_capture_loop``. The driver's
    mailbox keeps the newest capture under the waiting one's seq: every
    capture it lost closes ``dropped@submit``, and the span that rides
    with a delivered frame is the one its own capture opened."""
    log, drivers = [], []

    def factory(w, h, settings, overrides=None):
        drivers.append(AsyncEncodeDriver(FakePipe(depth=2, step_s=0.05)))
        return drivers[-1]

    server = make_server(tmp_path, monkeypatch, factory, log)
    rec = server.recorder = FlightRecorder(capacity=8192)
    frames = await serve_frames(server, 2.0, fps=120)
    assert len(drivers) == 1 and len(frames) >= 20
    drv = drivers[0]
    done = {}
    for tr in rec._completed():
        if tr.terminal == "acked":
            done.setdefault(tr.frame_id, []).append(tr)
    calls_at = [a for _n, a, _b in log]
    checked = 0
    for fid, payloads in frames.items():
        if fid not in done:
            continue                         # (cut off by the stop)
        assert len(done[fid]) == 1, fid
        tr = done[fid][0]
        shown = int(payloads[0][3:-2]) // 8
        cap = tr.spans["capture"]
        calls = [i for i, (_n, a, b) in enumerate(log)
                 if cap[0] <= a and b <= cap[1]]
        assert len(calls) == 1, (fid, calls)
        assert log[calls[0]][0] % 32 == shown, (fid, log[calls[0]], shown)
        # submit_wait begins at the survivor's own acceptance: after its
        # own capture, before the source was asked again
        t_acc = tr.spans["submit_wait"][0]
        later = calls_at[calls[0] + 1] if calls[0] + 1 < len(log) else t_acc
        assert cap[1] <= t_acc <= later, (fid, cap, t_acc, later)
        # and the seven stages tile acceptance to pack's end with no hole
        for a, b in zip(DRIVER_STAGES, DRIVER_STAGES[1:]):
            assert tr.spans[a][1] == tr.spans[b][0], (fid, a, b)
        assert all(tr.spans[s][0] <= tr.spans[s][1] for s in DRIVER_STAGES)
        checked += 1
    assert checked >= 20
    # the pipe stood full: a capture waited 8.3 ms at most where a queue's
    # oldest waited steps, and most captures were lost to a newer one
    waits = sorted(t[0].duration_ms("submit_wait") for t in done.values())
    assert waits[len(waits) // 2] < 25.0
    lost = [t for t in rec._completed() if t.terminal == "dropped@submit"]
    assert drv.frames_replaced_total > len(frames)
    assert len(lost) == drv.frames_replaced_total == drv.frames_dropped_total
    # a lost capture's span never rode on: it ends where it was refused
    assert all("submit_wait" not in t.spans for t in lost)
    assert rec.open_spans() == 0
