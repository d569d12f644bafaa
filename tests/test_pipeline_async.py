"""Async pipeline driver + donated staging ring (ISSUE 12).

Covers the tentpole's safety obligations, not its throughput claims
(benchmark/run.py measures those on the real chip):

* in-flight depth is bounded — behind a full pipeline one capture
  waits, and a newer one takes its place (ISSUE 43: a mailbox of one,
  latest wins, the lane facade's contract) instead of a queue growing;
* ``flush()`` drains deterministically, including when the drain
  errors mid-way;
* donated staging buffers are never read (or re-donated) after
  donation — the ring's use-after-donate guard falls back to a fresh
  allocation instead;
* a supervisor-style restart mid-flight (close + rebuild) neither
  deadlocks nor leaks a ring slot;
* a capture is launched when at most the running step of its stream is
  unfinished on the chip (ISSUE 47: the pipes' ``has_room`` over a
  pretend chip), whatever ``depth`` would admit: the chip never waits,
  the host launches after its pack, and a silent or stopped ready watch
  wedges nothing;
* slow-marked soak: ~10 s under ``fetch.hang`` chaos with no wedge and
  no monotonic in-flight growth.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from types import SimpleNamespace

import numpy as np
import pytest

from selkies_tpu.encoder.async_driver import AsyncEncodeDriver
from selkies_tpu.encoder.h264_device import StagingRing
from selkies_tpu.encoder.pipeline import _PipelineTelemetry
from selkies_tpu.robustness import FaultInjector


#: geometries match test_h264_batch (128x96, stripe 32) and
#: test_jpeg_encoder (160x128, stripe 64)
def _frame(h=128, w=160, seed=0):
    return np.random.RandomState(seed).randint(0, 255, (h, w, 3), np.uint8)


# ---------------------------------------------------------------------------
# staging ring


@pytest.fixture(params=["default device", "last device"])
def device(request):
    """Where a ring stages: the default device (None, the solo
    encoders') or another one (a lane's ring for one of its chips)."""
    import jax

    return None if request.param == "default device" else jax.devices()[-1]


def _on(device, *staged):
    """Every staged array lives where its ring was told to put it."""
    import jax

    want = {jax.devices()[0] if device is None else device}
    return all(a.devices() == want for a in staged)


def test_staging_ring_ping_pongs_and_releases(device):
    ring = StagingRing(depth=2, device=device)
    a, ta = ring.stage(_frame(seed=1))
    assert ta is not None and ring.in_use == 1
    b, tb = ring.stage(_frame(seed=2))
    assert tb is not None and ring.in_use == 2
    np.testing.assert_array_equal(np.asarray(a), _frame(seed=1))
    np.testing.assert_array_equal(np.asarray(b), _frame(seed=2))
    assert _on(device, a, b)
    ring.release(ta)
    ring.release(tb)
    assert ring.in_use == 0
    # the freed slots are reused (donated) in rotation
    c, tc = ring.stage(_frame(seed=3))
    assert tc == ta
    np.testing.assert_array_equal(np.asarray(c), _frame(seed=3))
    assert ring.stalls_total == 0
    assert _on(device, c)


def test_use_after_donate_guard_never_donates_busy_slot(device):
    """A slot whose ticket is still held must NOT be donated: the guard
    allocates fresh instead (counted), and the busy slots' arrays stay
    readable — the in-flight batch that references them is safe."""
    ring = StagingRing(depth=2, device=device)
    a, ta = ring.stage(_frame(seed=1))
    b, tb = ring.stage(_frame(seed=2))
    c, tc = ring.stage(_frame(seed=3))     # ring exhausted → fallback
    assert tc is None
    assert ring.stalls_total == 1
    assert ring.in_use == 2                # fallback holds no slot
    # the would-be-donated slot was not touched: both staged arrays are
    # still alive and bit-exact
    np.testing.assert_array_equal(np.asarray(a), _frame(seed=1))
    np.testing.assert_array_equal(np.asarray(b), _frame(seed=2))
    np.testing.assert_array_equal(np.asarray(c), _frame(seed=3))
    assert _on(device, a, b, c)            # the fallback too
    ring.release(ta)
    _, td = ring.stage(_frame(seed=4))     # freed slot donates again
    assert td == ta
    ring.release(tb)
    ring.release(td)
    ring.release(None)                      # fallback ticket is a no-op
    assert ring.in_use == 0


def test_staging_ring_shape_change_starts_fresh_lane(device):
    ring = StagingRing(depth=2, device=device)
    _, t0 = ring.stage(_frame(96, 128))
    assert ring.in_use == 1
    staged, t1 = ring.stage(_frame(64, 64))     # resize: new lane
    assert staged.shape == (64, 64, 3) and _on(device, staged)
    assert ring.in_use == 1 and t1 is not None


def test_stale_ticket_from_retired_lane_is_a_noop(device):
    """A ticket issued before a shape change must NOT free the new
    lane's same-index slot: that slot's array may ride an in-flight
    batch, and freeing it would let the next stage() donate (delete)
    a live buffer."""
    ring = StagingRing(depth=2, device=device)
    _, ta = ring.stage(_frame(96, 128))         # lane A, slot 0
    _, tb = ring.stage(_frame(64, 64))          # lane B, slot 0 (A retired)
    assert ring.in_use == 1
    ring.release(ta)                            # stale A-ticket: no-op
    assert ring.in_use == 1
    _, tc = ring.stage(_frame(64, 64))          # lane B, slot 1
    _, td = ring.stage(_frame(64, 64))          # exhausted → guard, not donate
    assert td is None and ring.stalls_total == 1
    ring.release(tb)
    ring.release(tc)
    assert ring.in_use == 0


# ---------------------------------------------------------------------------
# driver on a stub pipe (no jax — pure threading semantics)


class _StubPipe:
    """Pipelined-encoder lookalike with a controllable completion gate:
    while the gate is cleared, 'fetches' never land, so submit() blocks
    once the depth is reached — the shape of a stalled transport."""

    def __init__(self, depth=3, fail_on=()):
        self.depth = depth
        self.metrics = None
        self.gate = threading.Event()
        self.gate.set()
        self.arrive = threading.Semaphore(0)
        self._inflight: deque = deque()
        self._ready: list = []
        self._seq = 0
        self.fail_on = set(fail_on)
        self.closed = False
        #: submits that found the pipe full (the driver makes none)
        self.submitted_full = 0

    @property
    def n_inflight(self):
        return len(self._inflight)

    @property
    def has_room(self):
        return len(self._inflight) < self.depth

    def submit(self, frame):
        self.submitted_full += not self.has_room
        while len(self._inflight) >= self.depth:
            # like the real pipelines: a full submit harvests the oldest
            # into the ready list for the next poll/flush
            self._ready.append(self._drain_one())
        if self._seq in self.fail_on:
            self._seq += 1
            raise RuntimeError("injected submit failure")
        seq = self._seq
        self._seq += 1
        self._inflight.append((seq, frame))
        return seq

    def release(self, n):
        """Let exactly ``n`` frames arrive while the gate stays shut."""
        self.arrive.release(n)

    def _drain_one(self):
        while not self.gate.is_set() and not self.arrive.acquire(
                timeout=0.005):
            pass
        seq, frame = self._inflight.popleft()
        return (seq, [frame])           # the "stripes" say which capture

    def poll(self, flush_partial=True, wait=False):
        out, self._ready = self._ready, []
        if wait and self._inflight:
            out.append(self._drain_one())       # blocks at the gate
        while self._inflight and self.gate.is_set():
            out.append(self._drain_one())
        return out

    def flush(self):
        out, self._ready = self._ready, []
        while self._inflight:
            out.append(self._drain_one())
        return out

    def stats(self):
        return {"frames": self._seq}

    def close(self):
        self.closed = True
        self._inflight.clear()


def _wait_until(cond, timeout=5.0):
    t_end = time.monotonic() + timeout
    while not cond() and time.monotonic() < t_end:
        time.sleep(0.002)
    return cond()


def _submit_taken(drv, frame):
    """Submit, and wait until the driver thread took the capture out of
    the mailbox (the pipe had room): what a source slower than the step
    looks like."""
    seq = drv.try_submit(frame)
    assert seq is not None and drv.replaced_seq is None
    assert _wait_until(lambda: not drv._in_q)
    return seq


class _CountingMetrics:
    dropped = 0

    def inc_frames_dropped(self):
        self.dropped += 1


def test_driver_bounds_inflight_and_keeps_only_the_newest_capture():
    pipe = _StubPipe(depth=3)
    pipe.gate.clear()                       # nothing ever completes
    metrics = _CountingMetrics()
    drv = AsyncEncodeDriver(pipe, metrics=metrics)
    try:
        accepted = replaced = 0
        for i in range(50):
            if drv.try_submit(i) is not None:
                accepted += 1
                assert drv.replaced_seq is None
            else:
                replaced += 1
                # never a refusal: the newcomer rides on, under the seq
                # of the capture it found waiting
                assert drv.replaced_seq == accepted - 1
            time.sleep(0.005)
        # the pipe holds at most depth, the mailbox one: nothing grows
        assert pipe.n_inflight <= pipe.depth
        assert accepted <= pipe.depth + 1
        assert drv.n_inflight == pipe.depth + 1
        assert replaced == 50 - accepted > 0
        assert drv.frames_dropped_total == replaced == metrics.dropped
        st = drv.stats()
        assert st["frames_replaced"] == st["frames_dropped"] == replaced
        assert st["submit_queue_depth"] == 1
        with drv._cond:
            assert [f for _s, f, _t in drv._in_q] == [49]   # the newest
    finally:
        pipe.gate.set()
        drv.close()                          # non-blocking teardown
    drv._thread.join(timeout=10.0)           # thread reaps itself
    assert not drv._thread.is_alive()
    assert pipe.closed                       # thread-side cleanup ran


@pytest.mark.parametrize("paced", [True, False], ids=["paced", "burst"])
def test_driver_flush_drains_deterministically_in_order(paced):
    """Paced (each capture taken before the next comes) a mailbox and a
    queue are the same thing: every capture comes out. In a burst the
    survivors come out, in submission order, each under the seq its
    acceptance or its predecessor's gave it, the last capture among
    them; ``flush()`` returns with the mailbox empty."""
    pipe = _StubPipe(depth=4)
    drv = AsyncEncodeDriver(pipe)
    try:
        if paced:
            seqs = [_submit_taken(drv, i) for i in range(9)]
            assert seqs == list(range(9))
        else:
            seqs = [s for s in (drv.try_submit(i) for i in range(9))
                    if s is not None]
        out = drv.flush()
        assert not drv._in_q
        assert [s for s, _ in out] == seqs   # every seq given, in order
        frames = [stripes[0] for _s, stripes in out]
        assert frames == sorted(frames) and frames[-1] == 8
        if paced:
            assert frames == list(range(9))
        assert len(out) + drv.frames_replaced_total == 9
        assert drv.flush() == []             # drained means drained
    finally:
        drv.close()


def test_driver_flush_survives_submit_errors():
    pipe = _StubPipe(depth=4, fail_on={2})
    drv = AsyncEncodeDriver(pipe)
    errors = []
    drv.on_error = errors.append
    try:
        for i in range(5):
            _submit_taken(drv, i)
        out = drv.flush()
        # frame 2 died; the other four complete with the RIGHT seqs
        assert len(out) == 4
        assert [s for s, _ in out] == [0, 1, 3, 4]
        assert [stripes for _s, stripes in out] == [[0], [1], [3], [4]]
        assert drv.encode_errors_total >= 1
        assert errors and isinstance(errors[0], RuntimeError)
    finally:
        drv.close()


def test_behind_a_full_pipe_the_newest_capture_waits_under_the_oldest_seq():
    """ISSUE 43. A pipe whose oldest frame is slow to arrive: the driver
    fills the pipe's free slots; of the captures a, b, c that come then,
    a waits in the mailbox, b takes its place and c takes b's, each under
    a's seq and with its own acceptance time; none leaves the mailbox
    while the pipe is full; the slot freed by the oldest frame's arrival
    takes c, and frames come out in submission order."""
    pipe = _StubPipe(depth=3)
    pipe.gate.clear()                        # the oldest is not in yet
    drv = AsyncEncodeDriver(pipe)
    try:
        assert [_submit_taken(drv, f) for f in "xyz"] == [0, 1, 2]
        assert _wait_until(lambda: pipe.n_inflight == 3)
        t0 = time.monotonic()
        assert drv.try_submit("a") == 3 and drv.replaced_seq is None
        time.sleep(0.02)
        assert drv.try_submit("b") is None and drv.replaced_seq == 3
        time.sleep(0.02)
        t_c = time.monotonic()
        assert drv.try_submit("c") is None and drv.replaced_seq == 3
        time.sleep(0.05)                     # the driver waits, takes none
        assert pipe.n_inflight == 3 and pipe.submitted_full == 0
        with drv._cond:
            (seq, frame, t_accepted), = drv._in_q
            assert (seq, frame) == (3, "c")
            assert t0 < t_c <= t_accepted    # the survivor's own reading
            assert sorted(drv._waits) == [0, 1, 2]    # only those taken out
        assert drv.frames_dropped_total == drv.frames_replaced_total == 2
        st = drv.stats()
        assert (st["frames_dropped"], st["frames_replaced"]) == (2, 2)
        assert st["submit_queue_depth"] == 1 and drv.n_inflight == 4
        assert drv.poll() == []

        # the oldest arrives, and only it: one slot, and c takes it
        pipe.release(1)
        assert _wait_until(lambda: not drv._in_q)
        assert _wait_until(lambda: pipe.n_inflight == 3)
        assert drv.poll() == [(0, ["x"])]
        with drv._cond:
            assert drv._waits[3][0] == t_accepted     # submit_wait is c's
        assert pipe.submitted_full == 0
        # a plain acceptance again: a new seq, nothing replaced
        assert drv.try_submit("d") == 4 and drv.replaced_seq is None

        # flush() mid-flight: every survivor, in submission order, and
        # the mailbox empty
        pipe.gate.set()
        assert drv.flush() == [(1, ["y"]), (2, ["z"]), (3, ["c"]), (4, ["d"])]
        assert not drv._in_q
        assert pipe.submitted_full == 0 and pipe.n_inflight == 0
        assert drv.frames_dropped_total == 2
    finally:
        pipe.gate.set()
        drv.close()
    drv._thread.join(timeout=10.0)
    assert pipe.closed and pipe.submitted_full == 0


def test_close_midflight_with_a_capture_waiting_neither_blocks_nor_answers():
    """close() with the thread blocked on a frame that is late and a
    capture in the mailbox: returns at once, the waiting capture is
    abandoned, a capture that comes after it is refused (None, and no
    ``replaced_seq``: there is nothing it could ride under)."""
    pipe = _StubPipe(depth=3)
    pipe.gate.clear()
    drv = AsyncEncodeDriver(pipe)
    try:
        for i in range(3):
            _submit_taken(drv, i)
        assert _wait_until(lambda: pipe.n_inflight == 3)
        assert drv.try_submit(3) == 3
        assert drv.try_submit(4) is None and drv.replaced_seq == 3
        t0 = time.monotonic()
        drv.close()
        assert time.monotonic() - t0 < 1.0
        assert not drv._in_q and drv.stats()["submit_queue_depth"] == 0
        assert drv.try_submit(99) is None and drv.replaced_seq is None
        assert drv.frames_replaced_total == 1         # a refusal is none
    finally:
        pipe.gate.set()
        drv.close()
    drv._thread.join(timeout=10.0)
    assert not drv._thread.is_alive()
    assert pipe.closed and pipe.submitted_full == 0


def test_flush_with_a_capture_waiting_behind_a_full_pipe_takes_it_too():
    """A flush asked for while a capture waits behind a full pipe drains
    the pipe, the survivor after it, and acknowledges only then."""
    pipe = _StubPipe(depth=2)
    pipe.gate.clear()
    drv = AsyncEncodeDriver(pipe)
    try:
        for i in range(2):
            _submit_taken(drv, i)
        assert _wait_until(lambda: pipe.n_inflight == 2)
        assert drv.try_submit("old") == 2
        assert drv.try_submit("new") is None and drv.replaced_seq == 2
        got = []
        t = threading.Thread(target=lambda: got.extend(drv.flush()))
        t.start()
        time.sleep(0.05)
        assert t.is_alive() and drv._in_q    # nothing arrives: it waits
        pipe.gate.set()
        t.join(timeout=10.0)
        assert not t.is_alive()
        assert got == [(0, [0]), (1, [1]), (2, ["new"])]
        assert not drv._in_q and pipe.n_inflight == 0
    finally:
        pipe.gate.set()
        drv.close()


def test_no_capture_is_lost_twice_or_delivered_twice_under_contention():
    """The mailbox is shared by the capture loop's thread and the driver
    thread. Hammered with the interpreter switching threads every 10 us:
    every capture offered is either harvested or counted as replaced,
    exactly once; the seqs given out are the seqs harvested, in order;
    pictures come out in the order they went in."""
    import sys

    pipe = _StubPipe(depth=2)
    drv = AsyncEncodeDriver(pipe)
    offered, seqs, out = 20000, [], []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for i in range(offered):
            seq = drv.try_submit(i)
            if seq is not None:
                seqs.append(seq)
            else:
                assert drv.replaced_seq == seqs[-1]
            if i % 64 == 0:
                out += drv.poll()
        out += drv.flush(timeout=30.0)
    finally:
        sys.setswitchinterval(interval)
        drv.close()
    drv._thread.join(timeout=10.0)
    assert not drv._thread.is_alive()
    assert [s for s, _ in out] == seqs == list(range(len(seqs)))
    frames = [stripes[0] for _s, stripes in out]
    assert frames == sorted(set(frames)) and frames[-1] == offered - 1
    assert len(out) + drv.frames_replaced_total == offered
    assert drv.frames_dropped_total == drv.frames_replaced_total
    assert 0 < len(out) <= offered and pipe.submitted_full == 0


# ---------------------------------------------------------------------------
# driver on the real pipelines


def _jpeg_driver(**kw):
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    pipe = PipelinedJpegEncoder(JpegStripeEncoder(160, 128), depth=3,
                                fetch_group=2)
    return AsyncEncodeDriver(pipe, **kw), pipe


def test_the_driver_reads_a_pipe_only_through_its_interface():
    """What the driver may ask of a pipe is ``depth``, ``has_room``,
    ``n_inflight``, ``submit``, ``poll``, ``flush``, ``stats``,
    ``pop_trace``, ``close`` and the few optional public names it probes
    with getattr: never a private field of its neighbour."""
    touched = []

    class _Sealed(_StubPipe):
        def __getattr__(self, name):        # only names _StubPipe lacks
            if name.startswith("_"):
                touched.append(name)
                raise AssertionError(f"driver read pipe.{name}")
            raise AttributeError(name)

    drv = AsyncEncodeDriver(_Sealed(depth=2))
    try:
        for _ in range(4):
            _submit_taken(drv, object())
        got = []
        deadline = time.monotonic() + 10.0
        while len(got) < 4 and time.monotonic() < deadline:
            got += drv.poll()               # an idle pass: sleep branch
            time.sleep(0.01)
        assert drv.try_submit(object()) is not None
        got += drv.flush(timeout=10.0)
        assert [seq for seq, _ in got] == list(range(5))
        assert drv._thread.is_alive()
    finally:
        drv.close()
        drv._thread.join(timeout=5.0)
    assert drv.pipe.closed
    assert touched == []


@pytest.mark.parametrize("name, value", [
    ("SELKIES_TPU_ASYNC_BATCH", "3"),
    ("SELKIES_TPU_H264_ENTROPY", "host"),
    ("SELKIES_TPU_ME", "scan"),
])
def test_no_environment_variable_selects_an_encode_path(
        monkeypatch, name, value):
    """The served H.264 path is one: the driver over a depth-4 pipe over
    the device rung's encoder, dispatching the Pallas search. The
    ladder's ``tpu_entropy`` override is the only way off it."""
    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder
    from selkies_tpu.server.data_server import default_encoder_factory
    from selkies_tpu.settings import Settings

    monkeypatch.setenv(name, value)
    settings = Settings(argv=[], env={"SELKIES_ENCODER": "x264enc-striped"})
    drv = default_encoder_factory(128, 96, settings)
    try:
        assert isinstance(drv, AsyncEncodeDriver)
        assert isinstance(drv.pipe, PipelinedH264Encoder)
        assert drv.pipe.depth == 4
        base = drv.pipe.base
        assert base.entropy == "device"

        class _Seen(Exception):
            pass

        def spy(*args, me, **kwargs):       # no compile: the static
            raise _Seen(me)                 # argument is all we ask

        monkeypatch.setattr(dev, "encode_frame_p_cavlc_rgb", spy)
        for st in base.stripes:
            st.need_idr = False             # straight to a P dispatch
        with pytest.raises(_Seen) as seen:
            base.dispatch(_frame(96, 128))
        assert seen.value.args == ("pallas",)
    finally:
        drv.close()


def test_driver_streams_real_jpeg_and_reports_gauges():
    drv, pipe = _jpeg_driver()
    try:
        want = 6
        sent = offered = 0
        deadline = time.monotonic() + 60.0
        while sent < want and time.monotonic() < deadline:
            # (a picture of its own for every capture: one that rides on
            # in a waiting one's place must not repeat the frame before)
            if drv.try_submit(_frame(seed=offered)) is not None:
                sent += 1
            offered += 1
            time.sleep(0.01)
        out = drv.flush()
        assert len(out) == sent == offered - drv.frames_replaced_total
        assert all(stripes for _s, stripes in out)   # every frame emitted
        st = drv.stats()
        for key in ("inflight_batches", "inflight_batches_max",
                    "dispatch_p50_ms", "fetch_wait_p50_ms",
                    "frames_dropped", "encode_errors"):
            assert key in st
        assert st["inflight_batches"] == 0           # drained
        assert st["dispatch_p50_ms"] > 0.0
    finally:
        drv.close()


def _h264_driver(**kw):
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

    pipe = PipelinedH264Encoder(
        H264StripeEncoder(128, 96, stripe_height=32, qp=26), depth=3)
    return AsyncEncodeDriver(pipe, **kw), pipe


@pytest.mark.parametrize("codec", ["jpeg", "h264"])
def test_through_the_driver_the_ready_stages_tile_in_device_and_fetch_wait(
        codec):
    """ISSUE 42, on the real pipes behind the driver thread: every frame
    whose stamp had landed at its harvest carries ``device_wait``,
    ``device_run`` and ``ready_wait``, each >= 0, adding up to
    ``in_device`` + ``fetch_wait`` (``fetch_wait`` end less ``dispatch``
    end) to the clock reading; ``in_device`` is what it was; ``stats()``
    counts the launches, and closing the driver ends the watch's thread."""
    drv, pipe = (_jpeg_driver if codec == "jpeg" else _h264_driver)()
    size = (128, 160) if codec == "jpeg" else (96, 128)
    ready = ("device_wait", "device_run", "ready_wait")
    try:
        sent, traces = [], []
        deadline = time.monotonic() + 120.0
        while len(sent) < 8 and time.monotonic() < deadline:
            seq = drv.try_submit(_frame(*size, seed=len(sent)))
            if seq is not None:
                sent.append(seq)
            time.sleep(0.01)
            traces += [drv.pop_trace(s) for s, _stripes in drv.poll()]
        traces += [drv.pop_trace(s) for s, _stripes in drv.flush()]
        assert len(traces) == len(sent) == 8 and None not in traces
        split = [tr for tr in traces if "device_run" in tr]
        st = drv.stats()
        assert st["launches"] == 8
        assert 1 <= st["launches_into_idle"] <= 8
        assert len(split) == 8 - st["ready_stamps_missed"] >= 6
        for tr in traces:
            d, f = tr["dispatch"], tr["fetch_wait"]
            assert tr["in_device"] == (d[1], max(d[1], f[0]))
        worst = 0.0
        for tr in split:
            d, f = tr["dispatch"], tr["fetch_wait"]
            w, r, q = (tr[s] for s in ready)
            assert d[1] == w[0] <= w[1] == r[0] <= r[1] == q[0] <= q[1] \
                == f[1]
            parts = sum(tr[s][1] - tr[s][0] for s in ready)
            both = sum(tr[s][1] - tr[s][0]
                       for s in ("in_device", "fetch_wait"))
            worst = max(worst, abs(parts - both))
        assert worst < 1e-9
        assert any(t.name == "tpuenc-ready" for t in threading.enumerate())
    finally:
        drv.close()
    drv._thread.join(timeout=30.0)
    deadline = time.monotonic() + 5.0
    while any(t.name == "tpuenc-ready" for t in threading.enumerate()) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    # (another test's pipe may hold one of its own: none is this pipe's)
    assert not pipe._ready_watch.alive


def test_restart_midflight_releases_ring_and_recovers():
    """Supervisor-style restart: close() with work in flight must return
    promptly, leave no busy staging slot behind, and a rebuilt driver
    must stream normally (the PR 2 restart path rebuilds the encoder)."""
    drv, pipe = _jpeg_driver()
    for i in range(3):
        drv.try_submit(_frame(seed=i))
    t0 = time.monotonic()
    drv.close()                              # mid-flight teardown
    assert time.monotonic() - t0 < 1.0       # close never blocks the loop
    drv._thread.join(timeout=30.0)           # thread reaps itself
    assert not drv._thread.is_alive()
    assert pipe._staging.in_use == 0         # no leaked ring slot
    # rebuilt pipeline streams fine (fresh ring, fresh thread)
    drv2, pipe2 = _jpeg_driver()
    try:
        sent = 0
        deadline = time.monotonic() + 60.0
        while sent < 3 and time.monotonic() < deadline:
            if drv2.try_submit(_frame(seed=sent)) is not None:
                sent += 1
            time.sleep(0.01)
        assert len(drv2.flush()) == sent
        assert pipe2._staging.in_use == 0
    finally:
        drv2.close()


def test_midpass_harvest_error_preserves_completed_frames_and_tickets():
    """A harvest raising mid-drain must not discard frames already
    completed in the same pass, and the failing frame's staging ticket
    must be released — under the async driver this is a steady-state
    catch-and-continue path, so a leak here accumulates forever."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

    enc = H264StripeEncoder(128, 96, stripe_height=32)
    pipe = PipelinedH264Encoder(enc, depth=8)
    pipe.submit(_frame(96, 128, seed=0))     # warm (IDR + compiles)
    pipe.submit(_frame(96, 128, seed=1))
    pipe.flush()

    orig = enc.harvest
    calls = {"n": 0}

    def harvest(p, host=None):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected harvest failure")
        return orig(p, host=host)

    enc.harvest = harvest
    pipe.submit(_frame(96, 128, seed=2))     # seq 2
    pipe.submit(_frame(96, 128, seed=3))     # seq 3
    with pytest.raises(RuntimeError):
        pipe.flush()
    enc.harvest = orig
    # seq 2 completed before the failure and must still surface
    assert [s for s, _ in pipe.flush()] == [2]
    # the failed frame's ring slot was freed, not leaked
    assert pipe._staging.in_use == 0
    pipe.close()                             # and its ready watch's thread


# ---------------------------------------------------------------------------
# the rule a capture is admitted by (ISSUE 47): the real ``has_room`` of
# the pipes' shared telemetry, over a pretend chip


class _StepOut:
    """A pretend step's output: ready at the instant the pretend chip has
    run its step (for the ready watch too, unless the chip is ``silent``:
    then the watch blocks for it until the pipe closes)."""

    def __init__(self, ready_at, silent=None):
        self.ready_at, self.silent = ready_at, silent

    def is_ready(self):
        return time.monotonic() >= self.ready_at

    def wait(self):
        time.sleep(max(0.0, self.ready_at - time.monotonic()))

    def block_until_ready(self):
        if self.silent is not None:
            self.silent.wait(30.0)
        self.wait()
        return self


class _ChipPipe(_PipelineTelemetry):
    """The pipes' telemetry, so their ``has_room``, over a pretend chip
    that runs one step at a time in launch order, ``step_s`` each; the
    host's work for a frame is ``launch_s`` at the launch and ``pack_s``
    at the harvest. ``launches`` and ``packs`` say what happened when:
    a launch's frame, its instant, the steps truly unfinished then (by
    the chip's own clock, not the watch's), how long the chip had stood
    idle, when its step begins, and whether the pipe's oldest frame had
    ended; a blocking wait's frame and whether its step had begun."""

    def __init__(self, depth=4, step_s=0.06, launch_s=0.002, pack_s=0.012,
                 silent=False):
        self.depth = depth
        self.step_s, self.launch_s, self.pack_s = step_s, launch_s, pack_s
        self.metrics = None
        self.silent = threading.Event() if silent else None
        self._inflight: deque = deque()          # (seq, frame, out)
        self._ready: list = []
        self._seq = 0
        self._free_at = 0.0
        self.launches: list = []
        self.packs: list = []
        self.blocked: list = []
        self.inflight_max = 0
        self._init_telemetry()

    inflight_batches = 0

    @property
    def n_inflight(self):
        return len(self._inflight)

    def submit(self, frame):
        assert self.n_inflight < self.depth      # the driver asked first
        ahead = self._ready_watch.ahead
        time.sleep(self.launch_s)
        now = time.monotonic()
        outs = [out for _s, _f, out in self._inflight]
        begins = max(now, self._free_at)
        self.launches.append(SimpleNamespace(
            frame=frame, t=now, begins=begins,
            unfinished=sum(not out.is_ready() for out in outs),
            idle_s=(now - self._free_at) if self._seq else 0.0,
            oldest_ended=not outs or outs[0].is_ready()))
        self._free_at = begins + self.step_s
        out = _StepOut(self._free_at, self.silent)
        self._launched(out, ahead)
        seq, self._seq = self._seq, self._seq + 1
        self._inflight.append((seq, frame, out))
        self.inflight_max = max(self.inflight_max, self.n_inflight)
        return seq

    def _drain_one(self):
        seq, frame, out = self._inflight[0]
        if not out.is_ready():
            # blocking for a step that has not even begun would be
            # blocking for the one before it too
            self.blocked.append(SimpleNamespace(
                seq=seq, begun=time.monotonic() >= out.ready_at - self.step_s))
            out.wait()
        self._inflight.popleft()
        t0 = time.monotonic()
        time.sleep(self.pack_s)
        self.packs.append(SimpleNamespace(seq=seq, t0=t0,
                                          t1=time.monotonic()))
        return seq, [frame]

    def poll(self, flush_partial=True, wait=False):
        if wait and self._inflight:
            self._ready.append(self._drain_one())
        while self._inflight and self._inflight[0][2].is_ready():
            self._ready.append(self._drain_one())
        out, self._ready = self._ready, []
        return out

    def flush(self):
        while self._inflight:
            self._ready.append(self._drain_one())
        out, self._ready = self._ready, []
        return out

    def stats(self):
        return {"frames": self._seq, **self._telemetry_stats()}

    def close(self):
        self._inflight.clear()
        if self.silent is not None:
            self.silent.set()
        self._ready_watch.stop()


class _DepthOnlyPipe(_ChipPipe):
    """The same pretend chip under the rule before ISSUE 47."""

    @property
    def has_room(self):
        return self.n_inflight < self.depth


def _offer(drv, seconds, period_s):
    """A source of numbered captures, one every ``period_s``: what was
    offered and when, and what came out meanwhile."""
    offered, out = [], []
    t_end = time.monotonic() + seconds
    while time.monotonic() < t_end:
        offered.append(time.monotonic())
        drv.try_submit(len(offered) - 1)
        out += drv.poll()
        time.sleep(period_s)
    return offered, out


def _reap(drv):
    drv.close()
    drv._thread.join(timeout=10.0)
    assert not drv._thread.is_alive()
    assert _wait_until(lambda: not drv.pipe._ready_watch.alive)


def test_a_source_faster_than_the_step_keeps_one_step_running_and_one_queued():
    """Above the knee (a capture every 10 ms, a step of 60): a capture is
    launched only when at most the running step is unfinished, after the
    pack of the frame that has just ended, and it is the newest one; the
    chip goes from each step to the next without standing idle; what was
    offered is what came out plus what was replaced."""
    pipe = _ChipPipe(depth=4, step_s=0.06)
    drv = AsyncEncodeDriver(pipe)
    try:
        offered, out = _offer(drv, 0.9, 0.010)
        out += drv.flush(timeout=10.0)
    finally:
        _reap(drv)
    ls, n = pipe.launches, len(pipe.launches)
    assert 10 <= n == len(out)
    assert len(offered) == len(out) + drv.frames_replaced_total
    assert [f for _s, (f,) in out] == [l.frame for l in ls]
    # one running and one queued, never more: depth alone would have made
    # it three unfinished at every launch
    assert max(l.unfinished for l in ls) <= 1
    assert pipe.inflight_max <= 3
    # ... and never fewer: the chip did not wait for the host
    assert [l.idle_s for l in ls[1:] if l.idle_s > 0.0] == []
    # pack, then feed: a launch comes after the pack of the frame two
    # before it, into the step that runs then
    packed = {p.seq: p.t1 for p in pipe.packs}
    assert all(ls[k].t >= packed[k - 2] for k in range(3, n))
    # the capture the chip is given is about a tick old when it is
    # launched and under a step and a half old when its step begins
    # (behind three unfinished steps it would be over two)
    for l in ls[3:]:
        assert l.t - offered[l.frame] < 0.010 + 0.030
        assert l.begins - offered[l.frame] < 1.5 * pipe.step_s
    st = drv.stats()
    assert st["launches"] == n
    assert st["launches_held_for_chip"] >= n - 3
    # the driver blocked for the step that ran, never for a queued one
    assert pipe.blocked and all(b.begun for b in pipe.blocked)


def test_a_step_under_its_tick_is_launched_as_before_and_nothing_is_held():
    """A step of 15 ms under a tick of 40: at every launch one step or
    none is unfinished, the rule holds nothing back, and every capture is
    launched, in order, as under ``depth`` alone."""
    runs = []
    for cls in (_ChipPipe, _DepthOnlyPipe):
        pipe = cls(depth=4, step_s=0.015, pack_s=0.003)
        drv = AsyncEncodeDriver(pipe)
        try:
            offered, out = _offer(drv, 0.5, 0.040)
            out += drv.flush(timeout=10.0)
        finally:
            _reap(drv)
        assert drv.frames_replaced_total == 0
        assert drv.stats()["launches_held_for_chip"] == 0
        assert max(l.unfinished for l in pipe.launches) <= 1
        runs.append(([l.frame for l in pipe.launches], len(offered),
                     [f for _s, (f,) in out]))
    for launched, n_offered, delivered in runs:
        assert launched == delivered == list(range(n_offered))


def test_where_the_host_is_the_slower_side_the_launch_comes_before_the_pack():
    """A traced window's regime, 27 ms of host work a frame (launch 6,
    pack 21) against a step of 25: at the top of a pass one step or none
    is unfinished, so the rule does not bind: the capture is launched
    before the frame that has ended is packed, as under ``depth`` alone,
    and the chip is given as many steps."""
    frames = {}
    for cls in (_ChipPipe, _DepthOnlyPipe):
        pipe = cls(depth=4, step_s=0.025, launch_s=0.006, pack_s=0.021)
        drv = AsyncEncodeDriver(pipe)
        try:
            offered, out = _offer(drv, 1.0, 0.0125)
            out += drv.flush(timeout=10.0)
        finally:
            _reap(drv)
        frames[cls] = len(out)
        assert len(offered) == len(out) + drv.frames_replaced_total
        if cls is _ChipPipe:
            ls = pipe.launches
            assert max(l.unfinished for l in ls) <= 1
            # the frame before had ended, unpacked, at (nearly) every
            # launch: the launch was not kept behind its pack, and few
            # were held back at all
            assert sum(l.oldest_ended for l in ls[2:]) >= 0.8 * (len(ls) - 2)
            assert drv.stats()["launches_held_for_chip"] <= 0.2 * len(ls)
            assert all(b.begun for b in pipe.blocked)
    assert frames[_ChipPipe] >= 0.85 * frames[_DepthOnlyPipe] >= 20


@pytest.mark.parametrize("watch", ["silent", "stopped"])
def test_a_ready_watch_that_says_nothing_wedges_neither_the_driver_nor_flush(
        watch):
    """Stamps that never land (the watch's thread blocked for good): the
    count of unfinished steps is never taken above the pipe's own frames,
    so the capture waiting is launched once the frames ahead of it are
    packed. A stopped watch (its owner's, or an array that raised):
    ``depth`` alone, as before. Either way frames come out, ``flush()``
    returns and what was offered is accounted for."""
    pipe = _ChipPipe(depth=4, step_s=0.02, pack_s=0.004,
                     silent=watch == "silent")
    if watch == "stopped":
        pipe._ready_watch.stop()
    drv = AsyncEncodeDriver(pipe)
    try:
        offered, out = _offer(drv, 0.5, 0.005)
        assert len(out) >= 5                      # it streams meanwhile
        t0 = time.monotonic()
        out += drv.flush(timeout=10.0)
        assert time.monotonic() - t0 < 2.0 and not drv._in_q
        assert pipe.n_inflight == 0
        assert len(offered) == len(out) + drv.frames_replaced_total
        assert [f for _s, (f,) in out] == sorted(f for _s, (f,) in out)
        st = drv.stats()
        if watch == "silent":
            assert pipe._ready_watch.ahead == st["launches"] == len(out)
            assert pipe.inflight_max <= 2         # one packed, one launched
        else:
            assert (st["launches"], st["launches_held_for_chip"]) == (0, 0)
            assert pipe.inflight_max == pipe.depth
    finally:
        _reap(drv)


def test_flush_and_close_midflight_return_while_the_rule_holds_a_capture():
    """With one step running, one queued and a capture held back in the
    mailbox: ``flush()`` takes the survivor too and returns with nothing
    left anywhere; ``close()`` returns at once and the thread ends."""
    pipe = _ChipPipe(depth=4, step_s=0.08)
    drv = AsyncEncodeDriver(pipe)
    try:
        for i in range(2):
            _submit_taken(drv, i)
        assert drv.try_submit(2) == 2
        assert drv.try_submit(3) is None and drv.replaced_seq == 2
        time.sleep(0.02)
        # depth has room for two more, the chip's queue for none
        assert pipe.n_inflight == 2 < pipe.depth and drv._in_q
        assert not pipe.has_room
        out = drv.flush(timeout=10.0)
        assert [(s, f) for s, (f,) in out] == [(0, 0), (1, 1), (2, 3)]
        assert not drv._in_q and pipe.n_inflight == 0
        assert drv.stats()["launches_held_for_chip"] == 1

        for i in range(4, 6):
            _submit_taken(drv, i)
        assert drv.try_submit(6) is not None
        time.sleep(0.01)
        assert drv._in_q and pipe.n_inflight == 2
        t0 = time.monotonic()
        drv.close()
        assert time.monotonic() - t0 < 0.5
    finally:
        _reap(drv)
    assert not drv._in_q


# ---------------------------------------------------------------------------
# soak (slow): fetch.hang chaos — no wedge, no monotonic in-flight growth


@pytest.mark.slow
def test_soak():
    faults = FaultInjector()
    drv, pipe = _jpeg_driver()
    drv.faults = faults
    try:
        t_end = time.monotonic() + 10.0
        inflight_high = 0
        completed = 0
        i = 0
        next_arm = time.monotonic() + 0.5
        while time.monotonic() < t_end:
            if time.monotonic() >= next_arm:
                # repeated short D2H stalls at the driver's harvest site
                faults.arm("fetch.hang", times=1, arg="0.2")
                next_arm += 0.7
            drv.try_submit(_frame(seed=i % 7))
            i += 1
            completed += len(drv.poll())
            inflight_high = max(inflight_high,
                                drv.stats()["inflight_batches"])
            time.sleep(0.02)
        faults.disarm()
        completed += len(drv.flush())
        st = drv.stats()
        assert completed > 0                       # streamed through chaos
        assert inflight_high <= pipe.depth         # bounded, not monotonic
        assert st["inflight_batches"] == 0         # fully drained → no wedge
        assert pipe._staging.in_use == 0           # no leaked slots
    finally:
        drv.close()
