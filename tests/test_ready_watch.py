"""The ready watch (ISSUE 42): a thread of its own stamps the instant each
launched step's output became ready, and the harvest turns the stamp into
``device_wait``, ``device_run`` and ``ready_wait``, which tile ``in_device``
+ ``fetch_wait``.

* the watch itself, over arrays whose readiness the test drives by hand;
* ``ready_stages``: the tiling, the clipping, the missing stamp;
* both pipes: the H.264 pipe over a fake base encoder whose step output and
  fetch land when the test says, the JPEG pipe over the real tiny encoder
  with its step's ``packed`` wrapped so that the watch sees it ready when
  the test says;
* what the pipes admit a capture by (ISSUE 47, ``has_room``): the watch's
  count of unfinished steps, never above the pipe's own frames, and
  ``depth`` alone once the watch has stopped.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

from selkies_tpu.encoder.pipeline import (PipelinedH264Encoder,
                                          PipelinedJpegEncoder)
from selkies_tpu.observability import STAGES
from selkies_tpu.observability.device_probe import (ReadyStamp, ReadyWatch,
                                                    ready_stages)

READY = ("device_wait", "device_run", "ready_wait")
W, H = 160, 128


def until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


def threads_named(name):
    return [t for t in threading.enumerate() if t.name == name]


class Out:
    """A step's output buffer: ready when the test says (``land``)."""

    def __init__(self, raises=None, inner=None):
        self.gate = threading.Event()
        self.raises, self.inner = raises, inner

    def land(self):
        self.gate.set()

    def block_until_ready(self):
        assert self.gate.wait(10.0), "the test never let the step end"
        if self.raises is not None:
            raise self.raises
        return self

    # the JPEG pipe slices its step's output for the fetch
    def __getitem__(self, key):
        return self.inner[key]


def tiles(tr, missing_ok=False):
    """The three stages of one harvested frame add up to ``fetch_wait``
    end less ``dispatch`` end to the clock reading, each is >= 0, and
    ``in_device`` is what it was before there was a watch."""
    d, f = tr["dispatch"], tr["fetch_wait"]
    assert tr["in_device"] == (d[1], max(d[1], f[0]))
    if missing_ok and not set(READY) & set(tr):
        return False
    assert set(READY) <= set(tr) <= set(STAGES)
    w, r, q = (tr[s] for s in READY)
    assert w[0] == d[1] and w[1] == r[0] and r[1] == q[0] and q[1] == f[1]
    assert all(iv[1] >= iv[0] for iv in (w, r, q))
    parts = sum(iv[1] - iv[0] for iv in (w, r, q))
    assert parts == pytest.approx(f[1] - d[1], abs=1e-9)
    both = sum(tr[s][1] - tr[s][0] for s in ("in_device", "fetch_wait"))
    assert parts == pytest.approx(both, abs=1e-9)
    return True


# ---------------------------------------------------------------------------
# the watch


def test_the_watch_stamps_in_launch_order_and_counts_launches_into_idle():
    watch = ReadyWatch("test-ready")
    assert not watch.alive and watch.ahead == 0     # no launch, no thread
    a, b, c = Out(), Out(), Out()
    try:
        ahead = watch.ahead                  # read before the launch
        sa = watch.launched(a, ahead)        # found nothing ahead: idle
        assert watch.alive and threads_named("test-ready")
        ahead = watch.ahead
        assert ahead == 1                    # a is unfinished
        sb = watch.launched(b, ahead)
        assert (watch.launches, watch.launches_into_idle) == (2, 1)
        # b's buffer is ready first: the stamps still land in launch order
        b.land()
        time.sleep(0.02)
        assert sa.t_ready is None and sb.t_ready is None
        t0 = time.monotonic()
        a.land()
        until(lambda: watch.readied == 2)
        assert t0 <= sa.t_ready <= sb.t_ready <= time.monotonic()
        # each stamp knows when the step launched before it was ready
        assert sa.t_before is None and sb.t_before == sa.t_ready
        ahead = watch.ahead
        assert ahead == 0
        sc = watch.launched(c, ahead)        # everything before it ended
        assert (watch.launches, watch.launches_into_idle) == (3, 2)
        c.land()
        until(lambda: sc.t_ready is not None)
        assert watch.ahead == 0 and sc.t_before == sb.t_ready
    finally:
        watch.stop()
    watch.join(2.0)
    assert not watch.alive and not threads_named("test-ready")


def test_the_watch_drops_its_reference_when_the_array_is_ready():
    watch = ReadyWatch("test-ready")
    out = Out()
    ref = weakref.ref(out)
    stamp = watch.launched(out, 0)
    out.land()
    del out
    until(lambda: stamp.t_ready is not None)
    until(lambda: gc.collect() >= 0 and ref() is None)
    watch.stop()
    watch.join(2.0)


def test_stop_leaves_no_thread_and_no_reference_to_what_was_queued():
    watch = ReadyWatch("test-ready")
    blocked, queued = Out(), Out()
    refs = [weakref.ref(blocked), weakref.ref(queued)]
    stamps = [watch.launched(blocked, 0), watch.launched(queued, 1)]
    watch.stop()                 # the thread is blocked for the first
    assert watch.launched(Out(), 2) is None      # after a stop: nothing
    assert watch.launches == 2
    blocked.land()
    del blocked, queued
    watch.join(2.0)
    assert not watch.alive
    until(lambda: gc.collect() >= 0 and all(r() is None for r in refs))
    assert stamps[1].t_ready is None             # skipped, not stamped


def test_an_array_that_raises_stops_the_watch_with_one_warning(caplog):
    watch = ReadyWatch("test-ready")
    bad, later = Out(raises=RuntimeError("buffer deleted")), Out()
    with caplog.at_level(logging.WARNING,
                         "selkies_tpu.observability.device_probe"):
        stamps = [watch.launched(bad, 0), watch.launched(later, 1)]
        bad.land()
        later.land()
        watch.join(2.0)
    assert not watch.alive and watch.stopped
    assert isinstance(watch.error, RuntimeError)
    assert [r for r in caplog.records if "test-ready stopped" in r.message] \
        and len(caplog.records) == 1
    assert stamps[0].t_ready is None and stamps[1].t_ready is None
    assert watch.launched(Out(), 0) is None      # the owner goes on
    assert watch.launches == 2 and not watch.alive


# ---------------------------------------------------------------------------
# the three stages


def stamp_at(t_ready, t_before=None):
    s = ReadyStamp()
    s.t_ready, s.t_before = t_ready, t_before
    return s


@pytest.mark.parametrize("ready, before, want", [
    # queued behind the step before (R' 12), then ran to 17, then lay ready
    (17.0, 12.0, ((10.0, 12.0), (12.0, 17.0), (17.0, 20.0))),
    # the chip was free at the launch: no device_wait
    (17.0, 8.0, ((10.0, 10.0), (10.0, 17.0), (17.0, 20.0))),
    # no step before it
    (17.0, None, ((10.0, 10.0), (10.0, 17.0), (17.0, 20.0))),
    # the stamp landed after the fetch had ended (the watch woke late):
    # clipped to F, and R' with it
    (23.0, 21.0, ((10.0, 20.0), (20.0, 20.0), (20.0, 20.0))),
    (23.0, 15.0, ((10.0, 15.0), (15.0, 20.0), (20.0, 20.0))),
    # ready before the launch had returned: clipped to L
    (9.0, 8.0, ((10.0, 10.0), (10.0, 10.0), (10.0, 20.0))),
])
def test_ready_stages_tile_launch_to_fetched_and_clip(ready, before, want):
    got = ready_stages(10.0, 20.0, stamp_at(ready, before))
    assert tuple(got[s] for s in READY) == want
    assert sum(b - a for a, b in got.values()) == 10.0
    assert all(b >= a for a, b in got.values())


def test_a_stamp_that_has_not_landed_gives_no_stages_and_is_counted():
    assert ready_stages(10.0, 20.0, ReadyStamp()) == {}
    assert ready_stages(10.0, 20.0, None) == {}
    watch = ReadyWatch("test-ready")
    assert watch.stages(10.0, 20.0, ReadyStamp()) == {}
    assert watch.stages(10.0, 20.0, None) == {}          # a stopped watch's
    assert set(watch.stages(10.0, 20.0, stamp_at(15.0))) == set(READY)
    assert watch.counts() == {"launches": 0, "launches_into_idle": 0,
                              "ready_stamps_missed": 2}


# ---------------------------------------------------------------------------
# the pipes


class Fetch:
    """The H.264 pending's fetch: on the host when the test says."""

    def __init__(self, copy_s=0.0):
        self.gate = threading.Event()
        self.copy_s = copy_s

    def is_ready(self):
        return self.gate.is_set()

    def __array__(self, *_a, **_kw):
        assert self.gate.wait(10.0)
        time.sleep(self.copy_s)
        return np.zeros(4, np.uint8)


class FakeH264Base:
    """What ``PipelinedH264Encoder`` asks of its base encoder; every
    pending's step output (``buf``; an IDR has none, its ``fetch`` is the
    step's) and fetch land when the test says."""

    entropy = "device"

    def __init__(self, idr=False, copy_s=0.0):
        self.pendings = []
        self.idr, self.copy_s = idr, copy_s
        self.in_harvest = lambda p: None

    def dispatch(self, frame):
        fetch = Fetch(self.copy_s)
        if self.idr:
            # the watch blocks for the fetch itself
            fetch.block_until_ready = lambda: fetch.gate.wait(10.0)
        p = SimpleNamespace(fetch=fetch, buf=None if self.idr else Out())
        self.pendings.append(p)
        return p

    def harvest(self, p, host=None):
        self.in_harvest(p)
        return ["stripe"]


def h264_pipe(**kw):
    import jax.numpy as jnp

    base = FakeH264Base(**kw)
    pipe = PipelinedH264Encoder(base, depth=4)
    frame = jnp.zeros((8, 8, 3), jnp.uint8)      # on the device: no staging
    return pipe, base, frame


def land(p):
    """The step ends, then its fetch is on the host."""
    if p.buf is not None:
        p.buf.land()
    p.fetch.gate.set()


@pytest.fixture
def jpeg_pipe():
    """The real JPEG pipe at a tiny size; its step's ``packed`` wrapped so
    that the watch sees it ready when the test says (the fetch is the real
    one: on this CPU the step has run by the time its slice is read)."""
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    base = JpegStripeEncoder(W, H, stripe_height=64, quality=40)
    outs, step = [], base._step

    def gated_step(*a, **kw):
        packed, *rest = step(*a, **kw)
        outs.append(Out(inner=packed))
        return (outs[-1], *rest)

    base._step = gated_step
    pipe = PipelinedJpegEncoder(base, depth=4, fetch_group=2)
    yield pipe, outs
    for out in outs:
        out.land()
    pipe.close()


def frames(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (H, W, 3), np.uint8) for _ in range(n)]


def test_every_harvested_h264_frames_three_stages_tile():
    pipe, base, frame = h264_pipe()
    got = {}
    for n in range(12):
        seq = pipe.submit(frame)
        if n >= 2:
            land(base.pendings[n - 2])           # two steps behind
            until(lambda: pipe._ready_watch.readied >= n - 1)
        for s, _out in pipe.poll():
            got[s] = pipe.pop_trace(s)
        assert seq == n
    for p in base.pendings:
        land(p)
    until(lambda: pipe._ready_watch.readied == 12)
    for s, _out in pipe.flush():
        got[s] = pipe.pop_trace(s)
    assert sorted(got) == list(range(12))
    for tr in got.values():
        assert tiles(tr)
    st = pipe.stats()
    assert st["launches"] == 12 and st["ready_stamps_missed"] == 0
    # frames 0 and 1 were launched with nothing ended before them, and
    # one more step had ended before every launch from the third on
    assert st["launches_into_idle"] == 1
    # a frame launched behind two unfinished steps waited for the one
    # before it: its device_wait ends where that one's device_run ended
    for n in range(3, 12):
        assert got[n]["device_wait"][1] == max(
            got[n]["dispatch"][1], got[n - 1]["device_run"][1])
    pipe.close()
    until(lambda: not threads_named("tpuenc-ready"))


def test_every_harvested_jpeg_frames_three_stages_tile(jpeg_pipe):
    pipe, outs = jpeg_pipe
    got = {}
    for n, frame in enumerate(frames(10)):
        pipe.submit(frame)
        outs[n].land()
        until(lambda: pipe._ready_watch.readied == n + 1)
        for s, _out in pipe.poll(flush_partial=False):
            got[s] = pipe.pop_trace(s)
    for s, _out in pipe.flush():
        got[s] = pipe.pop_trace(s)
    assert sorted(got) == list(range(10))
    for tr in got.values():
        assert tiles(tr)
    # every launch found the step before it ended
    st = pipe.stats()
    assert (st["launches"], st["launches_into_idle"]) == (10, 10)
    assert st["ready_stamps_missed"] == 0
    # the first of a pair lay ready while its pair filled: its ready_wait
    # holds the second frame's staging and launch (D10)
    first, second = got[0], got[1]
    assert first["ready_wait"][1] == second["ready_wait"][1] \
        == first["fetch_wait"][1]
    assert first["ready_wait"][0] <= second["dispatch"][0]


def test_launches_into_idle_counts_the_launches_with_nothing_ahead():
    pipe, base, frame = h264_pipe()
    watch = pipe._ready_watch
    pipe.submit(frame)                           # nothing ahead: idle
    pipe.submit(frame)                           # the first is unfinished
    assert (watch.launches, watch.launches_into_idle) == (2, 1)
    base.pendings[0].buf.land()
    until(lambda: watch.readied == 1)
    pipe.submit(frame)                           # the second is unfinished
    assert (watch.launches, watch.launches_into_idle) == (3, 1)
    base.pendings[1].buf.land()
    base.pendings[2].buf.land()
    until(lambda: watch.readied == 3)
    pipe.submit(frame)                           # all three have ended
    st = pipe.stats()
    assert (st["launches"], st["launches_into_idle"]) == (4, 2)
    for p in base.pendings:
        land(p)
    pipe.flush()
    pipe.close()


@pytest.mark.parametrize("idr", [False, True], ids=["p-frame", "idr"])
def test_a_frame_the_driver_blocked_for_reads_a_ready_wait_of_its_copy(idr):
    pipe, base, frame = h264_pipe(idr=idr, copy_s=0.004)
    pipe.submit(frame)
    p, = base.pendings
    timer = threading.Timer(0.2, land, (p,))
    timer.start()
    (seq, _out), = pipe.poll(wait=True)          # blocks for the frame
    timer.join()
    # ... whose stamp lands while the copy runs (an IDR's step output is
    # its fetch: the watch was handed that)
    tr = pipe.pop_trace(seq)
    assert tiles(tr)
    f = tr["fetch_wait"]
    assert f[1] - f[0] >= 0.15
    assert tr["ready_wait"][0] >= f[0] + 0.1     # R inside the wait
    assert tr["ready_wait"][1] - tr["ready_wait"][0] < 0.1    # the copy
    assert tr["device_run"][1] - tr["device_run"][0] >= 0.1
    pipe.close()


def test_a_stamp_that_lands_after_the_fetch_is_clipped_to_its_end():
    pipe, base, frame = h264_pipe()
    pipe.submit(frame)
    p, = base.pendings

    def step_ends_during_the_pack(_p):
        p.buf.land()
        until(lambda: pipe._ready_watch.readied == 1)

    base.in_harvest = step_ends_during_the_pack
    p.fetch.gate.set()                           # the fetch is in first
    (seq, _out), = pipe.poll()
    tr = pipe.pop_trace(seq)
    assert tiles(tr)
    f = tr["fetch_wait"]
    assert tr["device_run"][1] == f[1] and tr["ready_wait"] == (f[1], f[1])
    assert pipe.stats()["ready_stamps_missed"] == 0
    pipe.close()


def test_a_stamp_that_never_lands_leaves_the_stages_out_and_is_counted():
    pipe, base, frame = h264_pipe()
    for _ in range(3):
        pipe.submit(frame)
    for p in base.pendings:
        p.fetch.gate.set()                       # fetched; never stamped
    got = [pipe.pop_trace(s) for s, _out in pipe.poll()]
    assert len(got) == 3
    for tr in got:
        assert not set(READY) & set(tr)
        assert tiles(tr, missing_ok=True) is False
    assert pipe.stats()["ready_stamps_missed"] == 3
    for p in base.pendings:
        p.buf.land()
    pipe.close()


def test_an_array_that_raises_stops_the_watch_and_not_the_pipe(caplog):
    pipe, base, frame = h264_pipe()
    with caplog.at_level(logging.WARNING,
                         "selkies_tpu.observability.device_probe"):
        pipe.submit(frame)
        base.pendings[0].buf.raises = RuntimeError("deleted buffer")
        land(base.pendings[0])
        pipe._ready_watch.join(2.0)
        for n in range(1, 5):
            pipe.submit(frame)
            land(base.pendings[n])
        got = [pipe.pop_trace(s) for s, _out in pipe.flush()]
    assert len(got) == 5 and all(tr is not None for tr in got)
    assert all("in_device" in tr and not set(READY) & set(tr) for tr in got)
    warnings = [r for r in caplog.records if "tpuenc-ready stopped" in
                r.getMessage()]
    assert len(warnings) == 1
    st = pipe.stats()
    assert st["frames"] == 5 and st["ready_stamps_missed"] == 5
    assert st["launches"] == 1           # no launch counted without a watch
    assert not threads_named("tpuenc-ready")
    pipe.close()


@pytest.mark.parametrize("codec", ["jpeg", "h264"])
def test_close_leaves_no_ready_thread_and_no_device_array(codec, request):
    if codec == "jpeg":
        pipe, outs = request.getfixturevalue("jpeg_pipe")
        for frame in frames(3):
            pipe.submit(frame)
        refs = [weakref.ref(o) for o in outs]
        arrays = [weakref.ref(o.inner) for o in outs]
        release = [o.land for o in outs]
        del outs[:]
    else:
        pipe, base, frame = h264_pipe()
        for _ in range(3):
            pipe.submit(frame)
        refs = [weakref.ref(p.buf) for p in base.pendings]
        arrays = []
        release = [p.buf.land for p in base.pendings]
        del base.pendings[:]
    assert threads_named("tpuenc-ready")
    pipe.close()                         # three frames in flight, unready
    release[0]()                         # the watch was blocked for one
    del release
    until(lambda: not threads_named("tpuenc-ready"))
    until(lambda: gc.collect() >= 0 and all(r() is None for r in refs))
    assert all(r() is None for r in arrays)


def test_a_watch_its_owner_stopped_resumes_and_keeps_its_counts():
    watch = ReadyWatch("test-ready")
    a, lost, b = Out(), Out(), Out()
    stamps = [watch.launched(a, 0)]
    a.land()
    until(lambda: watch.readied == 1)
    stamps.append(watch.launched(lost, 0))
    watch.stop()
    lost.land()
    watch.join(2.0)
    assert not watch.alive and watch.stopped
    watch.resume()
    # what was unstamped at the stop stays so, and is not waited for
    assert not watch.stopped and watch.ahead == 0
    assert stamps[1].t_ready is None
    stamps.append(watch.launched(b, watch.ahead))
    b.land()
    until(lambda: stamps[2].t_ready is not None)
    assert stamps[2].t_before is None        # a new thread knows none
    assert (watch.launches, watch.launches_into_idle, watch.readied) == (
        3, 3, 3)
    watch.stop()
    watch.join(2.0)
    assert not threads_named("test-ready")
    # a watch that an array stopped stays stopped
    bad = Out(raises=ValueError("gone"))
    watch.resume()
    watch.launched(bad, 0)
    bad.land()
    watch.join(2.0)
    watch.resume()
    assert watch.stopped


# ---------------------------------------------------------------------------
# what a pipe admits a capture by (ISSUE 47)


@pytest.fixture(params=["jpeg", "h264"])
def by_hand(request):
    """A pipe of depth 4 whose steps end when the test says: ``submit()``,
    ``end(k)`` (step k's output is ready, for the watch to see) and
    ``harvest()`` (every frame in flight is fetched and packed, whether
    the watch has seen its step end or not)."""
    if request.param == "jpeg":
        pipe, outs = request.getfixturevalue("jpeg_pipe")
        pictures = iter(frames(16))
        yield SimpleNamespace(
            pipe=pipe, submit=lambda: pipe.submit(next(pictures)),
            out=lambda k: outs[k], harvest=pipe.flush)
        return
    pipe, base, frame = h264_pipe()

    def harvest():
        for p in base.pendings:
            p.fetch.gate.set()
        return pipe.flush()

    yield SimpleNamespace(pipe=pipe, submit=lambda: pipe.submit(frame),
                          out=lambda k: base.pendings[k].buf,
                          harvest=harvest)
    for p in base.pendings:
        land(p)
    pipe.close()


def test_a_pipe_has_room_while_fewer_than_two_steps_are_unfinished(by_hand):
    """One rule for both pipes: room while fewer than ``depth`` frames are
    unpacked and, of them, fewer than two steps unfinished on the chip
    (the one that runs; after the launch, one queued behind it); the
    launches the second bound held back are counted, once each, and
    those that ``depth`` refused are not."""
    h, pipe = by_hand, by_hand.pipe
    watch = pipe._ready_watch
    assert pipe.depth == 4 and pipe.CHIP_STEPS == 2
    assert pipe.has_room                         # empty
    h.submit()
    assert pipe.has_room                         # one runs
    h.submit()
    assert not pipe.has_room and not pipe.has_room   # one runs, one queued
    assert pipe.n_inflight == 2 < pipe.depth
    assert pipe.stats()["launches_held_for_chip"] == 0   # none launched yet
    h.out(0).land()
    until(lambda: watch.readied == 1)
    assert pipe.has_room                         # its end made room
    h.submit()
    assert pipe.stats()["launches_held_for_chip"] == 1   # asked twice: one
    assert not pipe.has_room
    h.out(1).land()
    until(lambda: watch.readied == 2)
    h.submit()
    assert pipe.stats()["launches_held_for_chip"] == 2
    # four frames unpacked: depth's bound, whatever the chip still holds
    h.out(2).land()
    h.out(3).land()
    until(lambda: watch.ahead == 0)
    assert pipe.n_inflight == 4 and not pipe.has_room
    assert len(h.harvest()) == 4 and pipe.has_room
    h.submit()
    st = pipe.stats()
    assert (st["launches"], st["launches_held_for_chip"]) == (5, 2)
    h.out(4).land()


def test_an_empty_pipe_has_room_whatever_the_watch_still_counts(by_hand):
    """Stamps that never land: the steps unfinished are never taken to
    exceed the pipe's own frames in flight, so an empty pipe has room,
    and so has one with a single frame."""
    h, pipe = by_hand, by_hand.pipe
    h.submit()
    h.submit()
    assert not pipe.has_room
    assert len(h.harvest()) == 2                 # packed; never stamped
    assert pipe._ready_watch.ahead == 2 and pipe.n_inflight == 0
    assert pipe.has_room
    h.submit()
    assert pipe._ready_watch.ahead == 3 and pipe.has_room
    h.submit()
    assert not pipe.has_room                     # two of its own frames
    assert len(h.harvest()) == 2 and pipe.has_room
    assert pipe.stats()["ready_stamps_missed"] == 4
    for k in range(4):
        h.out(k).land()


@pytest.mark.parametrize("by", ["its owner", "an array that raised"])
def test_a_stopped_watch_leaves_depth_as_the_only_bound(by_hand, by, caplog):
    h, pipe = by_hand, by_hand.pipe
    watch = pipe._ready_watch
    n = 0
    if by == "its owner":
        watch.stop()
    else:
        with caplog.at_level(logging.WARNING,
                             "selkies_tpu.observability.device_probe"):
            h.submit()
            h.out(0).raises = RuntimeError("deleted buffer")
            h.out(0).land()
            watch.join(2.0)
        n = 1
    assert watch.stopped
    for _ in range(n, pipe.depth):
        assert pipe.has_room                     # as before ISSUE 47
        h.submit()
    assert pipe.n_inflight == pipe.depth and not pipe.has_room
    st = pipe.stats()
    assert (st["launches"], st["launches_held_for_chip"]) == (n, 0)
    assert len(h.harvest()) == pipe.depth and pipe.has_room
