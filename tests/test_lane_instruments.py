"""What a mesh lane writes into the flight recorder (ISSUE 35): the solo
driver's marks, under the solo driver's names.

* a served lane's frames carry ``submit_wait``, ``pipe_wait``, ``stage``,
  ``dispatch``, ``in_device``, ``fetch_wait``, ``pack`` tiling acceptance to
  the harvest's end, and ``lane_step`` across them;
* the mailbox is latest-wins: the capture timed is the one taken;
* the ``mesh-encode`` worker writes its own thread track, in five states;
* the lane's step names its phases, reached from a session's facade;
* the stages reach ``frame_stage_ms{stage,display}`` through the recorder;
* an injected encoder that does not say when it launched keeps one
  coarse ``dispatch``.

Real lanes run ``session:4`` over four of the virtual devices
``tests/conftest.py`` forces.
"""

import threading
import time

import numpy as np
import pytest

from selkies_tpu.observability import FlightRecorder, Metrics
from selkies_tpu.observability.tracing import STAGES, THREAD_STATES
from selkies_tpu.parallel.coordinator import (
    READY_THREAD, WORKER_THREAD, MeshEncodeCoordinator)
from selkies_tpu.robustness import FakeMeshEncoder

W, H, STRIPE_H = 64, 48, 16
#: a lane frame's stages in path order: they tile
TILING = ("submit_wait", "pipe_wait", "stage", "dispatch", "in_device",
          "fetch_wait", "pack")
WORKER_STATES = {"stage", "dispatch", "fetch_wait", "pack", "sleep"}
#: what the ready watch's stamp makes of ``in_device`` + ``fetch_wait``
READY = ("device_wait", "device_run", "ready_wait")


def frame_of(rng):
    return rng.integers(0, 255, (H, W, 3), np.uint8)


def hand_over(facade, rec):
    """The recorder reaches the lane the way ``_capture_loop`` hands it to
    any encoder (``server/data_server.py``)."""
    if getattr(facade, "recorder", False) is None:
        facade.recorder = rec


def serve(facades, seconds, rng, hz=60.0):
    """Stand in for the sessions' capture loops: a capture a tick into
    every mailbox, every poll's frames with their traces."""
    traces = {f.sid: [] for f in facades}
    t_end = time.monotonic() + seconds
    while time.monotonic() < t_end:
        for f in facades:
            f.try_submit(frame_of(rng))
        time.sleep(1.0 / hz)
        for f in facades:
            for seq, _stripes in f.poll():
                traces[f.sid].append(f.pop_trace(seq))
    for f in facades:
        for seq, _stripes in f.flush():
            traces[f.sid].append(f.pop_trace(seq))
    return traces


@pytest.fixture(scope="module")
def served():
    """One ``session:4`` JPEG lane, three sessions served for a while (the
    fourth slot stays free for the tests that join and leave)."""
    coord = MeshEncodeCoordinator("session:4", 1, W, H, stripe_h=STRIPE_H,
                                  framerate=60.0)
    rec = FlightRecorder(capacity=4096)
    facades = [coord.acquire(W, H) for _ in range(3)]
    assert None not in facades
    for f in facades:
        hand_over(f, rec)
    rng = np.random.default_rng(35)
    # the first dispatch compiles: not part of what is looked at
    serve(facades, 0.2, rng)
    t0 = time.monotonic()
    traces = serve(facades, 1.5, rng)
    t1 = time.monotonic()
    yield {"coord": coord, "rec": rec, "facades": facades, "traces": traces,
           "window": (t0, t1)}
    for f in facades:
        f.close()
    coord.stop()


def test_the_recorder_reaches_the_coordinator_through_a_facade(served):
    coord, rec = served["coord"], served["rec"]
    assert coord.recorder is rec
    other = FlightRecorder()
    for f in served["facades"]:
        assert f.recorder is rec
        hand_over(f, other)            # held already: the first one stays
    assert coord.recorder is rec


@pytest.mark.parametrize("session", [0, 1, 2])
def test_a_served_lanes_frames_carry_the_eight_stages_and_they_tile(
        served, session):
    traces = served["traces"][served["facades"][session].sid]
    assert len(traces) >= 10
    for iv in traces:
        # (and the ready watch's three inside in_device + fetch_wait,
        # where the frame's stamp had landed: tested further down)
        assert iv is not None and set(iv) - set(READY) == \
            set(TILING) | {"lane_step"}
        assert set(iv) <= set(STAGES)
        for a, b in zip(TILING, TILING[1:]):
            assert iv[a][0] <= iv[a][1] == iv[b][0], (a, b, iv)
        assert iv["pack"][0] <= iv["pack"][1]
        # the stages add up to the harvest's end less the acceptance
        parts = sum(iv[s][1] - iv[s][0] for s in TILING)
        assert parts == pytest.approx(
            iv["pack"][1] - iv["submit_wait"][0], abs=1e-4)
        # lane_step: from the take through the launch of the frame's step
        assert iv["lane_step"][0] == iv["pipe_wait"][0]
        assert iv["lane_step"][1] >= iv["dispatch"][1]
    # frames of one session harvest in the order they were taken
    taken = [iv["pipe_wait"][0] for iv in traces]
    assert taken == sorted(taken)


def test_sessions_taken_in_one_tick_share_the_take_and_the_step(served):
    by_take = {}
    for sid, traces in served["traces"].items():
        for iv in traces:
            by_take.setdefault(iv["pipe_wait"][0], {})[sid] = iv
    shared = [group for group in by_take.values() if len(group) > 1]
    assert shared, "no tick took two sessions' captures"
    for group in shared:
        ivs = list(group.values())
        for s in ("pipe_wait", "stage", "dispatch", "in_device",
                  "fetch_wait", "pack", "lane_step"):
            assert len({iv[s] for iv in ivs}) == 1, s
        # each waited in its own mailbox for its own time
        assert all(iv["submit_wait"][1] == ivs[0]["submit_wait"][1]
                   for iv in ivs)


def test_the_track_holds_only_the_workers_rows_none_overlapping(served):
    rec = served["rec"]
    t0, t1 = served["window"]
    assert {r[0] for r in rec.thread_track()} == {WORKER_THREAD}
    assert WORKER_THREAD == "mesh-encode"
    assert not rec.thread_track("tpuenc-async")
    rows = rec.thread_track(WORKER_THREAD, t0, t1)
    assert len(rows) > 50
    assert {r[1] for r in rows} == WORKER_STATES <= set(THREAD_STATES)
    for a, b in zip(rows, rows[1:]):
        assert a[2] < a[3] <= b[2] + 1e-6, (a, b)
    # what a frame's trace says the worker did is on the track
    iv = served["traces"][served["facades"][0].sid][-1]
    assert any(s == "stage" and a <= iv["stage"][0] and iv["stage"][1] <= b
               for _th, s, a, b in rec.thread_track(WORKER_THREAD))
    # and it is one more row of /debug/trace
    ev = rec.export_trace_events()["traceEvents"]
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == WORKER_THREAD for e in ev)


def test_a_lane_frames_stages_reach_frame_stage_ms_by_display(served):
    """What operators without a recorder dump read: the recorder publishes
    every stage of a closed span as ``frame_stage_ms{stage,display}``, a
    lane's ``submit_wait`` and ``lane_step`` with the rest; the lane keeps
    no window and no gauge of its own beside it."""
    coord = served["coord"]
    rec = FlightRecorder()
    rec.metrics = m = Metrics(port=0)
    for f in served["facades"]:
        display = f":{f.slot}"
        for iv in served["traces"][f.sid][:5]:
            tr = rec.begin(display, iv["submit_wait"][0])
            tr.merge(iv)
            rec.close(tr, "acked")
    text = m.render().decode()
    for f in served["facades"]:
        for stage in TILING + ("lane_step",):
            assert (f'frame_stage_ms_count{{display=":{f.slot}",'
                    f'stage="{stage}"}} 5.0') in text, (f.slot, stage)
    lane, = coord.stats()["lane_detail"]
    assert not [k for k in lane if "submit_wait" in k or "lane_step" in k]
    assert "mesh_submit_wait_ms" not in text
    assert "mesh_lane_step_ms" not in text


@pytest.mark.parametrize("when", ["attached", "closed-and-stopped"])
def test_the_lanes_step_names_its_phases_through_a_facade(served, when):
    from selkies_tpu.observability import device_phases

    coord = served["coord"]
    facade = coord.acquire(W, H)
    try:
        assert device_phases.base_encoder(facade) is coord.lanes[0].enc
        if when != "attached":
            # as the harness asks: the session released, the server stopped
            facade.close()
            coord.stop()
            assert facade.slot is None
        phases = device_phases.step_phases(facade, timeout_s=600.0)
    finally:
        facade.close()
        coord._ensure_thread()
    assert phases and {"colour", "transform", "entropy"} <= set(
        phases.values())
    lowered = coord.lanes[0].enc.lower_step().as_text()
    assert "local_step" in lowered


def test_a_facade_whose_coordinator_knows_no_lane_has_no_base():
    from types import SimpleNamespace

    from selkies_tpu.observability import device_phases
    from selkies_tpu.parallel.coordinator import MeshSessionFacade

    facade = MeshSessionFacade(SimpleNamespace(), 0)
    assert facade.base is None and facade.recorder is None
    assert device_phases.step_phases(facade) is None


# ---------------------------------------------------------------------------
# ticks driven by hand over an injected encoder: what is timed, exactly


class HeldEncoder(FakeMeshEncoder):
    """An injected lane encoder whose fetches land when the test says
    (``ready``), and which says when it launched if ``launches``."""

    def __init__(self, n, launches=True):
        super().__init__(n)
        self.ready = False
        self.launches = launches

    def dispatch(self, frames):
        time.sleep(0.002)                   # staging
        if self.launches:
            self.last_launch_at = time.monotonic()
        time.sleep(0.001)                   # the launch
        return super().dispatch(frames)

    def fetch_ready(self, pending):
        return self.ready

    def harvest(self, pending):
        time.sleep(0.001)
        return super().harvest(pending)


def by_hand(launches=True, max_inflight=2):
    """(coordinator with its worker stopped, its one session's facade, the
    lane's encoder, a recorder handed over)."""
    coord = MeshEncodeCoordinator(
        "session:1", 1, W, H, slots_per_lane=1, max_lanes=1,
        max_inflight=max_inflight,
        enc_factory=lambda n: HeldEncoder(n, launches))
    facade = coord.acquire(W, H)
    coord.stop()
    rec = FlightRecorder()
    hand_over(facade, rec)
    return coord, facade, coord.lanes[0].enc, rec


def test_a_capture_that_replaced_a_pending_one_is_not_the_one_timed():
    coord, facade, enc, _rec = by_hand()
    enc.ready = True
    t_first = time.monotonic()
    assert facade.try_submit(b"first") == 0
    time.sleep(0.03)
    t_second = time.monotonic()
    assert facade.try_submit(b"second") is None     # took first's place
    assert facade.replaced_seq == 0
    time.sleep(0.01)
    coord._tick()
    (seq, _stripes), = facade.poll()
    iv = facade.pop_trace(seq)
    wait = iv["submit_wait"]
    assert t_first + 0.03 <= t_second <= wait[0] <= wait[1]
    assert 0.01 <= wait[1] - wait[0] < 0.03


@pytest.mark.parametrize("harvested", ["making-room", "in-its-own-tick"])
def test_lane_step_covers_the_ticks_harvests(harvested):
    coord, facade, enc, rec = by_hand(max_inflight=1)
    enc.ready = harvested == "in-its-own-tick"
    got = {}
    for n in range(3):
        facade.try_submit(b"capture %d" % n)
        coord._tick()
        time.sleep(0.005)
        for seq, _stripes in facade.poll():
            got[seq] = facade.pop_trace(seq)
    if harvested == "in-its-own-tick":
        # dispatched and harvested by one tick: the step ends with the pack
        assert sorted(got) == [0, 1, 2]
        for iv in got.values():
            assert iv["lane_step"] == (iv["pipe_wait"][0], iv["pack"][1])
            assert iv["in_device"][1] - iv["in_device"][0] < 0.002
        return
    # a window of one, and no fetch lands by itself: the tick that takes
    # capture n+1 harvests frame n before it stages its own
    assert sorted(got) == [0, 1]
    coord._harvest_oldest(coord.lanes[0])
    (seq, _stripes), = facade.poll()
    got[seq] = facade.pop_trace(seq)
    for n in (0, 1):
        mine, nxt = got[n], got[n + 1]
        step = nxt["lane_step"]
        assert step[0] <= mine["fetch_wait"][0] <= mine["pack"][1] <= step[1]
        # the room-making is the next frame's pipe_wait
        assert nxt["pipe_wait"][0] <= mine["fetch_wait"][0]
        assert mine["pack"][1] <= nxt["pipe_wait"][1] == nxt["stage"][0]
        # and the frame waited on the device from its launch to that tick
        assert mine["in_device"] == (mine["dispatch"][1],
                                     mine["fetch_wait"][0])
        assert mine["lane_step"][1] <= mine["fetch_wait"][0]
    # the worker slept between the ticks that did work, and no row overlaps
    rows = rec.thread_track(WORKER_THREAD)
    assert {r[1] for r in rows} == WORKER_STATES
    for a, b in zip(rows, rows[1:]):
        assert a[3] <= b[2] + 1e-6, (a, b)
    sleeps = [r for r in rows if r[1] == "sleep"]
    assert len(sleeps) == 2 and all(r[3] - r[2] >= 0.004 for r in sleeps)


def test_an_encoder_without_the_launch_mark_keeps_the_coarse_dispatch():
    coord, facade, enc, rec = by_hand(launches=False)
    enc.ready = True
    facade.try_submit(b"capture")
    coord._tick()
    (seq, _stripes), = facade.poll()
    iv = facade.pop_trace(seq)
    assert set(iv) == (set(TILING) | {"lane_step"}) - {"stage"}
    assert iv["pipe_wait"][1] == iv["dispatch"][0]
    assert iv["dispatch"][1] - iv["dispatch"][0] >= 0.003   # staging too
    assert iv["dispatch"][1] == iv["in_device"][0]
    assert "stage" not in {r[1] for r in rec.thread_track(WORKER_THREAD)}


def test_without_a_recorder_nothing_is_written_and_frames_still_trace():
    coord, facade, enc, rec = by_hand()
    coord.recorder = None
    enc.ready = True
    facade.try_submit(b"capture")
    coord._tick()
    (seq, _stripes), = facade.poll()
    assert set(facade.pop_trace(seq)) == set(TILING) | {"lane_step"}
    assert rec.thread_track() == []


def test_the_worker_thread_is_named_as_its_track():
    coord = MeshEncodeCoordinator(
        "session:1", 1, W, H, slots_per_lane=1, max_lanes=1,
        enc_factory=lambda n: FakeMeshEncoder(n))
    try:
        facade = coord.acquire(W, H)
        assert facade is not None
        assert WORKER_THREAD in {t.name for t in threading.enumerate()}
    finally:
        coord.stop()


# ---------------------------------------------------------------------------
# the ready watch on a lane (ISSUE 42): device_wait, device_run, ready_wait


def lane_tiles(iv):
    """A lane frame's three stages tile ``in_device`` + ``fetch_wait``."""
    d, f = iv["dispatch"], iv["fetch_wait"]
    w, r, q = (iv[s] for s in READY)
    assert d[1] == w[0] <= w[1] == r[0] <= r[1] == q[0] <= q[1] == f[1]
    parts = sum(iv[s][1] - iv[s][0] for s in READY)
    both = sum(iv[s][1] - iv[s][0] for s in ("in_device", "fetch_wait"))
    assert parts == pytest.approx(both, abs=1e-9)
    assert parts == pytest.approx(f[1] - d[1], abs=1e-9)


@pytest.mark.parametrize("session", [0, 1, 2])
def test_a_served_lanes_frames_carry_the_ready_watchs_three_and_they_tile(
        served, session):
    facade = served["facades"][session]
    traces = served["traces"][facade.sid]
    split = [iv for iv in traces if set(READY) <= set(iv)]
    assert len(split) >= 0.5 * len(traces) > 0
    for iv in split:
        lane_tiles(iv)
        assert iv["in_device"] == (iv["dispatch"][1], max(
            iv["dispatch"][1], iv["fetch_wait"][0]))
    # sessions taken in one tick share the step, so its stamp too
    st = facade.stats()
    assert st == served["coord"].launch_stats()
    assert st["launches"] >= len(traces) and st["launches_into_idle"] >= 1
    assert st["launches"] == served["coord"].stats()["launches"]


def test_a_served_lane_counts_the_stripes_it_emitted_and_the_hosts_share(
        served):
    """``encoder_share`` reads a part over a whole, both keys of ``stats()``:
    the lane's, as the solo pipe's, has both."""
    st = served["facades"][0].stats()
    assert st["host_fallback_stripes"] == 0
    assert st["stripes_emitted"] >= sum(
        len(tr) for tr in served["traces"].values()) > 0


#: what ``stats()`` says of stripes for an encoder that codes none
NO_STRIPES = {"host_fallback_stripes": 0, "stripes_emitted": 0}


class StepOut:
    """A lane step's output: ready when the test says."""

    def __init__(self):
        self.gate = threading.Event()
        self.raises = None

    def block_until_ready(self):
        assert self.gate.wait(10.0)
        if self.raises is not None:
            raise self.raises


class Stamped(list):
    """A pending that says which buffer its step wrote."""

    step_out = None


class StampedEncoder(HeldEncoder):
    def __init__(self, n):
        super().__init__(n)
        self.outs = []

    def dispatch(self, frames):
        pending = Stamped(super().dispatch(frames))
        pending.step_out = StepOut()
        self.outs.append(pending.step_out)
        return pending


def stamped_by_hand(max_inflight=3):
    """``by_hand`` over an encoder whose step outputs land when the test
    says, with a watch of its own (``stop()`` ended the coordinator's)."""
    from selkies_tpu.observability.device_probe import ReadyWatch

    coord = MeshEncodeCoordinator(
        "session:1", 1, W, H, slots_per_lane=1, max_lanes=1,
        max_inflight=max_inflight, enc_factory=StampedEncoder)
    facade = coord.acquire(W, H)
    coord.stop()
    assert coord._ready_watch.stopped
    coord._ready_watch = watch = ReadyWatch(READY_THREAD)
    return coord, facade, coord.lanes[0].enc, watch


def wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


def test_a_lanes_launches_into_idle_are_those_with_nothing_ahead():
    coord, facade, enc, watch = stamped_by_hand()
    try:
        facade.try_submit(b"0")
        coord._tick()                        # nothing ahead: idle
        facade.try_submit(b"1")
        coord._tick()                        # step 0 unfinished
        assert facade.stats() == {"launches": 2, "launches_into_idle": 1,
                                  "ready_stamps_missed": 0,
                                  **NO_STRIPES}
        enc.outs[0].gate.set()
        enc.outs[1].gate.set()
        wait_for(lambda: watch.readied == 2)
        facade.try_submit(b"2")
        coord._tick()                        # both have ended: idle again
        assert facade.stats()["launches_into_idle"] == 2
        enc.outs[2].gate.set()
        wait_for(lambda: watch.readied == 3)
        enc.ready = True
        coord._tick()
        got = {seq: facade.pop_trace(seq) for seq, _s in facade.poll()}
        assert sorted(got) == [0, 1, 2]
        for iv in got.values():
            lane_tiles(iv)
        # step 1 was launched behind step 0: it waited for it
        assert got[1]["device_wait"][1] == max(
            got[1]["dispatch"][1], got[0]["device_run"][1])
        assert got[0]["device_wait"][1] == got[0]["dispatch"][1]
        # the results lay on the chip until the tick that harvested them
        assert got[0]["ready_wait"][1] - got[0]["ready_wait"][0] > 0.0
        assert coord.stats()["ready_stamps_missed"] == 0
    finally:
        watch.stop()
    wait_for(lambda: not watch.alive)


def test_a_lane_frame_harvested_before_its_stamp_has_none_and_is_counted():
    coord, facade, enc, watch = stamped_by_hand()
    try:
        facade.try_submit(b"0")
        enc.ready = True
        coord._tick()                        # harvested in its own tick
        (seq, _s), = facade.poll()
        iv = facade.pop_trace(seq)
        assert set(iv) == set(TILING) | {"lane_step"}
        assert facade.stats() == {"launches": 1, "launches_into_idle": 1,
                                  "ready_stamps_missed": 1,
                                  **NO_STRIPES}
        enc.outs[0].gate.set()
    finally:
        watch.stop()


def test_a_lane_step_output_that_raises_stops_the_watch_not_the_lane(caplog):
    import logging

    coord, facade, enc, watch = stamped_by_hand()
    enc.ready = True
    with caplog.at_level(logging.WARNING,
                         "selkies_tpu.observability.device_probe"):
        got = []
        for n in range(4):
            facade.try_submit(b"%d" % n)
            coord._tick()
            if n == 0:
                enc.outs[0].raises = RuntimeError("deleted buffer")
                enc.outs[0].gate.set()
                watch.join(2.0)
            got += facade.poll()
    assert [seq for seq, _s in got] == [0, 1, 2, 3]
    assert watch.stopped and not watch.alive
    assert len([r for r in caplog.records
                if "mesh-ready stopped" in r.getMessage()]) == 1
    assert coord.tick_errors_total == 0
    assert facade.stats()["launches"] == 1


def test_stop_leaves_no_mesh_ready_thread_and_no_step_output():
    import gc
    import weakref

    coord = MeshEncodeCoordinator(
        "session:1", 1, W, H, slots_per_lane=1, max_lanes=1, max_inflight=3,
        enc_factory=StampedEncoder, framerate=200.0)
    facade = coord.acquire(W, H)
    enc = coord.lanes[0].enc
    for n in range(3):
        facade.try_submit(b"%d" % n)
        wait_for(lambda: len(enc.outs) == n + 1)
    watch = coord._ready_watch
    assert watch.alive and watch.name == READY_THREAD
    assert READY_THREAD in {t.name for t in threading.enumerate()}
    refs = [weakref.ref(o) for o in enc.outs]
    first = enc.outs[0]
    facade.close()
    coord.stop()                             # three steps unready
    first.gate.set()                         # the watch was blocked for one
    del first, enc.outs[:]
    # (the module's served lane may hold a thread of that name of its own)
    wait_for(lambda: not watch.alive)
    coord.lanes[0].inflight_q.clear()        # (the pendings held them too)
    wait_for(lambda: gc.collect() >= 0 and all(r() is None for r in refs))
