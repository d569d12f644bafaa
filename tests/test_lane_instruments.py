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
    WORKER_THREAD, MeshEncodeCoordinator)
from selkies_tpu.robustness import FakeMeshEncoder

W, H, STRIPE_H = 64, 48, 16
#: a lane frame's stages in path order: they tile
TILING = ("submit_wait", "pipe_wait", "stage", "dispatch", "in_device",
          "fetch_wait", "pack")
WORKER_STATES = {"stage", "dispatch", "fetch_wait", "pack", "sleep"}


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
        assert iv is not None and set(iv) == set(TILING) | {"lane_step"}
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
