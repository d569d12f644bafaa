"""A lane-shaped configuration rehearsed on the CPU: four sessions on one
``session:4`` mesh lane over four of the virtual devices ``tests/conftest.py``
forces, as the next ``model_config`` PR adds it: a configuration file, a
``chips: 4`` cell and nothing else. Where ``BENCHMARK.json`` holds them they
are taken from it, where it does not they are appended to a copy, so the
test passes before that PR and after it.

Two lanes are rehearsed. *The program as it is*: whatever the lane writes
today, the twelve readers of the device-driving path each read nothing or a
number, and the line holds exactly what was read. *A lane that writes what
that PR will*: a stand-in, installed through the server's own
``coordinator_factory`` hook, marks ``submit_wait``, ``pipe_wait``, ``stage``
and ``in_device`` on the lane's frames and the states of a thread
``mesh-encode``, in a checkout whose ``BENCHMARK.json`` already holds the
configuration and the cell: the stage readers then read numbers, the line
holds them, and the idle split reads the ``mesh-encode`` track. The test pins
nothing of what today's program lacks."""

import asyncio
import json
import math
import os
import sys
import time
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark import cells, harness, trace  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import idle_by_thread_state, trace_idle  # noqa: E402

DISPLAYS = ["d0", "d1", "d2", "d3"]
CONFIG = "ws-4x1080p60-jpeg-lane4"
CELL = "jpeg-4x1080p60.lane4-scroll"
#: the entries that read the device-driving path: the driver's queues, the
#: idle split by its thread's states, the step's phases. They name no cell,
#: so a lane cell lists them like any other
DRIVING = {"driver_submit_wait_p50_ms": "submit_wait",
           "driver_pipe_wait_p50_ms": "pipe_wait",
           "driver_stage_p50_ms": "stage",
           "driver_in_device_p50_ms": "in_device",
           "idle_driver_stage_pct": None, "idle_driver_pack_pct": None,
           "idle_driver_fetch_pct": None, "idle_driver_sleep_pct": None,
           "idle_driver_other_pct": None, "phase_colour_ms": None,
           "phase_transform_ms": None, "phase_entropy_ms": None}


def lane_checkout(tmp_path, real_root=ROOT):
    """A checkout that holds the lane configuration and its cell: the ones
    ``real_root``'s ``BENCHMARK.json`` has, else appended to a copy."""
    root = spec_checks.scratch_checkout(tmp_path, real_root)
    spec = spec_checks.read_spec(real_root)
    if not any(c["name"] == CONFIG for c in spec["configs"]):
        conf = json.load(open(os.path.join(
            real_root, "benchmark", "configs", "ws-1080p60-jpeg.json")))
        conf.update(name=CONFIG, displays=DISPLAYS)
        conf["env"] = dict(conf["env"], SELKIES_TPU_MESH="session:4",
                           SELKIES_TPU_SESSIONS_PER_CHIP="1",
                           SELKIES_SECOND_SCREEN="true",
                           SELKIES_MAX_DISPLAYS="0")
        conf["regime"] = {"what": "not measured on a lane yet",
                          "frames_in_flight": [0.5, 50.0]}
        conf["step_program"] = "local_step"
        (root / "benchmark" / "configs" / (CONFIG + ".json")
         ).write_text(json.dumps(conf))
        spec["configs"].append({
            "name": CONFIG, "source": conf["source"], "reduced": [],
            "file": f"benchmark/configs/{CONFIG}.json",
            "why": "four sessions on one four-chip host"})
    if not any(w["name"] == CELL for w in spec["workloads"]):
        spec["workloads"].append({
            "name": CELL, "config": CONFIG, "traffic": "scroll", "chips": 4,
            "why": "four 1080p60 JPEG sessions, one lane step each tick"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return str(root)


def a_tree_that_already_holds_the_lane(tmp_path):
    """The repository as the next PR leaves it: its own ``BENCHMARK.json``
    and data files hold the configuration and the cell."""
    real = tmp_path / "real"
    real.mkdir()
    made = lane_checkout(real)
    spec = spec_checks.read_spec(made)
    assert [c["name"] for c in spec["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in spec["workloads"]].count(CELL) == 1
    return made


def writes_what_the_next_pr_will(run):
    """A ``coordinator_factory``: the program's coordinator, with the marks
    the next PR owes written from outside it. Per lane frame: ``submit_wait``
    (accepted into the session's mailbox to the worker taking it),
    ``pipe_wait`` (taken to its staging begins: the worker makes room),
    ``stage`` (the lane encoder's ``dispatch`` entered to the step launched),
    ``in_device`` (``dispatch`` done to the harvest beginning). Per worker
    thread, under its own name ``mesh-encode``: ``stage``, ``dispatch``,
    ``fetch_wait``, ``pack``, and ``sleep`` between ticks."""
    from selkies_tpu.parallel.coordinator import MeshEncodeCoordinator

    now = time.monotonic

    class Writes(MeshEncodeCoordinator):
        THREAD = "mesh-encode"

        def __init__(self, *a, **kw):
            self._accepted = {}        # sid -> its pending frame accepted at
            self._taking = (0.0, {})   # (taken at, {sid: accepted at})
            self._at_dispatch = {}     # id(pending) -> {sid: intervals}
            self._extra = {}           # (sid, seq) -> intervals
            self._tick_ended = None
            run.stand_in = self
            super().__init__(*a, **kw)

        def _state(self, state, t0, t1):
            if t1 > t0:
                run.server.recorder.thread_state(self.THREAD, state, t0, t1)

        def _build_lane(self):
            lane = super()._build_lane()
            if lane is not None:
                self._time(lane.enc)
            return lane

        def _time(self, enc):
            coord, dispatch, step = self, enc.dispatch, enc._step

            def timed_step(*a, **kw):
                enc.launched_at = now()
                return step(*a, **kw)

            def timed_dispatch(frames):
                t0 = enc.launched_at = now()
                pending = dispatch(frames)
                t1 = now()
                coord._state("stage", t0, enc.launched_at)
                coord._state("dispatch", enc.launched_at, t1)
                taken, accepted = coord._taking
                coord._at_dispatch[id(pending)] = {
                    sid: {"submit_wait": (min(t_acc, taken), taken),
                          "pipe_wait": (taken, t0),
                          "stage": (t0, enc.launched_at)}
                    for sid, t_acc in accepted.items()}
                return pending

            enc._step, enc.dispatch = timed_step, timed_dispatch

        def _submit(self, sid, frame):
            out = super()._submit(sid, frame)
            self._accepted[sid] = now()
            return out

        def _tick(self):
            t0 = now()
            if self._tick_ended is not None:
                self._state("sleep", self._tick_ended, t0)
            try:
                super()._tick()
            finally:
                self._tick_ended = now()

        def _tick_lane(self, lane, frames, took):
            taken = now()
            self._taking = (taken, {
                sess.sid: self._accepted.get(sess.sid, taken)
                for sess, _slot, _gen in took})
            super()._tick_lane(lane, frames, took)

        def _harvest_oldest(self, lane):
            pending, took, dispatch_iv = lane.inflight_q[0]
            t0 = now()
            mine = self._at_dispatch.pop(id(pending), {})
            for sess, _slot, gen in took:
                if not sess.closed and sess.gen == gen:
                    # before the harvest publishes the frame: the capture
                    # loop pops its trace as soon as it polls it
                    self._extra[(sess.sid, sess.seq)] = dict(
                        mine.get(sess.sid, {}),
                        in_device=(dispatch_iv[1], max(dispatch_iv[1], t0)))
            try:
                super()._harvest_oldest(lane)
            finally:
                t1 = now()
                stages = getattr(lane.enc, "last_harvest_stages", None) or {}
                split = min(t1, t0 + float(stages.get("fetch_ms", 0.0)) / 1e3)
                self._state("fetch_wait", t0, split)
                self._state("pack", split, t1)
            while len(self._extra) > 256:
                self._extra.pop(next(iter(self._extra)))

        def _pop_trace(self, sid, seq):
            iv = super()._pop_trace(sid, seq)
            extra = self._extra.pop((sid, seq), None)
            if iv is not None and extra:
                iv.update(extra)
            return iv

    return Writes


def a_fixture_trace_over(run):
    """A hand-made traced run over three seconds of the rehearsal's own
    window: one device that runs a step of 6 ms every 20 ms and the clock
    probe every 300 ms, on a clock that began ``began`` seconds into
    ``time.monotonic``; the thread track is the rehearsal's own. Rows of one
    thread are laid end to end (a program that writes the track itself and
    the stand-in beside it would overlap)."""
    from selkies_tpu.observability.tracing import FlightRecorder

    ms = 1e6
    began = run.window[0] + 0.5
    rec = FlightRecorder()
    at = {}
    for th, state, a, b in run.server.recorder.thread_track():
        a = max(a, at.get(th, a))
        if b > a:
            rec.thread_state(th, state, a, b)
            at[th] = b
    mods = [("jit_local_step(7)", (10 + 20 * k) * ms, 6 * ms)
            for k in range(100)]
    for j in range(7):
        end = (105 + 300 * j) * ms
        mods.append(("jit_selkies_clock_probe(3)", end - 1e3, 1e3))
        ready = began + end / 1e9
        rec.clock_pair(0, ready - 0.002, ready)
    prof = trace.Profile(modules={0: mods}, ops={0: []},
                         host=[(trace.WINDOW_SPAN, 5 * ms, 2000 * ms)])
    return SimpleNamespace(
        profile=prof, window=(began - 2.0, began + 28.0), seconds=30.0,
        rehearsal=None, server=SimpleNamespace(recorder=rec),
        cell=SimpleNamespace(traffic={"trace": {"start_s": 2.0}},
                             config={"step_program": "local_step"}))


@pytest.mark.parametrize("lane", ["as-it-is", "writes-what-the-next-pr-will"])
def test_four_sessions_on_a_mesh_lane_rehearse_as_one_cell(
        lane, tmp_path, capsys, monkeypatch):
    stand_in = lane != "as-it-is"
    root = lane_checkout(
        tmp_path, a_tree_that_already_holds_the_lane(tmp_path)
        if stand_in else ROOT)
    spec_checks.whole(spec_checks.read_spec(root), root)
    cell = cells.load_cell(CELL, root=root)
    assert cell.chips == 4 and cell.config["displays"] == DISPLAYS
    runs = []

    class Spy(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

        async def boot(self):
            task = await super().boot()
            if stand_in:
                self.server.coordinator_factory = \
                    writes_what_the_next_pr_will(self)
            return task

        async def measure(self):
            await super().measure()
            # as the window closes: the server forgets its lanes as it stops
            self.coordinators = list(self.server.mesh_coordinators.values())
            self.slots = {d: self.server.display_clients[d].encoder.slot
                          for d in DISPLAYS}
            self.first_encoder = self.server.display_clients["d0"].encoder

    monkeypatch.setattr(harness, "Run", Spy)
    monkeypatch.setenv("SELKIES_UPLOAD_DIR", str(tmp_path / "uploads"))
    args = bench_run.parse(["--workload", CELL, "--seed", str(2**31 + 27),
                            "--seconds", "3", "--trace", "1",
                            "--rehearsal", "256x144"])
    device = bench_run.device_info(cell.chips, True)
    assert device["platform"] == "cpu" and device["count"] >= 4
    capsys.readouterr()
    out = asyncio.run(bench_run.run_cell(args, cell, device, (256, 144)))
    err = capsys.readouterr().err
    run, = runs
    assert out["correct"] is True, (out, err[-3000:])
    assert out["failed"] == 0 and out["compared"]["unreadable"]["value"] == 0

    # four clients joined, each on a mesh lane slot of its own
    assert sorted(run.clients) == DISPLAYS
    assert len(run.coordinators) == 1
    assert (getattr(run, "stand_in", None) is run.coordinators[0]) == stand_in
    assert None not in run.slots.values() and len(set(run.slots.values())) == 4
    # what served is kept for the phase readers: the first display's facade
    assert run.served_encoder is run.first_encoder is not None
    # every source is attributed to its own display, and the recorder's
    # capture mark names the content step the picture shows (PR 25's repair)
    assert sorted(run.display_of_source.values()) == DISPLAYS
    assert len(run.display_of_source) == 4
    for did, (disagree, of) in run.tracing_disagrees().items():
        assert of > 0 and disagree == 0, (did, disagree, of)

    # delivered_fps is per session; the changes due are all four desktops'
    m = run.metrics
    frames = sum(1 for c in run.clients.values() for f in c.frames
                 if run.window[0] <= f.t_last < run.window[1])
    assert m["delivered_fps"] == pytest.approx(frames / 3.0 / 4)
    assert 0 < m["delivered_fps"] <= 63.0
    assert out["attempted"] >= 4 * 150

    # warm-up under tpu_mesh: SETTINGS to the first client's first frame
    got = out["metrics"]
    assert run.server.warmup.seconds == 0.0
    assert got["warmup_s"]["value"] > 0.0
    first = run.clients["d0"]
    assert got["warmup_s"]["value"] == pytest.approx(
        first.frames[0].t_last - first.t_settings)

    # the lane cell lists the twelve entries of the device-driving path like
    # any other cell. Whatever the lane writes, each reader reads nothing or
    # a number, never raises, and the line holds exactly the listed metrics
    # whose readers read something
    listed = {m["name"] for m in cell.per_layer}
    assert set(DRIVING) <= listed
    read = {}
    for name in sorted(listed):
        m = cells.layer_metric_spec(name, os.path.join(root, "benchmark"))
        read[name] = cells.module("readers", m["reader"]).read(
            run, m.get("args", {}))
    for name in DRIVING:
        assert read[name] is None or (
            math.isfinite(read[name]) and read[name] >= 0.0), name
    assert {n for n, v in read.items() if v is not None} == set(got)
    for name, value in got.items():
        assert value["value"] == pytest.approx(read[name]), name
    # a CPU has no device trace: nothing from one, whatever the lane writes
    device_only = {m["name"] for m in cell.per_layer
                   if m["source"] == "device_trace"} | {
        "device_queue_delay_p50_ms"}
    assert not device_only & set(got)
    assert "busy_s" not in out["device"] and "breakdown" not in out
    if not stand_in:
        return

    # a lane that writes the four stages: their readers read numbers, the
    # line holds them, and most of the window's frames carry all four
    for name, stage in DRIVING.items():
        if stage is not None:
            assert got[name]["value"] >= 0.0 and got[name]["unit"] == "ms"
    whole = [t for t in run.spans if all(
        s in t.spans for s in ("submit_wait", "pipe_wait", "stage",
                               "dispatch", "in_device", "fetch_wait"))]
    sent = [t for t in run.spans if "send" in t.spans]
    assert len(whole) >= 0.9 * len(sent) > 4 * 15
    for t in whole[:200]:
        sp = t.spans
        assert sp["submit_wait"][0] <= sp["submit_wait"][1] \
            == sp["pipe_wait"][0] <= sp["pipe_wait"][1] == sp["stage"][0]
        assert sp["dispatch"][1] == sp["in_device"][0] <= sp["in_device"][1]
    # the worker's states are on the track under the worker's own name, and
    # the idle split finds them there, second in its list: over a fixture
    # trace laid on the rehearsal's own seconds the five shares add up to
    # the idle share
    names = {r[0] for r in run.server.recorder.thread_track()}
    assert "mesh-encode" in names and "tpuenc-async" not in names
    fixture = a_fixture_trace_over(run)
    spec = {n: cells.layer_metric_spec(n)["args"] for n in DRIVING
            if n.startswith("idle_driver_")}
    assert sorted(spec.values(), key=json.dumps) == sorted(
        (dict(threads=["tpuenc-async", "mesh-encode"], **a)
         for a in spec_checks.IDLE_SPLIT), key=json.dumps)
    shares = {n: idle_by_thread_state.read(fixture, a)
              for n, a in spec.items()}
    assert fixture.driving_thread == "mesh-encode"
    assert None not in shares.values()
    assert sum(shares.values()) == pytest.approx(
        trace_idle.read(fixture, {}), abs=1e-6)
    assert trace_idle.read(fixture, {}) == pytest.approx(70.0, abs=0.1)
    assert shares["idle_driver_sleep_pct"] > 0.0
    assert shares["idle_driver_other_pct"] < 70.0
    assert "is read from 'mesh-encode'" in capsys.readouterr().err
