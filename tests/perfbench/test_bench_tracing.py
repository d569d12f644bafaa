"""The per-layer metrics that read the program's own tracing (ISSUE 25): the
entries and their files, the readers on hand-made traces, tracks and clock
pairs, and a traced rehearsal on the CPU, which prints the recorder's stages
and the stall watch's numbers and none of the device's."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark import cells, trace  # noqa: E402
from benchmark.readers import (clock_probe, encoder_share,  # noqa: E402
                               idle_by_thread_state, stall_watch, trace_idle,
                               trace_phase)
from selkies_tpu.observability.tracing import FlightRecorder  # noqa: E402

SPEC = spec_checks.read_spec(ROOT)
NEW = {m["name"]: m for m in SPEC["per_layer"]
       if m["name"] in spec_checks.APPENDED}
DEVICE_ONLY = {n for n, m in NEW.items() if m["source"] == "device_trace"} | {
    "device_queue_delay_p50_ms"}
MS = 1e6


def listed(name, workload):
    return any(x["name"] == name for x in cells.load_cell(workload).per_layer)


@pytest.mark.parametrize("name", spec_checks.APPENDED)
def test_new_metric_entry_and_its_file(name):
    m = spec_checks.appended_entry(SPEC, name)
    # a cell lists it where the entry names the cell, or names none
    for w in SPEC["workloads"]:
        assert listed(name, w["name"]) == (
            w["name"] in m.get("workloads", [w["name"]]))
    spec_checks.per_layer_metric_has_a_reader(name, ROOT)


def test_the_accepted_entries_are_untouched():
    """What it protects: the twelve accepted entries first and in order,
    every entry appended since after them and as it was added. What it no
    longer blocks: the count, which a later PR raises (the same check runs on
    a copy with entries appended, in ``test_bench_cells.py``)."""
    spec_checks.accepted_entries_are_untouched(SPEC)


# -- a hand-made traced run -----------------------------------------------

#: the session began 30 ms after it was asked for, at monotonic 102.03
ASKED, BEGAN = 102.0, 102.03


def traced_run(probes=10, wake_ms=(0.2, 0.0, 0.35, 0.1)):
    """Three traced seconds on one device: a step of 60 ms every 100 ms,
    a probe every 300 ms (1 us on the device, inside a gap), and the driver
    thread in ``sleep``, ``stage``, ``fetch_wait`` for 10 ms each of every
    40 ms gap and in no state for the last 10."""
    mods, rows = [], []
    rec = FlightRecorder()
    for k in range(30):
        s = 50 + 100 * k                       # ms on the session's clock
        mods.append(("jit_step(7)", s * MS, 60 * MS))
        for i, state in enumerate(("sleep", "stage", "fetch_wait")):
            a = BEGAN + (s + 60 + 10 * i) / 1e3
            rows.append(("tpuenc-async", state, a, a + 0.010))
    for thread, state, a, b in rows:
        rec.thread_state(thread, state, a, b)
    for j in range(probes):
        end = (145 + 300 * j) * MS             # inside a gap's last 10 ms
        mods.append(("jit_selkies_clock_probe(3)", end - 1e3, 1e3))
        ready = BEGAN + end / 1e9 + wake_ms[j % len(wake_ms)] / 1e3
        rec.clock_pair(0, ready - 0.004, ready)
    # pairs outside the traced seconds, as a real run has them
    rec.clock_pair(0, BEGAN - 0.3, BEGAN - 0.29)
    rec.clock_pair(0, BEGAN + 3.5, BEGAN + 3.51)
    prof = trace.Profile(modules={0: mods}, ops={0: []},
                         host=[(trace.WINDOW_SPAN, 50 * MS, 3000 * MS)])
    return SimpleNamespace(
        profile=prof, window=(100.0, 130.0), seconds=30.0, rehearsal=None,
        server=SimpleNamespace(recorder=rec),
        cell=SimpleNamespace(traffic={"trace": {"start_s": 2.0}},
                             config={"step_program": "step"}))


def no_encoder_is_built(*_a, **_kw):
    raise AssertionError("the phase reader built an encoder of its own")


def five_shares(run, which):
    """The idle split's five shares of the track of ``which`` (``threads``
    or ``thread``)."""
    return [idle_by_thread_state.read(run, dict(which, **a))
            for a in spec_checks.IDLE_SPLIT]


def shares(run):
    spec = {n: cells.layer_metric_spec(n) for n in NEW}
    spec = {n: v["args"] for n, v in spec.items()
            if v["reader"] == "idle_by_thread_state"}
    return {n: idle_by_thread_state.read(run, a) for n, a in spec.items()}


def test_idle_by_state_adds_up_to_the_idle_share(capsys):
    run = traced_run()
    assert clock_probe.align(run) == {0: pytest.approx(BEGAN, abs=1e-9)}
    got = shares(run)
    idle = trace_idle.read(run, {})
    assert sum(got.values()) == pytest.approx(idle, abs=1e-6)
    # 30 gaps of 40 ms in 3 s, a quarter of each in every state; the 10
    # probes' microsecond comes out of the unmarked quarter
    assert got["idle_driver_sleep_pct"] == pytest.approx(10.0, abs=1e-6)
    assert got["idle_driver_stage_pct"] == pytest.approx(10.0, abs=1e-6)
    assert got["idle_driver_fetch_pct"] == pytest.approx(10.0, abs=1e-6)
    assert got["idle_driver_pack_pct"] == 0.0
    assert got["idle_driver_other_pct"] == pytest.approx(
        10.0 - 100.0 * 10 * 1e-6 / 3.0, abs=1e-6)
    err = capsys.readouterr().err
    assert "clock pairs, device 0: 10 of 10" in err and "disagree" in err
    assert "idle gap of" in err


def test_with_two_probes_no_clock_and_no_idle_shares():
    run = traced_run(probes=2)
    assert clock_probe.align(run) is None
    assert set(shares(run).values()) == {None}
    # and without a trace, or from a program that keeps no pairs or track
    run = traced_run()
    run.profile = None
    assert set(shares(run).values()) == {None}
    run = traced_run()
    run.server.recorder = SimpleNamespace()
    assert set(shares(run).values()) == {None}
    assert clock_probe.read(run, {}) is None
    assert stall_watch.read(run, {"kind": "loop"}) is None


def test_queue_delay_and_stalls_of_the_window():
    run = traced_run()
    assert clock_probe.read(run, {"percentile": 50}) == pytest.approx(4.0)
    run.rehearsal = (256, 144)
    assert clock_probe.read(run, {"percentile": 50}) is None
    rec = run.server.recorder
    assert stall_watch.read(run, {"kind": "interpreter"}) == 0.0
    rec.stall("interpreter", 99.0, 99.2)       # before the window
    rec.stall("interpreter", 110.0, 110.06)
    rec.stall("loop", 111.0, 111.045)
    rec.stall("interpreter", 120.0, 120.113)
    assert stall_watch.read(run, {"kind": "interpreter"}) == \
        pytest.approx(113.0)
    assert stall_watch.read(run, {"kind": "loop"}) == pytest.approx(45.0)
    # every run, traced or not, says what stood still in its window
    from benchmark.harness import Run

    assert Run.stalls_by_kind(run) == {
        "interpreter": [(110.0, 110.06), (120.0, 120.113)],
        "loop": [(111.0, 111.045)]}
    run.server.recorder = object()             # a program with no stall watch
    assert Run.stalls_by_kind(run) == {}


@pytest.mark.parametrize("stats, want", [
    ({"primary": {"cavlc_frames": 960, "cavlc_low_tier_frames": 958}},
     100.0 * 958 / 960),
    ({"d0": {"cavlc_frames": 10, "cavlc_low_tier_frames": 10},
      "d1": {"cavlc_frames": 30, "cavlc_low_tier_frames": 0}}, 25.0),
    ({"primary": {"cavlc_frames": 960}}, None),        # PR 25's program
    ({"primary": {"cavlc_frames": 0, "cavlc_low_tier_frames": 0}}, None),
    ({"d0": {"cavlc_frames": 10, "cavlc_low_tier_frames": 10},
      "d1": {"frames": 30}}, None),
    ({}, None),
], ids=["share", "two-displays", "no-part", "whole-0", "one-lacks", "none"])
def test_a_share_of_the_encoders_own_counts(stats, want):
    spec = cells.layer_metric_spec("cavlc_low_tier_pct")
    assert spec == {"reader": "encoder_share",
                    "args": {"part": "cavlc_low_tier_frames",
                             "whole": "cavlc_frames"}}
    got = encoder_share.read(SimpleNamespace(encoder_stats=stats),
                             spec["args"])
    assert got == (want if want is None else pytest.approx(want))


def test_an_operation_that_holds_others_counts_its_own_time_only():
    ops = [("while.1", 0.0, 100.0), ("fusion.2", 10.0, 30.0),
           ("fusion.3", 50.0, 20.0), ("copy.4", 100.0, 5.0)]
    own = {n: t for n, _s, t in trace_phase.self_times(ops)}
    assert own == {"while.1": 50.0, "fusion.2": 30.0, "fusion.3": 20.0,
                   "copy.4": 5.0}


def test_phases_of_a_small_step_add_up_to_the_programs_time(capsys):
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("colour"):
            y = jnp.cumsum(x * 3.0 + 1.0)
        with jax.named_scope("entropy"):
            z = (y[::-1] * 2.0).astype(jnp.int32)
        return z, y.sum()

    class Encoder:
        def lower_step(self):
            return jax.jit(step).lower(
                jax.ShapeDtypeStruct((256,), jnp.float32))

    from selkies_tpu.observability import device_phases

    names = [n for n in device_phases.step_phases(Encoder())
             if not n.startswith(("param", "Arg_"))]
    assert len(names) >= 4
    # every operation of the program runs once in each of two executions,
    # 1 us each, back to back; a stray operation runs between them
    mods, ops = [], []
    for k in range(2):
        t0 = (100 + 500 * k) * 1e3
        mods.append(("jit_step(9)", t0, len(names) * 1e3))
        ops += [(n, t0 + i * 1e3, 1e3) for i, n in enumerate(names)]
    ops.append((names[0], 400e3, 50e3))         # of another program
    run = traced_run()
    run.profile = trace.Profile(modules={0: mods}, ops={0: ops},
                                host=[(trace.WINDOW_SPAN, 0.0, 2 * MS)])
    # the reader asks the encoder that served, wrappers and all, and builds
    # none: this server has no factory to build one from
    run.served_encoder = SimpleNamespace(pipe=SimpleNamespace(base=Encoder()))
    run.server = SimpleNamespace(recorder=FlightRecorder())
    ms = trace_phase.by_phase(run)
    assert ms["_step"] == pytest.approx(len(names) * 1e-3)
    parts = {k: v for k, v in ms.items() if k != "_step"}
    assert sum(parts.values()) == pytest.approx(ms["_step"])
    assert parts["colour"] > 0 and parts["entropy"] > 0
    assert set(parts) <= {"colour", "entropy", "other"}
    assert parts.get("other", 0.0) < 0.5 * ms["_step"]
    assert trace_phase.read(run, {"phase": "motion"}) == 0.0
    assert trace_phase.read(run, {"phase": "colour"}) == parts["colour"]
    assert "device phases over 2 executions of step" in capsys.readouterr().err
    # an encoder that offers no ``lower_step`` (a mesh lane's facade
    # today), a run that kept none: None, and nothing is built
    for served in (SimpleNamespace(sid=0, closed=False), None):
        run = traced_run()
        run.served_encoder = served
        run.server.encoder_factory = no_encoder_is_built
        assert trace_phase.read(run, {"phase": "colour"}) is None


def short_run(codec):
    """(the encoder of ``codec`` as the server's factory builds it, after a
    short run and its close; the factory)."""
    import numpy as np
    from selkies_tpu.server.data_server import default_encoder_factory
    from selkies_tpu.settings import Settings

    settings = Settings(argv=[], env={
        "SELKIES_ENCODER": codec, "SELKIES_TPU_STRIPE_HEIGHT": "16",
        "SELKIES_TPU_INTERPRET": "true"})

    def factory():
        return default_encoder_factory(64, 48, settings)

    served = factory()
    rng = np.random.default_rng(28)
    try:
        for _ in range(3):
            served.try_submit(rng.integers(0, 255, (48, 64, 3), np.uint8))
        assert len(served.flush(timeout=300.0)) >= 1
    finally:
        served.close()
    return served, factory


@pytest.mark.parametrize("codec", ["jpeg", "x264enc-striped"])
def test_the_phase_map_of_the_encoder_that_served_is_a_fresh_ones(codec):
    from selkies_tpu.encoder.async_driver import AsyncEncodeDriver
    from selkies_tpu.observability import device_phases

    served, factory = short_run(codec)
    assert isinstance(served, AsyncEncodeDriver)
    run = traced_run()
    run.served_encoder = served
    run.server.encoder_factory = no_encoder_is_built
    theirs = trace_phase._phase_map(run)
    fresh = factory()
    try:
        assert device_phases.base_encoder(fresh) is not \
            device_phases.base_encoder(served)
        want = device_phases.step_phases(fresh)
    finally:
        fresh.close()
    assert theirs and theirs == want
    wanted = {"colour", "transform", "entropy"} | (
        {"motion"} if codec != "jpeg" else set())
    assert wanted <= set(theirs.values())


def test_a_facade_without_lower_step_reads_no_phases_and_builds_nothing(
        capsys):
    """A mesh lane's facade as it is today: no ``lower_step`` behind it. The
    three phase readers return None without raising, and no solo encoder is
    built to stand in for the lane's."""
    from selkies_tpu.parallel.coordinator import MeshSessionFacade

    run = traced_run()
    run.cell.config["step_program"] = "step"
    run.served_encoder = MeshSessionFacade(SimpleNamespace(), 0)
    run.server.encoder_factory = no_encoder_is_built
    assert trace_phase._phase_map(run) is None
    for phase in ("colour", "transform", "entropy"):
        spec = cells.layer_metric_spec(f"phase_{phase}_ms")
        assert cells.module("readers", spec["reader"]).read(
            run, spec["args"]) is None
    assert "MeshSessionFacade" in capsys.readouterr().err


@pytest.mark.parametrize("args, tracks, want, says", [
    ({"threads": ["tpuenc-async", "mesh-encode"]}, ["tpuenc-async"],
     "tpuenc-async", None),
    ({"threads": ["tpuenc-async", "mesh-encode"]}, ["mesh-encode"],
     "mesh-encode", None),
    ({"thread": "tpuenc-async"}, ["tpuenc-async"], "tpuenc-async", None),
    ({"thread": "mesh-encode"}, ["tpuenc-async"], None, "nothing from"),
    ({"threads": ["tpuenc-async", "mesh-encode"]},
     ["mesh-encode", "tpuenc-async"], "tpuenc-async", "left a track too"),
    ({"threads": ["mesh-encode", "tpuenc-async"]},
     ["mesh-encode", "tpuenc-async"], "mesh-encode", "left a track too"),
    ({"threads": ["tpuenc-async", "mesh-encode"]}, ["device-probe-0"],
     None, "nothing from"),
], ids=["solo-of-two", "lane-of-two", "one-name", "one-name-absent",
        "both-first-listed", "both-other-order", "neither"])
def test_the_driving_thread_is_named_by_data(capsys, args, tracks, want,
                                              says):
    """``threads`` lists the names the device's driver may have; the first
    that left a track in the traced seconds is read and the run says which;
    the old one-name ``thread`` is still read; two tracks are said."""
    run = traced_run()
    rows = run.server.recorder.thread_track("tpuenc-async")
    rec = run.server.recorder = FlightRecorder()
    for name in tracks:
        for _th, state, a, b in rows:
            # the second thread sleeps where the first works: its shares
            # would differ, so the test sees whose were read
            rec.thread_state(name, "sleep" if name != tracks[0] else state,
                             a, b)
    for j in range(10):
        ready = BEGAN + (145 + 300 * j) * MS / 1e9
        rec.clock_pair(0, ready - 0.004, ready)
    capsys.readouterr()
    got = idle_by_thread_state.read(run, dict(args, states=["stage"]))
    err = capsys.readouterr().err
    if want is None:
        assert got is None and says in err
        return
    assert run.driving_thread == want
    assert f"is read from {want!r}" in err
    assert (says in err) if says else ("left a track too" not in err)
    first = want == tracks[0]
    assert got == pytest.approx(10.0 if first else 0.0, abs=1e-6)
    assert idle_by_thread_state.read(
        run, dict(args, states=["sleep"])) == pytest.approx(
        10.0 if first else 30.0, abs=1e-6)
    # the five shares of whichever thread was read add up to the idle share
    assert sum(five_shares(run, args)) == pytest.approx(
        trace_idle.read(run, {}), abs=1e-6)


def test_the_idle_split_on_four_devices_is_the_mean_over_devices():
    """A lane: one thread drives four devices. Each device's idle is laid
    over the one track, and a share is the mean of the four."""
    run = traced_run()
    one = run.profile.modules[0]
    probes = [e for e in one if e[0].startswith("jit_selkies_clock_probe")]
    # devices 1-3 run the step for 80 ms where device 0 runs it for 60
    longer = [(n, s, 80 * MS) if n.startswith("jit_step") else (n, s, d)
              for n, s, d in one]
    run.profile = trace.Profile(
        modules={0: one, 1: longer, 2: longer, 3: longer},
        ops={d: [] for d in range(4)}, host=run.profile.host)
    rec = run.server.recorder
    for dev in (1, 2, 3):
        for _n, s, d in probes:
            ready = BEGAN + (s + d) / 1e9
            rec.clock_pair(dev, ready - 0.004, ready)
    args = {"threads": ["mesh-encode", "tpuenc-async"]}
    sleep = idle_by_thread_state.read(run, dict(args, states=["sleep"]))
    stage = idle_by_thread_state.read(run, dict(args, states=["stage"]))
    # sleep covers ms 60-70 after a step's start, stage 70-80: a device
    # busy for 80 ms idles in neither
    assert sleep == pytest.approx(10.0 / 4, abs=1e-6)
    assert stage == pytest.approx(10.0 / 4, abs=1e-6)
    assert sum(five_shares(run, args)) == pytest.approx(
        trace_idle.read(run, {}), abs=1e-6)


def test_a_traced_rehearsal_prints_the_programs_own_and_no_device_metric(
        capsys):
    from benchmark import run as bench_run

    capsys.readouterr()
    code = bench_run.main(["--workload", "jpeg-1080p60.scroll", "--seed",
                           str(2**31 + 25), "--seconds", "3", "--trace", "1",
                           "--rehearsal", "256x144"])
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    assert code == 0 and out["correct"] is True, out
    assert out["device"]["platform"] == "cpu"
    got = out["metrics"]
    here = {n for n in NEW if listed(n, "jpeg-1080p60.scroll")}
    assert here - DEVICE_ONLY <= set(got)
    assert "cavlc_low_tier_pct" not in got      # the H.264 cell's alone
    assert not (set(got) & DEVICE_ONLY)
    for name in ("driver_submit_wait_p50_ms", "driver_pipe_wait_p50_ms",
                 "driver_stage_p50_ms", "driver_in_device_p50_ms",
                 "driver_pack_p50_ms", "server_harvest_wait_p50_ms"):
        assert got[name]["value"] >= 0.0 and got[name]["unit"] == "ms"
    assert got["interpreter_stall_max_ms"]["value"] >= 0.0
    assert got["loop_stall_max_ms"]["value"] >= 0.0
