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
        closed = False

        def lower_step(self):
            return jax.jit(step).lower(
                jax.ShapeDtypeStruct((256,), jnp.float32))

        def close(self):
            Encoder.closed = True

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
    run.width, run.height = 256, 144
    run.server = SimpleNamespace(
        recorder=FlightRecorder(), settings=None,
        encoder_factory=lambda w, h, s: Encoder())
    ms = trace_phase.by_phase(run)
    assert Encoder.closed
    assert ms["_step"] == pytest.approx(len(names) * 1e-3)
    parts = {k: v for k, v in ms.items() if k != "_step"}
    assert sum(parts.values()) == pytest.approx(ms["_step"])
    assert parts["colour"] > 0 and parts["entropy"] > 0
    assert set(parts) <= {"colour", "entropy", "other"}
    assert parts.get("other", 0.0) < 0.5 * ms["_step"]
    assert trace_phase.read(run, {"phase": "motion"}) == 0.0
    assert trace_phase.read(run, {"phase": "colour"}) == parts["colour"]
    assert "device phases over 2 executions of step" in capsys.readouterr().err
    # a program that names no phases, a trace without the step: None
    run = traced_run()
    run.server.encoder_factory = lambda w, h, s: object()
    run.width = run.height = 0
    run.server.settings = None
    assert trace_phase.read(run, {"phase": "colour"}) is None


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
