"""What ISSUE 42 adds to the benchmark: five per-layer entries that read
the ready watch's stages and counts as data (``recorder_stage``,
``encoder_share``), and a reader, ``ready_stamp``, that checks the stamps
against a device trace. (The reader's own entry, ``ready_stamp_lag_p50_ms``,
is listed for the H.264 cells since PR 44, which unpinned the two tests of
this directory that refused every way of listing it.)"""

import math
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark import cells, trace  # noqa: E402
from benchmark.readers import ready_stamp  # noqa: E402
from selkies_tpu.observability.tracing import FlightRecorder  # noqa: E402

SPEC = spec_checks.read_spec(ROOT)
STAGE_METRICS = {
    "driver_device_wait_p50_ms": (["device_wait"], 50),
    "driver_device_run_p50_ms": (["device_run"], 50),
    "driver_ready_wait_p50_ms": (["ready_wait"], 50),
    "driver_ready_wait_p95_ms": (["ready_wait"], 95)}
DATA_ONLY = list(STAGE_METRICS) + ["launch_idle_pct"]
MS = 1e6


def test_the_whole_spec_holds_with_the_five_entries_in_their_order():
    """Present, in their order, after every entry accepted before them: a
    subsequence of the list, not its tail (a later PR appends after them)."""
    spec_checks.whole(SPEC, ROOT)
    assert DATA_ONLY == spec_checks.DATA_ONLY
    names = [m["name"] for m in SPEC["per_layer"]]
    assert [n for n in names if n in DATA_ONLY] == DATA_ONLY
    before = spec_checks.ACCEPTED + spec_checks.APPENDED + \
        spec_checks.LATER[:spec_checks.LATER.index(DATA_ONLY[0])]
    assert max(names.index(n) for n in before) < names.index(DATA_ONLY[0])
    # and it fails where one of the five moves in front of an older entry
    moved = [m for m in SPEC["per_layer"] if m["name"] != DATA_ONLY[-1]]
    moved.insert(len(spec_checks.ACCEPTED), next(
        m for m in SPEC["per_layer"] if m["name"] == DATA_ONLY[-1]))
    with pytest.raises(AssertionError):
        spec_checks.accepted_entries_are_untouched(
            dict(SPEC, per_layer=moved))


@pytest.mark.parametrize("name", DATA_ONLY)
def test_a_new_entry_names_no_cell_and_reads_data_only(name):
    m = next(x for x in SPEC["per_layer"] if x["name"] == name)
    spec_checks.metric_entry(SPEC, m)
    assert "workloads" not in m and m["layer"] == "encode driver"
    assert m["better"] == "lower"
    body = spec_checks.per_layer_metric_has_a_reader(name, ROOT)
    if name in STAGE_METRICS:
        stages, q = STAGE_METRICS[name]
        assert (m["unit"], m["source"], m["moves"]) == (
            "ms", "program_span", "latency_p50_ms")
        assert body == {"reader": "recorder_stage",
                        "args": {"stages": stages, "percentile": q}}
    else:
        assert (m["unit"], m["source"], m["moves"]) == (
            "%", "program_counter", "delivered_fps")
        assert body == {"reader": "encoder_share", "args": {
            "part": "launches_into_idle", "whole": "launches"}}
    # every cell lists it, the one kept on file too
    for w in [w["name"] for w in SPEC["workloads"]] + ["h264-1080p60.scroll"]:
        assert name in {x["name"] for x in cells.load_cell(w).per_layer}


def test_the_lag_metric_is_listed_for_the_h264_cells():
    """Entered in PR 44 (reader and file on disk since PR 42): a device
    reading, so a rehearsal leaves it out for the stated reason; listed for
    the H.264 cells of ``workloads``, and the H.264 cell kept on file names
    it itself."""
    body = spec_checks.per_layer_metric_has_a_reader(
        "ready_stamp_lag_p50_ms", ROOT)
    assert body == {"reader": "ready_stamp", "args": {"percentile": 50}}
    m = next(x for x in SPEC["per_layer"]
             if x["name"] == "ready_stamp_lag_p50_ms")
    spec_checks.metric_entry(SPEC, m)
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "ms", "lower", "device_trace", "device", "latency_p50_ms")
    assert set(m["workloads"]) <= set(spec_checks.h264_cells(SPEC, ROOT))
    assert "h264-1080p120.scroll" in m["workloads"]
    assert m["name"] in spec_checks.no_device_in_a_rehearsal(SPEC, ROOT)
    assert m["name"] in {x["name"] for x in
                         cells.load_cell("h264-1080p60.scroll").per_layer}
    assert m["name"] not in {x["name"] for x in
                             cells.load_cell("jpeg-1080p60.scroll").per_layer}


# -- the reader, on a hand-made traced run ---------------------------------

#: the session began at monotonic 500.25: what a trace time is to be moved by
BEGAN = 500.25


def span(dispatch_end, ready, fetched, before=None):
    """A frame's span as the program writes it: R is ``device_run``'s end."""
    free = dispatch_end if before is None else min(
        max(before, dispatch_end), ready)
    return SimpleNamespace(spans={
        "dispatch": (dispatch_end - 0.002, dispatch_end),
        "device_wait": (dispatch_end, free), "device_run": (free, ready),
        "ready_wait": (ready, fetched),
        "fetch_wait": (fetched - 0.0001, fetched)})


def traced_run(lag_ms=(0.3, 0.1, 0.2, 0.4), devices=(0,), probe_wake_ms=0.0):
    """Three traced seconds: a step of 8 ms every 16 ms on each device
    (the last device ends 50 us after the first), a probe every 300 ms
    whose nearest pair woke ``probe_wake_ms`` late, and a frame a step
    stamped ``lag_ms`` (in turn) after its step's latest end."""
    rec = FlightRecorder()
    mods = {dev: [] for dev in devices}
    spans = []
    for k in range(180):
        start = (20 + 16 * k) * MS
        for i, dev in enumerate(devices):
            mods[dev].append(("jit_step(11)", start + i * 0.05 * MS, 8 * MS))
        end = BEGAN + (start + 8 * MS + (len(devices) - 1) * 0.05 * MS) / 1e9
        ready = end + lag_ms[k % len(lag_ms)] / 1e3
        spans.append(span(end - 0.007, ready, ready + 0.005))
    for j in range(10):
        end = (29.5 + 300 * j) * MS            # between two steps
        for dev in devices:
            mods[dev].append(("jit_selkies_clock_probe(3)", end - 1e3, 1e3))
            wake = (probe_wake_ms + (0.0, 0.15, 0.3)[j % 3]) / 1e3
            rec.clock_pair(dev, BEGAN + end / 1e9 - 0.003,
                           BEGAN + end / 1e9 + wake)
    prof = trace.Profile(modules=mods, ops={d: [] for d in devices},
                         host=[(trace.WINDOW_SPAN, 10 * MS, 3000 * MS)])
    return SimpleNamespace(
        profile=prof, window=(BEGAN - 2.0, BEGAN + 28.0), seconds=30.0,
        rehearsal=None, spans=spans, server=SimpleNamespace(recorder=rec),
        trace_asked_at=BEGAN - 0.03,
        cell=SimpleNamespace(traffic={"trace": {"start_s": 2.0}},
                             config={"step_program": "step"}))


@pytest.mark.parametrize("devices", [(0,), (0, 1, 2, 3)],
                         ids=["one-chip", "four-chips"])
def test_the_lag_is_recovered_to_a_microsecond(devices, capsys):
    run = traced_run(devices=devices)
    assert ready_stamp.read(run, {"percentile": 50}) == pytest.approx(
        0.2, abs=1e-3)
    assert ready_stamp.read(run, {"percentile": 95}) == pytest.approx(
        0.4, abs=1e-3)
    assert ready_stamp.read(run, {"percentile": 1}) == pytest.approx(
        0.1, abs=1e-3)
    err = capsys.readouterr().err
    assert "ready stamps: 180 of 180 stamps" in err
    assert "0 with none within 2 ms" in err and "0 clipped" in err


def test_a_frame_with_no_execution_is_left_out_and_said_so(capsys):
    run = traced_run()
    # a stamp 6 ms after any step's end (between two steps), and one whose
    # step the trace does not hold at all
    lost = BEGAN + (20 + 16 * 50 + 8 + 6) / 1e3
    run.spans.append(span(lost - 0.004, lost, lost + 0.001))
    assert ready_stamp.read(run, {"percentile": 50}) == pytest.approx(
        0.2, abs=1e-3)
    err = capsys.readouterr().err
    assert "180 of 181 stamps" in err and "1 with none within 2 ms" in err


def test_a_clipped_stamp_says_nothing_and_is_counted(capsys):
    run = traced_run()
    for tr in run.spans[:8]:                  # the watch woke after the fetch
        f = tr.spans["fetch_wait"]
        tr.spans["device_run"] = (tr.spans["device_run"][0], f[1])
        tr.spans["ready_wait"] = (f[1], f[1])
    assert ready_stamp.read(run, {}) == pytest.approx(0.2, abs=1e-3)
    err = capsys.readouterr().err
    assert "172 of 172 stamps" in err and "8 clipped stamps" in err


def test_a_stamp_nearer_than_the_probes_nearest_pair_moves_the_offset(capsys):
    # every probe woke at least 0.25 ms late: the clocks' offset, a lower
    # bound, is 0.25 ms too large until a stamp lies nearer its execution
    run = traced_run(probe_wake_ms=0.25)
    got = ready_stamp.read(run, {"percentile": 50})
    assert got == pytest.approx(0.2 - 0.1, abs=1e-3)     # above the nearest
    assert got >= 0.0
    assert "nearer its execution than the probe's nearest pair" in \
        capsys.readouterr().err


def test_none_untraced_in_a_rehearsal_and_from_a_program_without_stamps():
    run = traced_run()
    run.profile = None
    assert ready_stamp.read(run, {}) is None
    run = traced_run()
    run.rehearsal = (256, 144)
    assert ready_stamp.read(run, {}) is None
    run = traced_run()                        # the parent: no device_run
    for tr in run.spans:
        for s in ("device_wait", "device_run", "ready_wait"):
            del tr.spans[s]
    assert ready_stamp.read(run, {}) is None
    run = traced_run()                        # no clock: two probes
    run.server.recorder = FlightRecorder()
    assert ready_stamp.read(run, {}) is None
    run = traced_run()                        # another program's trace
    run.cell.config["step_program"] = "no_such_step"
    assert ready_stamp.read(run, {}) is None


# -- the CPU rehearsal's line holds the five, cell by cell -----------------

@pytest.mark.parametrize("workload", [
    "h264-1080p120.scroll", "jpeg-1080p60.scroll", "h264-1080p60.scroll"])
def test_the_rehearsals_traced_line_holds_the_five_as_finite_numbers(
        workload, capsys):
    from test_bench_rehearsal import rehearse

    code, out, err = rehearse(capsys, workload, 1, seed=str(2**31 + 42))
    assert code == 0 and out["correct"] is True, out
    got = out["metrics"]
    for name in DATA_ONLY:
        assert name in got, (name, sorted(got))
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0.0
        assert got[name]["unit"] == ("%" if name == "launch_idle_pct"
                                     else "ms")
    assert got["launch_idle_pct"]["value"] <= 100.0
    assert got["driver_ready_wait_p95_ms"]["value"] >= \
        got["driver_ready_wait_p50_ms"]["value"]
    # the three lie inside in_device + fetch_wait
    assert got["driver_device_run_p50_ms"]["value"] <= \
        got["driver_in_device_p50_ms"]["value"] \
        + got["driver_fetch_wait_p50_ms"]["value"] + 1e-6
    # no device, no lag, whichever cell lists the reader's entry
    assert "ready_stamp_lag_p50_ms" not in got
