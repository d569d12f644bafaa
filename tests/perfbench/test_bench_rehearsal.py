"""The harness rehearsed on the CPU: tiny geometry, Pallas in interpret
mode. A rehearsal names the CPU and
prints no device metric; without a TPU and without ``--rehearsal`` the run
command refuses."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DEVICE_METRICS = {"step_device_ms", "me_kernel_ms", "me_kernel_roofline",
                  "device_idle_pct"}


def rehearse(capsys, workload, trace, seconds="3", seed="20260928"):
    from benchmark import run as bench_run

    capsys.readouterr()
    code = bench_run.main(["--workload", workload, "--seed", seed,
                           "--seconds", seconds, "--trace", str(trace),
                           "--rehearsal", "256x144"])
    captured = capsys.readouterr()
    return code, json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_h264_rehearsal_reports_end_to_end_the_tail_and_the_regime(capsys):
    code, out, err = rehearse(capsys, "h264-1080p60.scroll", 0,
                              seed=str(2**31 + 20260928))
    assert code == 0 and out["correct"] is True, out
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    from benchmark.cells import load_cell

    # the cell's end-to-end metrics, however many BENCHMARK.json gives it
    assert set(out["metrics"]) == {
        m["name"] for m in load_cell("h264-1080p60.scroll").end_to_end} >= {
        "delivered_fps", "latency_p50_ms", "wire_kB_per_frame", "setup_s"}
    assert out["attempted"] >= 150 and out["failed"] == 0
    assert all(v["value"] > 0 for v in out["metrics"].values())
    # beside the bounded median, in every run: the tail and the regime
    w = out["window"]
    assert w["latency_p95_ms"] >= out["metrics"]["latency_p50_ms"]["value"]
    assert w["frames_in_flight"] == pytest.approx(
        out["metrics"]["latency_p50_ms"]["value"]
        * out["metrics"]["delivered_fps"]["value"] / 1000.0)
    assert w["regime"] in ("expected", "other")
    assert all(v > 0 for v in w["stalled_s"].values())     # {} on a quiet host
    assert "latency p95 over the same changes" in err and "regime:" in err
    assert list(out)[-1] == "compared"
    assert out["compared"]["unreadable"] == {"value": 0.0, "limit": 0}


def test_jpeg_traced_rehearsal_reports_per_layer_and_no_device_metric(capsys):
    code, out, _err = rehearse(capsys, "jpeg-1080p60.scroll", 1)
    assert code == 0 and out["correct"] is True, out
    assert out["device"]["platform"] == "cpu"
    got = set(out["metrics"])
    assert {"server_send_p50_ms", "submit_drop_pct", "driver_dispatch_p50_ms",
            "driver_fetch_wait_p50_ms", "frames_in_flight", "warmup_s",
            "compile_cache_misses", "latency_p95_ms"} <= got
    # no tpu_mesh: the server's own WarmUp.seconds (the stand-in's 0), not
    # SETTINGS to first frame
    assert out["metrics"]["warmup_s"]["value"] == 0.0
    # never a device metric from a CPU, and no busy time either
    assert not (got & DEVICE_METRICS)
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert out["metrics"]["frames_in_flight"]["value"] == pytest.approx(
        out["window"]["frames_in_flight"])


# -- the cell above the knee: 120 content steps a second, a client at 120 ----

NEW_CELL = "h264-1080p120.scroll"


def no_device_in_a_rehearsal():
    """What only a device's trace or its clock probe can say: a rehearsal
    has neither, and its line leaves these out for that stated reason.
    Derived (an entry's ``source`` is ``device_trace``, or its reader is one
    of the clock-probe readers), so a later PR's device reading is in it."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import spec_checks

    out = spec_checks.no_device_in_a_rehearsal(
        spec_checks.read_spec(ROOT), ROOT)
    assert DEVICE_METRICS <= out
    return out


def test_the_cell_above_the_knee_rehearses_end_to_end(capsys):
    from benchmark.cells import load_cell

    code, out, err = rehearse(capsys, NEW_CELL, 0, seed=str(2**31 + 41))
    cell = load_cell(NEW_CELL)
    assert code == 0 and out["correct"] is True, out
    # the accepted H.264 deployment (its default is 60), a client at 120
    assert cell.config["framerate"] == 60 and cell.chips == 1
    assert cell.traffic["client"] == {"framerate": 120}
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "delivered_fps", "latency_p50_ms", "wire_kB_per_frame", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    w = out["window"]
    assert w["latency_p95_ms"] >= out["metrics"]["latency_p50_ms"]["value"]
    assert w["frames_in_flight"] == pytest.approx(
        out["metrics"]["latency_p50_ms"]["value"]
        * out["metrics"]["delivered_fps"]["value"] / 1000.0)
    # 120 changes a second fall due, whatever is delivered
    assert 355 <= out["attempted"] <= 361 and out["failed"] == 0
    assert w["session_fps"] == {"primary": 120.0}
    assert "session rate, as the server says it: primary 120" in err
    assert "late against their 120 Hz ticks" in err
    assert len(w["latency_p50_by_second_ms"]) == 3
    assert w["regime"] in ("expected", "other")
    assert w["band"] == cell.config["regime"]["frames_in_flight"]
    # the ruler of two-row groups was read in every frame of the window
    assert out["compared"]["unreadable"] == {"value": 0.0, "limit": 0}
    assert "names another content step than the picture shows in 0 of" in err
    assert list(out)[-1] == "compared"


def test_the_cell_above_the_knee_traced_every_listed_key_or_a_stated_reason(
        capsys):
    from benchmark.cells import load_cell

    code, out, err = rehearse(capsys, NEW_CELL, 1)
    assert code == 0 and out["correct"] is True, out
    listed = {m["name"] for m in load_cell(NEW_CELL).per_layer}
    # the six that one codec alone has name this cell
    assert {"me_kernel_ms", "me_kernel_roofline", "phase_motion_ms",
            "cavlc_low_tier_pct", "fetch_prefix_hit_pct",
            "cavlc_tier_fill_pct"} <= listed
    got = out["metrics"]
    assert set(got) <= listed
    missing = listed - set(got)
    dark = no_device_in_a_rehearsal()
    assert missing <= dark, missing - dark
    assert "ready_stamp_lag_p50_ms" in missing
    assert not (set(got) & DEVICE_METRICS)
    for name in ("cavlc_low_tier_pct", "fetch_prefix_hit_pct",
                 "cavlc_tier_fill_pct"):
        assert 0.0 < got[name]["value"] <= 100.0 and got[name]["unit"] == "%"
    # where the step does not keep up, every second capture is replaced in
    # the mailbox before the pipe has room for it
    assert got["submit_drop_pct"]["value"] >= 0.0
    assert got["frames_in_flight"]["value"] == pytest.approx(
        out["window"]["frames_in_flight"])
    # a traced window is held to no band, and the idle split says why it
    # read nothing
    assert out["window"]["regime"] == "traced"
    assert "idle by thread state: not read:" in err


def test_the_cell_on_its_knee_is_kept_on_file_and_still_runs(capsys):
    """``h264-1080p60.scroll`` left ``workloads`` with PR 41 (PERF.md
    section 2); the first test of this file still runs it from its files.
    Here: what the harness knows of it (its own band, the session at the
    configuration's 60, every end-to-end metric, the six H.264 metrics)."""
    from benchmark.cells import BENCH_DIR, kept_cells, load_cell

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        live = {w["name"] for w in json.load(f)["workloads"]}
    kept = {w["name"]: w for w in kept_cells(BENCH_DIR)["workloads"]}
    assert "h264-1080p60.scroll" in kept and "h264-1080p60.scroll" not in live
    assert "15.5 ms" in kept["h264-1080p60.scroll"]["returns_when"]
    cell = load_cell("h264-1080p60.scroll")
    assert (cell.config_name, cell.traffic_name) == ("ws-1080p60-h264",
                                                     "scroll")
    assert "client" not in cell.traffic and cell.config["framerate"] == 60
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in load_cell(NEW_CELL).per_layer}
    code, out, err = rehearse(capsys, "h264-1080p60.scroll", 0, seed="41")
    assert code == 0 and out["correct"] is True, out
    assert out["window"]["band"] == [3.7, 6.2]
    assert out["window"]["session_fps"] == {"primary": 60.0}
    assert 175 <= out["attempted"] <= 181


def test_without_a_tpu_the_run_command_refuses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "jpeg-1080p60.scroll", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_an_unknown_workload_is_refused():
    from benchmark.cells import load_cell

    with pytest.raises(SystemExit):
        load_cell("no-such.cell")
