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
