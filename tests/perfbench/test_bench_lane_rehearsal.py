"""A lane-shaped configuration rehearsed on the CPU: four sessions on one
``session:4`` mesh lane over four of the virtual devices ``tests/conftest.py``
forces, as the next ``model_config`` PR will add it: a configuration file, a
``chips: 4`` cell and nothing else, in a checkout of its own. Nothing of it
is in ``BENCHMARK.json``."""

import asyncio
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark import cells, harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

DISPLAYS = ["d0", "d1", "d2", "d3"]
CELL = "jpeg-4x1080p60.lane4-scroll"


def lane_checkout(tmp_path):
    root = spec_checks.scratch_checkout(tmp_path)
    conf = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ws-1080p60-jpeg.json")))
    conf.update(name="ws-4x1080p60-jpeg-lane4", displays=DISPLAYS)
    conf["env"] = dict(conf["env"], SELKIES_TPU_MESH="session:4",
                       SELKIES_TPU_SESSIONS_PER_CHIP="1",
                       SELKIES_SECOND_SCREEN="true",
                       SELKIES_MAX_DISPLAYS="0")
    conf["regime"] = {"what": "not measured on a lane yet",
                      "frames_in_flight": [0.5, 50.0]}
    (root / "benchmark" / "configs" / "ws-4x1080p60-jpeg-lane4.json"
     ).write_text(json.dumps(conf))
    spec = spec_checks.read_spec(ROOT)
    spec["configs"].append({
        "name": conf["name"], "source": conf["source"], "reduced": [],
        "file": "benchmark/configs/ws-4x1080p60-jpeg-lane4.json",
        "why": "four sessions on one four-chip host"})
    spec["workloads"].append({
        "name": CELL, "config": conf["name"], "traffic": "scroll",
        "chips": 4, "why": "four 1080p60 JPEG sessions, one lane step each tick"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return str(root)


def test_four_sessions_on_a_mesh_lane_rehearse_as_one_cell(
        tmp_path, capsys, monkeypatch):
    root = lane_checkout(tmp_path)
    spec_checks.whole(spec_checks.read_spec(root), root)
    cell = cells.load_cell(CELL, root=root)
    assert cell.chips == 4 and cell.config["displays"] == DISPLAYS
    runs = []

    class Spy(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

        async def measure(self):
            await super().measure()
            # as the window closes: the server forgets its lanes as it stops
            self.coordinators = len(self.server.mesh_coordinators)
            self.slots = {d: self.server.display_clients[d].encoder.slot
                          for d in DISPLAYS}

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
    assert run.coordinators == 1
    assert None not in run.slots.values() and len(set(run.slots.values())) == 4
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
    # a lane has no tpuenc-async thread and none of the solo driver's
    # queues. The entries that read them name no cell, so the lane cell
    # lists them like any other; their readers find nothing on the lane's
    # run, quietly, and the harness leaves them out of the line. What else
    # the cell lists is all there, but for what only a device trace gives
    listed = {m["name"] for m in cell.per_layer}
    solo = {"driver_submit_wait_p50_ms", "driver_pipe_wait_p50_ms",
            "driver_stage_p50_ms", "driver_in_device_p50_ms",
            "idle_driver_stage_pct", "idle_driver_pack_pct",
            "idle_driver_fetch_pct", "idle_driver_sleep_pct",
            "idle_driver_other_pct", "phase_colour_ms",
            "phase_transform_ms", "phase_entropy_ms"}
    assert solo <= listed
    for name in sorted(solo):
        m = cells.layer_metric_spec(name)
        assert cells.module("readers", m["reader"]).read(
            run, m.get("args", {})) is None, name
    device_only = {m["name"] for m in cell.per_layer
                   if m["source"] == "device_trace"} | {
        "device_queue_delay_p50_ms"}
    assert listed - device_only - solo == set(got)
    assert "busy_s" not in out["device"] and "breakdown" not in out
