"""ISSUE 42 on a lane: the lane-shaped configuration of
``test_bench_lane_rehearsal.py`` rehearsed once more, traced, and its line
holds the five data-only metrics of the ready watch: a lane's coordinator
stamps its steps as the solo pipes do (thread ``mesh-ready``), its facade's
``stats()`` counts the launches, and the entries name no cell, so a lane
cell lists them like any other. (That file's stand-in coordinator wrote
from outside what a lane did not write yet; these the lane writes itself,
so the program is rehearsed as it is.)"""

import asyncio
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from benchmark import cells, harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from test_bench_lane_rehearsal import (CELL, DISPLAYS,  # noqa: E402
                                       lane_checkout)
from test_bench_ready import DATA_ONLY  # noqa: E402

READY = ("device_wait", "device_run", "ready_wait")


def test_a_lanes_line_holds_the_five_and_its_frames_tile(
        tmp_path, capsys, monkeypatch):
    root = lane_checkout(tmp_path)
    cell = cells.load_cell(CELL, root=root)
    assert set(DATA_ONLY) <= {m["name"] for m in cell.per_layer}
    runs = []

    class Spy(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Spy)
    monkeypatch.setenv("SELKIES_UPLOAD_DIR", str(tmp_path / "uploads"))
    args = bench_run.parse(["--workload", CELL, "--seed", str(2**31 + 42),
                            "--seconds", "3", "--trace", "1",
                            "--rehearsal", "256x144"])
    device = bench_run.device_info(cell.chips, True)
    out = asyncio.run(bench_run.run_cell(args, cell, device, (256, 144)))
    run, = runs
    assert out["correct"] is True, out
    got = out["metrics"]
    for name in DATA_ONLY:
        assert name in got, (name, sorted(got))
        assert math.isfinite(got[name]["value"]) and got[name]["value"] >= 0.0
    assert "ready_stamp_lag_p50_ms" not in got
    # every display's facade gave the lane's counts (read one after the
    # other while the lane went on)
    assert sorted(run.encoder_stats) == DISPLAYS
    first = run.encoder_stats[DISPLAYS[0]]
    for st in run.encoder_stats.values():
        assert st["launches"] >= st["launches_into_idle"] >= 1
        assert 0 <= st["launches"] - first["launches"] <= 8
    # and the window's frames tile: the three add up to in_device +
    # fetch_wait in every frame that carries them, which most do
    sent = [t for t in run.spans if "send" in t.spans]
    split = [t for t in sent if all(s in t.spans for s in READY)]
    assert len(split) >= 0.5 * len(sent) > 4 * 15
    worst = 0.0
    for t in split:
        parts = sum(t.spans[s][1] - t.spans[s][0] for s in READY)
        both = sum(t.spans[s][1] - t.spans[s][0]
                   for s in ("in_device", "fetch_wait"))
        assert min(t.spans[s][1] - t.spans[s][0] for s in READY) >= 0.0
        worst = max(worst, abs(parts - both))
    assert worst < 1e-9
