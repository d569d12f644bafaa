"""The harness is driven by data: a cell, a traffic mix, a configuration and
a per-layer metric are found by the names in BENCHMARK.json, and each can be
added as new files plus entries with no edit to a file that is there. Also:
BENCHMARK.json keeps to the contract's shape."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("w", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(w):
    cell = cells.load_cell(w)
    assert cell.config["name"] == cell.config_name
    gen = cells.module("sources", cell.traffic["generator"])
    assert hasattr(gen, "Source")
    ref = cells.module("reference", cell.config["reference"])
    assert {"undecodable", "unreadable", "bad_tiles"} <= set(
        cell.limits()) <= {"undecodable", "unreadable", "y_outside_pct",
                           "c_outside_pct", "bad_tiles"}
    lo, hi = cell.config["regime"]["frames_in_flight"]
    assert 0 < lo < hi
    assert ref.steps(cell.config["quantiser"])[0].shape == (ref.BLOCK,) * 2
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    assert cell.config["control"]["env"]


@pytest.mark.parametrize("m", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader_of_its_own(m):
    spec = cells.layer_metric_spec(m)
    assert callable(cells.module("readers", spec["reader"]).read)


def test_a_later_pr_adds_a_cell_a_mix_a_configuration_and_a_metric(tmp_path):
    """Only new files and new entries: nothing that is there is edited."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    for sub in ("configs", "traffic", "layer_metrics", "sources", "readers"):
        (bench / sub).mkdir(parents=True)
    conf = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ws-1080p60-jpeg.json")))
    conf.update(name="ws-720p30-jpeg", width=1280, height=720, framerate=30)
    (bench / "configs" / "ws-720p30-jpeg.json").write_text(json.dumps(conf))
    (bench / "traffic" / "blink.json").write_text(json.dumps({
        "generator": "blink", "params": {"hz": 2}, "check_frames": 4}))
    (bench / "sources" / "blink.py").write_text(
        "from benchmark.sources.desktop import ClockedSource\n"
        "class Source(ClockedSource):\n"
        "    def index_at(self, t):\n        return int(t * 2)\n")
    (bench / "layer_metrics" / "ack_p95_ms.json").write_text(json.dumps({
        "reader": "recorder_stage",
        "args": {"stages": ["ack"], "percentile": 95}}))
    (bench / "layer_metrics" / "frames_seen.json").write_text(json.dumps({
        "reader": "frames_seen", "args": {}}))
    (bench / "readers" / "frames_seen.py").write_text(
        "def read(run, args):\n    return float(len(run.spans)) or None\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({
        "name": "ws-720p30-jpeg", "source": "somewhere public",
        "file": "benchmark/configs/ws-720p30-jpeg.json", "reduced": [],
        "why": "a smaller screen"})
    spec["workloads"].append({
        "name": "jpeg-720p30.blink", "config": "ws-720p30-jpeg",
        "traffic": "blink", "chips": 1, "why": "a cursor blinks"})
    for name, reader_unit in (("ack_p95_ms", "ms"), ("frames_seen", "count")):
        spec["per_layer"].append({
            "name": name, "unit": reader_unit, "better": "lower",
            "source": "program_span", "layer": "server",
            "moves": "latency_p50_ms", "workloads": ["jpeg-720p30.blink"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = cells.load_cell("jpeg-720p30.blink", root=str(root))
    assert (cell.config["width"], cell.traffic["generator"]) == (1280, "blink")
    assert [m["name"] for m in cell.per_layer
            if "workloads" in m] == ["ack_p95_ms", "frames_seen"]
    gen = cells.module("sources", "blink", str(bench))
    assert gen.Source.index_at(None, 1.6) == 3

    class FakeTrace:
        def __init__(self, ms):
            self.spans = {"ack": (0.0, ms / 1000.0)}
            self.terminal = "acked"

    class FakeRun:
        spans = [FakeTrace(ms) for ms in range(1, 101)]

    for name, want in (("ack_p95_ms", 95.0), ("frames_seen", 100.0)):
        spec_m = cells.layer_metric_spec(name, str(bench))
        reader = cells.module("readers", spec_m["reader"], str(bench))
        assert reader.read(FakeRun, spec_m["args"]) == pytest.approx(want)
    # and the cells that were there still load from the same checkout shape
    shutil.copytree(os.path.join(ROOT, "benchmark", "traffic"),
                    bench / "traffic", dirs_exist_ok=True)
    shutil.copytree(os.path.join(ROOT, "benchmark", "configs"),
                    bench / "configs", dirs_exist_ok=True)
    assert cells.load_cell("h264-1080p60.scroll",
                           root=str(root)).traffic_name == "scroll"


# -- the contract's shape ----------------------------------------------------

def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16 and len(SPEC["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
    assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
    body = json.load(open(os.path.join(ROOT, c["file"])))
    assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    assert all(k in body for k in c["reduced"])
    for text in (c["source"], c["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_entry(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in SPEC["configs"]}


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    e2e = m in SPEC["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert keys <= set(m) <= keys | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in (("host_clock", "device_trace") if e2e else (
        "device_trace", "program_span", "program_counter", "host_clock"))
    if e2e:
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in SPEC["workloads"]}
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    assert len({w["name"] for w in SPEC["workloads"]}) == len(SPEC["workloads"])
