"""The harness is driven by data: a cell, a traffic mix, a configuration and
a per-layer metric are found by the names in BENCHMARK.json, and each can be
added as new files plus entries with no edit to a file that is there. Also:
BENCHMARK.json keeps to the contract's shape."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark import cells  # noqa: E402

SPEC = spec_checks.read_spec(ROOT)


@pytest.mark.parametrize("w", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(w):
    spec_checks.cell_finds_its_files(w, ROOT)


@pytest.mark.parametrize("m", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader_of_its_own(m):
    spec_checks.per_layer_metric_has_a_reader(m, ROOT)


#: what reads the device-driving path: the solo driver's queues, the idle
#: split by its thread's states, the step's phases. The entries name no cell
DRIVING_PATH = {
    "driver_submit_wait_p50_ms", "driver_pipe_wait_p50_ms",
    "driver_stage_p50_ms", "driver_in_device_p50_ms",
    "idle_driver_stage_pct", "idle_driver_pack_pct", "idle_driver_fetch_pct",
    "idle_driver_sleep_pct", "idle_driver_other_pct", "phase_colour_ms",
    "phase_transform_ms", "phase_entropy_ms"}
LANE_CELL = "jpeg-4x720p30.lane4-blink"


def a_later_prs_checkout(tmp_path, solo):
    """The real ``BENCHMARK.json`` and data files with a later PR's files
    and entries appended: a solo cell on a new configuration with a new mix
    and two metrics of its own (where ``solo``), and a ``chips: 4``
    lane-shaped cell on a configuration of its own with one metric on a new
    layer ``lanes``. Returns (root, spec, the files as they were)."""
    root = spec_checks.scratch_checkout(tmp_path)
    bench = root / "benchmark"
    for sub in ("sources", "readers"):
        (bench / sub).mkdir()
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}
    conf = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ws-1080p60-jpeg.json")))
    conf.update(name="ws-720p30-jpeg", source="somewhere public",
                width=1280, height=720, framerate=30)
    (bench / "traffic" / "blink.json").write_text(json.dumps({
        "generator": "blink", "params": {"hz": 2}, "check_frames": 4}))
    (bench / "sources" / "blink.py").write_text(
        "from benchmark.sources.desktop import ClockedSource\n"
        "class Source(ClockedSource):\n"
        "    def index_at(self, t):\n        return int(t * 2)\n")
    spec = json.loads(json.dumps(SPEC))
    if solo:
        (bench / "configs" / "ws-720p30-jpeg.json").write_text(
            json.dumps(conf))
        (bench / "layer_metrics" / "ack_p95_ms.json").write_text(json.dumps({
            "reader": "recorder_stage",
            "args": {"stages": ["ack"], "percentile": 95}}))
        (bench / "layer_metrics" / "frames_seen.json").write_text(json.dumps({
            "reader": "frames_seen", "args": {}}))
        (bench / "readers" / "frames_seen.py").write_text(
            "def read(run, args):\n    return float(len(run.spans)) or None\n")
        spec["configs"].append({
            "name": "ws-720p30-jpeg", "source": "somewhere public",
            "file": "benchmark/configs/ws-720p30-jpeg.json", "reduced": [],
            "why": "a smaller screen"})
        spec["workloads"].append({
            "name": "jpeg-720p30.blink", "config": "ws-720p30-jpeg",
            "traffic": "blink", "chips": 1, "why": "a cursor blinks"})
        for name, unit in (("ack_p95_ms", "ms"), ("frames_seen", "count")):
            spec["per_layer"].append({
                "name": name, "unit": unit, "better": "lower",
                "source": "program_span", "layer": "server",
                "moves": "latency_p50_ms",
                "workloads": ["jpeg-720p30.blink"]})
    four = dict(conf, name="ws-4x720p30-jpeg-lane4",
                source="somewhere else public",
                displays=["d0", "d1", "d2", "d3"],
                step_program="local_step")
    four["env"] = dict(conf["env"], SELKIES_TPU_MESH="session:4")
    (bench / "configs" / "ws-4x720p30-jpeg-lane4.json").write_text(
        json.dumps(four))
    (bench / "layer_metrics" / "lane_tick_p95_ms.json").write_text(
        json.dumps({"reader": "recorder_stage",
                    "args": {"stages": ["lane_tick"], "percentile": 95}}))
    spec["configs"].append({
        "name": four["name"], "source": four["source"], "reduced": [],
        "file": "benchmark/configs/ws-4x720p30-jpeg-lane4.json",
        "why": "four sessions on one mesh lane of a four-chip host"})
    spec["workloads"].append({
        "name": LANE_CELL, "config": four["name"], "traffic": "blink",
        "chips": 4, "why": "one SPMD step over four chips each tick"})
    spec["per_layer"].append({
        "name": "lane_tick_p95_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "lanes",
        "moves": "latency_p50_ms", "workloads": [LANE_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root, spec, before


@pytest.mark.parametrize("solo", [False, True],
                         ids=["one-more-cell", "two-more-cells"])
def test_a_later_pr_adds_a_four_chip_lane_cell(tmp_path, solo):
    """A ``chips: 4`` lane-shaped cell beside the cells that are there, and
    beside a later solo cell too: every check tier 1 makes of the real file
    holds (with the two cells PR 24 was accepted with: one four-chip cell of
    three and of four). The lane cell inherits the twelve entries of the
    device-driving path, which name no cell, and its one metric of its own
    sits on the layer ``lanes``, which none of those entries names."""
    root, spec, before = a_later_prs_checkout(tmp_path, solo)
    assert len(spec["workloads"]) == len(SPEC["workloads"]) + (
        2 if solo else 1)
    assert [w["chips"] for w in spec["workloads"]].count(4) == \
        [w["chips"] for w in SPEC["workloads"]].count(4) + 1
    spec_checks.whole(spec_checks.read_spec(str(root)), str(root))
    for rel, was in before.items():
        assert (root / rel).read_bytes() == was, rel
    cell = cells.load_cell(LANE_CELL, root=str(root))
    assert cell.chips == 4 and len(cell.config["displays"]) == 4
    theirs = {m["name"] for m in cell.per_layer}
    assert theirs >= DRIVING_PATH
    assert not theirs & {"phase_motion_ms", "cavlc_low_tier_pct",
                         "me_kernel_ms", "ack_p95_ms", "frames_seen"}
    assert [(m["name"], m["layer"]) for m in cell.per_layer
            if "workloads" in m] == [("lane_tick_p95_ms", "lanes")]
    assert "lanes" not in {m["layer"] for m in cell.per_layer
                           if "workloads" not in m}
    # and no cell that was there, or that the same PR adds, takes it
    for w in spec["workloads"]:
        if w["name"] != LANE_CELL:
            other = cells.load_cell(w["name"], root=str(root))
            assert "lane_tick_p95_ms" not in {
                m["name"] for m in other.per_layer}


def test_a_later_pr_adds_a_cell_a_mix_a_configuration_and_a_metric(tmp_path):
    """Only new files and new entries: nothing that is there is edited. The
    checkout is the real ``BENCHMARK.json`` and the real data files with a
    later PR's appended (a solo cell, and a ``chips: 4`` lane-shaped cell
    beside it), and every check tier 1 makes of the real file is made of it:
    one that pins a count fails here too."""
    root, spec, before = a_later_prs_checkout(tmp_path, solo=True)
    bench = root / "benchmark"

    spec_checks.whole(spec_checks.read_spec(str(root)), str(root))
    assert len(spec["per_layer"]) == len(SPEC["per_layer"]) + 3
    for rel, was in before.items():
        assert (root / rel).read_bytes() == was, rel

    cell = cells.load_cell("jpeg-720p30.blink", root=str(root))
    assert (cell.config["width"], cell.traffic["generator"]) == (1280, "blink")
    # the new solo cell inherits what the solo driver, its thread track and
    # the step's phases give: those entries name no cell, so the later PR
    # edits none of them; only what one codec alone has names its cell
    theirs = {m["name"] for m in cell.per_layer}
    assert theirs >= DRIVING_PATH
    assert not theirs & {"phase_motion_ms", "cavlc_low_tier_pct",
                         "lane_tick_p95_ms"}
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
    # and the cells that were there load from the same checkout, with the
    # metrics they had and none of the later PR's
    for w in SPEC["workloads"]:
        there = cells.load_cell(w["name"], root=str(root))
        here = cells.load_cell(w["name"])
        assert there.traffic_name == here.traffic_name == w["traffic"]
        assert there.per_layer == here.per_layer
        assert there.end_to_end == here.end_to_end


def test_the_accepted_entries_check_lets_the_count_grow_and_nothing_else():
    """The accepted-entries check, handed a spec with one more per-layer
    entry than the real file has, holds; handed one whose accepted entries
    moved or whose appended entry changed, it fails."""
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"].append(dict(spec["per_layer"][-1], name="one_more"))
    spec_checks.accepted_entries_are_untouched(spec)
    swapped = json.loads(json.dumps(SPEC))
    swapped["per_layer"][0], swapped["per_layer"][1] = \
        swapped["per_layer"][1], swapped["per_layer"][0]
    with pytest.raises(AssertionError):
        spec_checks.accepted_entries_are_untouched(swapped)
    gone = json.loads(json.dumps(SPEC))
    gone["per_layer"] = [m for m in gone["per_layer"]
                         if m["name"] != "cavlc_low_tier_pct"]
    with pytest.raises(AssertionError):
        spec_checks.accepted_entries_are_untouched(gone)


# -- the contract's shape ----------------------------------------------------

def test_top_level_keys_and_limits():
    spec_checks.top_level(SPEC, ROOT)


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    spec_checks.config_entry(SPEC, c, ROOT)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_entry(w):
    spec_checks.workload_entry(SPEC, w)


def test_cells_are_distinct_and_few_take_four_chips():
    spec_checks.cells_are_distinct_and_few_take_four_chips(SPEC)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    spec_checks.metric_entry(SPEC, m)


def test_names_are_unique_and_setup_is_there():
    spec_checks.names_are_unique_and_setup_is_there(SPEC)


# -- what PR 41 added and took out, and what PR 44 unpinned ------------------

@pytest.mark.parametrize("name", spec_checks.H264_ALONE)
def test_what_one_codec_alone_has_names_every_h264_cell(name):
    """The six entries that only the H.264 step can fill list the H.264
    cells of ``workloads``, all of them and no other, however many there
    are; a cell kept on file says itself which of them named it."""
    spec_checks.what_one_codec_alone_has_names_every_h264_cell(
        SPEC, ROOT, name)
    h264 = spec_checks.h264_cells(SPEC, ROOT)
    assert h264 and set(h264) < {w["name"] for w in SPEC["workloads"]}
    # the rule fails where a list lacks an H.264 cell or names another
    for wrong in (h264[:-1], h264 + ["jpeg-1080p60.scroll"]):
        spec = json.loads(json.dumps(SPEC))
        next(m for m in spec["per_layer"]
             if m["name"] == name)["workloads"] = wrong
        with pytest.raises(AssertionError):
            spec_checks.what_one_codec_alone_has_names_every_h264_cell(
                spec, ROOT, name)


def test_the_cell_above_the_knee_is_the_accepted_h264_deployment_at_120():
    """No file of sizes of its own: the deployment is the accepted one
    (the server's default is 60, its range 8-120), and the mix says what
    its client asks for."""
    new, knee = cells.load_cell("h264-1080p120.scroll"), \
        cells.load_cell("h264-1080p60.scroll")
    assert (new.config_name, new.traffic_name, new.chips) == (
        "ws-1080p60-h264", "scroll120", 1)
    assert new.config == knee.config and new.config["reduced"] == []
    assert new.traffic["client"] == {"framerate": 120}
    assert new.end_to_end == knee.end_to_end
    assert [m["name"] for m in new.per_layer] == \
        [m["name"] for m in knee.per_layer]
    # one configuration, a band for each of its mixes: since PR 43's mailbox
    # of one the cell above the knee holds the pipe's four and a little
    # (5.19-5.27 in flight), re-fitted in PR 44 in PR 41's proportions
    regime = new.config["regime"]
    lo, hi = regime["frames_in_flight"]
    assert lo < 5.19 and 5.27 < hi < 6.7
    by_mix = regime["frames_in_flight_by_traffic"]
    assert by_mix["scroll"] == [3.7, 6.2]
    assert all(0 < a < b for a, b in by_mix.values())


def test_cells_kept_on_file_are_whole_and_load_by_their_names():
    kept = spec_checks.kept_cells_are_whole(SPEC, ROOT)
    assert "h264-1080p60.scroll" in kept
    for name in kept:
        cell = cells.load_cell(name)
        assert cell.name == name and len(cell.end_to_end) == 4
    # a name that is in neither place is refused as ever
    with pytest.raises(SystemExit):
        cells.load_cell("h264-1080p60.scrol")


@pytest.mark.parametrize("leaving", [w["name"] for w in SPEC["workloads"]])
def test_a_cell_that_leaves_workloads_stays_on_file(tmp_path, leaving):
    """What a ``benchmark`` PR does to take a cell out and keep it, for any
    cell of ``workloads`` by its name: its configuration gets a second cell
    under a mix it does not yet have, the entry moves from
    ``BENCHMARK.json`` to ``benchmark/kept_cells.json`` with the metrics
    that named it (struck from their lists), no file of the cell is
    deleted, every check of the spec holds, and the harness still loads the
    cell with the metrics it had."""
    root = spec_checks.scratch_checkout(tmp_path)
    (root / "BENCHMARK.json").write_text(json.dumps(SPEC, indent=1))
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in root.rglob("*") if p.is_file()
              and p.name not in ("BENCHMARK.json", "kept_cells.json")}
    spec, gone = spec_checks.take_a_cell_out_and_keep_it(
        SPEC, str(root), leaving)
    assert len(spec["workloads"]) == len(SPEC["workloads"])
    spec_checks.whole(spec_checks.read_spec(str(root)), str(root))
    assert leaving in spec_checks.kept_cells_are_whole(spec, str(root))
    for rel, was in before.items():
        assert (root / rel).read_bytes() == was, rel
    there, here = cells.load_cell(leaving, root=str(root)), \
        cells.load_cell(leaving)
    assert there.config == here.config and there.traffic == here.traffic
    assert [m["name"] for m in there.per_layer] == \
        [m["name"] for m in here.per_layer]
    assert there.end_to_end == here.end_to_end
    # the metrics that named it name it no more, and none is left empty
    for m in spec["per_layer"]:
        assert leaving not in m.get("workloads", [])
        assert m.get("workloads", [""]) != []


def a_later_prs_typing_checkout(tmp_path):
    """What the PRs after PR 44 bring, appended to a copy of the real files:
    a mix with a generator of its own, an H.264 cell and a JPEG cell under
    it on the accepted configurations, and two per-layer entries, one that
    names the new H.264 cell and one that names none (read from a device's
    trace). The six entries that only the H.264 step fills gain the new
    H.264 cell: an addition to their lists. Returns (root, spec, the files
    as they were)."""
    root = spec_checks.scratch_checkout(tmp_path)
    bench = root / "benchmark"
    (bench / "sources").mkdir()
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}
    (bench / "traffic" / "idle.json").write_text(json.dumps({
        "generator": "idle", "params": {"blink_hz": 1}, "check_frames": 4}))
    (bench / "sources" / "idle.py").write_text(
        "from benchmark.sources.desktop import ClockedSource\n"
        "class Source(ClockedSource):\n"
        "    def index_at(self, t):\n        return int(t)\n")
    (bench / "layer_metrics" / "gate_closed_pct.json").write_text(json.dumps({
        "reader": "recorder_terminal", "args": {"terminal": "gated"}}))
    (bench / "layer_metrics" / "step_device_p95_ms.json").write_text(
        json.dumps({"reader": "trace_program",
                    "args": {"config_key": "step_program"}}))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"] += [
        {"name": "h264-1080p60.idle", "config": "ws-1080p60-h264",
         "traffic": "idle", "chips": 1, "why": "a cursor blinks, H.264"},
        {"name": "jpeg-1080p60.idle", "config": "ws-1080p60-jpeg",
         "traffic": "idle", "chips": 1, "why": "a cursor blinks, JPEG"}]
    for m in spec["per_layer"]:
        if m["name"] in spec_checks.H264_ALONE:
            m["workloads"].append("h264-1080p60.idle")
    spec["per_layer"] += [
        {"name": "gate_closed_pct", "unit": "%", "better": "higher",
         "source": "program_span", "layer": "server",
         "moves": "delivered_fps", "workloads": ["h264-1080p60.idle"]},
        {"name": "step_device_p95_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "device programs",
         "moves": "delivered_fps"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root, spec, before


def test_a_later_pr_adds_an_h264_cell_a_jpeg_cell_a_mix_and_two_metrics(
        tmp_path):
    """The acceptance of ISSUE 44's part A: to a copy of the real files a
    later PR appends an H.264 cell, a JPEG cell, a mix with its own
    generator and two per-layer entries, edits no file that is there, and
    every spec-level check tier 1 makes of the real file holds of the copy:
    the shape, the accepted entries in their order with PR 42's five a
    subsequence, what one codec alone has, a cell that leaves and is kept
    (an old one and a new one), what a rehearsal cannot read."""
    root, spec, before = a_later_prs_typing_checkout(tmp_path)
    assert len(spec["workloads"]) == len(SPEC["workloads"]) + 2
    assert len(spec["per_layer"]) == len(SPEC["per_layer"]) + 2
    there = spec_checks.read_spec(str(root))
    spec_checks.whole(there, str(root))
    for rel, was in before.items():
        assert (root / rel).read_bytes() == was, rel
    # (1) every H.264 cell, the new one too, and no JPEG cell
    h264 = spec_checks.h264_cells(there, str(root))
    assert h264 == spec_checks.h264_cells(SPEC, ROOT) + ["h264-1080p60.idle"]
    for name in spec_checks.H264_ALONE:
        spec_checks.what_one_codec_alone_has_names_every_h264_cell(
            there, str(root), name)
    # (2) PR 42's five where they were, with two entries after them
    names = [m["name"] for m in there["per_layer"]]
    assert names[-2:] == ["gate_closed_pct", "step_device_p95_ms"]
    assert [n for n in names if n in spec_checks.DATA_ONLY] == \
        spec_checks.DATA_ONLY
    # (4) derived, so the entry read from a device's trace is in it and the
    # one read from spans is not
    dark = spec_checks.no_device_in_a_rehearsal(there, str(root))
    assert dark == spec_checks.no_device_in_a_rehearsal(SPEC, ROOT) | {
        "step_device_p95_ms"}
    # the new cells list what names no cell and what names them
    new_h, new_j = (cells.load_cell(w, root=str(root)) for w in (
        "h264-1080p60.idle", "jpeg-1080p60.idle"))
    assert {m["name"] for m in new_h.per_layer} >= DRIVING_PATH | set(
        spec_checks.H264_ALONE) | {"gate_closed_pct", "step_device_p95_ms"}
    theirs = {m["name"] for m in new_j.per_layer}
    assert theirs >= DRIVING_PATH | {"step_device_p95_ms"}
    assert not theirs & (set(spec_checks.H264_ALONE) | {"gate_closed_pct"})
    assert cells.module("sources", "idle", str(root / "benchmark")) \
        .Source.index_at(None, 2.5) == 2
    # (3) a cell leaves this later file and is kept: an old one, a new one
    for leaving in (SPEC["workloads"][0]["name"], "h264-1080p60.idle"):
        sub = tmp_path / leaving
        sub.mkdir()
        copy = spec_checks.scratch_checkout(sub, real_root=str(root))
        shutil.copytree(root / "benchmark" / "sources",
                        copy / "benchmark" / "sources")
        after, _gone = spec_checks.take_a_cell_out_and_keep_it(
            there, str(copy), leaving)
        spec_checks.whole(spec_checks.read_spec(str(copy)), str(copy))
        assert leaving in spec_checks.kept_cells_are_whole(after, str(copy))
        assert [m["name"] for m in
                cells.load_cell(leaving, root=str(copy)).per_layer] == \
            [m["name"] for m in
             cells.load_cell(leaving, root=str(root)).per_layer]


def test_a_mixs_own_limits_hold_on_the_real_files():
    spec_checks.a_mixs_own_limits_only_tighten(SPEC, ROOT)
    conf = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ws-1080p60-h264.json")))
    assert conf["limits_by_traffic"]["typing"]["y_outside_pct"] < \
        conf["limits"]["y_outside_pct"]


@pytest.mark.parametrize("own", [
    {"no-such-mix": {"y_outside_pct": 0.01}},
    {"typing": {"psnr_db": 30.0}},
    {"typing": {"y_outside_pct": 0.7}},
    {"typing": {}},
], ids=["a-mix-not-on-file", "a-number-the-reference-lacks",
        "looser-than-the-configurations", "nothing-in-it"])
def test_a_mixs_own_limits_that_loosen_or_name_nothing_are_refused(
        tmp_path, own):
    root = spec_checks.scratch_checkout(tmp_path)
    path = root / "benchmark" / "configs" / "ws-1080p60-h264.json"
    (root / "BENCHMARK.json").write_text(json.dumps(SPEC))
    spec_checks.whole(SPEC, str(root))
    conf = json.loads(path.read_text())
    conf["limits_by_traffic"] = own
    path.write_text(json.dumps(conf))
    with pytest.raises(AssertionError):
        spec_checks.whole(SPEC, str(root))
