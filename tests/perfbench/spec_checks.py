"""What tier 1 holds ``BENCHMARK.json`` to, as functions of a spec and the
checkout it lies in. The tests call them on the real file; the test that
plays a later PR calls ``whole`` on a copy of the real file with a
configuration, a cell and per-layer metrics appended, so a check that pins
how many entries there are fails there, beside the real file's."""

import json
import os
import re
import shutil

from benchmark import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: the per-layer entries PR 24 was accepted with: they stay first, in order
ACCEPTED = ["latency_p95_ms", "warmup_s", "compile_cache_misses",
            "server_send_p50_ms", "submit_drop_pct",
            "driver_dispatch_p50_ms", "driver_fetch_wait_p50_ms",
            "frames_in_flight", "step_device_ms", "me_kernel_ms",
            "me_kernel_roofline", "device_idle_pct"]
#: the entries appended since, in the order they came; what each says is
#: BENCHMARK.json's to state and ``metric_entry``'s to check
APPENDED = ["driver_submit_wait_p50_ms", "driver_pipe_wait_p50_ms",
            "driver_stage_p50_ms", "driver_in_device_p50_ms",
            "driver_pack_p50_ms", "server_harvest_wait_p50_ms",
            "idle_driver_stage_pct", "idle_driver_pack_pct",
            "idle_driver_fetch_pct", "idle_driver_sleep_pct",
            "idle_driver_other_pct", "device_queue_delay_p50_ms",
            "phase_colour_ms", "phase_transform_ms", "phase_entropy_ms",
            "phase_motion_ms", "interpreter_stall_max_ms",
            "loop_stall_max_ms", "cavlc_low_tier_pct"]

#: and after those, in the order they came: PR 29's, PR 33's, PR 42's five
#: (which read the ready watch's stages and counts as data), PR 44's
DATA_ONLY = ["driver_device_wait_p50_ms", "driver_device_run_p50_ms",
             "driver_ready_wait_p50_ms", "driver_ready_wait_p95_ms",
             "launch_idle_pct"]
LATER = ["fetch_prefix_hit_pct", "cavlc_tier_fill_pct", *DATA_ONLY,
         "ready_stamp_lag_p50_ms"]
#: what only the H.264 step can fill: these entries list the H.264 cells
H264_ALONE = ["me_kernel_ms", "me_kernel_roofline", "phase_motion_ms",
              "cavlc_low_tier_pct", "fetch_prefix_hit_pct",
              "cavlc_tier_fill_pct"]
#: readers of the device probe's clock pairs: no device, nothing to read
CLOCK_PROBE_READERS = {"clock_probe"}

#: the five ways the idle split reads its thread's track (the ``args`` of the
#: five ``idle_driver_*_pct`` files, less which threads): together they
#: cover every idle second once
IDLE_SPLIT = ({"states": ["stage", "dispatch"]}, {"states": ["pack", "emit"]},
              {"states": ["fetch_wait"]}, {"states": ["sleep"]},
              {"unmarked": True})


def read_spec(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def scratch_checkout(tmp_path, real_root=ROOT):
    """A checkout in ``tmp_path`` that holds the real data files (those of
    the checkout at ``real_root``), as a later PR finds them: it adds files
    and a ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(real_root, "benchmark", sub),
                        root / "benchmark" / sub)
    kept = os.path.join(real_root, "benchmark", "kept_cells.json")
    if os.path.exists(kept):
        shutil.copy(kept, root / "benchmark" / "kept_cells.json")
    return root


def top_level(spec, root):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["paths"]) <= 16 and len(spec["command"]) <= 32
    assert 1 <= len(spec["configs"]) <= 24 and 1 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["end_to_end"]) <= 16 and 1 <= len(spec["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def config_entry(spec, c, root):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
    assert any(c["file"].startswith(p + "/") for p in spec["paths"])
    with open(os.path.join(root, c["file"])) as f:
        body = json.load(f)
    assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    assert all(k in body for k in c["reduced"])
    for text in (c["source"], c["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(w["config"] == c["name"] for w in spec["workloads"])


def workload_entry(spec, w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in spec["configs"]}


def cells_are_distinct_and_few_take_four_chips(spec):
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(spec["workloads"]) // 2)


def metric_entry(spec, m):
    e2e = m in spec["end_to_end"]
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
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in spec["workloads"]}
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def names_are_unique_and_setup_is_there(spec):
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for kind in ("workloads", "configs"):
        assert len({x["name"] for x in spec[kind]}) == len(spec[kind])
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)


def cell_finds_its_files(workload, root):
    bench_dir = os.path.join(root, "benchmark")
    cell = cells.load_cell(workload, root=root)
    assert cell.config["name"] == cell.config_name
    gen = cells.module("sources", cell.traffic["generator"], bench_dir)
    assert hasattr(gen, "Source")
    ref = cells.module("reference", cell.config["reference"], bench_dir)
    assert {"undecodable", "unreadable", "bad_tiles"} <= set(
        cell.limits()) <= {"undecodable", "unreadable", "y_outside_pct",
                           "c_outside_pct", "bad_tiles"}
    lo, hi = cell.config["regime"]["frames_in_flight"]
    assert 0 < lo < hi
    assert ref.steps(cell.config["quantiser"])[0].shape == (ref.BLOCK,) * 2
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    assert cell.config["control"]["env"]
    return cell


def a_mixs_own_limits_only_tighten(spec, root):
    """``limits_by_traffic`` of a configuration: every key is a mix on file,
    every number under it is one of the configuration's ``limits`` (what the
    reference computes), and its value is at or under the configuration's
    own: a mix may need a tighter limit to tell its control from its sound
    runs, and none is ever loosened by one."""
    traffic = os.path.join(root, "benchmark", "traffic")
    for c in spec["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            config = json.load(f)
        for mix, own in config.get("limits_by_traffic", {}).items():
            assert os.path.exists(os.path.join(traffic, mix + ".json")), mix
            assert own and set(own) <= set(config["limits"]), (mix, own)
            for k, v in own.items():
                assert 0 <= v <= config["limits"][k], (mix, k, v)


def kept_cells_are_whole(spec, root):
    """A cell that left ``workloads`` and is kept on file
    (``benchmark/kept_cells.json``): it is in no list of ``BENCHMARK.json``
    any more, its entry is as it was there, its configuration is still one
    of ``configs`` (with another cell), its files are on disk, the harness
    loads it by its name with the metrics that named it, and it says when
    it returns. Returns the names; [] where no cell is kept."""
    kept = cells.kept_cells(os.path.join(root, "benchmark"))
    live = {w["name"] for w in spec["workloads"]}
    names = {m["name"] for m in spec["per_layer"]}
    extra = {"per_layer", "left", "returns_when"}
    for w in kept.get("workloads", []):
        assert w["name"] not in live and extra <= set(w)
        # ``readings``: what the builder read of a cell that was measured
        # and not admitted (optional)
        workload_entry(spec, {k: v for k, v in w.items()
                              if k not in extra | {"readings"}})
        assert w["returns_when"] and w["left"]
        assert set(w["per_layer"]) <= names
        for m in spec["end_to_end"] + spec["per_layer"]:
            assert w["name"] not in m.get("workloads", [])
        cell = cell_finds_its_files(w["name"], root)
        assert {m["name"] for m in cell.per_layer} >= set(w["per_layer"])
    return [w["name"] for w in kept.get("workloads", [])]


def per_layer_metric_has_a_reader(name, root):
    bench_dir = os.path.join(root, "benchmark")
    spec = cells.layer_metric_spec(name, bench_dir)
    assert callable(cells.module("readers", spec["reader"], bench_dir).read)
    return spec


def accepted_entries_are_untouched(spec):
    """The twelve first entries as they were accepted and in their order,
    every entry appended since after them and in the order it came (PR 42's
    five among them: present, in their order, after every entry accepted
    before them; a subsequence, not the tail). How many follow, and what
    stands between, is no business of this check: a later PR appends."""
    names = [m["name"] for m in spec["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    came = APPENDED + LATER
    assert [n for n in names if n in came] == came
    for name in APPENDED:
        appended_entry(spec, name)


def appended_entry(spec, name):
    m = next(x for x in spec["per_layer"] if x["name"] == name)
    metric_entry(spec, m)
    # appended after the accepted entries, to a layer they already name
    assert spec["per_layer"].index(m) >= len(ACCEPTED)
    assert m["layer"] in {x["layer"]
                          for x in spec["per_layer"][:len(ACCEPTED)]}
    return m


def codec_of(spec, root, w):
    """What the configuration of the cell entry ``w`` hands the server as
    its encoder."""
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, conf["file"])) as f:
        return json.load(f)["env"]["SELKIES_ENCODER"]


def h264_cells(spec, root):
    """The cells of ``workloads`` whose configuration says
    ``x264enc-striped``, in their order."""
    return [w["name"] for w in spec["workloads"]
            if codec_of(spec, root, w) == "x264enc-striped"]


def what_one_codec_alone_has_names_every_h264_cell(spec, root, name):
    """A cell that reports ``delivered_fps`` reports what moves it: an entry
    that only the H.264 step can fill lists the H.264 cells of
    ``workloads``, all of them and no other (however many there are); an
    H.264 cell kept on file says itself that the entry named it."""
    m = next(x for x in spec["per_layer"] if x["name"] == name)
    assert sorted(m["workloads"]) == sorted(h264_cells(spec, root))
    assert len(set(m["workloads"])) == len(m["workloads"])
    assert m["moves"] == "delivered_fps"
    for w in cells.kept_cells(os.path.join(root, "benchmark")).get(
            "workloads", []):
        if codec_of(spec, root, w) == "x264enc-striped":
            assert name in w["per_layer"], (w["name"], name)


def no_device_in_a_rehearsal(spec, root):
    """The per-layer entries that only a device's trace or its clock probe
    can fill: a CPU rehearsal has neither, and its line leaves them out for
    that stated reason. Derived from what each entry says of itself
    (``source``) and from its reader, not a list of names."""
    out = set()
    for m in spec["per_layer"]:
        reader = cells.layer_metric_spec(
            m["name"], os.path.join(root, "benchmark"))["reader"]
        if m["source"] == "device_trace" or reader in CLOCK_PROBE_READERS:
            out.add(m["name"])
    return out


def take_a_cell_out_and_keep_it(spec, root, leaving):
    """What a ``benchmark`` PR does to take the cell ``leaving`` out of
    ``workloads`` and keep it on file, played on the checkout at ``root``
    (a scratch one) for any number of cells: its configuration gets a
    second cell under a mix it does not have yet (the contract lets no
    configuration go empty), the entry moves to ``kept_cells.json`` with the
    metrics that named it, which strike it from their lists, and no file of
    the cell is deleted. Returns (the new spec, the entry that left)."""
    spec = json.loads(json.dumps(spec))
    bench = os.path.join(root, "benchmark")
    gone = next(w for w in spec["workloads"] if w["name"] == leaving)
    mix = "stays"
    shutil.copy(os.path.join(bench, "traffic", gone["traffic"] + ".json"),
                os.path.join(bench, "traffic", mix + ".json"))
    stays = dict(gone, name=gone["config"] + ".stays", traffic=mix,
                 why="a second cell of the configuration whose cell leaves")
    spec["workloads"] = [w for w in spec["workloads"] if w is not gone] \
        + [stays]
    named = []
    for m in spec["end_to_end"] + spec["per_layer"]:
        if leaving in m.get("workloads", []):
            if m in spec["per_layer"]:
                named.append(m["name"])
            m["workloads"].remove(leaving)
            # the cell that takes its configuration's place is named by
            # what one codec alone has, and by a list it would leave empty
            if m["name"] in H264_ALONE or not m["workloads"]:
                m["workloads"].append(stays["name"])
    kept = cells.kept_cells(bench) or {"workloads": []}
    kept["workloads"].append(dict(gone, per_layer=named, left="PR n",
                                  returns_when="a stated reading"))
    with open(os.path.join(bench, "kept_cells.json"), "w") as f:
        json.dump(kept, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return spec, gone


def whole(spec, root):
    """Every check above, over every entry of ``spec``."""
    top_level(spec, root)
    for c in spec["configs"]:
        config_entry(spec, c, root)
    for w in spec["workloads"]:
        workload_entry(spec, w)
        cell_finds_its_files(w["name"], root)
    cells_are_distinct_and_few_take_four_chips(spec)
    for m in spec["end_to_end"] + spec["per_layer"]:
        metric_entry(spec, m)
    for m in spec["per_layer"]:
        per_layer_metric_has_a_reader(m["name"], root)
    names_are_unique_and_setup_is_there(spec)
    accepted_entries_are_untouched(spec)
    for name in H264_ALONE:
        what_one_codec_alone_has_names_every_h264_cell(spec, root, name)
    no_device_in_a_rehearsal(spec, root)
    a_mixs_own_limits_only_tighten(spec, root)
    kept_cells_are_whole(spec, root)
