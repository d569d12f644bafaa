"""The typing cells rehearsed on the CPU (256x144, Pallas in interpret mode):
each ends ``correct`` with every listed key that needs no device read, the
same changes fall due in every run, a run whose server withholds a stripe
of what was typed is not correct, and each configuration's control on the
typing mix comes out not correct by the number it is there to fail."""

import asyncio
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import spec_checks  # noqa: E402
from benchmark.cells import load_cell  # noqa: E402
from test_bench_check import _rehearse, serve_a_stale_stripe  # noqa: E402
from test_bench_rehearsal import rehearse  # noqa: E402

SPEC = spec_checks.read_spec(ROOT)
CELLS = ["h264-1080p60.typing", "jpeg-1080p60.typing"]
#: the schedule's first six seconds hold 22 keystrokes, whatever the seed
DUE_IN_SIX_SECONDS = 22


@pytest.mark.parametrize("workload", CELLS)
def test_a_typing_cell_is_an_accepted_configuration_under_the_typing_mix(
        workload):
    cell = load_cell(workload)
    assert cell.traffic_name == "typing" and cell.chips == 1
    assert cell.config_name in {c["name"] for c in SPEC["configs"]}
    assert cell.config["reduced"] == [] and "client" not in cell.traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "delivered_fps", "latency_p50_ms", "wire_kB_per_frame", "setup_s"}
    # a band of its own: a stream of five frames a second holds a third of
    # a frame in flight, not the scroll cells' three to five
    lo, hi = cell.config["regime"]["frames_in_flight_by_traffic"]["typing"]
    assert 0 < lo < hi < 1
    listed = {m["name"] for m in cell.per_layer}
    h264 = cell.config["env"]["SELKIES_ENCODER"] == "x264enc-striped"
    assert (set(spec_checks.H264_ALONE) <= listed) == h264
    assert ("ready_stamp_lag_p50_ms" in listed) == h264


@pytest.mark.parametrize("workload,seed", [
    (CELLS[0], 2**31 + 44), (CELLS[1], 44)])
def test_a_typing_cell_rehearses_end_to_end(workload, seed, capsys):
    code, out, err = rehearse(capsys, workload, 0, seconds="6",
                              seed=str(seed))
    cell = load_cell(workload)
    assert code == 0 and out["correct"] is True, out
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    # the same changes in every run, every one shown; frames: one a
    # keystroke and one a paint-over, in the pauses and in the middle of a
    # burst (eight of the 22 keystrokes are followed by over 15 still ticks;
    # on a loaded CPU two keystrokes a few ticks apart can share a frame and
    # a paint-over can be overtaken by the next keystroke)
    assert out["attempted"] == DUE_IN_SIX_SECONDS and out["failed"] == 0
    frames = out["metrics"]["delivered_fps"]["value"] * 6.0
    assert DUE_IN_SIX_SECONDS - 3 <= round(frames) <= DUE_IN_SIX_SECONDS + 10
    w = out["window"]
    assert w["session_fps"] == {"primary": 60.0}
    assert w["band"] == cell.config["regime"][
        "frames_in_flight_by_traffic"]["typing"]
    assert w["frames_in_flight"] < 1.0
    # content is read from the recorder: every frame of the window has one
    assert out["compared"]["unreadable"] == {"value": 0.0, "limit": 0}
    assert out["compared"]["bad_tiles"]["value"] == 0
    assert f"changes due in the window: {DUE_IN_SIX_SECONDS}; " \
        f"never shown: 0" in err
    assert "late against their 60 Hz ticks" in err
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("workload", CELLS)
def test_a_typing_cell_traced_every_listed_key_or_a_stated_reason(
        workload, capsys):
    code, out, err = rehearse(capsys, workload, 1, seconds="6")
    assert code == 0 and out["correct"] is True, out
    listed = {m["name"] for m in load_cell(workload).per_layer}
    got = out["metrics"]
    assert set(got) <= listed
    dark = spec_checks.no_device_in_a_rehearsal(SPEC, ROOT)
    assert listed - set(got) <= dark, (listed - set(got)) - dark
    assert not set(got) & dark
    assert out["attempted"] == DUE_IN_SIX_SECONDS and out["failed"] == 0
    assert out["window"]["regime"] == "traced"
    assert got["frames_in_flight"]["value"] == pytest.approx(
        out["window"]["frames_in_flight"])
    # a capture that emits nothing is no drop (what is dropped here is the
    # CPU's: an interpreted step does not keep up with 60 captures a second)
    assert 0.0 <= got["submit_drop_pct"]["value"] < 100.0
    assert "idle by thread state: not read:" in err


def test_the_frame_still_open_when_the_stream_runs_dry_is_closed_and_counts():
    """A client knows a frame complete when the next one begins. In a stream
    of five frames a second the last frame before the window's end can have
    no successor until the desktop stops: once the stream has run dry the
    harness closes it, so that it is attributed and counted where it arrived
    (left open it was in no count and ``unreadable``: 28 runs of 28 on the
    chip, PERF.md, PR 44)."""
    from benchmark.client import Client, Frame
    from benchmark.harness import Run

    c = Client(0, "primary", 64, 48)
    c._open = Frame(7, 3, 10.0, 10.1)
    run = Run.__new__(Run)
    run.cell = SimpleNamespace(traffic={"drain_s": 0.1})
    run.sources, run.clients = [], {"primary": c}
    run._shown_so_far = lambda: [1.0]
    asyncio.run(run.drain())
    assert c._open is None and [f.frame_id for f in c.frames] == [7]


def test_a_typing_run_whose_server_withholds_a_stripe_is_not_correct(
        monkeypatch):
    """The row band under the typed line is frozen where stripes are
    packed: every stripe still decodes, every change is still 'shown' on
    time by a frame of the other band, and the last picture lacks the lower
    half of what was typed: ``bad_tiles`` is over 0, ``correct`` false."""
    serve_a_stale_stripe(monkeypatch)
    out = _rehearse("jpeg-1080p60.typing", seconds=6.0)
    assert out["attempted"] == DUE_IN_SIX_SECONDS
    assert out["compared"]["undecodable"]["value"] == 0
    assert out["compared"]["bad_tiles"]["value"] > 0
    assert out["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_on_the_typing_mix_is_not_correct(workload, capsys):
    """The configuration's control (its own coarser quantiser, paint-over
    too) through the whole run at the test's size: the picture that stands
    still is then painted over at the coarser step, and ``y_outside_pct``
    says so."""
    from benchmark import run as bench_run

    capsys.readouterr()
    code = bench_run.main(["--workload", workload, "--seed", "7",
                           "--seconds", "6", "--trace", "0", "--control", "1",
                           "--rehearsal", "256x144"])
    import json

    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 0 and out["attempted"] == DUE_IN_SIX_SECONDS
    y = out["compared"]["y_outside_pct"]
    assert y["value"] > y["limit"], out["compared"]
    assert out["correct"] is False
    # the H.264 cell's limit is the mix's own: on a picture that stands still
    # the control reads 0.12 on the chip (0.28 here) where it reads 3.85 on
    # one that scrolls, under the configuration's 0.6; the sound runs read 0
    cell = load_cell(workload)
    if cell.config["reference"] == "h264":
        assert y["limit"] == cell.config["limits_by_traffic"]["typing"][
            "y_outside_pct"] < cell.config["limits"]["y_outside_pct"]
