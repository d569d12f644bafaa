"""The trace reduction (on a hand-made profile and on the small recorded
trace kept beside it) and the roofline count against hand-worked numbers."""

import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import roofline, trace  # noqa: E402

MS = 1e6


def hand_made() -> trace.Profile:
    """A 100 ms window on one device: two steps of 20 ms, the second with a
    kernel of 5 ms inside it, a stray transfer outside any program, and the
    traced window on the session's clock."""
    return trace.Profile(
        modules={0: [("jit_step(123)", 10 * MS, 20 * MS),
                     ("jit_step(123)", 50 * MS, 20 * MS),
                     ("jit_convert_element_type(9)", 75 * MS, 1 * MS)]},
        ops={0: [("fusion.1", 10 * MS, 12 * MS), ("copy.2", 22 * MS, 8 * MS),
                 ("fusion.1", 50 * MS, 12 * MS),
                 ("me_mc_stripes.1", 62 * MS, 5 * MS),
                 ("copy-start.3", 90 * MS, 2 * MS)]},
        host=[(trace.WINDOW_SPAN, 0.0, 100 * MS)])


def test_busy_is_the_union_of_programs_and_operations():
    p = hand_made()
    assert trace.window_s(p) == pytest.approx(0.100)
    # 20 + 20 + 1 + 2 ms; operations inside a program add nothing
    assert trace.busy_s(p) == {0: pytest.approx(0.043)}


def test_program_and_kernel_time_per_execution():
    p = hand_made()
    assert trace.program_ms_per_step(p, "step") == pytest.approx(20.0)
    assert trace.program_ms_per_step(p, "other") is None
    assert trace.kernel_ms_per_call(p, "me_mc_stripes") == pytest.approx(5.0)
    assert trace.kernel_ms_per_call(p, "me_mc") is None    # a name, no prefix
    assert trace.kernel_ms_per_call(p, "fusion") == pytest.approx(12.0)


def test_events_that_straddle_the_window_do_not_count_as_steps():
    p = hand_made()
    p.modules[0].append(("jit_step(123)", 95 * MS, 20 * MS))
    assert trace.program_ms_per_step(p, "step") == pytest.approx(20.0)
    assert trace.busy_s(p)[0] == pytest.approx(0.043 + 0.005 - 0.0)


def test_top_operations_by_device_time():
    top = trace.top_device_ops(hand_made(), k=2)
    assert top[0] == ["fusion.1", pytest.approx(0.024)]
    assert top[1] == ["copy.2", pytest.approx(0.008)]


def test_idle_gaps_are_named_by_the_program_that_ended_them():
    gaps = trace.idle_gaps(hand_made(), k=4)
    # 30..50 ends with a step; 76..90 with a bare transfer, the next program
    # never comes; 0..10 ends with the first step; 92..100 runs out
    assert gaps[0] == ["before step", pytest.approx(0.020)]
    assert gaps[1] == ["until the window's end", pytest.approx(0.014)]
    assert gaps[2] == ["before step", pytest.approx(0.010)]
    assert gaps[3] == ["until the window's end", pytest.approx(0.008)]
    assert trace.program_of("jit_encode_frame_p_cavlc_rgb(106714)") == \
        "encode_frame_p_cavlc_rgb"


def test_a_profile_survives_its_json():
    p = hand_made()
    q = trace.Profile.from_json(p.to_json())
    assert (q.modules, q.ops, q.host) == (p.modules, p.ops, p.host)


def test_hlo_text_is_cut_to_the_operations_name():
    text = ("%me_mc_stripes.1 = (s32[17,4,120]{2,1,0:T(4,128)S(1)}, "
            "u8[17,64,1920]{2,1,0}) custom-call(...)")
    assert trace.short_op_name(text) == "me_mc_stripes.1"
    assert trace.short_op_name("%fusion.24 = u32[557056] fusion(") == "fusion.24"


# -- the small recorded trace: a cut of a real v5e trace of the H.264 cell ----

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "h264_scroll_v5e.json.gz")


@pytest.fixture(scope="module")
def recorded() -> trace.Profile:
    with gzip.open(FIXTURE, "rt") as f:
        return trace.Profile.from_json(f.read())


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    with open(FIXTURE.replace(".json.gz", ".expected.json")) as f:
        import json
        want = json.load(f)
    assert trace.window_s(recorded) == pytest.approx(want["window_s"])
    assert trace.busy_s(recorded)[0] == pytest.approx(want["busy_s"])
    assert trace.program_ms_per_step(
        recorded, "encode_frame_p_cavlc_rgb") == pytest.approx(
        want["step_device_ms"])
    assert trace.kernel_ms_per_call(
        recorded, "me_mc_stripes") == pytest.approx(want["me_kernel_ms"])
    assert [n for n, _ in trace.top_device_ops(recorded, 3)] == want["top3"]
    # sanity, by eye on the trace: a P step is tens of ms, the kernel a few
    assert 20 < want["step_device_ms"] < 80 and 2 < want["me_kernel_ms"] < 20
    assert 0 < want["busy_s"] < want["window_s"]


# -- the roofline count ----------------------------------------------------------

def test_me_count_for_the_served_1080p_shape_by_hand():
    ops, bytes_ = roofline.me_ops_and_bytes(17, 64, 1920, search=12)
    luma = 17 * 64 * 1920                       # 2,088,960
    assert luma == 2_088_960
    assert ops == 625 * 2_088_960 * 2 == 2_611_200_000
    # cur + ref luma in, ref chroma in, pred luma + chroma out, 8160 MVs out
    assert bytes_ == 3 * 2_088_960 + 2 * 1_044_480 + 8160 * 8 == 8_421_120


def test_roofline_share_and_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    ops, bytes_ = roofline.me_ops_and_bytes(17, 64, 1920)
    pct, bound = roofline.roofline_pct(ops, bytes_, 7.6e-3, peak,
                                       "int8_ops_per_s")
    assert bound == "memory"                    # 10.28 us against 6.64 us
    assert pct == pytest.approx(100 * (8_421_120 / 819e9) / 7.6e-3)
    assert 0.13 < pct < 0.14
    pct2, bound2 = roofline.roofline_pct(1e12, 1e3, 1.0, peak,
                                         "bf16_flops_per_s")
    assert bound2 == "compute" and pct2 == pytest.approx(100 / 197.0)


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
