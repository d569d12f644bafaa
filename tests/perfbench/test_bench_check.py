"""What decides ``correct``: the comparison's arithmetic, the control (the
program's own coarser quantiser) failing it at a size a test run can hold,
and a whole run with the timed path broken underneath coming out false."""

import asyncio
import io
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.cells import load_cell  # noqa: E402
from benchmark.reference import h264 as ref_h264  # noqa: E402
from benchmark.reference import jpeg as ref_jpeg  # noqa: E402
from benchmark.sources.desktop import draw_desktop  # noqa: E402

W, H = 320, 192


@pytest.mark.parametrize("ref", [ref_jpeg, ref_h264], ids=["jpeg", "h264"])
def test_the_reference_transform_is_orthonormal(ref):
    b = ref.basis()
    assert np.allclose(b @ b.T, np.eye(ref.BLOCK), atol=1e-12)


def test_reference_steps_are_the_standards():
    luma, chroma = ref_jpeg.steps({"jpeg_quality": 50})
    assert luma[0, 0] == 16 and luma[7, 7] == 99 and chroma[0, 0] == 17
    luma40, _ = ref_jpeg.steps({"jpeg_quality": 40})
    assert luma40[0, 0] == 20 and luma40[0, 1] == 14     # scale 125%
    assert ref_h264.qstep(4) == 1.0 and ref_h264.qstep(28) == 16.0
    assert ref_h264.steps({"qp": 34})[1][0, 0] == ref_h264.qstep(32)


def test_coef_error_is_in_quantiser_steps():
    src = np.zeros((8, 8))
    dec = np.full((8, 8), 4.0)             # DC off by 4 * 8 = 32
    e = check.coef_error(src, dec, ref_jpeg.basis(), np.full((8, 8), 16.0))
    assert e[0, 0, 0, 0] == pytest.approx(2.0) and e[0, 0].sum() == pytest.approx(2.0)


def test_a_quantised_picture_is_inside_and_a_coarser_one_is_not():
    rng = np.random.default_rng(0)
    b, q = ref_jpeg.basis(), ref_jpeg.steps({"jpeg_quality": 40})[0]
    src = rng.uniform(40, 200, (64, 64))
    blocks = src.reshape(8, 8, 8, 8).transpose(0, 2, 1, 3)
    coef = np.einsum("ij,yxjk,lk->yxil", b, blocks, b)

    def rebuild(step):
        c = np.round(coef / step) * step
        return np.einsum("ji,yxjk,kl->yxil", b, c, b).transpose(
            0, 2, 1, 3).reshape(64, 64)
    fine = check.outside(check.coef_error(src, rebuild(q), b, q), 0.5)
    coarse = check.outside(check.coef_error(src, rebuild(2 * q), b, q), 0.5)
    assert fine.mean() < 1e-9 and coarse.mean() > 0.2


def test_a_stale_patch_is_a_bad_tile_and_noise_is_not():
    rng = np.random.default_rng(1)
    b, q = ref_h264.basis(), ref_h264.steps({"qp": 25})[0]
    src = rng.uniform(40, 200, (64, 64))
    near = src + rng.uniform(-2, 2, src.shape)
    assert check.bad_tile_count(check.coef_error(src, near, b, q), 1.0) == 0
    stale = near.copy()
    stale[16:48, 0:16] = 225.0                 # a glyph that is not there
    assert check.bad_tile_count(check.coef_error(src, stale, b, q), 1.0) == 2


def test_verdict_needs_every_number_under_its_limit_and_something_compared():
    limits = {"a": 1.0, "b": 0.0}
    assert check.verdict({"a": 0.5, "b": 0.0}, limits, 3)[0]
    assert not check.verdict({"a": 1.5, "b": 0.0}, limits, 3)[0]
    assert not check.verdict({"a": 0.5, "b": 1.0}, limits, 3)[0]
    assert not check.verdict({"a": 0.5, "b": 0.0}, limits, 0)[0]


# -- the control, at a size a test run can hold --------------------------------

def _jpeg_numbers(quality, seed):
    from PIL import Image

    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    cell = load_cell("jpeg-1080p60.scroll")
    base = draw_desktop(W, H, seed)
    twice = np.concatenate([base, base], axis=0)
    enc = JpegStripeEncoder(W, H, stripe_height=64, quality=quality)
    fid = check.Fidelity(ref_jpeg, cell.config["quantiser"])
    for k in range(2):
        frame = twice[12 * k:12 * k + H]
        planes = [np.zeros((H, W)) for _ in range(3)]
        for s in enc.encode_frame(frame):
            img = Image.open(io.BytesIO(s.jpeg))
            img.draft("YCbCr", img.size)
            a = np.asarray(img)
            rows = min(a.shape[0], H - s.y_start)
            for p, plane in enumerate(planes):
                plane[s.y_start:s.y_start + rows] = a[:rows, :W, p]
        fid.add(frame, *planes)
    return fid.numbers(), cell.limits()


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_jpeg_program_passes_and_its_coarser_quantiser_fails(seed):
    cell = load_cell("jpeg-1080p60.scroll")
    q_control = int(cell.config["control"]["env"]["SELKIES_JPEG_QUALITY"])
    sound, limits = _jpeg_numbers(40, seed)
    control, _ = _jpeg_numbers(q_control, seed)
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert control["y_outside_pct"] > limits["y_outside_pct"], control
    assert control["y_outside_pct"] > 3 * sound["y_outside_pct"]


def _h264_numbers(qp, seed):
    from benchmark.decoders import h264 as dec
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    cell = load_cell("h264-1080p60.scroll")
    base = draw_desktop(W, H, seed)
    twice = np.concatenate([base, base], axis=0)
    enc = H264StripeEncoder(W, H, stripe_height=64, qp=qp)
    decs = {}
    fid = check.Fidelity(ref_h264, cell.config["quantiser"])
    y = np.zeros((H, W))
    cb, cr = np.zeros((H // 2, W // 2)), np.zeros((H // 2, W // 2))
    for k in range(3):
        frame = twice[12 * k:12 * k + H]
        for s in enc.encode_frame(frame):
            d = decs.setdefault(s.y_start, dec.Decoder())
            py, pu, pv = d.decode(s.annexb)
            rows = min(py.shape[0], H - s.y_start)
            y[s.y_start:s.y_start + rows] = py[:rows, :W]
            c0 = s.y_start // 2
            cb[c0:c0 + rows // 2] = pu[:rows // 2, :W // 2]
            cr[c0:c0 + rows // 2] = pv[:rows // 2, :W // 2]
        if k:
            fid.add(frame, y, cb, cr)
    for d in decs.values():
        d.close()
    numbers = fid.numbers()
    limits = cell.limits()
    return {k: numbers[k] for k in limits if k in numbers}, limits


def test_h264_program_passes_and_its_coarser_quantiser_fails():
    cell = load_cell("h264-1080p60.scroll")
    qp_control = int(cell.config["control"]["env"]["SELKIES_H264_CRF"])
    sound, limits = _h264_numbers(25, 21)
    control, _ = _h264_numbers(qp_control, 21)
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert control["y_outside_pct"] > limits["y_outside_pct"], control
    assert control["y_outside_pct"] > 3 * max(sound["y_outside_pct"], 0.02)


# -- a whole run with the timed path broken underneath ---------------------------

def _rehearse(workload, seed=5, seconds=3.0, **kw):
    """Everything of a run but the harness's look for a chip."""
    from benchmark import run as bench_run

    args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"])
    bench_run.T_PROCESS = time.monotonic()
    cell = load_cell(workload)
    device = bench_run.device_info(cell.chips, rehearsal=True)
    return asyncio.run(bench_run.run_cell(args, cell, device, (256, 144)))


def serve_a_stale_stripe(monkeypatch, y_start=64):
    """Freeze one row band's bytes where stripes are packed."""
    from selkies_tpu.server.data_server import DataStreamingServer

    real = DataStreamingServer._pack_stripe
    frozen = {}

    def stale(frame_id, s, encoder):
        if s.y_start == y_start:
            s = frozen.setdefault("stripe", s)
        return real(frame_id, s, encoder)

    monkeypatch.setattr(DataStreamingServer, "_pack_stripe",
                        staticmethod(stale))


def test_a_run_whose_server_serves_a_stale_stripe_is_not_correct(monkeypatch):
    """One row band's bytes are frozen where they are produced: the client
    still decodes every stripe, frames still arrive on time, and the picture
    no longer shows the desktop it claims to show."""
    serve_a_stale_stripe(monkeypatch)
    out = _rehearse("jpeg-1080p60.scroll")
    assert out["attempted"] > 100 and out["failed"] == 0
    assert out["compared"]["undecodable"]["value"] == 0
    assert out["compared"]["bad_tiles"]["value"] > 0
    assert out["correct"] is False


def test_a_run_whose_top_band_cannot_be_read_is_not_correct(monkeypatch):
    """Every third frame's top stripe is cut short where it is packed: the
    harness cannot say which step those frames show, so they fall out of the
    latencies and of the sample. They are counted, and the count is held
    to 0."""
    import dataclasses

    from selkies_tpu.server.data_server import DataStreamingServer

    real = DataStreamingServer._pack_stripe

    def cut(frame_id, s, encoder):
        if s.y_start == 0 and frame_id % 3 == 0:
            s = dataclasses.replace(s, jpeg=s.jpeg[:len(s.jpeg) // 8])
        return real(frame_id, s, encoder)

    monkeypatch.setattr(DataStreamingServer, "_pack_stripe",
                        staticmethod(cut))
    out = _rehearse("jpeg-1080p60.scroll")
    assert out["compared"]["unreadable"]["value"] >= 30
    assert out["compared"]["unreadable"]["limit"] == 0
    assert out["correct"] is False


@pytest.mark.parametrize("contents,want", [
    ([3, 4, 5, 6], 0), ([3, None, 5, None], 2), ([None] * 4, 4)])
def test_unreadable_counts_the_windows_frames_without_a_content(contents, want):
    from benchmark.client import Frame
    from benchmark.harness import Run

    class FakeClient:
        frames = [Frame(i, 3, 10.0 + i, 10.1 + i, content=c)
                  for i, c in enumerate(contents)]
        # before and after the window: never counted
        frames = [Frame(90, 3, 1.0, 1.1), *frames, Frame(91, 3, 99.0, 99.1)]

    run = Run.__new__(Run)
    run.window, run.clients = (10.0, 20.0), {"primary": FakeClient}
    assert run.unreadable() == want


def test_the_grace_for_the_last_changes_starts_after_the_first_reading():
    """After the window the first reading of the frames holds the loop, the
    server's too; the time allowed for the last changes to be shown is time
    in which frames flow, so it may not run meanwhile (a traced H.264 run on
    the chip lost 10 of 1800 changes that way: PERF.md)."""
    from types import SimpleNamespace

    from benchmark.harness import Run

    run = Run.__new__(Run)
    run.cell = SimpleNamespace(traffic={"drain_s": 0.4})
    run.sources, run.clients = [], {}
    looks = []

    def shown_so_far():
        looks.append(time.monotonic())
        if len(looks) == 1:
            time.sleep(0.6)               # longer than the whole grace
        return [None] if len(looks) < 3 else [1.0]

    run._shown_so_far = shown_so_far
    asyncio.run(run.drain())
    assert len(looks) == 3


def test_the_same_run_unbroken_is_correct():
    out = _rehearse("jpeg-1080p60.scroll")
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    assert set(out["metrics"]) == {"delivered_fps", "latency_p50_ms",
                                   "wire_kB_per_frame", "setup_s"}
