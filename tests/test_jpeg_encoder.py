import io

import numpy as np
import pytest
from PIL import Image

from selkies_tpu.encoder.jpeg import JpegStripeEncoder
from selkies_tpu.encoder import entropy_py
from selkies_tpu.native import entropy_lib
from selkies_tpu.encoder.jpeg_tables import std_tables


def smooth_frame(h, w, seed=0):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    r = 128 + 100 * np.sin(xx / 97.0) * np.cos(yy / 53.0)
    g = 128 + 100 * np.cos(xx / 71.0)
    b = 128 + 100 * np.sin(yy / 89.0)
    return np.clip(np.stack([r, g, b], axis=-1), 0, 255).astype(np.uint8)


def psnr(a, b):
    mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
    return 10 * np.log10(255.0**2 / mse)


def decode_stripes(stripes, h, w):
    """Composite decoded stripes onto a canvas like the client does."""
    canvas = np.zeros((h, w, 3), dtype=np.uint8)
    for s in stripes:
        img = np.asarray(Image.open(io.BytesIO(s.jpeg)).convert("RGB"))
        rows = min(img.shape[0], h - s.y_start)
        canvas[s.y_start:s.y_start + rows, :, :] = img[:rows, :w]
    return canvas


def test_stripes_decode_and_psnr():
    h, w = 128, 160
    frame = smooth_frame(h, w)
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=90)
    stripes = enc.encode_frame(frame)
    assert len(stripes) == 2
    assert [s.y_start for s in stripes] == [0, 64]
    for s in stripes:
        assert s.jpeg.startswith(b"\xff\xd8") and s.jpeg.endswith(b"\xff\xd9")
    rec = decode_stripes(stripes, h, w)
    assert psnr(frame, rec) > 35.0


def test_unpadded_dimensions():
    # 1080 is not a multiple of 64; 150 not a multiple of 16
    h, w = 100, 150
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=85)
    stripes = enc.encode_frame(smooth_frame(h, w))
    assert len(stripes) == 2  # padded to 128 rows
    rec = decode_stripes(stripes, h, w)
    assert psnr(smooth_frame(h, w), rec) > 30.0


def test_damage_gating_skips_static_stripes():
    h, w = 128, 160
    frame = smooth_frame(h, w)
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=80,
                            use_paint_over_quality=False)
    assert len(enc.encode_frame(frame)) == 2
    assert enc.encode_frame(frame) == []  # identical frame → nothing
    frame2 = frame.copy()
    frame2[70, 10] ^= 0xFF  # touch stripe 1 only
    out = enc.encode_frame(frame2)
    assert [s.y_start for s in out] == [64]


def test_paintover_escalation():
    h, w = 64, 64
    frame = smooth_frame(h, w)
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                            paintover_quality=95, paint_over_trigger_frames=3)
    first = enc.encode_frame(frame)
    assert len(first) == 1 and not first[0].is_paintover
    outs = [enc.encode_frame(frame) for _ in range(6)]
    paint = [o for frame_out in outs for o in frame_out]
    assert len(paint) == 1 and paint[0].is_paintover
    # paint-over stripe is visibly better than the low-quality first pass
    rec_low = decode_stripes(first, h, w)
    rec_hi = decode_stripes(paint, h, w)
    assert psnr(frame, rec_hi) > psnr(frame, rec_low) + 3


def test_force_keyframe_reemits_everything():
    h, w = 128, 64
    frame = smooth_frame(h, w)
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=70,
                            use_paint_over_quality=False)
    enc.encode_frame(frame)
    assert enc.encode_frame(frame) == []
    enc.force_keyframe()
    assert len(enc.encode_frame(frame)) == 2


def test_native_entropy_matches_python_oracle():
    lib = entropy_lib()
    if lib is None:
        pytest.skip("no C++ toolchain")
    rng = np.random.default_rng(7)
    by, bx = 4, 6
    # sparse, mixed-sign coefficients exercising runs, ZRL, and categories
    y = (rng.integers(-40, 40, size=(by, bx, 64))
         * (rng.random((by, bx, 64)) < 0.15)).astype(np.int16)
    cb = (rng.integers(-20, 20, size=(by // 2, bx // 2, 64))
          * (rng.random((by // 2, bx // 2, 64)) < 0.1)).astype(np.int16)
    cr = np.zeros_like(cb)
    dc_l, ac_l, dc_c, ac_c = std_tables()
    cap = y.size * 4 + cb.size * 8 + 4096
    out = np.empty(cap, dtype=np.uint8)
    n = lib.jpeg_encode_scan_420(
        y, cb, cr, by, bx,
        dc_l.code_arr, dc_l.len_arr, ac_l.code_arr, ac_l.len_arr,
        dc_c.code_arr, dc_c.len_arr, ac_c.code_arr, ac_c.len_arr,
        out, cap)
    assert n > 0
    assert out[:n].tobytes() == entropy_py.encode_scan_420(y, cb, cr)


def test_device_entropy_mode_matches_host_mode():
    h, w = 128, 160
    frame = smooth_frame(h, w)
    frames = [frame, frame, np.roll(frame, 5, axis=1)]
    enc_d = JpegStripeEncoder(w, h, stripe_height=64, quality=60, entropy="device")
    enc_h = JpegStripeEncoder(w, h, stripe_height=64, quality=60, entropy="host")
    for f in frames:
        out_d = enc_d.encode_frame(f)
        out_h = enc_h.encode_frame(f)
        assert [(s.y_start, s.jpeg) for s in out_d] == \
               [(s.y_start, s.jpeg) for s in out_h]


def test_pipelined_encoder_matches_sync():
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder
    h, w = 128, 96
    frames = [smooth_frame(h, w), smooth_frame(h, w),
              np.roll(smooth_frame(h, w), 7, axis=0),
              np.roll(smooth_frame(h, w), 9, axis=1)]
    sync = JpegStripeEncoder(w, h, stripe_height=64, quality=55)
    want = [[(s.y_start, s.jpeg) for s in sync.encode_frame(f)] for f in frames]

    pipe = PipelinedJpegEncoder(
        JpegStripeEncoder(w, h, stripe_height=64, quality=55), depth=3)
    got = {}
    for f in frames:
        pipe.submit(f)
        for seq, stripes in pipe.poll():
            got[seq] = [(s.y_start, s.jpeg) for s in stripes]
    for seq, stripes in pipe.flush():
        got[seq] = [(s.y_start, s.jpeg) for s in stripes]
    pipe.close()        # or its ready thread outlives the test
    assert [got[i] for i in range(len(frames))] == want


def test_pipelined_paintover_not_duplicated():
    """With frames in flight, a paint-over must fire exactly once."""
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder
    h, w = 64, 64
    frame = smooth_frame(h, w)
    pipe = PipelinedJpegEncoder(
        JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                          paintover_quality=95, paint_over_trigger_frames=3),
        depth=3)
    outs = []
    for _ in range(12):
        pipe.submit(frame)
        outs.extend(s for _, st in pipe.poll() for s in st)
    outs.extend(s for _, st in pipe.flush() for s in st)
    pipe.close()
    paint = [s for s in outs if s.is_paintover]
    assert len(paint) == 1


def test_pipeline_partial_group_flushed_by_poll():
    """fetch_group > 1 must not strand frames when submissions pause
    (regression: poll() flushes a partial fetch group)."""
    import numpy as np

    from selkies_tpu.encoder.jpeg import JpegStripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    enc = PipelinedJpegEncoder(
        JpegStripeEncoder(64, 64, stripe_height=64), depth=8, fetch_group=4)
    rng = np.random.default_rng(0)
    for i in range(2):   # fewer than fetch_group
        enc.submit(rng.integers(0, 255, (64, 64, 3), dtype=np.uint8))
    got = []
    for _ in range(50):
        got += enc.poll()
        if len(got) == 2:
            break
    enc.close()
    assert len(got) == 2
    assert all(stripes for _, stripes in got)


def test_watermark_overlay(tmp_path):
    """pixelflux watermark parity: PNG blended on device at the configured
    location; output decodes with the mark present."""
    import io

    import numpy as np
    from PIL import Image

    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    wm = Image.new("RGBA", (32, 16), (255, 0, 0, 255))
    wm_path = tmp_path / "wm.png"
    wm.save(wm_path)

    frame = np.full((64, 128, 3), 32, np.uint8)
    plain = JpegStripeEncoder(128, 64, stripe_height=64, quality=90)
    marked = JpegStripeEncoder(128, 64, stripe_height=64, quality=90,
                               watermark_path=str(wm_path),
                               watermark_location=0)  # top-left
    out_p = plain.encode_frame(frame)
    out_m = marked.encode_frame(frame)
    img_p = np.asarray(Image.open(io.BytesIO(out_p[0].jpeg)).convert("RGB"))
    img_m = np.asarray(Image.open(io.BytesIO(out_m[0].jpeg)).convert("RGB"))
    # top-left region (16px margin) turns red; far corner unchanged
    assert img_m[20, 20, 0] > 180 and img_m[20, 20, 1] < 90
    assert abs(int(img_p[60, 120, 0]) - int(img_m[60, 120, 0])) < 10
    # opaque overlay exact: (32*0 + 255*255 + 127)//255 == 255
    assert img_p[20, 20, 0] < 60


def test_watermark_missing_file_disabled(tmp_path):
    import numpy as np

    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    enc = JpegStripeEncoder(64, 64, watermark_path=str(tmp_path / "nope.png"))
    assert enc._wm_scaled is None
    assert enc.encode_frame(np.zeros((64, 64, 3), np.uint8))


def test_watermark_clamped_at_frame_edge(tmp_path):
    """A mark bigger than the space at its placement is cropped, never a
    constructor crash (regression)."""
    import numpy as np
    from PIL import Image

    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    wm_path = tmp_path / "big.png"
    Image.new("RGBA", (64, 64), (0, 255, 0, 255)).save(wm_path)
    enc = JpegStripeEncoder(64, 64, stripe_height=64,
                            watermark_path=str(wm_path),
                            watermark_location=0)
    assert enc._wm_scaled is not None
    assert enc.encode_frame(np.zeros((64, 64, 3), np.uint8))
