"""Rate/distortion quality gate: tpuenc-H.264 vs x264 superfast.

"matches the reference" includes what
pixels look like at a bitrate. The reference's daily driver is pixelflux's
x264 at preset superfast, tune zerolatency, with in-loop deblocking
(reference gstwebrtc_app.py:609-640); tpuenc ships integer-pel ME,
Intra16x16-only keyframes, and no deblocking. This tool measures what
those missing tools actually cost:

  * corpus: synthetic desktop content (scrolling text-like pattern,
    window/desktop pattern, smooth gradient pan) — the content class the
    product streams;
  * tpuenc: QP sweep over the real H264StripeEncoder; distortion comes
    from the encoder's reconstruction planes, which the conformance
    suite certifies bit-exact with libavcodec's decode of the stream;
  * x264: CRF sweep through the same libavcodec (native/conformance.cpp
    conf_x264_new), decoded back with the same conformance decoder;
  * metrics: mean Y-PSNR vs the BT.601 luma of the source, bits per
    frame, and the Bjøntegaard-delta rate (BD-rate) of tpuenc against
    x264 over the overlapping quality range.

Run: ``python tools/quality_measure.py [--width W --height H --frames N]``
→ one JSON document (also suitable for BASELINE.md tables).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------- corpus


def _text_pattern(h, w, rng):
    """Text-like rows: high-contrast fine horizontal structure."""
    img = np.full((h, w, 3), 242, np.uint8)
    y = 8
    while y < h - 12:
        n_words = rng.integers(4, 10)
        x = 12
        for _ in range(n_words):
            ww = int(rng.integers(20, 90))
            if x + ww >= w - 12:
                break
            img[y:y + 9, x:x + ww] = rng.integers(10, 70)
            x += ww + 12
        y += 16
    return img


def corpus(width, height, n_frames, kind, seed=0):
    """Yield n_frames of one content class."""
    rng = np.random.default_rng(seed)
    if kind == "scroll":
        page = _text_pattern(height * 2, width, rng)
        for t in range(n_frames):
            y0 = (7 * t) % height
            yield page[y0:y0 + height]
    elif kind == "desktop":
        base = np.full((height, width, 3), 52, np.uint8)
        for _ in range(6):                      # windows
            y0, x0 = rng.integers(0, height // 2), rng.integers(0, width // 2)
            hh, ww = rng.integers(80, height // 2), rng.integers(120, width // 2)
            base[y0:y0 + hh, x0:x0 + ww] = rng.integers(180, 250, 3)
            base[y0:y0 + 14, x0:x0 + ww] = rng.integers(60, 120, 3)
        cursor = rng.integers(0, 200, (24, 24, 3), dtype=np.uint8)
        for t in range(n_frames):
            f = base.copy()
            cy = (13 * t) % (height - 24)
            cx = (29 * t) % (width - 24)
            f[cy:cy + 24, cx:cx + 24] = cursor
            yield f
    elif kind == "gradient":
        yy, xx = np.mgrid[0:height, 0:width].astype(np.float32)
        for t in range(n_frames):
            r = (xx + 3 * t) % 256
            g = (yy + 2 * t) % 256
            b = ((xx + yy) / 2 + 5 * t) % 256
            yield np.stack([r, g, b], -1).astype(np.uint8)
    else:
        raise ValueError(kind)


def _bt601_y(rgb):
    r = rgb[..., 0].astype(np.float64)
    g = rgb[..., 1].astype(np.float64)
    b = rgb[..., 2].astype(np.float64)
    return np.clip(0.299 * r + 0.587 * g + 0.114 * b, 0, 255)


def _to_yuv420(rgb):
    """Full-range BT.601 4:2:0 planes (matches ops/color)."""
    r = rgb[..., 0].astype(np.float64)
    g = rgb[..., 1].astype(np.float64)
    b = rgb[..., 2].astype(np.float64)
    y = 0.299 * r + 0.587 * g + 0.114 * b
    cb = 128 - 0.168736 * r - 0.331264 * g + 0.5 * b
    cr = 128 + 0.5 * r - 0.418688 * g - 0.081312 * b

    def sub(p):
        h, w = p.shape
        return p.reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))

    clip = lambda p: np.clip(np.round(p), 0, 255).astype(np.uint8)
    return clip(y), clip(sub(cb)), clip(sub(cr))


def _psnr(a, b):
    mse = float(np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2))
    if mse <= 0:
        return 99.0
    return 10.0 * np.log10(255.0 ** 2 / mse)


# ----------------------------------------------------------------- tpuenc


def measure_tpuenc(frames, width, height, qp):
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    # paint-over disabled (trigger unreachable): RD points must measure
    # one QP, not a mixture with the paint-over QP
    enc = H264StripeEncoder(width, height, qp=qp,
                            paint_over_trigger_frames=10 ** 9)
    total_bytes = 0
    psnrs = []
    for f in frames:
        stripes = enc.encode_frame(f)
        total_bytes += sum(len(s.annexb) for s in stripes)
        recon = np.asarray(enc._ref_y)[:height, :width]
        psnrs.append(_psnr(recon, _bt601_y(f)))
    return total_bytes / len(psnrs), float(np.mean(psnrs))


# ------------------------------------------------------------------ x264


def measure_x264(frames, width, height, crf, preset=b"superfast"):
    from selkies_tpu.encoder.conformance import ConformanceDecoder
    from selkies_tpu.native import conformance_lib

    lib = conformance_lib()
    if lib is None:
        raise RuntimeError("conformance/x264 lib unavailable")
    h = lib.conf_x264_new(width, height, crf, 0, preset)
    if not h:
        raise RuntimeError("libx264 encoder unavailable")
    dec = ConformanceDecoder("h264", max_dim=max(width, height))
    out = np.empty(1 << 24, np.uint8)
    total_bytes = 0
    psnrs = []
    pending = []                   # frames awaiting decode output
    try:
        for f in frames:
            y, u, v = _to_yuv420(f)
            n = lib.conf_enc_encode(h, np.ascontiguousarray(y.reshape(-1)),
                                    np.ascontiguousarray(u.reshape(-1)),
                                    np.ascontiguousarray(v.reshape(-1)),
                                    out, out.size)
            if n < 0:
                raise RuntimeError(f"x264 encode failed ({n})")
            pending.append(_bt601_y(f))
            if n > 0:
                total_bytes += int(n)
                got = dec.decode(bytes(out[:n]))
                if got is not None:
                    yd, _, _ = got
                    src_y = pending.pop(0)
                    psnrs.append(_psnr(yd[:height, :width], src_y))
        n = lib.conf_enc_flush(h, out, out.size)
        if n > 0:
            total_bytes += int(n)
            got = dec.decode(bytes(out[:n]))
            if got is not None:
                yd, _, _ = got
                psnrs.append(_psnr(yd[:height, :width], pending.pop(0)))
        for yd, _, _ in dec.flush():
            if pending:
                psnrs.append(_psnr(yd[:height, :width], pending.pop(0)))
    finally:
        lib.conf_enc_free(h)
        dec.close()
    return total_bytes / max(len(psnrs), 1), float(np.mean(psnrs))


# --------------------------------------------------------------- BD-rate


def bd_rate(rd_ref, rd_test):
    """Bjøntegaard delta rate of test vs ref (negative = test cheaper).

    rd_*: [(bytes_per_frame, psnr)] — integrated over the overlapping
    PSNR range with a cubic fit of log-rate vs PSNR.
    """
    ref = sorted(rd_ref, key=lambda p: p[1])
    test = sorted(rd_test, key=lambda p: p[1])
    lr_ref = np.log10([p[0] for p in ref])
    q_ref = np.array([p[1] for p in ref])
    lr_test = np.log10([p[0] for p in test])
    q_test = np.array([p[1] for p in test])
    lo = max(q_ref.min(), q_test.min())
    hi = min(q_ref.max(), q_test.max())
    if hi <= lo:
        return None
    pr = np.polyfit(q_ref, lr_ref, min(3, len(ref) - 1))
    pt = np.polyfit(q_test, lr_test, min(3, len(test) - 1))
    xs = np.linspace(lo, hi, 128)
    ir = np.trapezoid(np.polyval(pr, xs), xs)
    it = np.trapezoid(np.polyval(pt, xs), xs)
    return float((10 ** ((it - ir) / (hi - lo)) - 1) * 100.0)


# ------------------------------------------------------------------ main


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=1280)
    ap.add_argument("--height", type=int, default=704)
    ap.add_argument("--frames", type=int, default=48)
    ap.add_argument("--kinds", default="scroll,desktop,gradient")
    ap.add_argument("--tpu-qps", default="20,26,32,38")
    ap.add_argument("--x264-crfs", default="18,23,28,33")
    args = ap.parse_args()

    result = {"width": args.width, "height": args.height,
              "frames": args.frames,
              "x264": "libx264 superfast tune=zerolatency (the reference's "
                      "pixelflux posture, gstwebrtc_app.py:609-640)",
              "corpora": {}}
    for kind in args.kinds.split(","):
        frames = list(corpus(args.width, args.height, args.frames, kind))
        rd_tpu, rd_x264 = [], []
        for qp in (int(q) for q in args.tpu_qps.split(",")):
            bpf, psnr = measure_tpuenc(frames, args.width, args.height, qp)
            rd_tpu.append({"qp": qp, "bytes_per_frame": round(bpf),
                           "y_psnr": round(psnr, 2)})
        for crf in (int(c) for c in args.x264_crfs.split(",")):
            bpf, psnr = measure_x264(frames, args.width, args.height, crf)
            rd_x264.append({"crf": crf, "bytes_per_frame": round(bpf),
                            "y_psnr": round(psnr, 2)})
        bd = bd_rate(
            [(p["bytes_per_frame"], p["y_psnr"]) for p in rd_x264],
            [(p["bytes_per_frame"], p["y_psnr"]) for p in rd_tpu])
        result["corpora"][kind] = {
            "tpuenc": rd_tpu,
            "x264_superfast": rd_x264,
            "bd_rate_vs_x264_pct": round(bd, 1) if bd is not None else None,
        }
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
