"""CAVLC conformance fuzzer: crafted level arrays → C++ coder → ffmpeg,
and the device-CAVLC differential mode.

Mode 1 (``python tools/cavlc_fuzz.py [n]``): drives h264_encode_picture
with synthetic quantized-level arrays (bypassing the device transforms) so
every (totalCoeff, trailingOnes, nC-class, total_zeros, run_before) table
entry gets exercised, then decodes with OpenCV/ffmpeg and compares against
the NumpyMirror reconstruction.  Validates the hand-entered spec tables in
native/cavlc.cpp.

Mode 2 (``python tools/cavlc_fuzz.py --device [n] [--geom=WxH]``):
differential-fuzzes the ON-DEVICE CAVLC packer (encoder/device_cavlc.py)
against the native _libselkies_cavlc.so reference over random P-frame level
tensors — full residual surface (luma + chroma DC/AC), random MVs (skip/mvd
paths), |level| > 127 edges and escape-overflow magnitudes — and random
stripe capacities, so that frames land on every rung of the pack's output
ladder and past the capacity, plus quiet frames (the low rungs, a desktop's
own) and a constructed pair one bit either side of every rung boundary.  The tiered pack's buffer must equal the
single-tier body's byte for byte; non-overflow stripes must be
BIT-IDENTICAL to native; overflow stripes must be flagged (they take the
flat16 + host fallback in the product).  tests/test_device_cavlc.py runs a
seeded subset of this under tier 1.
"""

import functools
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from selkies_tpu.encoder.h264 import make_pps, make_sps  # noqa: E402
from selkies_tpu.native import cavlc_lib  # noqa: E402
from selkies_tpu.ops.h264_transform import NumpyMirror  # noqa: E402


def mirror_recon_luma(levels, qp, pred=128):
    """Decoder-side luma recon for P-style plain 4×4 levels (n,16,4,4)."""
    d = NumpyMirror.dequant4(levels, qp)
    r = NumpyMirror.inverse_dct4(d)
    return r + pred  # caller clips


def assemble_plane(blocks, mb_w, mb_h):
    """(n,16,4,4) → (H, W) with raster 4×4 grid inside raster MBs."""
    n = mb_w * mb_h
    v = blocks.reshape(mb_h, mb_w, 4, 4, 4, 4)
    v = v.transpose(0, 2, 4, 1, 3, 5)
    return v.reshape(mb_h * 16, mb_w * 16)


def encode_two_frames(luma_levels, mb_w, mb_h, qp):
    lib = cavlc_lib()
    n = mb_w * mb_h
    zero_mv = np.zeros((n, 2), np.int32)
    zero_luma = np.zeros((n, 16, 16), np.int32)
    zero_ldc = np.zeros((n, 16), np.int32)
    zero_cdc = np.zeros((n, 2, 4), np.int32)
    zero_cac = np.zeros((n, 2, 4, 16), np.int32)
    cap = 1 << 22
    buf = np.empty(cap, np.uint8)
    # IDR: all-zero levels → flat 128
    sz = lib.h264_encode_picture(1, mb_w, mb_h, qp, 0, 0, zero_mv, zero_luma,
                                 zero_ldc, zero_cdc, zero_cac, buf, cap)
    idr = bytes(buf[:sz])
    ll = np.ascontiguousarray(luma_levels.reshape(n, 16, 16), np.int32)
    sz = lib.h264_encode_picture(0, mb_w, mb_h, qp, 1, 0, zero_mv, ll,
                                 zero_ldc, zero_cdc, zero_cac, buf, cap)
    p = bytes(buf[:sz])
    return make_sps(mb_w * 16, mb_h * 16) + make_pps() + idr + p


def decode_stream(data):
    import cv2  # lazy: the --device mode needs no decoder

    path = tempfile.mktemp(suffix=".h264")
    with open(path, "wb") as f:
        f.write(data)
    cap = cv2.VideoCapture(path)
    cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
    frames = []
    while True:
        ok, y = cap.read()
        if not ok:
            break
        frames.append(y.copy())
    os.unlink(path)
    return frames


def random_levels(rng, n_mb, density, magnitude):
    lv = rng.integers(-magnitude, magnitude + 1, (n_mb, 16, 4, 4))
    mask = rng.random((n_mb, 16, 4, 4)) < density
    return (lv * mask).astype(np.int32)


def check_seed(seed, qp=26, mb_w=2, mb_h=2, density=None, magnitude=None):
    rng = np.random.default_rng(seed)
    density = density if density is not None else rng.uniform(0.05, 0.9)
    magnitude = magnitude if magnitude is not None else int(rng.integers(1, 9))
    levels = random_levels(rng, mb_w * mb_h, density, magnitude)
    stream = encode_two_frames(levels, mb_w, mb_h, qp)
    frames = decode_stream(stream)
    if len(frames) != 2:
        return False, f"decoded {len(frames)} frames", levels
    expect = np.clip(
        mirror_recon_luma(levels, qp) .astype(np.int64), -10**9, 10**9)
    expect = np.clip(assemble_plane(expect, mb_w, mb_h) , 0, 255)
    got = frames[1].astype(np.int64)
    if not np.array_equal(got, expect):
        diff = int(np.abs(got - expect).max())
        return False, f"pixel mismatch max {diff}", levels
    return True, "", levels


def random_p_frame(rng, S, n_mb, density, magnitude, mv_range=12):
    """Random device-encoder-shaped P-frame level tensors for S stripes."""
    def sparse(shape, mag):
        lv = rng.integers(-mag, mag + 1, shape)
        return (lv * (rng.random(shape) < density)).astype(np.int32)

    mv = rng.integers(-mv_range, mv_range + 1, (S, n_mb, 2)).astype(np.int32)
    if rng.random() < 0.3:
        mv[:] = 0                        # all-skip / skip-run paths
    elif rng.random() < 0.3:
        mv[:] = mv[:, :1]                # uniform motion → long skip runs
    luma = sparse((S, n_mb, 16, 4, 4), magnitude)
    cdc = sparse((S, n_mb, 2, 2, 2), magnitude)
    cac = sparse((S, n_mb, 2, 4, 4, 4), magnitude)
    cac[..., 0, 0] = 0                   # device zeroes the AC DC slot
    return mv, luma, cdc, cac


@functools.lru_cache(maxsize=None)
def _packer(mb_w, mb_h, max_stripe_bytes, tiered):
    """The product's pack, jitted once per shape and capacity (on the
    chip an un-jitted call would compile operation by operation)."""
    import jax

    from selkies_tpu.encoder import device_cavlc as dcav

    return jax.jit(functools.partial(
        dcav.pack_p_frame, mb_w=mb_w, mb_h=mb_h,
        max_stripe_bytes=max_stripe_bytes, tiered=tiered))


def device_buffer(frame, mb_w, mb_h, max_stripe_bytes, tiered=True):
    """The fetchable buffer of one (mv, luma, cdc, cac) frame with every
    stripe damaged and updated, as a host array."""
    import jax.numpy as jnp

    every = jnp.ones(frame[0].shape[0], bool)
    return np.asarray(_packer(mb_w, mb_h, max_stripe_bytes, tiered)(
        *[jnp.asarray(x) for x in frame], every, every))


def check_device_frame(mv, luma, cdc, cac, *, mb_w, mb_h, qp, frame_num,
                       max_stripe_bytes):
    """Differential: one frame's device pack + host glue vs native coder.

    The buffer of the tiered pack (the output stage sized by the frame's
    bits) must equal the single-tier pack's byte for byte, whichever rung
    the frame takes; non-overflow stripes must then be bit-identical to
    native.  Returns (ok, why, n_overflow, rung) with rung the host's
    reading of the index the device branched on: 0 is the capacity-sized
    stage, ``len(tier_words(...)) - 1`` the low tier.  Overflow
    stripes are exempt from the bit-compare (the product recodes them
    from flat16 via the native path, which IS the reference — trivially
    identical) but must be flagged so that fallback actually engages.
    """
    from selkies_tpu.encoder import device_cavlc as dcav
    from selkies_tpu.encoder.h264 import encode_picture_nals_np

    S, n_mb = mv.shape[:2]
    buf, single = [
        device_buffer((mv, luma, cdc, cac), mb_w, mb_h, max_stripe_bytes,
                      tiered) for tiered in (True, False)]
    t_bits, base_words, _, ovf = dcav.parse_cavlc_head(buf, S)
    rung = int(dcav.tier_index(
        t_bits, dcav.tier_words(max_stripe_bytes, n_mb)))
    n_ovf = int(ovf.sum())
    if not np.array_equal(buf, single):
        return False, f"rung {rung}'s buffer differs from the " \
            "single-tier body's", n_ovf, rung

    ldc = np.zeros((n_mb, 4, 4), np.int32)
    for s in range(S):
        if ovf[s]:
            continue
        ref = encode_picture_nals_np(
            mv[s], luma[s], ldc, cdc[s], cac[s], is_idr=False,
            mb_w=mb_w, mb_h=mb_h, qp=qp, frame_num=frame_num)
        pb, nbits = dcav.payload_slice(buf, S, base_words, t_bits, s)
        if dcav.assemble_p_slice(pb, nbits, qp, frame_num) != ref:
            return False, f"stripe {s} bit mismatch", n_ovf, rung
    return True, "", n_ovf, rung


#: per-stripe capacities the fuzz draws from, in bytes per macroblock (the
#: served stripe has 273): against stripes of ~10 to ~1,600 B/MB they put
#: frames on every rung of ladders of two to five (the floor is 16 B/MB)
#: and past the capacity (the stripe-size overflow flag)
STRIPE_BYTES_PER_MB = (64, 256, 1024, 8192)

#: and no capacity past the served one: ``_stripe_words`` carries a
#: unit's first and last word in 15 bits each, so a stripe past word
#: 32,767 of a larger capacity comes out wrong and unflagged (PERF.md
#: section 7, PR 33: found by this fuzz at 120x4 on the chip)
MAX_STRIPE_BYTES = 4 << 15


def check_device_seed(seed, mb_w=None, mb_h=None, S=2, qp=None,
                      frame_num=None, max_stripe_bytes=None, density=None):
    """:func:`check_device_frame` over one seed's random frame
    (``density``: the share of coefficients that are not zero, where the
    seed's own draw of 2-90% is not wanted)."""
    rng = np.random.default_rng(seed)
    mb_w = mb_w if mb_w is not None else int(rng.integers(2, 7))
    mb_h = mb_h if mb_h is not None else int(rng.integers(1, 4))
    qp = qp if qp is not None else int(rng.integers(10, 48))
    frame_num = frame_num if frame_num is not None else int(
        rng.integers(1, 16))
    drawn = rng.uniform(0.02, 0.9)
    density = drawn if density is None else density
    # |level| > 127 (int8-sparse overflow) and escape-overflow (> ~2064)
    # edges both land regularly
    magnitude = int(rng.choice([1, 2, 8, 30, 127, 200, 2063, 2500]))
    mv, luma, cdc, cac = random_p_frame(rng, S, mb_w * mb_h, density,
                                        magnitude)
    if max_stripe_bytes is None:
        max_stripe_bytes = min(
            int(rng.choice(STRIPE_BYTES_PER_MB)) * mb_w * mb_h,
            MAX_STRIPE_BYTES)
    return check_device_frame(
        mv, luma, cdc, cac, mb_w=mb_w, mb_h=mb_h, qp=qp,
        frame_num=frame_num, max_stripe_bytes=max_stripe_bytes)


#: one lone luma coefficient codes in 1..14 bits as it runs through these
#: (level_code 0..13 at suffix_length 0): a knob of one bit a step
_KNOB = [v for m in range(2, 9) for v in (m, -m)]


def boundary_frames(seed, mb_w, mb_h, S=2, rung=1, density=0.3):
    """Two frames either side of a rung boundary, and the capacity that
    puts it there: (at, over, max_stripe_bytes).  Stripe 0 of ``at`` is
    exactly as many bits long as rung ``rung`` of the capacity's ladder
    holds (the last bit that rung takes), stripe 0 of ``over`` one bit
    longer (the rung above; past rung 0, the capacity, the stripe is
    flagged); every other stripe is shorter.  ``density`` sets how many
    bytes per macroblock the boundary lies at, so how many rungs the
    ladder has below it (0.3: ~130 B/MB, one rung more; 0.06: ~45 B/MB,
    none: the boundary of the low tier)."""
    from selkies_tpu.encoder import device_cavlc as dcav

    rng = np.random.default_rng(seed)
    n_mb = mb_w * mb_h
    mv, luma, cdc, cac = random_p_frame(rng, S, n_mb, density, 8)
    luma[1:, n_mb // 2:] = 0             # the other stripes: shorter

    def with_knobs(steps):               # stripe 0, block 0 of three MBs
        out = luma.copy()
        for mb, k in enumerate(steps):
            out[0, mb, 0] = 0
            out[0, mb, 0, 0, 0] = _KNOB[k]
        return out

    def bits_of(lu):
        return dcav.parse_cavlc_head(device_buffer(
            (mv, lu, cdc, cac), mb_w, mb_h, MAX_STRIPE_BYTES), S)[0]

    def steps_for(extra):                # 0..39 bits over three knobs
        return [min(13, max(0, extra - 13 * i)) for i in range(3)]

    extra = int(-bits_of(with_knobs([0, 0, 0]))[0] % 32)
    at, over = with_knobs(steps_for(extra)), with_knobs(steps_for(extra + 1))
    t_at, t_over = bits_of(at), bits_of(over)
    assert t_at[0] % 32 == 0 and t_over[0] == t_at[0] + 1, (t_at, t_over)
    assert (t_at[1:] < t_at[0]).all(), t_at
    msb = int(t_at[0]) // 8 * dcav.TIER_RATIO ** rung
    assert dcav.tier_words(msb, n_mb)[rung] * 32 == t_at[0]
    return (mv, at, cdc, cac), (mv, over, cdc, cac), msb


def boundary_wants(rung):
    """What the pair of :func:`boundary_frames` has to read: {side: (rung
    taken, stripes flagged)}."""
    return {"at": (rung, 0),
            "over": (max(rung - 1, 0), int(rung == 0))}


#: (rung, density) of the pairs main_device constructs: the boundaries of a
#: ladder of three, the served shape: its capacity, V / 4 with V / 16 below
#: it, and V / 16 as the low tier
BOUNDARIES = ((0, 0.3), (1, 0.3), (2, 0.06))


def main_device(n, geom=None):
    """``n`` random frames, a quarter as many quiet ones, then the
    constructed pairs either side of every rung boundary. ``geom`` (mb_w,
    mb_h) pins one geometry: on the chip every geometry and capacity is a
    compile of its own."""
    import collections

    from selkies_tpu.encoder import device_cavlc as dcav

    fails, n_ovf, rungs = [], 0, collections.Counter()

    def note(label, result):
        nonlocal n_ovf
        ok, why, ovf, rung = result
        n_ovf += ovf
        rungs[rung] += 1
        if not ok:
            fails.append((label, why))
            print(f"{label}: FAIL ({why})")

    mb_w, mb_h = geom or (None, None)
    for seed in range(n):
        note(f"seed {seed}", check_device_seed(seed, mb_w=mb_w, mb_h=mb_h))
    # quiet frames (0.2-3% of the coefficients: 2-30 B/MB, a streaming
    # desktop's), which the draw above all but never makes: the low rungs
    n_quiet = n // 4
    for seed in range(n_quiet):
        note(f"quiet seed {seed}", check_device_seed(
            seed, mb_w=mb_w, mb_h=mb_h, density=0.002 * (1 + seed % 16)))
    edges = [geom] if geom else [(4, 2), (6, 3), (5, 1)]
    n_edge = 0
    for seed, (mb_w, mb_h) in enumerate(edges):
        for rung, density in BOUNDARIES:
            at, over, msb = boundary_frames(seed, mb_w, mb_h, rung=rung,
                                            density=density)
            ladder = dcav.tier_words(msb, mb_w * mb_h)
            wants = boundary_wants(rung)
            for name, frame in (("at", at), ("over", over)):
                result = check_device_frame(
                    *frame, mb_w=mb_w, mb_h=mb_h, qp=26, frame_num=3,
                    max_stripe_bytes=msb)
                if result[0] and (result[3], result[2]) != wants[name]:
                    result = (False, f"took rung {result[3]} of {ladder} "
                              f"with {result[2]} stripes flagged") + result[2:]
                note(f"boundary {mb_w}x{mb_h} rung {rung} of {ladder} {name}",
                     result)
                n_edge += 1
    total = n + n_quiet + n_edge
    took = ", ".join(f"{rungs[k]} rung {k}" for k in sorted(rungs))
    print(f"{total - len(fails)}/{total} passed ({n} random, {n_quiet} "
          f"quiet, {n_edge} at the rung boundaries; {took}, rung 0 the "
          f"capacity; {n_ovf} overflow stripes took the flagged fallback)")
    return 1 if fails else 0


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n = int(args[0]) if args else 500
    if "--device" in sys.argv:
        geom = next((tuple(int(v) for v in a[7:].split("x"))
                     for a in sys.argv if a.startswith("--geom=")), None)
        return main_device(n, geom)
    fails = []
    for seed in range(n):
        ok, why, _ = check_seed(seed)
        if not ok:
            fails.append((seed, why))
            print(f"seed {seed}: FAIL ({why})")
    print(f"{n - len(fails)}/{n} passed")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
