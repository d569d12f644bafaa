"""On-device CAVLC (encoder/device_cavlc.py): bit-exactness vs native.

Tier-1-safe seeded subset of ``tools/cavlc_fuzz.py --device``: the device
packer's P-slice payloads, glued to a host slice header, must be
BIT-IDENTICAL to native/cavlc.cpp over the full residual surface (luma +
chroma DC/AC, skip/mvd paths, |level| > 127), and overflow must be
flagged exactly where the flat16 + host fallback has to engage.
"""

import numpy as np
import pytest

from selkies_tpu.native import cavlc_lib

pytestmark = pytest.mark.skipif(
    cavlc_lib() is None, reason="native CAVLC reference unavailable")


def _fuzz():
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    return importlib.import_module("cavlc_fuzz")


# one fixed small geometry so the jitted pack compiles once per capacity for
# the whole seeded sweep (distinct geometries cost a CPU recompile each)
GEOM = dict(mb_w=4, mb_h=2, S=2)

#: (seed, max_stripe_bytes, the output tier the frame has to take). At 64 KiB
#: a stripe of 8 macroblocks cannot leave the low tier (its limit is 128 kbit);
#: at 2 KiB the limit is 4,096 bits and the capacity 16,384, so the same
#: content lands under the limit (9, 14, 25), over it (0-3, 8) and past the
#: capacity, where the stripe is flagged and the rest stays exact (5, 7, 30)
CASES = [(seed, 65536, "low") for seed in range(12)] \
    + [(seed, 2048, "high") for seed in (0, 1, 2, 3, 8, 5, 7, 30)] \
    + [(seed, 2048, "low") for seed in (9, 14, 25)]


@pytest.mark.parametrize("seed,max_stripe_bytes,tier", CASES)
def test_device_pack_matches_native(seed, max_stripe_bytes, tier):
    """Bit-exact against native/cavlc.cpp, and the tiered pack's buffer
    byte-identical to the single-tier body's, whichever tier it takes."""
    fuzz = _fuzz()
    ok, why, _, took = fuzz.check_device_seed(
        seed, max_stripe_bytes=max_stripe_bytes, **GEOM)
    assert ok, why
    assert took == tier


@pytest.mark.parametrize("side,tier", [("at", "low"), ("over", "high")])
def test_device_pack_tier_boundary(side, tier):
    """A stripe of exactly 32 * V_LO bits is the last the low tier takes;
    one bit more goes to the high tier. Both stay bit-exact and
    byte-identical to the single-tier body."""
    fuzz = _fuzz()
    at, over, msb = fuzz.boundary_frames(0, GEOM["mb_w"], GEOM["mb_h"])
    ok, why, n_ovf, took = fuzz.check_device_frame(
        *(at if side == "at" else over), mb_w=GEOM["mb_w"],
        mb_h=GEOM["mb_h"], qp=26, frame_num=3, max_stripe_bytes=msb)
    assert ok, why
    assert (took, n_ovf) == (tier, 0)


@pytest.mark.parametrize("tiered", [True, False])
def test_device_pack_overflow_levels_flagged_and_rest_exact(tiered):
    """|level| past the 28-bit escape must flag its stripe (the product
    then recodes it from flat16); clean stripes in the same frame stay
    bit-exact — through the tiered and the single-tier body."""
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav
    from selkies_tpu.encoder.h264 import encode_picture_nals_np

    mb_w, mb_h, S = 4, 2, 2
    n = mb_w * mb_h
    mv = np.zeros((S, n, 2), np.int32)
    luma = np.zeros((S, n, 16, 4, 4), np.int32)
    cdc = np.zeros((S, n, 2, 2, 2), np.int32)
    cac = np.zeros((S, n, 2, 4, 4, 4), np.int32)
    luma[0, 0, 0, 0, 1] = 3000          # escape overflow → fallback
    luma[1, 2, 3, 2, 2] = 2063          # still encodable, > int8 range
    words, t_bits, base_words, ovf = [np.asarray(x) for x in (
        dcav.pack_p_frame_words(
            jnp.asarray(mv), jnp.asarray(luma), jnp.asarray(cdc),
            jnp.asarray(cac), jnp.ones(S, bool),
            mb_w=mb_w, mb_h=mb_h, max_stripe_bytes=16384, tiered=tiered))]
    assert list(ovf) == [True, False]
    payload = np.stack(
        [(words >> 24) & 0xFF, (words >> 16) & 0xFF,
         (words >> 8) & 0xFF, words & 0xFF], -1).astype(np.uint8).reshape(-1)
    start = int(base_words[1]) * 4
    nbits = int(t_bits[1])
    got = dcav.assemble_p_slice(
        payload[start:start + ((nbits + 31) // 32) * 4], nbits, 26, 3)
    ldc = np.zeros((n, 4, 4), np.int32)
    ref = encode_picture_nals_np(
        mv[1], luma[1], ldc, cdc[1], cac[1], is_idr=False,
        mb_w=mb_w, mb_h=mb_h, qp=26, frame_num=3)
    assert got == ref


def test_pack_under_vmap_equals_solo_pack():
    """The mesh lanes run the pack under ``jax.vmap`` with the single-tier
    body (parallel/mesh_h264.py): every lane's buffer must be the solo
    tiered pack's, byte for byte, for a low-tier and a high-tier frame in
    one batch."""
    import functools

    import jax
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav

    fuzz = _fuzz()
    mb_w, mb_h, S, msb = GEOM["mb_w"], GEOM["mb_h"], GEOM["S"], 2048
    frames = [fuzz.random_p_frame(np.random.default_rng(seed), S,
                                  mb_w * mb_h, density, 8)
              for seed, density in ((1, 0.02), (2, 0.6))]
    ones = jnp.ones((len(frames), S), bool)
    stacked = [jnp.asarray(np.stack(x)) for x in zip(*frames)]
    lane = functools.partial(dcav.pack_p_frame, mb_w=mb_w, mb_h=mb_h,
                             max_stripe_bytes=msb, tiered=False)
    lanes = np.asarray(jax.jit(jax.vmap(lane))(*stacked, ones, ones))
    tiers = set()
    for k, frame in enumerate(frames):
        solo = fuzz.device_buffer(frame, mb_w, mb_h, msb)
        np.testing.assert_array_equal(lanes[k], solo)
        tiers.add(bool(dcav.takes_low_tier(
            dcav.parse_cavlc_head(solo, S)[0], msb)))
    assert tiers == {True, False}


def test_host_low_tier_count_equals_the_device_predicate(monkeypatch):
    """``cavlc_low_tier_frames`` / ``cavlc_frames`` in the pipeline's
    stats: the host counts, from the head it fetched, the P frames whose
    largest stripe fit the device's low output tier. Held against the
    device's predicate written out, over a sequence with busy frames, a
    static stretch with its paint-over frame, and quiet frames."""
    from selkies_tpu.encoder import device_cavlc as dcav
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

    w, h, sh = 256, 128, 64
    enc = H264StripeEncoder(w, h, stripe_height=sh, qp=26, search=4,
                            paint_over_trigger_frames=2, entropy="device")
    pipe = PipelinedH264Encoder(enc, depth=2)
    msb = dcav.default_max_stripe_bytes(enc.pad_w // 16, sh // 16)
    heads = []
    parse = dcav.parse_cavlc_head

    def recording(host, n_stripes):
        out = parse(host, n_stripes)
        heads.append(out[0])
        return out

    monkeypatch.setattr(dcav, "parse_cavlc_head", recording)
    rng = np.random.default_rng(5)
    noise = [rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
             for _ in range(2)]
    yy, xx = np.mgrid[0:h, 0:w]
    smooth = [np.stack([(xx + 2 * t) % 255, yy % 255, (xx + yy) % 255],
                       -1).astype(np.uint8) for t in range(3)]
    for frame in [noise[0], noise[1]] + [noise[1]] * 4 + smooth:
        pipe.submit(frame)
        pipe.poll()
    pipe.flush()
    st = pipe.stats()
    limit = 32 * (msb // 4 // dcav.LOW_TIER_DIV)
    low = [int(t.max()) <= limit for t in heads]
    assert st["cavlc_frames"] == len(heads) >= 8
    assert st["cavlc_low_tier_frames"] == sum(low)
    assert 0 < sum(low) < len(low), [int(t.max()) for t in heads]


def test_update_mask_packs_nothing():
    """Stripes outside the update mask must contribute zero payload (the
    fetch prefix only carries emitting stripes)."""
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav

    mb_w, mb_h, S = 4, 2, 2
    n = mb_w * mb_h
    mv = np.zeros((S, n, 2), np.int32)
    luma = np.zeros((S, n, 16, 4, 4), np.int32)
    luma[:, :, :, 1, 1] = 5
    cdc = np.zeros((S, n, 2, 2, 2), np.int32)
    cac = np.zeros((S, n, 2, 4, 4, 4), np.int32)
    _, t_bits, _, _ = dcav.pack_p_frame_words(
        jnp.asarray(mv), jnp.asarray(luma), jnp.asarray(cdc),
        jnp.asarray(cac), jnp.asarray([True, False]),
        mb_w=mb_w, mb_h=mb_h, max_stripe_bytes=16384)
    t_bits = np.asarray(t_bits)
    assert t_bits[0] > 0 and t_bits[1] == 0


def test_ep_escape_sequential_reset_semantics():
    """00 00 00 00 01 must escape to 00 00 03 00 00 03 01 (the inserted
    0x03 resets the zero-run count) — the exact semantics of
    native/cavlc.cpp append_nal."""
    from selkies_tpu.encoder.device_cavlc import _ep_escape

    assert _ep_escape(np.array([0, 0, 0, 0, 1], np.uint8)) == \
        bytes([0, 0, 3, 0, 0, 3, 1])
    assert _ep_escape(np.array([0, 0, 0, 0, 0, 1], np.uint8)) == \
        bytes([0, 0, 3, 0, 0, 3, 0, 1])
    assert _ep_escape(np.array([0, 0, 2], np.uint8)) == bytes([0, 0, 3, 2])
    assert _ep_escape(np.array([0, 0, 4], np.uint8)) == bytes([0, 0, 4])
    assert _ep_escape(np.array([1, 2, 3], np.uint8)) == bytes([1, 2, 3])
