"""On-device CAVLC (encoder/device_cavlc.py): bit-exactness vs native.

Tier-1-safe seeded subset of ``tools/cavlc_fuzz.py --device``: the device
packer's P-slice payloads, glued to a host slice header, must be
BIT-IDENTICAL to native/cavlc.cpp over the full residual surface (luma +
chroma DC/AC, skip/mvd paths, |level| > 127), and overflow must be
flagged exactly where the flat16 + host fallback has to engage.
"""

import functools

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

#: (seed, max_stripe_bytes, the rung of the output ladder the frame has to
#: take: 0 is the capacity). A stripe of 8 macroblocks gets ladders of five
#: (64 KiB: 524,288 / 131,072 / 32,768 / 8,192 / 2,048 bits), four (8 KiB:
#: 65,536 down to 1,024) and three (2 KiB: 16,384 / 4,096 / 1,024), and the
#: same content lands on every rung that its bits can reach (no stripe of 8
#: macroblocks holds the 131,072 bits that rung 0 of 64 KiB starts at) and
#: past the capacity, where the stripe is flagged and the rest stays exact
#: (2 KiB: 5, 7, 30)
CASES = [(seed, 65536, rung) for seed, rung in enumerate(
    (3, 3, 2, 3, 1, 2, 2, 1, 2, 3, 2, 2))] \
    + [(25, 65536, 4), (16, 65536, 1)] \
    + [(seed, 8192, rung) for seed, rung in (
        (5, 0), (10, 0), (0, 1), (8, 1), (9, 2), (14, 2), (25, 3))] \
    + [(seed, 2048, 0) for seed in (0, 1, 2, 3, 8, 5, 7, 30)] \
    + [(9, 2048, 1), (14, 2048, 1), (25, 2048, 2)]


@pytest.mark.parametrize("seed,max_stripe_bytes,rung", CASES)
def test_device_pack_matches_native(seed, max_stripe_bytes, rung):
    """Bit-exact against native/cavlc.cpp, and the tiered pack's buffer
    byte-identical to the single-tier body's, whichever rung it takes."""
    fuzz = _fuzz()
    ok, why, _, took = fuzz.check_device_seed(
        seed, max_stripe_bytes=max_stripe_bytes, **GEOM)
    assert ok, why
    assert took == rung


def test_cases_take_every_rung_at_two_capacities():
    """The sweep above leaves no rung of the served shape (a ladder of
    three or more: capacity, middle, low tier) untaken."""
    from selkies_tpu.encoder import device_cavlc as dcav

    n_mb = GEOM["mb_w"] * GEOM["mb_h"]
    for msb, reachable in ((65536, (1, 2, 3, 4)), (8192, (0, 1, 2, 3)),
                           (2048, (0, 1, 2))):
        assert len(dcav.tier_words(msb, n_mb)) == reachable[-1] + 1
        assert {r for _, m, r in CASES if m == msb} == set(reachable)


@pytest.mark.parametrize("mb_w,mb_h,rungs", [
    (120, 4, (32768, 8192, 2048)),         # the served 1080p stripe
    (120, 68, (524288, 131072, 32768)),    # the full-frame profile
    (240, 4, (65536, 16384, 4096)),        # a 4K stripe
])
def test_default_capacity_gets_three_rungs(mb_w, mb_h, rungs):
    """The rungs follow the capacity and the stripe's macroblocks alone:
    a default capacity (256-512 B/MB) ends at 16-32 B/MB."""
    from selkies_tpu.encoder import device_cavlc as dcav

    msb = dcav.default_max_stripe_bytes(mb_w, mb_h)
    assert dcav.tier_words(msb, mb_w * mb_h) == rungs


@pytest.mark.parametrize("side", ["at", "over"])
@pytest.mark.parametrize("rung,density", [
    (0, 0.3), (1, 0.3), (2, 0.3), (3, 0.3), (2, 0.06)])
def test_device_pack_tier_boundary(rung, density, side):
    """A stripe of exactly 32 x a rung's words is the last that rung
    takes; one bit more goes to the rung above (past rung 0, the capacity,
    the stripe is flagged). At density 0.3 the ladder goes on below the
    boundary; at 0.06 the rung is the low tier of a ladder of three. All
    stay bit-exact and byte-identical to the single-tier body."""
    from selkies_tpu.encoder import device_cavlc as dcav

    fuzz = _fuzz()
    at, over, msb = fuzz.boundary_frames(
        0, GEOM["mb_w"], GEOM["mb_h"], rung=rung, density=density)
    ladder = dcav.tier_words(msb, GEOM["mb_w"] * GEOM["mb_h"])
    assert len(ladder) == (3 if density < 0.1 else rung + 2), ladder
    ok, why, n_ovf, took = fuzz.check_device_frame(
        *(at if side == "at" else over), mb_w=GEOM["mb_w"],
        mb_h=GEOM["mb_h"], qp=26, frame_num=3, max_stripe_bytes=msb)
    assert ok, why
    assert (took, n_ovf) == fuzz.boundary_wants(rung)[side]


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


def _last_unit_by_scatter(g0, V):
    """The plain reference: what ``_stripe_words`` computed until PR 39, a
    scatter-max of the unit indices into their start words (clipped to
    the last one) and a running maximum over the words."""
    import jax
    import jax.numpy as jnp

    S, U = g0.shape
    g0c = jnp.clip(g0, 0, V - 1)
    srows = jnp.arange(S, dtype=jnp.int32)[:, None]
    bidx = jnp.arange(U, dtype=jnp.int32)[None, :]
    lastblk = jnp.zeros((S, V), jnp.int32).at[srows, g0c].max(bidx)
    return jax.lax.associative_scan(jnp.maximum, lastblk, axis=1)


def _start_words(kind: str, V: int, S: int = 3, U: int = 12961):
    """g0 [S, U] as ``_unit_spans`` makes it (the word each unit starts in:
    an exclusive running sum of bit lengths, over 32) for the served
    stripe's 12,961 units, with long runs of empty units in every kind."""
    rng = np.random.default_rng(V)
    Lb = rng.integers(1, 545, (S, U)) * (rng.random((S, U)) < 0.3)
    Lb[:, -40:] = 0                      # the stripe's last macroblock skipped
    # "sparse": a few hundred words, whatever the rung holds
    scale = {"sparse": 0.02, "overflow": 1.3, "exact": 1.0}.get(kind, 0.5)
    Lb = (Lb * (scale * 32 * V / Lb.sum(1, keepdims=True))).astype(np.int64)
    if kind == "exact":
        # the last unit that holds bits ends on bit 32 * V, so the empty
        # units after it start in word V, one past the last output word
        last = U - 1 - np.argmax(Lb[:, ::-1] > 0, axis=1)
        Lb[np.arange(S), last] += 32 * V - Lb.sum(1)
        assert (Lb.sum(1) == 32 * V).all() and (Lb >= 0).all()
    if kind == "masked":
        Lb[1] = 0                        # a stripe outside the update mask
    g0 = ((np.cumsum(Lb, axis=1) - Lb) >> 5).astype(np.int32)
    if kind == "overflow":
        assert (g0[:, -1] > V).all()     # units past the last word: clipped
    if kind == "exact":
        assert (g0[:, -1] == V).all()
    return g0


#: the served 1080p stripe's three rungs, and a V that is no power of two
#: and no multiple of the histogram's 128 lanes
@pytest.mark.parametrize("V", [32768, 8192, 2048, 1000])
@pytest.mark.parametrize("kind", [
    "sparse", "overflow", "exact", "masked", "vmap"])
def test_last_unit_equals_the_scatter_form(kind, V):
    """``_last_unit`` (a histogram of the start words on the MXU and a
    running sum) gives the scatter form's integers for any g0 the pack
    can make: runs of empty units, units clipped at V - 1, a stripe of
    exactly 32 * V bits, a stripe that packs nothing, and a batch of
    lanes under ``jax.vmap``."""
    import jax
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav

    new = functools.partial(dcav._last_unit, V=V)
    ref = functools.partial(_last_unit_by_scatter, V=V)
    if kind == "vmap":
        g0 = jnp.asarray(np.stack([
            _start_words(k, V, S=2) for k in ("sparse", "overflow", "masked")]))
        new, ref = jax.vmap(new), jax.vmap(ref)
    else:
        g0 = jnp.asarray(_start_words(kind, V))
    got = np.asarray(jax.jit(new)(g0))
    assert got.dtype == np.int32 and got.shape == g0.shape[:-1] + (V,)
    np.testing.assert_array_equal(got, np.asarray(jax.jit(ref)(g0)))
    assert (got[..., -1] == g0.shape[-1] - 1).all()   # every unit counted


def test_stripe_words_lowers_to_no_scatter():
    """The scatter of 17 x 12,961 updates cost 1.9 ms of every step
    whatever the rung (PERF.md, PR 39); it must not come back unnoticed,
    and the reference above must still be one (the check sees what it is
    for)."""
    import jax
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav

    S, U, W, V = 2, 217, dcav.UNIT_WORDS, 512
    cs = jax.ShapeDtypeStruct((S, U * W), jnp.uint32)
    g = jax.ShapeDtypeStruct((S, U), jnp.int32)
    text = jax.jit(dcav._stripe_words, static_argnums=(4, 5)).lower(
        cs, cs, g, g, V, W).as_text()
    assert "gather" in text and "dot_general" in text
    assert "scatter" not in text
    assert "scatter" in jax.jit(
        _last_unit_by_scatter, static_argnums=1).lower(g, V).as_text()


def test_pack_under_vmap_equals_solo_pack():
    """The mesh lanes run the pack under ``jax.vmap`` with the single-tier
    body (parallel/mesh_h264.py): every lane's buffer must be the solo
    tiered pack's, byte for byte, for a frame of each rung of the ladder
    in one batch."""
    import functools

    import jax
    import jax.numpy as jnp

    from selkies_tpu.encoder import device_cavlc as dcav

    fuzz = _fuzz()
    mb_w, mb_h, S, msb = GEOM["mb_w"], GEOM["mb_h"], GEOM["S"], 2048
    frames = [fuzz.random_p_frame(np.random.default_rng(seed), S,
                                  mb_w * mb_h, density, 8)
              for seed, density in ((1, 0.0), (1, 0.02), (2, 0.6))]
    ones = jnp.ones((len(frames), S), bool)
    stacked = [jnp.asarray(np.stack(x)) for x in zip(*frames)]
    lane = functools.partial(dcav.pack_p_frame, mb_w=mb_w, mb_h=mb_h,
                             max_stripe_bytes=msb, tiered=False)
    lanes = np.asarray(jax.jit(jax.vmap(lane))(*stacked, ones, ones))
    rungs = dcav.tier_words(msb, mb_w * mb_h)
    took = set()
    for k, frame in enumerate(frames):
        solo = fuzz.device_buffer(frame, mb_w, mb_h, msb)
        np.testing.assert_array_equal(lanes[k], solo)
        took.add(int(dcav.tier_index(
            dcav.parse_cavlc_head(solo, S)[0], rungs)))
    assert took == {0, 1, 2} == set(range(len(rungs)))


def test_host_low_tier_count_equals_the_device_predicate(monkeypatch):
    """``cavlc_low_tier_frames`` / ``cavlc_frames`` in the pipeline's
    stats: the host counts, from the head it fetched, the P frames whose
    largest stripe fit the lowest rung of the device's output ladder, and
    adds up the words of the rungs the frames took and the payload words
    they carried. Held against the device's index rule written out (and
    run as the device runs it), over a sequence that opens with an IDR
    and has busy frames, a static stretch with its paint-over frame,
    quiet frames and one between."""
    import jax.numpy as jnp

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
    patched = smooth[2].copy()           # a few busy macroblocks: the
    patched[:32, :64] = noise[0][:32, :64]           # middle rung
    frames = [noise[0], noise[1]] + [noise[1]] * 4 + smooth + [patched]
    for frame in frames:
        pipe.submit(frame)
        pipe.poll()
    pipe.flush()
    pipe.close()        # or its ready thread outlives the test
    st = pipe.stats()
    V = msb // 4
    rungs = (V, V // 4, V // 16)         # 64 B/MB and 16 B/MB of 64 MBs
    assert dcav.tier_words(msb, 64) == rungs
    took = [sum(int(t.max()) <= 32 * v for v in rungs[1:]) for t in heads]
    assert took == [int(dcav.tier_index(jnp.asarray(t, jnp.int32), rungs))
                    for t in heads]
    # the IDR went the host coder's way and is in neither count
    assert st["cavlc_frames"] == len(heads) == len(frames) - 1
    assert st["cavlc_low_tier_frames"] == took.count(2)
    assert st["cavlc_tier_words"] == sum(
        enc.n_stripes * rungs[k] for k in took)
    assert st["cavlc_payload_words"] == sum(
        int(np.minimum((t + 31) // 32, V).sum()) for t in heads)
    assert 0 < st["cavlc_payload_words"] < st["cavlc_tier_words"]
    assert len(set(took)) == 3, str([int(t.max()) for t in heads])


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
