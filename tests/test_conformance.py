"""Bitstream conformance: decode tpuenc output with a production decoder.

The browser's WebCodecs decoders are the real consumers (reference client
selkies-core.js:2032/2155/2925); libavcodec stands in for them here.  The
H.264 check is the strong one: the decoder's pixels must be BIT-EXACT with
the encoder's own reconstruction loop, because both are required to run the
identical §8.5 integer arithmetic.
"""

import numpy as np
import pytest

from selkies_tpu.encoder import conformance

pytestmark = pytest.mark.skipif(
    not conformance.available(), reason="libavcodec conformance decoder unavailable")

RNG = np.random.default_rng(7)


def _smooth_frame(h, w, seed=0, shift=0):
    """Natural-ish content: smooth gradients + a few rectangles, rolled by
    ``shift`` pixels to exercise motion search."""
    yy, xx = np.mgrid[0:h, 0:w]
    base = (128 + 90 * np.sin(xx / 37.0 + seed) * np.cos(yy / 23.0)).astype(np.float32)
    img = np.stack([base, np.roll(base, 5, 1), 255 - base], axis=-1)
    r = np.random.default_rng(seed)
    for _ in range(6):
        y0, x0 = r.integers(0, h - 16), r.integers(0, w - 16)
        hh, ww = r.integers(8, h - y0 + 1), r.integers(8, w - x0 + 1)
        img[y0:y0 + hh, x0:x0 + ww] = r.integers(0, 256, 3)
    img = np.roll(img, shift, axis=1)
    return np.clip(img, 0, 255).astype(np.uint8)



def _src_planes(frame):
    """Source YCbCr 4:2:0 planes via the encoder's own color path."""
    import jax.numpy as jnp
    from selkies_tpu.encoder.h264_device import prepare_planes
    h, w = frame.shape[:2]
    return tuple(np.asarray(p) for p in
                 prepare_planes(jnp.asarray(frame), h, w))

# ---------------------------------------------------------------------------
# H.264


def test_h264_idr_bit_exact_with_recon():
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h, sh = 128, 96, 48
    enc = H264StripeEncoder(w, h, stripe_height=sh, qp=24)
    frame = _smooth_frame(h, w, seed=1)
    stripes = enc.encode_frame(frame)
    assert len(stripes) == len(enc.stripes)
    decoders = {st.y0: conformance.ConformanceDecoder("h264", max_dim=256)
                for st in enc.stripes}
    for s in stripes:
        assert s.is_key
        got = decoders[s.y_start].decode(s.annexb)
        assert got is not None
        dy, du, dv = got
        i = s.y_start // enc.stripe_h
        ry, rcb, rcr = enc.stripe_ref(i)
        np.testing.assert_array_equal(dy, ry[:s.height, :w])
        np.testing.assert_array_equal(du, rcb[:s.height // 2, :w // 2])
        np.testing.assert_array_equal(dv, rcr[:s.height // 2, :w // 2])
    for d in decoders.values():
        d.close()


def test_h264_p_frames_bit_exact_over_gop():
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h, sh = 112, 64, 32
    enc = H264StripeEncoder(w, h, stripe_height=sh, qp=28, search=8)
    decoders = {st.y0: conformance.ConformanceDecoder("h264", max_dim=256)
                for st in enc.stripes}
    # 6 frames of horizontally-scrolling content → P frames with real MVs
    for t in range(6):
        frame = _smooth_frame(h, w, seed=3, shift=3 * t)
        stripes = enc.encode_frame(frame)
        for s in stripes:
            got = decoders[s.y_start].decode(s.annexb)
            assert got is not None, f"t={t} stripe {s.y_start}: no frame out"
            dy, du, dv = got
            ry, rcb, rcr = enc.stripe_ref(s.y_start // enc.stripe_h)
            np.testing.assert_array_equal(
                dy, ry[:s.height, :w],
                err_msg=f"t={t} stripe {s.y_start} luma mismatch")
            np.testing.assert_array_equal(du, rcb[:s.height // 2, :w // 2])
            np.testing.assert_array_equal(dv, rcr[:s.height // 2, :w // 2])
    for d in decoders.values():
        d.close()


@pytest.mark.slow  # ~50 s (a fresh 2-shard SPMD compile); transitively
# covered in tier 1 — test_parallel pins the SFE bytes to the solo
# encoder's, whose output the tier-1 conformance tests above decode
def test_sfe_multi_shard_stream_decodes_bit_exact():
    """Split-frame encoding (ISSUE 15): one frame's stripe bands encoded
    on DIFFERENT chips must decode in libavcodec bit-exact with the
    encoder's own sharded reconstruction planes — IDR then P — i.e. the
    host-concatenated access unit is a conformant stream, not merely
    byte-stable."""
    import jax

    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    w, h, sh = 112, 64, 32                    # 2 stripes, one per shard
    mesh = parse_mesh_spec("session:1,stripe:2", jax.devices()[:2])
    enc = MeshH264Encoder(mesh, 1, w, h, stripe_h=sh, qp=28, search=4,
                          me="xla")
    decoders = {i * sh: conformance.ConformanceDecoder("h264", max_dim=256)
                for i in range(h // sh)}
    for t in range(3):
        frame = _smooth_frame(h, w, seed=3, shift=3 * t)
        (stripes,), _ = enc.encode_frames([frame])
        assert len(stripes) == h // sh, f"t={t}: torn access unit"
        ref_y = np.asarray(enc._ref_y)[0]
        ref_cb = np.asarray(enc._ref_cb)[0]
        ref_cr = np.asarray(enc._ref_cr)[0]
        for s in stripes:
            got = decoders[s.y_start].decode(s.annexb)
            assert got is not None, f"t={t} stripe {s.y_start}: no frame"
            dy, du, dv = got
            y0 = s.y_start
            np.testing.assert_array_equal(
                dy, ref_y[y0:y0 + s.height, :w],
                err_msg=f"t={t} stripe {y0} luma mismatch")
            np.testing.assert_array_equal(
                du, ref_cb[y0 // 2:(y0 + s.height) // 2, :w // 2])
            np.testing.assert_array_equal(
                dv, ref_cr[y0 // 2:(y0 + s.height) // 2, :w // 2])
    for d in decoders.values():
        d.close()


def test_h264_quality_reasonable():
    """Decoded pixels must resemble the source (catches e.g. swapped
    chroma or broken prediction that bit-exactness alone can't: if recon
    itself were broken, recon==decode would still pass)."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h = 128, 64
    enc = H264StripeEncoder(w, h, stripe_height=64, qp=18)
    frame = _smooth_frame(h, w, seed=5)
    (s,) = enc.encode_frame(frame)
    dec = conformance.ConformanceDecoder("h264", max_dim=256)
    dy, du, dv = dec.decode(s.annexb)
    dec.close()
    sy, scb, scr = _src_planes(frame)
    err = np.abs(dy.astype(np.int32) - sy.astype(np.int32))
    assert err.mean() < 4.0, err.mean()
    cerr = np.abs(du.astype(np.int32) - scb.astype(np.int32))
    assert cerr.mean() < 5.0, cerr.mean()


def test_h264_fullframe_mode():
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h = 96, 80
    enc = H264StripeEncoder(w, h, qp=26, fullframe=True)
    assert len(enc.stripes) == 1
    dec = conformance.ConformanceDecoder("h264", max_dim=256)
    for t in range(3):
        stripes = enc.encode_frame(_smooth_frame(h, w, seed=9, shift=2 * t))
        (s,) = stripes
        assert s.height == h
        dy, _, _ = dec.decode(s.annexb)
        np.testing.assert_array_equal(dy, enc.stripe_ref(0)[0][:h, :w])
    dec.close()


def test_h264_device_cavlc_bit_identical_to_host_path_and_decodes():
    """ISSUE 1 acceptance: device-packed stripes (entropy='device') must
    be byte-identical to the host-CAVLC path for P frames AND for the
    IDR fallback, and must decode bit-exact against the encoder's own
    reconstruction in libavcodec."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h, sh = 112, 64, 32
    dev_enc = H264StripeEncoder(w, h, stripe_height=sh, qp=28, search=8,
                                entropy="device")
    host_enc = H264StripeEncoder(w, h, stripe_height=sh, qp=28, search=8,
                                 entropy="host")
    assert dev_enc.entropy == "device" and host_enc.entropy == "host"
    decoders = {st.y0: conformance.ConformanceDecoder("h264", max_dim=256)
                for st in dev_enc.stripes}
    saw_p = False
    for t in range(5):
        frame = _smooth_frame(h, w, seed=13, shift=3 * t)
        d_stripes = dev_enc.encode_frame(frame)
        h_stripes = host_enc.encode_frame(frame)
        assert [s.annexb for s in d_stripes] == \
            [s.annexb for s in h_stripes], f"t={t}: entropy modes differ"
        for s in d_stripes:
            saw_p |= not s.is_key
            got = decoders[s.y_start].decode(s.annexb)
            assert got is not None, f"t={t} stripe {s.y_start}"
            dy, du, dv = got
            ry, rcb, rcr = dev_enc.stripe_ref(s.y_start // dev_enc.stripe_h)
            np.testing.assert_array_equal(dy, ry[:s.height, :w])
            np.testing.assert_array_equal(du, rcb[:s.height // 2, :w // 2])
            np.testing.assert_array_equal(dv, rcr[:s.height // 2, :w // 2])
    assert saw_p, "no P frames exercised the device packer"
    for d in decoders.values():
        d.close()


# ---------------------------------------------------------------------------
# JPEG


@pytest.mark.parametrize("entropy", ["device", "host"])
def test_jpeg_stripes_decode_and_match_source(entropy):
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    w, h, sh = 128, 96, 48
    enc = JpegStripeEncoder(w, h, stripe_height=sh, quality=90,
                            entropy=entropy)
    frame = _smooth_frame(h, w, seed=11)
    stripes = enc.encode_frame(frame)
    assert stripes, "first frame must emit all stripes"
    sy, scb, scr = _src_planes(frame)
    for s in stripes:
        dec = conformance.ConformanceDecoder("mjpeg", max_dim=256)
        got = dec.decode(s.jpeg)
        dec.close()
        assert got is not None
        dy, du, dv = got
        assert dy.shape == (sh, enc.pad_w)
        ref = sy[s.y_start:s.y_start + sh]
        err = np.abs(dy[:ref.shape[0], :w].astype(np.int32)
                     - ref[:, :w].astype(np.int32))
        assert err.mean() < 3.5, (s.y_start, err.mean())
        cref = scb[s.y_start // 2:(s.y_start + sh) // 2]
        cerr = np.abs(du[:cref.shape[0], :w // 2].astype(np.int32)
                      - cref[:, :w // 2].astype(np.int32))
        assert cerr.mean() < 4.5, (s.y_start, cerr.mean())


def test_h264_partial_last_stripe_decodes():
    """A display height that is not a stripe multiple leaves a short last
    stripe; the uniform encode grid codes full stripe_h rows, so the SPS
    must declare the coded height and crop — libavcodec rejected the old
    mismatched headers with 'first_mb_in_slice overflow'."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    w, h, sh = 128, 80, 64           # stripes: 64 rows + 16-row remainder
    enc = H264StripeEncoder(w, h, stripe_height=sh, qp=24)
    frame = _smooth_frame(h, w, seed=7)
    stripes = enc.encode_frame(frame)
    assert [s.height for s in stripes] == [64, 16]
    for s in stripes:
        dec = conformance.ConformanceDecoder("h264", max_dim=256)
        got = dec.decode(s.annexb)
        dec.close()
        assert got is not None, f"stripe {s.y_start} undecodable"
        dy, _, _ = got
        assert dy.shape == (s.height, w)
        i = s.y_start // enc.stripe_h
        ry, _, _ = enc.stripe_ref(i)
        np.testing.assert_array_equal(dy, ry[:s.height, :w])


def test_deblock_enabled_slice_header_decodes():
    """STAGED deblocking groundwork: a P slice written with
    disable_deblocking_filter_idc=0 (+ the two offset fields) must
    parse and decode in libavcodec, and the decoder's in-loop filter
    must actually engage (pixels differ from the unfiltered stream).
    The flag is off in the product until the device reconstruction
    mirrors the filter (see encode_picture_nals_np docstring)."""
    import numpy as np

    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.encoder.h264 import (H264StripeEncoder,
                                          encode_picture_nals_np)

    # smooth content at a high QP: deblocking only engages where the
    # step across a block edge is SMALLER than alpha(qp) — flat
    # gradients with coarse quantization, not high-contrast noise
    W, H = 128, 64
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    f0 = np.clip(np.stack([96 + xx / 3 + yy / 5] * 3, -1),
                 0, 255).astype(np.uint8)
    f1 = np.clip(np.stack([96 + (xx + 2) / 3 + (yy + 7) / 5] * 3, -1),
                 0, 255).astype(np.uint8)

    def encode(deblock):
        enc = H264StripeEncoder(W, H, stripe_height=64, qp=44)
        out = []
        for t, f in enumerate((f0, f1)):
            p = enc.dispatch(f)
            host = np.asarray(p.fetch)
            if p.is_idr:
                stripes = enc.harvest(p, host=host)
                out.append(b"".join(s.annexb for s in stripes))
                continue
            # P frame: re-code the fetched levels with the flag
            S = enc.n_stripes
            row = np.asarray(p.flat16[0]).astype(np.int32)
            parts, pos = [], 0
            for shape, size in enc._shapes:
                parts.append(row[pos:pos + size].reshape(shape))
                pos += size
            mv, luma, luma_dc, chroma_dc, chroma_ac = parts
            nals = encode_picture_nals_np(
                mv, luma, luma_dc, chroma_dc, chroma_ac,
                is_idr=False, mb_w=enc.pad_w // 16,
                mb_h=enc.stripe_h // 16, qp=44, frame_num=1,
                deblock=deblock)
            out.append(nals)
        return out

    plain = encode(False)
    filtered = encode(True)
    assert plain[0] == filtered[0]            # IDR untouched
    assert plain[1] != filtered[1]            # P slice header differs

    def decode(streams):
        dec = conformance.ConformanceDecoder("h264", max_dim=256)
        frames = []
        for s in streams:
            got = dec.decode(s)
            if got is not None:
                frames.append(got)
        frames.extend(dec.flush())
        dec.close()
        return frames

    fa = decode(plain)
    fb = decode(filtered)
    assert len(fa) == 2 and len(fb) == 2      # both streams fully decode
    np.testing.assert_array_equal(fa[0][0], fb[0][0])   # IDR identical
    # the in-loop filter engaged: P pictures differ between streams
    assert not np.array_equal(fa[1][0], fb[1][0])
