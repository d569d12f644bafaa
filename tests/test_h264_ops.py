"""H.264 transform/quant/motion op tests against independent numpy mirrors."""

import numpy as np
import pytest

from selkies_tpu.ops import h264_transform as ht
from selkies_tpu.ops.motion import (NumpyMotionMirror, full_search_mv,
                                    mc_chroma, mc_luma)

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# transforms


def test_forward_inverse_roundtrip_lossless_at_qp0():
    """At QP 0 (and low magnitudes) quant→dequant→idct must invert the
    forward path to within the known H.264 reconstruction envelope."""
    x = RNG.integers(-255, 256, (64, 4, 4)).astype(np.int32)
    w = np.asarray(ht.forward_dct4(x))
    for qp in (0, 10, 24, 38, 51):
        z = np.asarray(ht.quant4(w, qp, intra=True))
        d = np.asarray(ht.dequant4(z, qp))
        r = np.asarray(ht.inverse_dct4(d))
        qstep = 0.625 * 2 ** (qp / 6)
        # measured envelope ≈1.3-1.6×Qstep (intra deadzone + basis norms)
        assert np.abs(r - x).max() <= qstep * 2 + 2, qp


def test_inverse_dct_matches_numpy_mirror():
    d = RNG.integers(-2000, 2000, (128, 4, 4)).astype(np.int32)
    ours = np.asarray(ht.inverse_dct4(d))
    mirror = ht.NumpyMirror.inverse_dct4(d)
    np.testing.assert_array_equal(ours, mirror)


def test_dequant_matches_mirror():
    z = RNG.integers(-100, 100, (32, 4, 4)).astype(np.int32)
    for qp in (0, 7, 23, 36, 51):
        np.testing.assert_array_equal(
            np.asarray(ht.dequant4(z, qp)), ht.NumpyMirror.dequant4(z, qp))
        np.testing.assert_array_equal(
            np.asarray(ht.dequant_dc16(z, qp)),
            ht.NumpyMirror.dequant_dc16(z, qp))
    for qpc in (0, 17, 29, 39):
        z2 = RNG.integers(-100, 100, (32, 2, 2)).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(ht.dequant_dc2(z2, qpc)),
            ht.NumpyMirror.dequant_dc2(z2, qpc))


def test_dc16_roundtrip():
    """I16 DC path: decoder output must land in the AC dequant domain,
    i.e. rec ≈ 4·dc (d = 4·W consistency), within a Qstep-scaled bound."""
    dc = RNG.integers(-4080, 4080, (16, 4, 4)).astype(np.int32)
    for qp in (0, 20, 36, 44):
        y = np.asarray(ht.hadamard4_fwd(dc))
        z = np.asarray(ht.quant_dc16(y, qp))
        rec = np.asarray(ht.dequant_dc16(z, qp))
        qstep = 0.625 * 2 ** (qp / 6)
        err = np.abs(rec / 4.0 - dc)
        # inverse Hadamard spreads per-level error ×4 (in units of 4·W)
        assert err.max() <= qstep * 4 + 4, (qp, err.max())


def test_dc2_roundtrip():
    dc = RNG.integers(-4080, 4080, (16, 2, 2)).astype(np.int32)
    for qp in (0, 20, 39):
        y = np.asarray(ht.hadamard2_fwd(dc))
        z = np.asarray(ht.quant_dc2(y, qp))
        rec = np.asarray(ht.dequant_dc2(z, qp))
        qstep = 0.625 * 2 ** (qp / 6)
        err = np.abs(rec / 4.0 - dc)
        assert err.max() <= qstep * 4 + 4, (qp, err.max())


def test_qpc_table():
    assert ht.qpc_for(20) == 20
    assert ht.qpc_for(30) == 29
    assert ht.qpc_for(40) == 36
    assert ht.qpc_for(51) == 39


def test_block_layout_roundtrip():
    import jax.numpy as jnp
    p = jnp.asarray(RNG.integers(0, 255, (16, 32)))
    b = ht.plane_to_blocks(p)
    assert b.shape == (4, 8, 4, 4)
    np.testing.assert_array_equal(np.asarray(ht.blocks_to_plane(b)),
                                  np.asarray(p))


# ---------------------------------------------------------------------------
# motion


def test_full_search_finds_translation():
    h, w = 64, 128
    ref = RNG.integers(0, 256, (h, w)).astype(np.uint8)
    # shift content by (3, -5): cur[y, x] = ref[y-3, x+5]
    cur = np.roll(np.roll(ref, 3, axis=0), -5, axis=1)
    mv, sad0, best = full_search_mv(cur, ref, search=8)
    mv = np.asarray(mv)
    # interior MBs must find exactly (-3, +5)... mv points from cur into ref
    inner = mv[1:-1, 1:-1]
    assert (inner[..., 0] == -3).all() and (inner[..., 1] == 5).all()
    assert np.asarray(best)[1:-1, 1:-1].max() == 0


def test_full_search_zero_bias_on_flat():
    flat = np.full((32, 32), 77, np.uint8)
    mv, sad0, best = full_search_mv(flat, flat, search=4)
    assert (np.asarray(mv) == 0).all()   # ties must resolve to (0,0)


def test_mc_luma_matches_mirror():
    h, w = 32, 48
    ref = RNG.integers(0, 256, (h, w)).astype(np.uint8)
    mv = RNG.integers(-6, 7, (h // 16, w // 16, 2)).astype(np.int32)
    ours = np.asarray(mc_luma(ref, mv, search=8))
    mirror = NumpyMotionMirror.mc_luma(ref, mv)
    np.testing.assert_array_equal(ours, mirror)


def test_mc_luma_edge_extension():
    """MVs pointing outside the plane must clamp like the decoder."""
    ref = np.arange(32 * 32, dtype=np.uint8).reshape(32, 32)
    mv = np.full((2, 2, 2), -8, np.int32)   # everything points up-left
    ours = np.asarray(mc_luma(ref, mv, search=8))
    mirror = NumpyMotionMirror.mc_luma(ref, mv)
    np.testing.assert_array_equal(ours, mirror)


def test_mc_chroma_halfpel_matches_mirror():
    hc, wc = 16, 24
    ref_c = RNG.integers(0, 256, (hc, wc)).astype(np.uint8)
    # odd MVs exercise the half-pel bilinear path
    mv = RNG.integers(-5, 6, (hc // 8, wc // 8, 2)).astype(np.int32)
    ours = np.asarray(mc_chroma(ref_c, mv, search=8))
    mirror = NumpyMotionMirror.mc_chroma(ref_c, mv)
    np.testing.assert_array_equal(ours, mirror)


def test_batched_search_over_stripes():
    stripes = RNG.integers(0, 256, (3, 32, 64)).astype(np.uint8)
    mv, sad0, best = full_search_mv(stripes, stripes, search=4)
    assert np.asarray(mv).shape == (3, 2, 4, 2)
    assert (np.asarray(best) == 0).all()


@pytest.mark.parametrize("sequence", ["plain", "keyframes", "undershoot",
                                      "flush_in_flight"])
@pytest.mark.parametrize("rung", ["device", "host"])
def test_pipelined_h264_matches_synchronous(rung, sequence):
    """Each ladder rung's encoder behind the wrapper the factory gives it
    (``PipelinedH264Encoder(depth=4)``: a fetch of its own per frame;
    ``ThreadedEncoderAdapter(depth=3)``) must produce the byte-identical
    stream the synchronous ``encode_frame`` does: over a plain run, with
    keyframes requested mid-stream, with a prefix that holds no payload
    (every P frame takes the undershoot re-read), and across a ``flush``
    with three frames in flight."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import (PipelinedH264Encoder,
                                              ThreadedEncoderAdapter)

    def frame(t, h=96, w=160):
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        base = 128 + 80 * np.sin((xx + 7 * t) / 23) * np.cos(yy / 17)
        f = np.clip(np.stack([base, base + 10, base - 10], -1),
                    0, 255).astype(np.uint8)
        return f

    n = 8
    key_at = (3, 6) if sequence == "keyframes" else ()
    a = H264StripeEncoder(160, 96, stripe_height=32, qp=24, entropy=rung)
    b = H264StripeEncoder(160, 96, stripe_height=32, qp=24, entropy=rung)
    if sequence == "undershoot":
        # the head and nothing of the payload, whatever the content
        b._choose_prefix = lambda every_bucket=False: b._fixed_bytes
    pipe = (PipelinedH264Encoder(b, depth=4) if rung == "device"
            else ThreadedEncoderAdapter(b, depth=3))

    want = []
    for t in range(n):
        if t in key_at:
            a.request_keyframe()
        want.append([(s.y_start, s.is_key, s.annexb)
                     for s in a.encode_frame(frame(t))])
    got_frames = {}
    try:
        for t in range(n):
            if t in key_at:
                if rung == "host":
                    # the adapter's worker takes a request with whichever
                    # frame it dispatches next: name that frame
                    got_frames.update(pipe.flush())
                pipe.request_keyframe()     # device: frames in flight
            pipe.submit(frame(t))
            if sequence == "flush_in_flight" and t < 3:
                if t == 2:
                    flushed = pipe.flush()
                    assert [seq for seq, _ in flushed] == [0, 1, 2]
                    got_frames.update(flushed)
                continue                    # no poll: three in flight
            got_frames.update(pipe.poll())
        got_frames.update(pipe.flush())
    finally:
        pipe.close()
    assert sorted(got_frames) == list(range(n))
    for t in range(n):
        got = [(s.y_start, s.is_key, s.annexb) for s in got_frames[t]]
        assert got == want[t], f"frame {t} diverged"
    for t in key_at:
        assert all(s.is_key for s in got_frames[t]) and got_frames[t]
    if sequence == "undershoot":
        assert b.d2h_refetch_bytes_total > a.d2h_refetch_bytes_total == 0
        if rung == "device":
            assert b.prefix_hit_frames_total == 0
            assert b.cavlc_frames_total == n - 1


def test_every_frame_program_has_a_caller():
    """Every ``jax.jit``-wrapped name of encoder/h264_device.py is used
    by code under selkies_tpu/ (its own definition and docstrings do not
    count): a program nothing dispatches is deleted, not kept compiled
    in the reader's head."""
    import ast
    import pathlib

    import jax

    import selkies_tpu
    from selkies_tpu.encoder import h264_device as dev

    jitted = {name for name, obj in vars(dev).items()
              if isinstance(obj, type(jax.jit(lambda: 0)))
              and getattr(obj, "__module__", None) == dev.__name__}
    assert {"encode_frame_idr_rgb", "encode_frame_p_cavlc_rgb",
            "encode_frame_p_rgb", "fetch_prefix"} <= jitted
    used = set()
    for path in pathlib.Path(selkies_tpu.__file__).parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Name):
                used.add(node.id)
            elif isinstance(node, ast.Attribute):
                used.add(node.attr)
    assert sorted(jitted - used) == []


def test_sparse_pack_roundtrip_exact():
    """Device sparse pack vs the dense flat16 it summarizes."""
    import jax.numpy as jnp
    import numpy as np
    from selkies_tpu.encoder import h264_device as dev

    rng = np.random.default_rng(9)
    S, W = 3, 5000
    flat = np.zeros((S, W), np.int16)
    # sparse content + one dense stripe + one |level|>127 stripe
    for i in range(40):
        flat[0, rng.integers(0, W)] = rng.integers(-100, 100)
    flat[1, :] = rng.integers(-5, 5, W)            # count overflow
    flat[2, 100] = 300                             # range overflow
    damage = jnp.asarray([True, True, True])
    buf = np.asarray(dev._pack_sparse(
        jnp.asarray(flat), damage, damage, cap_frac=4))
    pad_words, n_cells, cap = dev.sparse_geometry(W)
    head = buf[:4 * S].reshape(S, 4)
    counts = head[:, 0].astype(int) + (head[:, 1].astype(int) << 8)
    ovf = head[:, 3] != 0
    assert not ovf[0] and ovf[1] and ovf[2]
    fixed = 4 * S + S * (n_cells // 8)
    bitmaps = buf[4 * S:fixed].reshape(S, n_cells // 8)
    used = np.minimum(counts, cap) * dev.CELL
    starts = np.concatenate([[0], np.cumsum(used)[:-1]]) + fixed
    bits = np.unpackbits(bitmaps[0], bitorder="little")[:n_cells]
    idx = np.flatnonzero(bits)
    cells = buf[starts[0]:starts[0] + used[0]].view(np.int8) \
        .astype(np.int32).reshape(-1, dev.CELL)
    dense = np.zeros(pad_words, np.int32)
    dense.reshape(-1, dev.CELL)[idx[:len(cells)]] = cells
    np.testing.assert_array_equal(dense[:W], flat[0])
