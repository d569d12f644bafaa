import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selkies_tpu.ops import (
    base_quant_tables,
    block_dct2,
    block_idct2,
    blockify,
    dct8_matrix,
    quality_scaled_tables,
    rgb_to_ycbcr,
    subsample_420,
    unblockify,
)
from selkies_tpu.ops.quant import ZIGZAG, quantize_blocks, zigzag_blocks


def test_dct_matrix_orthonormal():
    c = np.asarray(dct8_matrix())
    np.testing.assert_allclose(c @ c.T, np.eye(8), atol=1e-6)


def test_dct_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.uniform(-128, 127, size=(4, 5, 8, 8)).astype(np.float32)
    y = block_idct2(block_dct2(jnp.asarray(x)))
    np.testing.assert_allclose(np.asarray(y), x, atol=1e-3)


def test_dct_dc_term():
    x = jnp.full((1, 8, 8), 100.0)
    c = np.asarray(block_dct2(x))[0]
    assert abs(c[0, 0] - 800.0) < 1e-3  # orthonormal: DC = 8 * mean
    assert np.abs(c).sum() - abs(c[0, 0]) < 1e-3


def test_blockify_roundtrip():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 255, size=(64, 128)).astype(np.float32)
    b = blockify(jnp.asarray(x))
    assert b.shape == (8, 16, 8, 8)
    np.testing.assert_array_equal(np.asarray(unblockify(b)), x)
    # block (0,1) is columns 8..16 of rows 0..8
    np.testing.assert_array_equal(np.asarray(b[0, 1]), x[:8, 8:16])


def test_rgb_to_ycbcr_known_values():
    rgb = jnp.asarray(
        np.array([[[255, 255, 255], [0, 0, 0], [255, 0, 0]]], dtype=np.uint8)[None]
    )
    y, cb, cr = rgb_to_ycbcr(rgb[0])
    y, cb, cr = np.asarray(y), np.asarray(cb), np.asarray(cr)
    assert abs(y[0, 0] - 255.0) < 0.1 and abs(cb[0, 0] - 128) < 0.6
    assert abs(y[0, 1] - 0.0) < 0.1
    assert abs(y[0, 2] - 76.2) < 0.5 and cr[0, 2] > 200


def _mean_2x2_f64(x):
    x = np.asarray(x, np.float64)
    h, w = x.shape[-2:]
    return x.reshape(*x.shape[:-2], h // 2, 2, w // 2, 2).mean(axis=(-3, -1))


@pytest.mark.parametrize("shape, vmapped", [
    ((4, 4), False),            # by hand
    ((1088, 1920), False),      # a served plane
    ((3, 32, 256), True),       # a leading axis under vmap: the mesh's lanes
    ((64, 1920), False),        # a lane's h_local that is not 1088
])
def test_subsample_420(shape, vmapped):
    """The 2x2 mean of the same four samples, against float64 numpy."""
    if shape == (4, 4):
        x = np.arange(16, dtype=np.float32).reshape(4, 4)
    else:
        x = np.random.default_rng(2).uniform(0, 255, shape).astype(np.float32)
    f = jax.vmap(subsample_420) if vmapped else subsample_420
    s = np.asarray(f(jnp.asarray(x)))
    assert s.dtype == np.float32
    assert s.shape == shape[:-2] + (shape[-2] // 2, shape[-1] // 2)
    np.testing.assert_allclose(s, _mean_2x2_f64(x), rtol=0, atol=1e-4)
    if shape == (4, 4):
        assert s[0, 0] == (0 + 1 + 4 + 5) / 4
    if vmapped:     # a leading axis without vmap is the same planes
        np.testing.assert_array_equal(np.asarray(subsample_420(jnp.asarray(x))), s)


def test_prepare_planes_chroma_is_the_float64_mean_rounded():
    """``prepare_planes``' uint8 planes against float64 colour conversion,
    2x2 mean, round and clip of the same RGB: at most 0.05% of the samples
    of a plane differ, and by one level (a float32 sum within 1e-5 of a
    half rounds the other way: 58 Cb and 5 Cr samples of 522,240 on this
    picture, as many as a reshape-mean's)."""
    from selkies_tpu.encoder.h264_device import prepare_planes
    from selkies_tpu.ops.color import _RGB2YCC

    h, w = 1088, 1920
    rgb = np.random.default_rng(7).integers(0, 256, (h, w, 3), dtype=np.uint8)
    m = np.asarray(_RGB2YCC, np.float64)
    x = rgb.astype(np.float64)
    want = [x @ m[0], _mean_2x2_f64(x @ m[1] + 128.0),
            _mean_2x2_f64(x @ m[2] + 128.0)]
    got = prepare_planes(jnp.asarray(rgb), h, w)
    for name, g, wv in zip("y cb cr".split(), got, want):
        g = np.asarray(g)
        assert g.dtype == np.uint8 and g.shape == wv.shape
        d = np.abs(g.astype(np.int64) - np.clip(np.round(wv), 0, 255))
        assert d.max() <= 1, name
        assert (d != 0).mean() <= 0.0005, (name, int((d != 0).sum()))


def test_quality_tables_monotone():
    q10_l, _ = quality_scaled_tables(10)
    q90_l, _ = quality_scaled_tables(90)
    assert (q10_l.astype(int) >= q90_l.astype(int)).all()
    q100_l, q100_c = quality_scaled_tables(100)
    assert (q100_l == 1).all() and (q100_c == 1).all()
    q50_l, _ = quality_scaled_tables(50)
    base_l, _ = base_quant_tables()
    np.testing.assert_array_equal(q50_l, base_l)


def test_zigzag_is_permutation():
    assert sorted(ZIGZAG.tolist()) == list(range(64))
    # spec spot checks
    assert ZIGZAG[0] == 0 and ZIGZAG[1] == 1 and ZIGZAG[2] == 8 and ZIGZAG[63] == 63


def test_quantize_and_zigzag():
    coeffs = jnp.asarray(np.full((2, 2, 8, 8), 50.0, dtype=np.float32))
    table = jnp.asarray(np.full((8, 8), 25.0, dtype=np.float32))
    q = quantize_blocks(coeffs, table)
    assert q.dtype == jnp.int16
    assert (np.asarray(q) == 2).all()
    z = zigzag_blocks(q)
    assert z.shape == (2, 2, 64)


def test_full_search_mc_matches_separate_path():
    """The fused ME+MC scan must reproduce full_search_mv + mc_luma +
    mc_chroma exactly (mv tie-breaks included)."""
    import numpy as np
    import jax.numpy as jnp
    from selkies_tpu.ops.motion import (full_search_mc, full_search_mv,
                                        mc_chroma, mc_luma)

    rng = np.random.default_rng(11)
    h, w = 64, 96
    ref = rng.integers(0, 256, (h, w), dtype=np.uint8)
    # shifted + noisy current frame exercises real motion
    cur = np.roll(ref, (3, -5), axis=(0, 1))
    cur = np.clip(cur.astype(np.int32)
                  + rng.integers(-6, 7, cur.shape), 0, 255).astype(np.uint8)
    ref_cb = rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)
    ref_cr = rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)

    mv_want, _, _ = full_search_mv(jnp.asarray(cur), jnp.asarray(ref),
                                   search=8)
    py_want = mc_luma(jnp.asarray(ref), mv_want, search=8)
    pcb_want = mc_chroma(jnp.asarray(ref_cb), mv_want, search=8)
    pcr_want = mc_chroma(jnp.asarray(ref_cr), mv_want, search=8)

    mv, py, pcb, pcr = full_search_mc(
        jnp.asarray(cur), jnp.asarray(ref), jnp.asarray(ref_cb),
        jnp.asarray(ref_cr), search=8)
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(mv_want))
    np.testing.assert_array_equal(np.asarray(py), np.asarray(py_want))
    np.testing.assert_array_equal(np.asarray(pcb), np.asarray(pcb_want))
    np.testing.assert_array_equal(np.asarray(pcr), np.asarray(pcr_want))
