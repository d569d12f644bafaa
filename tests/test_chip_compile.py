"""Ask the chip's compiler, without the chip (on-chip-measurement guide §2,
rehearsal 3): the kernels and steps of the served path are compiled for a
*described* TPU v5e at the real 1080p widths. Nothing runs — a pass here
says the chip's compiler accepts the program and that it fits the device,
never that it is correct or fast.

The topology is described inside a module-scoped fixture (never at import,
in a skipif, or in parametrize arguments): only one process may hold the
TPU library, and under xdist every worker imports every test file. All
compile tests live in THIS file so one worker owns the library.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

W, H, SH = 1920, 1088, 64          # 1080p padded to the stripe grid
S = H // SH                        # 17 stripes
HBM_BYTES = 16 * 1024 ** 3         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _check(compiled, *, kernel: bool, label: str, t0: float):
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes + mem.generated_code_size_in_bytes)
    print(f"[chip-compile] {label}: {time.time() - t0:.1f}s "
          f"temp={mem.temp_size_in_bytes >> 20}MiB "
          f"args={mem.argument_size_in_bytes >> 20}MiB "
          f"out={mem.output_size_in_bytes >> 20}MiB "
          f"code={mem.generated_code_size_in_bytes >> 20}MiB")
    assert total < HBM_BYTES, (label, total)
    assert ("tpu_custom_call" in compiled.as_text()) == kernel, label


def _planes(one_chip):
    y = _sds(one_chip, (H, W), jnp.uint8)
    c = _sds(one_chip, (H // 2, W // 2), jnp.uint8)
    return y, c


def test_jpeg_step_1080p(one_chip, no_persistent_cache):
    """The served JPEG program: encode body + device Huffman pack, as
    ``jpeg._device_pipeline`` wires them (~30 s)."""
    from selkies_tpu.encoder.jpeg import _device_pipeline

    _packer, step = _device_pipeline(H, W, SH)
    frame = _sds(one_chip, (H, W, 3), jnp.uint8)
    q = _sds(one_chip, (2, 8, 8), jnp.float32)
    qsel = _sds(one_chip, (S,), jnp.int32)
    t0 = time.time()
    _check(step.lower(frame, frame, q, q, qsel).compile(),
           kernel=False, label="jpeg step 1088x1920", t0=t0)


def test_me_kernel_1080p_striped(one_chip, no_persistent_cache):
    """The Pallas ME+MC kernel at the served striped shape (~11 s).

    Accepted by the v5e compiler with ``vmem_limit_bytes`` = 100 MiB
    (ops/pallas_me.py asks for that for every shape; the 4K striped
    shape (34, 64, 3840) was accepted at the same limit, by hand)."""
    from selkies_tpu.ops.pallas_me import me_mc_stripes

    cur = _sds(one_chip, (S, SH, W), jnp.uint8)
    c = _sds(one_chip, (S, SH // 2, W // 2), jnp.uint8)
    t0 = time.time()
    _check(me_mc_stripes.lower(cur, cur, c, c, search=12,
                               interpret=False).compile(),
           kernel=True, label="me_mc_stripes (17,64,1920)", t0=t0)


def test_h264_idr_step_1080p(one_chip, no_persistent_cache):
    from selkies_tpu.encoder import h264_device as dev

    y, c = _planes(one_chip)
    rgb = _sds(one_chip, (H, W, 3), jnp.uint8)
    qp = _sds(one_chip, (), jnp.int32)
    t0 = time.time()
    _check(dev.encode_frame_idr_rgb.lower(
        rgb, y, c, c, y, c, c, qp, pad_h=H, pad_w=W, n_stripes=S,
        sh=SH).compile(), kernel=False, label="idr step 1088x1920", t0=t0)


#: any array tiled onto the 128 lanes, whatever its sublane tile
_LANE_TILED_ARRAY = re.compile(
    r"\b\w+\[([\d,]+)\]\{([\d,]+):T\(\d+,128\)[^}]*\}")


def _lane_padded_arrays(hlo_text: str, units: int):
    """The lane-tiled arrays of the pack's output stage (the branches of
    its ``switch``) that have a dimension of ``units`` and a physical
    minor dimension under 128, which the chip pads to 128 lanes."""
    found = set()
    for line in hlo_text.splitlines():
        if "/cond/branch_" not in line:
            continue
        for m in _LANE_TILED_ARRAY.finditer(line):
            shape = [int(d) for d in m.group(1).split(",")]
            minor = int(m.group(2).split(",")[0])
            if units in shape and shape[minor] < 128:
                found.add(m.group(0))
    return sorted(found)


@pytest.mark.parametrize("n_stripes", [
    2,                                          # tier 1: the same per-stripe
    pytest.param(S, marks=pytest.mark.slow),    # program, fewer of them
])
def test_device_cavlc_pack_1080p(one_chip, no_persistent_cache, n_stripes):
    """``device_cavlc.pack_p_frame`` at the served stripe shape — 480
    macroblocks, the solo encoder's per-stripe byte budget. All 17 stripes
    (the served frame) compile for ~5 min, which is the slow case's; tier 1
    asks the compiler about the same stripe program at two stripes."""
    from selkies_tpu.encoder import device_cavlc as dcav

    mb_w, mb_h = W // 16, SH // 16
    n = mb_w * mb_h
    msb = dcav.default_max_stripe_bytes(mb_w, mb_h)

    def pack(mv, luma, cdc, cac, damage, update):
        return dcav.pack_p_frame(mv, luma, cdc, cac, damage, update,
                                 mb_w=mb_w, mb_h=mb_h, max_stripe_bytes=msb)

    i32 = jnp.int32
    t0 = time.time()
    compiled = jax.jit(pack).lower(
        _sds(one_chip, (n_stripes, n, 2), i32),
        _sds(one_chip, (n_stripes, n, 16, 4, 4), i32),
        _sds(one_chip, (n_stripes, n, 2, 2, 2), i32),
        _sds(one_chip, (n_stripes, n, 2, 4, 4, 4), i32),
        _sds(one_chip, (n_stripes,), jnp.bool_),
        _sds(one_chip, (n_stripes,), jnp.bool_)).compile()
    _check(compiled, kernel=False, label=f"pack_p_frame {n_stripes}x480",
           t0=t0)
    # the output stage's histogram (``_last_unit``) meets two one-hot
    # operands over the stripe's 27 * 480 + 1 units; one laid out with
    # under 128 of anything on the lanes is padded to 128 of them (a
    # minor dimension of 32: 17 x 12,961 x 128 x 2 B = 56 MB a step)
    units = 27 * n + 1
    assert _lane_padded_arrays(      # the check sees what it is for
        f'%e = bf16[17,{units},32]{{2,1,0:T(8,128)(2,1)}} convert(%p), '
        'metadata={op_name="jit(f)/entropy/cond/branch_2_fun/eq"}', units)
    assert not _lane_padded_arrays(
        f'%e = pred[2,256,{units}]{{1,2,0:T(8,128)(4,1)}} compare(%p), '
        'metadata={op_name="jit(f)/entropy/cond/branch_0_fun/eq"}', units)
    text = compiled.as_text()
    assert "/cond/branch_" in text and "scatter" not in text
    assert _lane_padded_arrays(text, units) == []


#: the served encoder's fetch-prefix tiers (``H264StripeEncoder
#: ._prefix_tiers()`` at 1920x1080, stripes of 64): 8 KiB doubling up to
#: the packed buffer's own 17 x (12 B head + 128 KiB) bytes
PREFIX_TIERS_1080P = [8192 << i for i in range(9)] + [S * (12 + (128 << 10))]


@pytest.mark.parametrize("tier", range(len(PREFIX_TIERS_1080P)))
def test_fetch_prefix_tier_1080p(one_chip, no_persistent_cache, tier):
    """Every slice program the content can select (encoder/h264.py
    ``dispatch``): one small compile each, all made with the P step at an
    encoder's first P frame."""
    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    enc = H264StripeEncoder(W, 1080, stripe_height=SH)
    assert enc._prefix_tiers() == PREFIX_TIERS_1080P
    prefix = PREFIX_TIERS_1080P[tier]
    t0 = time.time()
    compiled = dev.fetch_prefix.lower(
        _sds(one_chip, (enc._buf_bytes,), jnp.uint8), prefix=prefix).compile()
    _check(compiled, kernel=False, label=f"fetch_prefix {prefix}", t0=t0)
    assert time.time() - t0 < 30.0


def test_pallas_dct_alive_1080p(one_chip, no_persistent_cache):
    """ops/pallas_dct.py is not on the served path (the XLA DCT won); one
    compile at (1088, 1920) says whether it is still alive."""
    from selkies_tpu.ops.pallas_dct import dct8_quant_raster

    plane = _sds(one_chip, (H, W), jnp.float32)
    recip = _sds(one_chip, (H // 8, 8, 8), jnp.float32)
    t0 = time.time()
    _check(dct8_quant_raster.lower(plane, recip, interpret=False).compile(),
           kernel=True, label="pallas dct (1088,1920)", t0=t0)


#: ``f32[544,2,960,2]{3,2,1,0:T(8,128)...}``: type, shape, minor-to-major
_TILED_ARRAY = re.compile(r"\b\w+\[([\d,]+)\]\{([\d,]+):T\(8,128\)[^}]*\}")


def _narrow_tiled_arrays(hlo_text: str, scope: str):
    """The (8, 128)-tiled arrays of ``scope``'s operations whose physical
    minor dimension is under 8: the chip pads it to 128 lanes, so such an
    array holds 16 to 128 times its data."""
    found = set()
    for line in hlo_text.splitlines():
        if f"/{scope}/" not in line:
            continue
        for m in _TILED_ARRAY.finditer(line):
            shape = [int(d) for d in m.group(1).split(",")]
            minor = int(m.group(2).split(",")[0])
            if shape[minor] < 8:
                found.add(m.group(0))
    return sorted(found)


def test_colour_phase_pads_no_lane_1080p(one_chip, no_persistent_cache):
    """``prepare_planes`` at (1088, 1920), for what the CPU backend cannot
    show: a 4:2:0 mean through ``f32[544,2,960,2]{3,2,1,0:T(8,128)}`` is a
    535 MB buffer for an 8.4 MB plane (``temp_size_in_bytes`` 543 MB, 3.1 ms
    of every step: PERF.md, PR 36)."""
    from selkies_tpu.encoder import h264_device as dev

    assert _narrow_tiled_arrays(      # the check sees what it is for
        '%r = f32[544,2,960,2]{3,2,1,0:T(8,128)} reshape(%p), '
        'metadata={op_name="jit(f)/colour/reshape"}', "colour")
    assert not _narrow_tiled_arrays(  # channels last, laid out plane-major
        '%c = f32[1088,1920,3]{1,0,2:T(8,128)} convert(%p), '
        'metadata={op_name="jit(f)/colour/convert"}', "colour")
    rgb = _sds(one_chip, (H, W, 3), jnp.uint8)
    t0 = time.time()
    compiled = jax.jit(dev.prepare_planes, static_argnums=(1, 2)).lower(
        rgb, H, W).compile()
    _check(compiled, kernel=False, label="prepare_planes 1088x1920", t0=t0)
    text = compiled.as_text()
    assert "/colour/" in text
    assert _narrow_tiled_arrays(text, "colour") == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.slow   # ~4.6 min here: a by-hand rehearsal, not tier 1
def test_h264_p_cavlc_step_1080p(one_chip, no_persistent_cache, monkeypatch):
    """The whole served P step — planes, damage, Pallas ME, transform,
    recon, device CAVLC — with the COMPILED kernel inside.

    conftest.py asks for Pallas interpreter mode for the whole test run;
    this test takes the request back for its own trace (the steering is
    the test's, not a program option) and proves the kernel is there."""
    from selkies_tpu.encoder import device_cavlc as dcav
    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.runtime import INTERPRET_ENV

    monkeypatch.delenv(INTERPRET_ENV, raising=False)
    jax.clear_caches()            # no interpret-mode trace may be reused
    y, c = _planes(one_chip)
    rgb = _sds(one_chip, (H, W, 3), jnp.uint8)
    paint = _sds(one_chip, (S,), jnp.int32)
    qp = _sds(one_chip, (), jnp.int32)
    msb = dcav.default_max_stripe_bytes(W // 16, SH // 16)
    t0 = time.time()
    try:
        compiled = dev.encode_frame_p_cavlc_rgb.lower(
            rgb, y, c, c, y, c, c, paint, qp, qp, pad_h=H, pad_w=W,
            n_stripes=S, sh=SH, search=12, max_stripe_bytes=msb,
            me="pallas").compile()
    finally:
        jax.clear_caches()
    _check(compiled, kernel=True, label="P cavlc step 1088x1920", t0=t0)
