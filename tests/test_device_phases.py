"""Names inside the device step (ISSUE 25, part C): the served step
programs wrap their phases in ``jax.named_scope``; ``device_phases`` reads
the scopes back from the compiled program; and a scope is metadata only:
the bitstream of a fixed input is byte for byte what it is without them."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selkies_tpu.observability import device_phases
from selkies_tpu.ops.phases import PHASES, phase

HLO = """
HloModule jit_step

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jit(main)/colour/mul" stack_frame_id=4}
}

ENTRY %main (frame.1: f32[8]) -> f32[8] {
  %frame.1 = f32[8]{0} parameter(0), metadata={op_name="frame"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%frame.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %fusion.3 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jit(main)/colour/mul" stack_frame_id=4}
  %me_mc_stripes.1 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/motion/jit(me_mc_stripes)/pallas_call"}
  %bitcast.7 = f32[8]{0} bitcast(%me_mc_stripes.1)
  %lonely.2 = f32[8]{0} negate(%frame.1), metadata={op_name="jit(step)/jit(main)/neg"}
  %rewritten.5 = f32[8]{0} add(%bitcast.7, %lonely.2), metadata={op_name="reduce_window_sum"}
  ROOT %add.9 = f32[8]{0} add(%rewritten.5, %lonely.2), metadata={op_name="jit(step)/jit(main)/entropy/entropy/add"}
}
"""


def test_a_phase_map_from_hlo_text():
    m = device_phases.phase_map(HLO)
    assert m["fusion.3"] == "colour"              # a fusion: its root's scope
    assert m["me_mc_stripes.1"] == "motion"
    assert m["add.9"] == "entropy"
    assert m["bitcast.7"] == "motion"             # no metadata: its operand's
    assert m["copy-start.1"] == m["copy-done.1"] == "colour"   # its user's
    assert m["lonely.2"] == "other"               # the program's, unscoped
    assert m["rewritten.5"] == "motion"           # the compiler's own name
    assert device_phases.phase_of_op_name("jit(f)/transform/motion/x") \
        == "transform"                            # the outermost phase


def test_the_jpeg_step_names_every_phase_but_motion():
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder

    enc = JpegStripeEncoder(256, 144, stripe_height=64)
    m = device_phases.step_phases(enc)
    assert set(m.values()) - {"other"} == set(PHASES) - {"motion"}
    # reached through the server's wrappers too
    from selkies_tpu.encoder.async_driver import AsyncEncodeDriver
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    drv = AsyncEncodeDriver(PipelinedJpegEncoder(enc, depth=2))
    try:
        assert device_phases.base_encoder(drv) is enc
    finally:
        drv.close()
    assert device_phases.step_phases(object()) is None


def test_the_h264_step_names_all_five_phases():
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    enc = H264StripeEncoder(64, 48, stripe_height=16, qp=26)
    m = device_phases.step_phases(enc)
    assert set(m.values()) >= set(PHASES)


def frames(w, h, n=3):
    rng = np.random.default_rng(7)
    base = rng.integers(0, 255, (h, w + 8 * n, 3), np.uint8)
    base[: h // 2] = 40                       # a flat half, a busy half
    return [np.ascontiguousarray(base[:, 4 * i: 4 * i + w]) for i in range(n)]


def encode_jpeg():
    from selkies_tpu.encoder import jpeg

    jpeg._device_pipeline.cache_clear()
    enc = jpeg.JpegStripeEncoder(64, 48, stripe_height=16)
    return [s.jpeg for f in frames(64, 48) for s in enc.encode_frame(f)]


def encode_h264():
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    enc = H264StripeEncoder(64, 48, stripe_height=16, qp=26)
    return [s.annexb for f in frames(64, 48) for s in enc.encode_frame(f)]


@pytest.mark.parametrize("encode", [encode_jpeg, encode_h264],
                         ids=["jpeg", "h264"])
def test_scopes_change_no_byte_of_the_bitstream(monkeypatch, encode):
    jax.clear_caches()
    with_scopes = encode()
    assert with_scopes and all(len(b) > 0 for b in with_scopes)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()                       # trace again, without them
    try:
        without = encode()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_scopes == without


def test_the_phase_decorator_scopes_what_the_function_traces():
    @phase("entropy")
    def pack(x):
        return x * 2 + 1

    text = jax.jit(pack).lower(jnp.ones(8)).as_text(debug_info=True)
    assert "entropy" in text
    with pytest.raises(AssertionError):
        phase("no-such-phase")
