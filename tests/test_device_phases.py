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
    # a scope inside a vmapped function, as a mesh lane's step has them
    of = device_phases.phase_of_op_name
    assert of("jit(local_step)/shard_map/vmap(colour)/mul") == "colour"
    assert of("jit(f)/vmap(vmap(motion))/x") == "motion"
    assert of("jit(f)/entropy/vmap()/vmap(jit(colour))/x") == "entropy"
    assert of("jit(f)/vmap(jit(colour))/x") is None    # a function's name


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


def test_the_phases_a_text_names():
    # the optimised module's: the operations inside fusions count too
    assert device_phases.phases_named(HLO) >= {"colour", "motion"}
    assert device_phases.phases_named("no scope path here") == set()
    # the lowered module's: named locations, not files'; inside a
    # shard_map the path starts at the mapped function
    lowered = ('#loc7 = loc("jit(local_step)/shard_map"(#loc3))\n'
               '#loc8 = loc("vmap(colour)/mul"(#loc7))\n'
               '#loc9 = loc("entropy/cumsum")\n'
               '#loc10 = loc("/checkout/motion/transform/ops.py":27:18)')
    assert device_phases.phases_named(lowered) == {"colour", "entropy"}


#: a step whose second scope is the program's argument, compiled twice in
#: two processes that share one persistent compile cache: the cache's key
#: leaves metadata out, so the second process is handed the first's scopes
STALE = """
import logging, sys
import jax, jax.numpy as jnp
from selkies_tpu.observability import device_phases
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
logging.basicConfig(level=logging.WARNING)

def step(x):
    with jax.named_scope("colour"):
        y = x * 2 + 1
    with jax.named_scope(sys.argv[2]):
        return jnp.cumsum(y, axis=0) @ y.T

class Enc:
    def lower_step(self):
        return jax.jit(step).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32))

first = jax.jit(step).lower(
    jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
print("LOADED", sorted(device_phases.phases_named(first)))
print("READ", sorted(set(device_phases.step_phases(Enc()).values()) - {"other"}))
"""


def test_a_cached_executable_with_another_trees_scopes_is_compiled_again(
        tmp_path):
    """The trap of PR 35: a lane step whose ``entropy`` scope was new came
    back from the cache under the scopes of the tree that compiled it
    first, and ``phase_entropy_ms`` read 0.0."""
    import os
    import subprocess
    import sys

    script = tmp_path / "stale.py"
    script.write_text(STALE)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run(scope):
        p = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "cache"), scope],
            capture_output=True, text=True, env=env, timeout=300, cwd=root)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout, p.stderr

    out, err = run("transform")
    assert "READ ['colour', 'transform']" in out
    assert "another tree's executable" not in err
    out, err = run("entropy")
    # the cache handed the first process's executable back ...
    assert "LOADED ['colour', 'transform']" in out
    # ... and the reader saw it, said so, and read the program's own names
    assert "another tree's executable" in err
    assert "READ ['colour', 'entropy']" in out
    # kept under the key that holds the metadata: found there next time
    out, err = run("entropy")
    assert "READ ['colour', 'entropy']" in out
