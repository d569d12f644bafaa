import numpy as np
import pytest

from selkies_tpu.encoder import entropy_py
from selkies_tpu.encoder.device_entropy import (
    DeviceEntropyPacker,
    stuff_bytes,
    words_to_stripe_bytes,
)


def random_coeffs(rng, by, bx, density=0.15, amp=400):
    """Sparse int16 zigzag coefficients within legal category ranges."""
    c = (rng.integers(-amp, amp + 1, size=(by, bx, 64))
         * (rng.random((by, bx, 64)) < density)).astype(np.int16)
    return c


def host_reference(yq, cbq, crq, stripe_h):
    yrows, crows = stripe_h // 8, stripe_h // 16
    s_cnt = yq.shape[0] // yrows
    return [
        entropy_py.encode_scan_420(
            yq[s * yrows:(s + 1) * yrows],
            cbq[s * crows:(s + 1) * crows],
            crq[s * crows:(s + 1) * crows],
        )
        for s in range(s_cnt)
    ]


# the budgets the server runs (16 words a block, the rule's bytes a stripe)
# where a case names none; blocks this dense need the 56 words that hold
# the worst legal block
@pytest.mark.parametrize("pad_h,pad_w,stripe_h,density,budget", [
    (64, 64, 64, 0.15, {}),
    (128, 96, 64, 0.3, {"block_words": 56}),
    (192, 128, 64, 0.02, {}),
    (64, 32, 32, 0.6, {"block_words": 56}),
])
def test_device_pack_matches_host_oracle(pad_h, pad_w, stripe_h, density,
                                         budget):
    rng = np.random.default_rng(pad_h * 1000 + pad_w)
    by, bx = pad_h // 8, pad_w // 8
    cby, cbx = pad_h // 16, pad_w // 16
    yq = random_coeffs(rng, by, bx, density)
    cbq = random_coeffs(rng, cby, cbx, density / 2, amp=200)
    crq = random_coeffs(rng, cby, cbx, density / 2, amp=200)

    packer = DeviceEntropyPacker(pad_h, pad_w, stripe_h, **budget)
    words, nbytes, base_words, overflow = packer.pack(yq, cbq, crq)
    assert not np.asarray(overflow).any()
    stripes = words_to_stripe_bytes(
        np.asarray(words), np.asarray(base_words), np.asarray(nbytes))

    ref = host_reference(yq, cbq, crq, stripe_h)
    assert len(stripes) == len(ref)
    for s, (dev, host) in enumerate(zip(stripes, ref)):
        assert stuff_bytes(dev) == host, f"stripe {s} mismatch"


def test_device_pack_extreme_values():
    """DC swings near the category-11 limit and dense max-amp ACs."""
    pad_h = pad_w = 64
    by = bx = 8
    rng = np.random.default_rng(3)
    yq = random_coeffs(rng, by, bx, 0.9, amp=800)
    yq[:, :, 0] = rng.integers(-1000, 1000, size=(by, bx))  # wild DC deltas
    cbq = random_coeffs(rng, 4, 4, 0.9, amp=800)
    crq = random_coeffs(rng, 4, 4, 0.9, amp=800)
    # blocks near the worst legal one need its 56 words (the 96 of them
    # together stay under the served 16 KiB)
    packer = DeviceEntropyPacker(pad_h, pad_w, 64, block_words=56)
    words, nbytes, base_words, overflow = packer.pack(yq, cbq, crq)
    assert not np.asarray(overflow).any()
    dev = words_to_stripe_bytes(
        np.asarray(words), np.asarray(base_words), np.asarray(nbytes))[0]
    assert stuff_bytes(dev) == host_reference(yq, cbq, crq, 64)[0]


def test_all_zero_blocks():
    packer = DeviceEntropyPacker(64, 64, 64)
    z = np.zeros((8, 8, 64), np.int16)
    zc = np.zeros((4, 4, 64), np.int16)
    words, nbytes, base_words, overflow = packer.pack(z, zc, zc)
    dev = words_to_stripe_bytes(
        np.asarray(words), np.asarray(base_words), np.asarray(nbytes))[0]
    assert stuff_bytes(dev) == host_reference(z, zc, zc, 64)[0]


def test_stuff_bytes():
    assert stuff_bytes(b"\xff\x00\xff") == b"\xff\x00\x00\xff\x00"
    assert stuff_bytes(b"abc") == b"abc"


def test_no_default_precision_f32_matmuls_in_pack_graph():
    """MXU-precision canary: the TPU lowers DEFAULT-precision f32
    dot_generals to bf16 operand rounding, which silently corrupts the
    packed Huffman table (found on a real v5e: stripes decoded at ~10 dB
    while every CPU test passed). CPU runs can't reproduce that rounding,
    so instead assert structurally that every floating dot in the pack
    graph pins Precision.HIGHEST."""
    import jax
    import jax.numpy as jnp

    packer = DeviceEntropyPacker(32, 32, 32)
    yq = jnp.zeros((4, 4, 64), jnp.int16)
    cq = jnp.zeros((2, 2, 64), jnp.int16)
    jaxpr = jax.make_jaxpr(packer._pack_fn)(yq, cq, cq)

    def walk(jx, out):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(eqn)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr, out)
        return out

    dots = walk(jaxpr.jaxpr, [])
    assert dots, "expected at least the _lut512 one-hot matmul"
    for eqn in dots:
        if any(jnp.issubdtype(v.aval.dtype, jnp.floating)
               for v in eqn.invars):
            prec = eqn.params.get("precision")
            assert prec is not None and "HIGHEST" in str(prec), (
                f"f32 dot_general with default precision in pack graph: "
                f"{eqn.params}")
