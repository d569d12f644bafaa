"""The H.264 step's two compiled-in choices, each against its reference:
the host rung's two prefix sizes (the slice is part of its step), and the
motion search, whose XLA and scan forms are the Pallas kernel's references.
"""

import numpy as np

from selkies_tpu.encoder.h264 import H264StripeEncoder

W, H = 128, 96


def frames_seq(n, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 256, (H, W, 3), np.uint8)
    return [np.roll(base, 3 * i, axis=0) for i in range(n)]


def annexbs(stripes):
    return [s.annexb for s in stripes]


def test_two_tier_prefix_shrinks_for_static_content(monkeypatch):
    """Static frames must ship the small head, not the worst-case one
    (code-review r3: a fixed large prefix costs 10-30x the D2H bytes on
    an idle desktop), once the guess has forgotten the busy ones. Uses a
    geometry large enough that the two tiers are distinct buckets."""
    monkeypatch.setattr(H264StripeEncoder, "PREFIX_MEMORY_FRAMES", 2)
    rng = np.random.default_rng(3)
    base = rng.integers(0, 256, (256, 320, 3), np.uint8)
    frames = [np.roll(base, 5 * min(i, 2), axis=0) for i in range(8)]
    enc = H264StripeEncoder(320, 256, stripe_height=32, entropy="host")
    assert enc._prefix_small < enc._host_step_prefix
    lens = []
    for f in frames:
        p = enc.dispatch(f)
        enc.harvest(p)
        if not p.is_idr:
            lens.append(p.head_len)
    # busy frames ship the large head, quiet frames re-tier to small
    assert lens[0] == enc._host_step_prefix
    assert lens[-1] == enc._prefix_small


def test_me_backends_agree(monkeypatch):
    """pallas / chunked-xla / scan backends produce identical bitstreams
    (the bit-identical-winners contract of ops/pallas_me.py). The
    backend is a static jit arg, so flipping it mid-process takes effect.
    """
    import selkies_tpu.encoder.h264_device as dev

    frames = frames_seq(4, seed=7)
    res = {}
    for backend in ("pallas", "xla", "scan"):
        enc = H264StripeEncoder(W, H, stripe_height=32)
        monkeypatch.setattr(dev, "ME", backend)
        res[backend] = [annexbs(enc.encode_frame(f)) for f in frames]
    assert res["pallas"] == res["xla"] == res["scan"]
