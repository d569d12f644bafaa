"""Batched H.264 dispatch: the headline-path guarantees.

The 46 fps config-2 number rides dispatch_batch/submit_batch
(h264_device.encode_frame_p_batch_rgb — the reference chain inside one
program); these tests pin the claims BASELINE.md makes about it:
bitstreams bit-identical to sequential encoding, IDR recovery through
the single-frame path, partial batches, two-tier head prefixes, and the
undershoot fallback.
"""

import numpy as np
import pytest

from selkies_tpu.encoder.h264 import H264StripeEncoder
from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

W, H = 128, 96


def frames_seq(n, seed=0, still_after=None):
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 256, (H, W, 3), np.uint8)
    out = []
    for i in range(n):
        k = i if still_after is None else min(i, still_after)
        out.append(np.roll(base, 3 * k, axis=0))
    return out


def annexbs(stripes):
    return [s.annexb for s in stripes]


def encode_sequential(frames, key_at=()):
    enc = H264StripeEncoder(W, H, stripe_height=32)
    out = []
    for i, f in enumerate(frames):
        if i in key_at:
            enc.request_keyframe()
        out.append(enc.encode_frame(f))
    return out


def encode_batched(frames, batch, key_at=(), use_submit_batch=False):
    enc = H264StripeEncoder(W, H, stripe_height=32)
    pipe = PipelinedH264Encoder(enc, depth=4 * batch, batch=batch)
    got = {}
    if use_submit_batch:
        import jax.numpy as jnp
        for i in range(0, len(frames), batch):
            chunk = frames[i:i + batch]
            if len(chunk) == batch:
                pipe.submit_batch(jnp.stack([jnp.asarray(f)
                                             for f in chunk]))
            else:
                for f in chunk:
                    pipe.submit(f)
            for seq, s in pipe.poll(flush_partial=False):
                got[seq] = s
    else:
        for i, f in enumerate(frames):
            if i in key_at:
                for seq, s in pipe.flush():
                    got[seq] = s
                pipe.request_keyframe()
            pipe.submit(f)
            for seq, s in pipe.poll(flush_partial=False):
                got[seq] = s
    for seq, s in pipe.flush():
        got[seq] = s
    assert len(got) == len(frames)
    return [got[i] for i in range(len(frames))]


def test_batch_bitstreams_match_sequential():
    frames = frames_seq(9)
    ref = encode_sequential(frames)
    got = encode_batched(frames, batch=3)
    for i in range(len(frames)):
        assert annexbs(ref[i]) == annexbs(got[i]), f"frame {i}"


def test_submit_batch_matches_sequential():
    frames = frames_seq(8)
    ref = encode_sequential(frames)
    got = encode_batched(frames, batch=4, use_submit_batch=True)
    for i in range(len(frames)):
        assert annexbs(ref[i]) == annexbs(got[i]), f"frame {i}"


def test_partial_batch_and_midstream_idr_match_sequential():
    frames = frames_seq(8)
    ref = encode_sequential(frames, key_at=(5,))
    got = encode_batched(frames, batch=3, key_at=(5,))
    for i in range(len(frames)):
        assert annexbs(ref[i]) == annexbs(got[i]), f"frame {i}"
    # the mid-stream keyframe really landed
    assert any(s.is_key for s in got[5])


def test_idr_recovery_avoids_batch_program(monkeypatch):
    """While any stripe needs an IDR, dispatch_batch must ride the
    already-compiled single-frame path, never a fresh (B-1)-shaped
    batched program."""
    import jax.numpy as jnp

    import selkies_tpu.encoder.h264_device as dev

    enc = H264StripeEncoder(W, H, stripe_height=32)
    calls = []
    for name in ("encode_frame_p_batch_rgb", "encode_frame_p_batch_cavlc_rgb"):
        real = getattr(dev, name)

        def spy(*a, _real=real, **k):
            calls.append(a[0].shape[0])
            return _real(*a, **k)

        monkeypatch.setattr(dev, name, spy)
    frames = frames_seq(4)
    rgbs = jnp.stack([jnp.asarray(f) for f in frames])
    pends = enc.dispatch_batch(rgbs, fetch=True)   # first call: IDR path
    assert calls == []                             # no batch program ran
    for p in pends:
        enc.harvest(p)
    pends = enc.dispatch_batch(rgbs, fetch=True)   # steady state
    assert calls == [4]


def test_two_tier_prefix_shrinks_for_static_content(monkeypatch):
    """Static frames must ship the small head, not the worst-case one
    (code-review r3: a fixed large prefix costs 10-30x the D2H bytes on
    an idle desktop), once the guess has forgotten the busy ones. Uses a
    geometry large enough that the two tiers are distinct buckets."""
    monkeypatch.setattr(H264StripeEncoder, "PREFIX_MEMORY_FRAMES", 2)
    rng = np.random.default_rng(3)
    base = rng.integers(0, 256, (256, 320, 3), np.uint8)
    frames = [np.roll(base, 5 * min(i, 2), axis=0) for i in range(8)]
    enc = H264StripeEncoder(320, 256, stripe_height=32)
    assert enc._prefix_small < enc._batch_prefix
    lens = []
    for f in frames:
        p = enc.dispatch(f, fetch=True)
        enc.harvest(p)
        if not p.is_idr:
            lens.append(p.head_len)
    # busy frames ship the large head, quiet frames re-tier to small
    assert lens[0] == enc._batch_prefix
    assert lens[-1] == enc._prefix_small


def test_batch_undershoot_recovers_exactly():
    """Force a tiny large-tier prefix so every batch frame undershoots:
    the flat16 fallback must still produce bitstreams identical to
    sequential encoding."""
    frames = frames_seq(7)
    ref = encode_sequential(frames)

    enc = H264StripeEncoder(W, H, stripe_height=32)
    enc._batch_prefix = enc._bucket(enc._fixed_bytes + 64)
    enc._prefix_small = enc._batch_prefix
    pipe = PipelinedH264Encoder(enc, depth=12, batch=3)
    got = {}
    for f in frames:
        pipe.submit(f)
        for seq, s in pipe.poll(flush_partial=False):
            got[seq] = s
    for seq, s in pipe.flush():
        got[seq] = s
    for i in range(len(frames)):
        assert annexbs(ref[i]) == annexbs(got[i]), f"frame {i}"


def test_flush_drains_partial_batch_buffer():
    """flush() must dispatch and drain a tail smaller than ``batch``
    immediately — with the poll deadline pushed out of reach, the only
    way the buffered frames can exit is flush() itself draining
    ``_batch_frames``."""
    frames = frames_seq(5)
    ref = encode_sequential(frames)
    enc = H264StripeEncoder(W, H, stripe_height=32)
    pipe = PipelinedH264Encoder(enc, depth=12, batch=3,
                                batch_deadline_s=3600.0)
    got = {}
    for f in frames:
        pipe.submit(f)          # one full batch dispatches; 2 stay buffered
    assert len(pipe._batch_frames) == 2
    for seq, s in pipe.flush():
        got[seq] = s
    assert sorted(got) == list(range(len(frames)))
    assert not pipe._batch_frames and pipe.n_inflight == 0
    for i in range(len(frames)):
        assert annexbs(ref[i]) == annexbs(got[i]), f"frame {i}"


def test_me_backends_agree(monkeypatch):
    """pallas / chunked-xla / scan backends produce identical bitstreams
    (the bit-identical-winners contract of ops/pallas_me.py). The
    backend is a static jit arg, so flipping it mid-process takes effect
    (code-review r3: env read at trace time was invisible to the cache).
    """
    import selkies_tpu.encoder.h264_device as dev

    frames = frames_seq(4, seed=7)
    res = {}
    for backend in ("pallas", "xla", "scan"):
        enc = H264StripeEncoder(W, H, stripe_height=32)
        monkeypatch.setattr(dev, "_me_backend", lambda b=backend: b)
        res[backend] = [annexbs(enc.encode_frame(f)) for f in frames]
    assert res["pallas"] == res["xla"] == res["scan"]
