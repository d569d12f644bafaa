"""The H.264 fetch prefix follows the content (ISSUE 29).

On the device-entropy tier a P frame's bytes reach the host in one
transfer, sized at dispatch from the last frames' own bits and started
right behind the frame's step; ``harvest`` re-reads only on the frame
after content got busier. What these hold:

* busy content stops undershooting once the guess has seen it, every
  later frame is a prefix hit, and the bitstream equals, byte for byte,
  that of an encoder made to undershoot on every frame (the path every
  busy frame took before);
* a cut from quiet to busy costs one recovered, uncounted frame; sizes
  that alternate do not undershoot again while the guess remembers the
  larger; content that stays quiet returns to the small tier;
* after an encoder's first P frame no program is left to compile,
  whichever tier the content selects.
"""

from __future__ import annotations

import numpy as np
import pytest

import selkies_tpu.encoder.h264_device as dev
from selkies_tpu.encoder.h264 import H264StripeEncoder
from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

#: test_h264_batch's two-tier geometry: eight stripes of 32 rows, wide
#: enough that a noisy frame at qp 18 (40 kB) is past the first guess
W, H, STRIPE, QP = 320, 256, 32, 18


def _noise():
    return np.random.default_rng(3).integers(0, 256, (H, W, 3), np.uint8)


def _busy(n):
    base = _noise()
    return [np.roll(base, 5 * i, axis=0) for i in range(n)]


def _encoder(**kw):
    return H264StripeEncoder(W, H, stripe_height=STRIPE, qp=QP, **kw)


def _bytes(stripes):
    return [(s.y_start, s.is_key, s.annexb) for s in stripes]


def _through_pipe(enc, frames, depth=3):
    """Frames through a pipe of ``depth``; per frame, the stripes and the
    encoder's counters as its harvest left them."""
    pipe = PipelinedH264Encoder(enc, depth=depth)
    out, counters = {}, []
    harvest = enc.harvest

    def counting_harvest(p, host=None):
        stripes = harvest(p, host=host)
        counters.append((enc.d2h_refetch_bytes_total,
                         enc.prefix_hit_frames_total))
        return stripes

    enc.harvest = counting_harvest
    for f in frames:
        pipe.submit(f)
        out.update(pipe.poll())
    out.update(pipe.flush())
    pipe.close()        # or its ready thread outlives the test
    return [(_bytes(out[i]),) + counters[i]
            for i in range(len(frames))], pipe.stats()


def test_busy_content_is_fetched_whole_and_equals_the_undershoot_path():
    frames = _busy(10)
    depth = 3
    tiered = _encoder()
    got, stats = _through_pipe(tiered, frames, depth)

    forced = _encoder()
    forced._choose_prefix = lambda every_bucket=False: forced._prefix_small
    want, forced_stats = _through_pipe(forced, frames, depth)

    # the frames dispatched before the first busy harvest moved the guess
    # undershoot (at most the pipe's depth of them); none after
    settled = 1 + depth
    refetched = [r for _s, r, _h in got]
    assert refetched[settled - 1] > 0
    assert refetched[settled:] == [refetched[settled - 1]] * (
        len(frames) - settled)
    hits = [h for _s, _r, h in got]
    assert [b - a for a, b in zip(hits[settled - 1:], hits[settled:])] == \
        [1] * (len(frames) - settled)
    assert stats["cavlc_frames"] == len(frames) - 1
    assert stats["prefix_hit_frames"] >= len(frames) - settled
    # the forced encoder re-reads on every P frame and counts no hit ...
    assert forced_stats["prefix_hit_frames"] == 0
    forced_refetched = [r for _s, r, _h in want]
    assert forced_refetched[0] == 0                   # the IDR
    assert all(b > a for a, b in zip(forced_refetched, forced_refetched[1:]))
    # ... and the two bitstreams are the same bytes
    for i, ((a, _r, _h), (b, _r2, _h2)) in enumerate(zip(got, want)):
        assert a == b, f"frame {i}"
    # fewer bytes over the wire than head + re-read
    assert stats["d2h_bytes_per_frame"] < forced_stats["d2h_bytes_per_frame"]


def test_a_cut_to_busy_costs_one_recovered_frame_and_quiet_returns_small(
        monkeypatch):
    # the guess remembers this many frames: quiet that long is quiet
    monkeypatch.setattr(H264StripeEncoder, "PREFIX_MEMORY_FRAMES", 3)
    base = _noise()
    quiet = [base] * 4                       # IDR, then nothing changes
    busy = [np.roll(base, 5 * (i + 1), axis=0) for i in range(3)]
    frames = quiet + busy + [busy[-1]] * 5

    enc = _encoder()
    whole = _encoder()                       # never undershoots
    whole._choose_prefix = lambda every_bucket=False: whole._buf_bytes
    lens, hit_steps, refetch_steps = [], [], []
    for i, f in enumerate(frames):
        p = enc.dispatch(f)
        hits, refetched = (enc.prefix_hit_frames_total,
                           enc.d2h_refetch_bytes_total)
        got = enc.harvest(p)
        assert _bytes(got) == _bytes(whole.encode_frame(f)), f"frame {i}"
        if not p.is_idr:
            lens.append(p.head_len)
            hit_steps.append(enc.prefix_hit_frames_total - hits)
            refetch_steps.append(enc.d2h_refetch_bytes_total - refetched)
    small = enc._prefix_small
    cut = len(quiet) - 1                     # index among the P frames
    # the first P frame ships the first guess; quiet ones, and the cut
    # dispatched after them, the small tier
    assert lens[1:cut + 1] == [small] * cut
    assert hit_steps[cut] == 0 and refetch_steps[cut] > 0
    assert lens[cut + 1] > small and lens[cut + 1] in enc._prefix_tiers()
    # every frame but the cut is a hit with no re-read
    assert hit_steps[:cut] + hit_steps[cut + 1:] == [1] * (len(lens) - 1)
    assert not any(refetch_steps[:cut] + refetch_steps[cut + 1:])
    # quiet again: the guess remembers the busy frames for three more,
    # then the small tier
    assert lens[cut + len(busy)] == lens[cut + 1]
    assert lens[-1] == small


def test_sizes_that_alternate_undershoot_once():
    """A frame's bits follow how far the content moved since the frame
    before: two small moves, then one past the motion search. The first
    large frame undershoots; the guess remembers it, so no later one does
    (a guess from the last frame alone would miss every third frame)."""
    base = _noise()
    enc = _encoder()
    shift, undershot, sizes = 0, [], []
    for i in range(13):
        shift += 26 if i % 3 == 0 and i else 2
        refetched = enc.d2h_refetch_bytes_total
        stripes = enc.encode_frame(np.roll(base, shift, axis=0))
        sizes.append(sum(len(s.annexb) for s in stripes))
        undershot.append(enc.d2h_refetch_bytes_total > refetched)
    large, small = sizes[3::3], sizes[4::3] + sizes[5::3]
    # they do alternate, past what a guess from a small frame holds
    assert min(large) > enc._bucket(max(small) * 3 // 2)
    assert undershot == [i == 3 for i in range(13)]
    assert enc.prefix_hit_frames_total == enc.cavlc_frames_total - 1


def test_no_program_is_left_to_compile_after_the_first_p_frame(monkeypatch):
    """What the boot warm-up does (server/main.py WarmUp: a keyframe and
    two moving frames, flushed one by one) leaves nothing cold, whichever
    tier the content then selects."""
    from selkies_tpu.encoder.async_driver import AsyncEncodeDriver

    # a guess with no memory walks the most tiers
    monkeypatch.setattr(H264StripeEncoder, "PREFIX_MEMORY_FRAMES", 1)
    base = _noise()
    enc = _encoder()
    drv = AsyncEncodeDriver(PipelinedH264Encoder(enc, depth=4))
    try:
        for i in range(3):
            drv.submit(np.roll(base, 5 * i, axis=0))
            assert len(drv.flush(timeout=600.0)) == 1
        assert enc._prefix_tiers()[0] == enc._prefix_small
        assert enc._prefix_tiers()[-1] == enc._buf_bytes
        assert len(set(enc._prefix_tiers())) == len(enc._prefix_tiers())

        cold = []
        real = enc.compile_watch.first_use

        def first_use(program):
            if program not in enc.compile_watch._warm:
                cold.append(program)
            return real(program)

        enc.compile_watch.first_use = first_use
        compiled = dev.fetch_prefix._cache_size()
        sizes = set()
        # quiet, busier step by step, quiet again: the guess walks tiers
        walk = [base] * 3 + [np.roll(base, 7 * i, axis=1) for i in range(4)]
        walk += [(base // (1 << k)) for k in (1, 3, 5)] + [base // 32] * 3
        for f in walk:
            drv.submit(f)
            assert len(drv.flush(timeout=600.0)) == 1
            sizes.add(enc._sparse_guess)
        assert len(sizes) >= 3               # the walk did change tiers
        assert cold == []
        assert dev.fetch_prefix._cache_size() == compiled
    finally:
        drv.close()


@pytest.mark.parametrize("entropy", ["device", "host"])
def test_only_the_own_slice_program_gets_every_bucket(entropy):
    enc = H264StripeEncoder(W, H, stripe_height=STRIPE, entropy=entropy)
    if entropy == "host":
        enc._sparse_guess = enc._bucket(enc._host_step_prefix * 2)
        assert enc._choose_prefix() == enc._host_step_prefix
    else:                   # the size compiled into a step is that rung's
        enc._sparse_guess = enc._bucket(enc._prefix_small * 8)
        assert not hasattr(enc, "_host_step_prefix")
    assert enc._choose_prefix(every_bucket=True) == enc._sparse_guess
    enc._sparse_guess = enc._prefix_small
    assert enc._choose_prefix() == enc._choose_prefix(every_bucket=True) \
        == enc._prefix_small
