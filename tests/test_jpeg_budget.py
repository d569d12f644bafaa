"""JPEG's device-pack budget: one rule beside the packer
(``device_entropy.default_max_stripe_bytes``), which both serving paths
follow by passing nothing, read against the stripes of the benchmark's own
desktop; a stripe past it still comes out bit-exact through the host."""

import functools
import json

import jax
import numpy as np
import pytest

from benchmark.sources.desktop import draw_desktop, draw_ruler
from selkies_tpu.encoder import device_entropy, jpeg
from selkies_tpu.encoder.device_entropy import (
    DeviceEntropyPacker, default_max_stripe_bytes)
from selkies_tpu.encoder.jpeg import JpegStripeEncoder

SEED = 2147483000


def scan_bytes(enc, stripes):
    """Each stripe's entropy-coded scan: the JPEG less its headers and EOI,
    which are not the pack's."""
    return [len(s.jpeg) - len(enc._stripe_headers(0)) - 2 for s in stripes]


@functools.lru_cache(maxsize=2)
def desktop_twice(w, h):
    base = draw_desktop(w, h, SEED)
    draw_ruler(base, 4)
    return np.concatenate([base, base])


def scroll_frame(w, h, offset, rows=None):
    """The benchmark's scroll: the desktop ``offset`` rows into its period
    (``rows`` of it, where the frame is to fill the encoder's padding)."""
    return desktop_twice(w, h)[offset:offset + (rows or h)]


@pytest.mark.parametrize("stripe_h,pad_w,nbytes", [
    (64, 1920, 16384),      # every accepted cell: the program it compiles
    (64, 1280, 16384),
    (16, 1920, 16384),
    (64, 32, 16384),
    (64, 2560, 28672),
    (64, 3840, 40960),
    (32, 3840, 16384),      # pixels, not the width by name
    (128, 1920, 40960),
])
def test_the_rule(stripe_h, pad_w, nbytes):
    assert default_max_stripe_bytes(stripe_h, pad_w) == nbytes


def test_the_rule_is_monotone_in_pixels_and_whole_kilowords():
    px = sorted({sh * w for sh in (16, 32, 64, 128)
                 for w in range(16, 8192 + 16, 16)})
    caps = [default_max_stripe_bytes(1, p) for p in px]
    assert caps == sorted(caps)
    assert all(c % 4096 == 0 and c >= 16384 for c in caps)
    assert all(6 * c >= p for c, p in zip(caps, px) if p > 64 * 1920)


@pytest.mark.parametrize("w,h,over_16k", [(1920, 1080, 0), (3840, 2160, 20)])
def test_the_rule_leaves_the_stripes_read_room(w, h, over_16k):
    """The readings the rule's docstring carries, taken again: the host
    coder's scans over a scroll's period (the densest offsets among them)."""
    enc = JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                            entropy="host")
    sizes = []
    for off in (0, 628, 1708 % h):
        enc.force_keyframe()        # every stripe, damaged or not
        sizes.append(scan_bytes(enc, enc.encode_frame(scroll_frame(w, h, off))))
    sizes = np.array(sizes)
    cap = default_max_stripe_bytes(64, enc.pad_w)
    assert cap >= 1.15 * sizes.max()
    assert (sizes > 16384).sum(axis=1).min() >= over_16k
    if w == 3840:       # proportion to 1080p's 16 KiB would not do
        assert sizes.max() > 32768


def lane_packer(pad_h, pad_w):
    from selkies_tpu.parallel import make_batched_entropy_step, parse_mesh_spec

    mesh = parse_mesh_spec("session:1,stripe:1", jax.devices()[:1])
    _, (_, _, cap, packer) = make_batched_entropy_step(mesh, pad_h, pad_w, 64)
    assert cap == packer.cap_words
    return packer


@pytest.mark.parametrize("path", ["solo", "lane"])
@pytest.mark.parametrize("w,h,words", [(1920, 1080, 4096),
                                       (3840, 2160, 10240)])
def test_both_serving_paths_build_the_rules_packer(path, w, h, words):
    pad_h, pad_w = -(-h // 64) * 64, -(-w // 16) * 16
    packer = (jpeg._device_pipeline(pad_h, pad_w, 64)[0] if path == "solo"
              else lane_packer(pad_h, pad_w))
    assert packer.max_stripe_words == words \
        == default_max_stripe_bytes(64, pad_w) // 4
    assert packer.block_words == 16
    assert packer.cap_words == (pad_h // 64) * words


def test_the_densest_4k_stripes_of_the_desktop_are_coded_on_the_device():
    """Six full-width stripes of a 3840x2176 frame, rows 64-448: its four
    densest and the two between them (the whole frame says the same of all
    34 in 3.3 GB). The device pack holds every one, and its bytes are the
    host coder's; the old 16 KiB flagged all six, 26 of the frame's 34."""
    w, h = 3840, 384
    frame = scroll_frame(w, 2160, 1708, rows=2176)[64:64 + h]
    dev = JpegStripeEncoder(w, h, stripe_height=64, quality=40)
    host = JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                             entropy="host")
    got, want = dev.encode_frame(frame), host.encode_frame(frame)
    assert [s.jpeg for s in got] == [s.jpeg for s in want]
    assert len(got) == dev.stripes_emitted_total == 6
    assert dev.host_fallback_stripes_total == 0
    sizes = np.array(scan_bytes(dev, got))
    assert sizes.min() > 16384 and (sizes > 32768).sum() == 2


@pytest.fixture
def small_budget(monkeypatch):
    """Every packer built inside gets an explicit budget of 1 KiB a stripe:
    an argument a test passes, where the program passes none."""
    jpeg._device_pipeline.cache_clear()
    monkeypatch.setattr(
        device_entropy, "DeviceEntropyPacker",
        functools.partial(DeviceEntropyPacker, max_stripe_bytes=1024))
    yield 1024
    jpeg._device_pipeline.cache_clear()


def noise_then_flat(w, h):
    """Two frames: every stripe past a 1 KiB budget, then every one under."""
    rng = np.random.default_rng(7)
    return [rng.integers(0, 256, (h, w, 3), dtype=np.uint8),
            np.full((h, w, 3), 90, np.uint8)]


def test_a_stripe_past_an_explicit_budget_falls_back_bit_exact(small_budget):
    from selkies_tpu.encoder.async_driver import AsyncEncodeDriver
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    w, h = 96, 128
    base = JpegStripeEncoder(w, h, stripe_height=64, quality=40)
    assert base._packer.max_stripe_words == small_budget // 4
    host = JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                             entropy="host")
    drv = AsyncEncodeDriver(PipelinedJpegEncoder(base, depth=2))
    try:
        for frame in noise_then_flat(w, h):
            drv.try_submit(frame)
            (_, got), = drv.flush()
            assert [s.jpeg for s in got] == \
                [s.jpeg for s in host.encode_frame(frame)]
        st = drv.stats()
    finally:
        drv.close()
    assert (st["host_fallback_stripes"], st["stripes_emitted"]) == (2, 4)
    # the harness prints the head of this line, 400 characters of it
    line = json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in st.items()})[:400]
    assert '"host_fallback_stripes": 2, "stripes_emitted": 4,' in line


def test_a_lanes_stripe_past_an_explicit_budget_falls_back_bit_exact(
        small_budget):
    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh import MeshStripeEncoder

    w, h = 96, 128
    mesh = parse_mesh_spec("session:1,stripe:1", jax.devices()[:1])
    lane = MeshStripeEncoder(mesh, 1, w, h, stripe_h=64, quality=40)
    assert lane._packer.max_stripe_words == small_budget // 4
    host = JpegStripeEncoder(w, h, stripe_height=64, quality=40,
                             entropy="host")
    for frame in noise_then_flat(w, h):
        (got,), _ = lane.encode_frames([frame])
        assert [s.jpeg for s in got] == \
            [s.jpeg for s in host.encode_frame(frame)]
    assert (lane.host_fallback_stripes_total,
            lane.stripes_emitted_total) == (2, 4)


def test_a_capacity_whose_words_pass_15_bits_is_refused():
    DeviceEntropyPacker(64, 64, 64, max_stripe_bytes=(1 << 17) - 4)
    with pytest.raises(ValueError, match="15 bits"):
        DeviceEntropyPacker(64, 64, 64, max_stripe_bytes=1 << 17)
