"""Sharded multi-session encode vs. the single-frame encoder oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from selkies_tpu.encoder.jpeg import _encode_body
from selkies_tpu.ops.quant import quality_scaled_tables
from selkies_tpu.parallel import BatchedSessionEncoder, make_mesh


STRIPE_H = 16
W, H = 32, 64  # 4 stripes
N_SESSIONS = 4


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(jax.devices()[:8])  # (4, 2)


def _quant_tables():
    ly, lc = quality_scaled_tables(40)
    py, pc = quality_scaled_tables(90)
    qy = jnp.stack([jnp.asarray(ly, jnp.float32), jnp.asarray(py, jnp.float32)])
    qc = jnp.stack([jnp.asarray(lc, jnp.float32), jnp.asarray(pc, jnp.float32)])
    return qy, qc


def test_mesh_shape(mesh):
    assert mesh.shape["session"] == 4
    assert mesh.shape["stripe"] == 2


def test_batched_matches_single_frame_oracle(mesh):
    rng = np.random.default_rng(7)
    frames = rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8)
    qsel = np.zeros((N_SESSIONS, H // STRIPE_H), np.int32)
    qsel[1, 2] = 1  # one paint-over stripe to exercise per-stripe tables

    enc = BatchedSessionEncoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H)
    yq, cbq, crq, damage, session_bits, total_bits = enc.step(frames, qsel)

    qy, qc = _quant_tables()
    body = functools.partial(_encode_body, stripe_h=STRIPE_H)
    for n in range(N_SESSIONS):
        ref = body(
            jnp.asarray(frames[n]), jnp.zeros((H, W, 3), jnp.uint8),
            qy, qc, jnp.asarray(qsel[n]))
        np.testing.assert_array_equal(np.asarray(yq)[n], np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(cbq)[n], np.asarray(ref[1]))
        np.testing.assert_array_equal(np.asarray(crq)[n], np.asarray(ref[2]))
        np.testing.assert_array_equal(np.asarray(damage)[n], np.asarray(ref[3]))
    assert int(total_bits) == int(np.asarray(session_bits).sum())


def test_prev_chain_damage_goes_quiet(mesh):
    rng = np.random.default_rng(3)
    frames = rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8)
    enc = BatchedSessionEncoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H)
    enc.step(frames)
    _, _, _, damage2, _, _ = enc.step(frames)  # identical frame → no damage
    assert int(np.asarray(damage2).max()) == 0


def test_geometry_validation(mesh):
    with pytest.raises(ValueError):
        BatchedSessionEncoder(mesh, 3, W, H, stripe_h=STRIPE_H)  # 3 % 4
    with pytest.raises(ValueError):
        BatchedSessionEncoder(mesh, 4, W, 48, stripe_h=STRIPE_H)  # 48 % 32


@pytest.mark.slow  # ~114 s; tests/test_graft_entry.py keeps the entrypoint
# covered in tier 1
def test_dryrun_multichip_entrypoint():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    g.dryrun_multichip(8)


def test_entry_compiles_and_runs():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    fn, example_args = g.entry()
    out = jax.jit(fn)(*example_args)
    jax.block_until_ready(out)
    words, nbytes, base, ovf, damage, new_prev = out
    assert not bool(np.asarray(ovf).any())
    assert int(np.asarray(nbytes).min()) > 0


# ---------------------------------------------------------------- config 5
# Entropy-through sharded step: wire-ready stripes for N sessions from one
# mesh dispatch, bit-exact with the solo JpegStripeEncoder.


def _staged_total(enc):
    """Stagings so far, over the rings of a lane's chips."""
    return sum(ring.staged_total for *_, ring, _ in enc._staging._chips)


def _frame_seq(rng, n_frames):
    """Per-session frame sequence: random → static → partial change."""
    f0 = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    seq = [f0, f0.copy()]
    f2 = f0.copy()
    f2[H // 2:H // 2 + STRIPE_H] = rng.integers(
        0, 256, (STRIPE_H, W, 3), dtype=np.uint8)
    seq.append(f2)
    while len(seq) < n_frames:
        seq.append(seq[-1].copy())
    return seq


def test_mesh_stripe_encoder_matches_solo(mesh):
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder
    from selkies_tpu.parallel import MeshStripeEncoder

    rng = np.random.default_rng(11)
    n_frames = 5
    seqs = [_frame_seq(np.random.default_rng(100 + n), n_frames)
            for n in range(N_SESSIONS)]

    menc = MeshStripeEncoder(
        mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
        paint_over_trigger_frames=2)
    solos = [JpegStripeEncoder(
        W, H, stripe_height=STRIPE_H, paint_over_trigger_frames=2,
        entropy="device") for _ in range(N_SESSIONS)]

    for t in range(n_frames):
        frames = [seqs[n][t] for n in range(N_SESSIONS)]
        mesh_out, session_bytes = menc.encode_frames(frames)
        assert session_bytes.shape == (N_SESSIONS,)
        for n in range(N_SESSIONS):
            solo_out = solos[n].encode_frame(seqs[n][t])
            assert [s.y_start for s in mesh_out[n]] == \
                [s.y_start for s in solo_out], f"frame {t} session {n}"
            assert [s.is_paintover for s in mesh_out[n]] == \
                [s.is_paintover for s in solo_out]
            for ms, ss in zip(mesh_out[n], solo_out):
                assert ms.jpeg == ss.jpeg, \
                    f"frame {t} session {n} stripe {ms.y_start}"


def test_mesh_stripe_encoder_none_frames_and_keyframe(mesh):
    from selkies_tpu.parallel import MeshStripeEncoder

    rng = np.random.default_rng(5)
    menc = MeshStripeEncoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H)
    frames = list(rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8))
    out, _ = menc.encode_frames(frames)
    assert all(len(s) == H // STRIPE_H for s in out)   # first: all stripes

    # idle slots (None) produce nothing, upload nothing and keep the
    # keyframe flag armed
    menc.force_keyframe(2)
    staged = _staged_total(menc)
    out, _ = menc.encode_frames([None] * N_SESSIONS)
    assert all(len(s) == 0 for s in out)
    assert menc._first[2]
    assert _staged_total(menc) == staged
    out, _ = menc.encode_frames(frames)                # same content
    assert len(out[2]) == H // STRIPE_H                # keyframe fired
    assert all(len(out[n]) == 0 for n in range(N_SESSIONS) if n != 2)


def test_parse_mesh_spec():
    from selkies_tpu.parallel import parse_mesh_spec

    m = parse_mesh_spec("session:4,stripe:2", jax.devices()[:8])
    assert m.shape["session"] == 4 and m.shape["stripe"] == 2
    m = parse_mesh_spec("session:8", jax.devices()[:8])
    assert m.shape["session"] == 8 and m.shape["stripe"] == 1
    with pytest.raises(ValueError):
        parse_mesh_spec("session:64", jax.devices()[:8])
    with pytest.raises(ValueError):
        parse_mesh_spec("tensor:2", jax.devices()[:8])


def test_reset_session_zeroes_prev_planes(mesh):
    """Slot recycling must not leak the previous occupant's pixels: the
    prev planes and the idle-tick re-present buffer go to zero."""
    import numpy as np
    from selkies_tpu.parallel.mesh import MeshStripeEncoder

    enc = MeshStripeEncoder(mesh, 4, 128, 128, stripe_h=64)
    frames = [np.full((128, 128, 3), 200, np.uint8)] * 4
    out, _ = enc.harvest(enc.dispatch(frames))
    assert any(stripes for stripes in out)
    assert np.asarray(enc._prev).any()
    enc.reset_session(1)
    prev = np.asarray(enc._prev)
    assert not prev[1].any()           # recycled slot zeroed
    assert prev[0].any()               # neighbours untouched
    staged = np.asarray(enc._staging.stage([None] * 4)[0])
    assert not staged[1].any()         # and its staged pieces with it
    assert (staged[0] == 200).all()
    assert enc._first[1]


# ---------------------------------------------------------------- mesh H.264
# the H.264 profile over the ("session", "stripe") mesh,
# bit-exact against the solo H264StripeEncoder oracle.


def _h264_seq(rng, n_frames):
    """random → shifted (motion) → static → one-stripe change → static."""
    f0 = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    f1 = np.roll(f0, 4, axis=0)                       # vertical scroll
    seq = [f0, f1, f1.copy()]
    f3 = f1.copy()
    f3[H // 2:H // 2 + STRIPE_H] = rng.integers(
        0, 256, (STRIPE_H, W, 3), dtype=np.uint8)
    seq.append(f3)
    while len(seq) < n_frames:
        seq.append(seq[-1].copy())
    return seq


def test_mesh_h264_matches_solo(mesh):
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    n_frames = 6
    seqs = [_h264_seq(np.random.default_rng(200 + n), n_frames)
            for n in range(N_SESSIONS)]

    menc = MeshH264Encoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
                           paint_over_trigger_frames=2, me="xla")
    solos = [H264StripeEncoder(W, H, stripe_height=STRIPE_H,
                               paint_over_trigger_frames=2)
             for _ in range(N_SESSIONS)]

    for t in range(n_frames):
        frames = [seqs[n][t] for n in range(N_SESSIONS)]
        mesh_out, coded = menc.encode_frames(frames)
        assert coded.shape == (N_SESSIONS,)
        for n in range(N_SESSIONS):
            solo_out = solos[n].encode_frame(seqs[n][t])
            assert [(s.y_start, s.is_key) for s in mesh_out[n]] == \
                [(s.y_start, s.is_key) for s in solo_out], \
                f"frame {t} session {n}"
            for ms, ss in zip(mesh_out[n], solo_out):
                assert ms.annexb == ss.annexb, \
                    f"frame {t} session {n} stripe {ms.y_start}"


def test_mesh_h264_idle_keyframe_and_reset(mesh):
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    rng = np.random.default_rng(6)
    menc = MeshH264Encoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
                           me="xla")
    frames = list(rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8))
    out, _ = menc.encode_frames(frames)
    assert all(len(s) == H // STRIPE_H for s in out)      # join: all IDR
    assert all(s.is_key for sess in out for s in sess)

    # idle (None) slots emit nothing; a pending keyframe stays armed
    menc.force_keyframe(2)
    out, _ = menc.encode_frames([None] * N_SESSIONS)
    assert all(len(s) == 0 for s in out)
    assert menc._need_idr[2].all()
    out, _ = menc.encode_frames(frames)                   # same pixels
    assert len(out[2]) == H // STRIPE_H and all(
        s.is_key for s in out[2])                         # IDR fired
    assert all(len(out[n]) == 0 for n in range(N_SESSIONS) if n != 2)

    # reset zeroes the inter reference planes (no cross-occupant leak)
    menc.reset_session(1)
    assert not np.asarray(menc._ref_y)[1].any()
    assert not np.asarray(menc._prev_y)[1].any()
    assert np.asarray(menc._ref_y)[0].any()
    staged = np.asarray(menc._staging.stage([None] * N_SESSIONS)[0])
    assert not staged[1].any()         # the staged pieces too
    np.testing.assert_array_equal(staged[0], frames[0])


# ------------------------------------------------------------ lane staging
# A lane stages each chip's share of a frame through that chip's
# StagingRing (parallel/mesh.py LaneStaging). The reference is a second
# encoder fed private copies, one call at a time: for JPEG on a
# one-device mesh, as chip_smoke.py --chips 4 has it on the chips (all
# four sessions' pieces joined on one device); for H.264 on the lane's
# own mesh, whose programs the module has compiled (the one-device step
# is two minutes of compile here, and the staging is the same code).


def _lane_pair(kind, mesh):
    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh import MeshStripeEncoder
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    one = parse_mesh_spec("session:1,stripe:1", jax.devices()[:1])
    if kind == "jpeg":
        return [MeshStripeEncoder(m, N_SESSIONS, W, H, stripe_h=STRIPE_H)
                for m in (mesh, one)]
    return [MeshH264Encoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
                            me="xla") for _ in range(2)]


def _wire(out):
    return [[(s.y_start, getattr(s, "annexb", None) or s.jpeg) for s in sess]
            for sess in out[0]]


@pytest.mark.parametrize("spec,n,hw,pad", [
    ("session:4", 4, (60, 30), (64, 32)),             # a whole session a chip
    ("session:4", 8, (64, 32), (64, 32)),             # two sessions a chip
    ("session:1,stripe:4", 1, (50, 30), (64, 32)),    # a band of rows a chip
    ("session:1,stripe:4", 1, (20, 32), (64, 32)),    # bands below the frame
    ("session:2,stripe:2", 2, (33, 17), (64, 32)),
])
def test_lane_staging_puts_each_chip_its_rows(spec, n, hw, pad):
    """The batch the step reads is every frame edge-padded to the lane's
    geometry, each chip holding its sessions' rows of it; None keeps a
    slot's pieces, ``reset`` blacks them, and what was staged is not the
    caller's memory."""
    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh import LaneStaging, plane_sharding

    lane = parse_mesh_spec(spec, jax.devices()[:4])
    sharding = plane_sharding(lane)
    staging = LaneStaging(sharding, n, *pad)
    rng = np.random.default_rng(47)
    frames = [rng.integers(1, 256, hw + (3,), dtype=np.uint8)
              for _ in range(n)]
    want = np.stack([np.pad(f, ((0, pad[0] - hw[0]), (0, pad[1] - hw[1]),
                                (0, 0)), mode="edge") for f in frames])
    batch, reuse, tickets = staging.stage(frames)
    for f in frames:
        f[...] = 0
    assert batch.sharding == sharding and not reuse.any()
    assert len(tickets) == len(staging._chips) * n // lane.shape["session"]
    np.testing.assert_array_equal(np.asarray(batch), want)
    batch, reuse, tickets = staging.stage([None] * n)
    assert reuse.all() and tickets == []
    np.testing.assert_array_equal(np.asarray(batch), want)
    staging.reset(n - 1)
    want[n - 1] = 0
    np.testing.assert_array_equal(
        np.asarray(staging.stage([None] * n)[0]), want)


@pytest.mark.parametrize("kind", ["jpeg", "h264"])
def test_lane_frames_in_flight_are_never_torn(mesh, kind):
    """After ``dispatch`` returns the frame is the lane's: the caller
    writes the next frame into the very arrays it passed and dispatches
    again, two steps in flight, and both harvests are the replay's. The
    frames need no padding, so nothing but the staging stands between
    the caller's writes and the pixels the device reads."""
    lane, ref = _lane_pair(kind, mesh)
    rng = np.random.default_rng(41)
    steps = [rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8)
             for _ in range(3)]
    want = [_wire(ref.encode_frames([f.copy() for f in step]))
            for step in steps]
    bufs = [f.copy() for f in steps[0]]
    got = [_wire(lane.encode_frames(bufs))]
    pend = []
    for step in steps[1:]:
        for buf, f in zip(bufs, step):
            buf[...] = f
        pend.append(lane.dispatch(bufs))
    for buf in bufs:
        buf[...] = 0
    got += [_wire(lane.harvest(p)) for p in pend]
    assert all(len(sess) == H // STRIPE_H for g in got for sess in g)
    assert got == want


def test_lane_stages_only_the_slots_that_have_a_frame(mesh):
    """A slot given None keeps its staged pieces: the rings count one
    staging for each chip a NEW frame has rows on, and the None slots
    harvest as an idle re-present (nothing), on a step that ran."""
    lane, _ = _lane_pair("jpeg", mesh)
    rng = np.random.default_rng(43)
    first = list(rng.integers(0, 256, (N_SESSIONS, H, W, 3), dtype=np.uint8))
    lane.encode_frames(first)
    chips_per_frame = mesh.shape["stripe"]
    before = _staged_total(lane)
    assert before == 2 * N_SESSIONS * chips_per_frame   # zeros, then frames
    new = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
    out, _ = lane.encode_frames([new, None, new, None])
    assert _staged_total(lane) == before + 2 * chips_per_frame
    assert [len(s) for s in out] == [H // STRIPE_H, 0, H // STRIPE_H, 0]
    # what rode again is what was staged: the same frame now is no damage
    out, _ = lane.encode_frames([None, first[1], None, first[3]])
    assert [len(s) for s in out] == [0, 0, 0, 0]
    assert not any(ring.stalls_total for *_, ring, _ in lane._staging._chips)


# ------------------------------------------------------------- SFE (ISSUE 15)
# Split-frame encoding: ONE session's frame stripe-sharded across every
# chip of the mesh. The concatenated multi-shard access unit must be
# byte-identical to the single-chip encode — IDR, P, and the
# overflow→flat16 fallback stripes — and a failed stripe job must never
# tear the access unit.


@pytest.fixture(scope="module")
def sfe_mesh():
    from selkies_tpu.parallel import parse_mesh_spec

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return parse_mesh_spec("session:1,stripe:4", jax.devices()[:4])


def test_sfe_concat_bit_exact_and_never_torn(mesh, monkeypatch):
    """Multi-shard SFE vs the solo single-chip oracle over an IDR + P +
    still + partial-change sequence: per-stripe bytes AND the
    concatenated access unit must match, and the harvest must attribute
    per-shard fetch walls. Then whole-frame containment on the SAME
    encoder: one stripe job failing mid-harvest must withhold the WHOLE
    frame — sibling stripes' device references already advanced, so
    emitting them would drift every later P frame — and resync with a
    full IDR next tick.

    Runs on the module mesh (stripe axis 2) with the exact encoder
    geometry test_mesh_h264_matches_solo already compiled, so the SPMD
    programs come from the in-process compile cache — tier-1 pays for
    the containment coverage, not a duplicate ~60 s compile; the wider
    4-shard fan-out stays covered by the slow-marked overflow +
    conformance tests on sfe_mesh."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.parallel import mesh_h264 as m
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    seq = _h264_seq(np.random.default_rng(300), 5)
    menc = MeshH264Encoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
                           paint_over_trigger_frames=2, me="xla")
    solo = H264StripeEncoder(W, H, stripe_height=STRIPE_H,
                             paint_over_trigger_frames=2)
    assert menc.n_shards == 2
    idle = [None] * (N_SESSIONS - 1)            # single-session SFE drive

    for t, frame in enumerate(seq):
        mesh_out, coded = menc.encode_frames([frame] + idle)
        solo_out = solo.encode_frame(frame)
        assert [(s.y_start, s.is_key) for s in mesh_out[0]] == \
            [(s.y_start, s.is_key) for s in solo_out], f"frame {t}"
        cat_mesh = b"".join(s.annexb for s in mesh_out[0])
        cat_solo = b"".join(s.annexb for s in solo_out)
        assert cat_mesh == cat_solo, f"frame {t} access unit differs"
    st = menc.last_harvest_stages
    assert st is not None
    assert len(st["per_shard_fetch_ms"]) == 2
    assert st["concat_ms"] >= 0.0

    # --- whole-frame containment: no torn access unit, ever -----------
    real = m.dcav.assemble_p_slice
    fails = {"n": 0}

    def fail_once(*a, **kw):
        if fails["n"] == 0:
            fails["n"] += 1
            raise RuntimeError("injected stripe entropy failure")
        return real(*a, **kw)

    monkeypatch.setattr(m.dcav, "assemble_p_slice", fail_once)
    pa = menc.dispatch([np.roll(seq[-1], 4, axis=0)] + idle)
    pb = menc.dispatch([np.roll(seq[-1], 8, axis=0)] + idle)  # successor
    out1, coded1 = menc.harvest(pa)             # stripe job fails here
    assert out1[0] == []                        # withheld, not torn
    assert int(coded1[0]) == 0
    assert menc._need_idr[0].all()              # full resync armed
    monkeypatch.setattr(m.dcav, "assemble_p_slice", real)
    # the successor was dispatched as P BEFORE the failure surfaced: its
    # prediction chain consumed the withheld frame's references, so it
    # must be withheld too — never a client frame predicted off pixels
    # the client never received
    out_b, _ = menc.harvest(pb)
    assert out_b[0] == []
    out2, _ = menc.encode_frames([np.roll(seq[-1], 12, axis=0)] + idle)
    assert len(out2[0]) == H // STRIPE_H
    assert all(s.is_key for s in out2[0])       # clean full IDR AU

    # --- idle sessions must still resync: the withheld frame's content
    # never reached the client, so a None re-present is NOT a no-op for
    # a withheld session — the armed full-frame IDR runs anyway instead
    # of deferring until fresh damage (which may never come)
    fails["n"] = 0
    monkeypatch.setattr(m.dcav, "assemble_p_slice", fail_once)
    out3, _ = menc.encode_frames([np.roll(seq[-1], 16, axis=0)] + idle)
    assert out3[0] == []                        # withheld again
    monkeypatch.setattr(m.dcav, "assemble_p_slice", real)
    out4, _ = menc.encode_frames([None] + idle)  # idle tick
    assert len(out4[0]) == H // STRIPE_H        # full IDR resync anyway
    assert all(s.is_key for s in out4[0])


@pytest.mark.slow  # ~44 s (a fresh SPMD compile); the flat16 recovery
# path itself is tier-1-covered: the concat test's IDR stripes recover
# through the same exact[(n,g)] flat16 route (host_path = ovf | idr),
# and the device-side ovf FLAG is pinned by test_device_cavlc — this
# pins their end-to-end combination on the SFE mesh
def test_sfe_overflow_flat16_fallback_bit_exact(sfe_mesh):
    """Pathological stripes overflow the device CAVLC budget and recover
    through the exact flat16 host coder — on the SFE mesh this fallback
    must stay byte-identical to the solo encoder taking the same
    fallback (shrunken budget forces it deterministically)."""
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    rng = np.random.default_rng(17)
    menc = MeshH264Encoder(sfe_mesh, 1, W, H, stripe_h=STRIPE_H, me="xla",
                           search=4)
    solo = H264StripeEncoder(W, H, stripe_height=STRIPE_H, search=4)
    # identical tiny per-stripe budgets BEFORE the first (lazy) step
    # build: full-noise P frames then exceed it and take the flat16 path
    menc._cavlc_msb = 64
    solo._cavlc_msb = 64
    for t in range(2):
        frame = rng.integers(0, 256, (H, W, 3), dtype=np.uint8)
        mesh_out, _ = menc.encode_frames([frame])
        solo_out = solo.encode_frame(frame)
        assert b"".join(s.annexb for s in mesh_out[0]) == \
            b"".join(s.annexb for s in solo_out), f"frame {t}"
    assert menc.host_fallback_stripes_total > 0


@pytest.mark.slow  # ~43 s; transitively covered in tier 1 —
# test_mesh_h264_matches_solo pins mesh bytes to the solo encoder's, and
# test_conformance decodes the solo output in libavcodec
def test_mesh_h264_decodes_in_conformance_oracle(mesh):
    """Mesh-encoded stripes must decode in libavcodec, IDR then P."""
    from selkies_tpu.encoder import conformance
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    if conformance.ConformanceDecoder is None:
        pytest.skip("conformance decoder unavailable")
    menc = MeshH264Encoder(mesh, N_SESSIONS, W, H, stripe_h=STRIPE_H,
                           me="xla")
    smooth = np.zeros((H, W, 3), np.uint8)
    yy, xx = np.mgrid[0:H, 0:W]
    smooth[..., 0] = (xx * 4) % 256
    smooth[..., 1] = (yy * 4) % 256
    smooth[..., 2] = 128
    out, _ = menc.encode_frames([smooth] * N_SESSIONS)
    shifted = np.roll(smooth, 2, axis=0)
    out2, _ = menc.encode_frames([shifted] * N_SESSIONS)

    dec = conformance.ConformanceDecoder("h264", max_dim=256)
    n_dec = 0
    for s in (x for x in out[0] + out2[0] if x.y_start == 0):
        got = dec.decode(s.annexb)
        if got is not None:
            n_dec += 1
            y, u, v = got
            assert y.shape == (STRIPE_H, W)
    got = dec.flush()
    n_dec += 1 if got else 0
    assert n_dec >= 2
    dec.close()
