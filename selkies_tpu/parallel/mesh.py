"""Device mesh construction and the sharded multi-session encode step.

Replaces (TPU-natively) the reference's per-display C++ thread-pool
parallelism (pixelflux capture/encode threads, reference selkies.py:2846-2904)
with SPMD over a ``jax.sharding.Mesh``: sessions are data-parallel, a frame's
height is spatially sharded on stripe boundaries, and the global rate signal
is a psum over both axes.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..encoder.h264_device import StagingRing, StagingTicket
from ..encoder.jpeg import _encode_body
from ..runtime import CompileWatch

def fetch_sharded_prefix(prefix):
    """Materialize an eagerly-fetching sharded device array shard by
    shard, attributing the D2H wall to each stripe-axis block.

    Returns ``(host, per_shard_ms)``: the assembled host ndarray and a
    map of stripe-axis block index (dim 1 of the array) to the host
    milliseconds spent blocked on that shard's transfer — the flight
    recorder's per-shard fetch attribution for split-frame encoding
    (ISSUE 15). Several sessions' shards on the same stripe block fold
    to the max (the gating wall). Falls back to one whole-array gather
    when shards are not addressable from this process."""
    try:
        if not getattr(prefix, "is_fully_addressable", True):
            # a process-spanning mesh would leave the remote shards'
            # regions of the np.empty buffer as garbage — fall through
            # to the whole-array gather, which fails loudly instead
            raise ValueError("prefix not fully addressable")
        shards = list(prefix.addressable_shards)
        if not shards:
            raise ValueError("no addressable shards")
        host = np.empty(prefix.shape, dtype=prefix.dtype)
        per_shard: dict = {}
        for sh in shards:
            t0 = time.perf_counter()
            host[sh.index] = np.asarray(sh.data)
            ms = (time.perf_counter() - t0) * 1000.0
            k = 0
            if len(sh.index) > 1 and isinstance(sh.index[1], slice):
                k = int(sh.index[1].start or 0)
            per_shard[k] = max(per_shard.get(k, 0.0), ms)
        return host, per_shard
    except Exception:
        t0 = time.perf_counter()
        host = np.asarray(prefix)
        return host, {0: (time.perf_counter() - t0) * 1000.0}


def plane_sharding(mesh: Mesh) -> NamedSharding:
    """``P("session", "stripe")`` on ``mesh``, spelled the way a jitted
    step's OUTPUTS come back: axes of size 1 dropped, trailing ``None``
    trimmed. jit keys its compiled programs on argument shardings, and a
    state array that enters the first step as ``P("session", "stripe")``
    returns as e.g. ``P(None, "stripe")`` — an equivalent sharding, a
    different key, and so a second compile of the same (for H.264,
    minutes-long) program on the second step. Starting in the returned
    form compiles each step once."""
    spec = [a if mesh.shape[a] > 1 else None for a in ("session", "stripe")]
    while spec and spec[-1] is None:
        spec.pop()
    return NamedSharding(mesh, P(*spec))


class LaneStaging:
    """How a lane's frames reach its chips: the ``[N, pad_h, pad_w, 3]``
    batch a step reads is put together from pieces, one per session and
    chip that holds rows of it, each staged through that chip's
    :class:`StagingRing` as a solo encoder stages its frames. A piece
    stays until its session's next frame replaces it: a session with no
    new frame uploads nothing."""

    def __init__(self, sharding: NamedSharding, n_sessions: int,
                 pad_h: int, pad_w: int) -> None:
        self._sharding = sharding
        self._shape = (n_sessions, pad_h, pad_w, 3)
        #: per chip: its sessions, its rows of each, its ring (a session
        #: may hold three slots: the piece the next step reads and those
        #: of the coordinator's two dispatches in flight) and its pieces
        self._chips = []
        for dev, idx in sharding.addressable_devices_indices_map(
                self._shape).items():
            n0, n1, _ = idx[0].indices(n_sessions)
            r0, r1, _ = idx[1].indices(pad_h)
            self._chips.append((range(n0, n1), r0, r1, StagingRing(
                depth=3 * (n1 - n0), device=dev), [None] * (n1 - n0)))
        self._replaced: List[StagingTicket] = []
        for n in range(n_sessions):
            self.reset(n)

    def put(self, session: int, frame: np.ndarray) -> None:
        """Stage one session's frame, edge-padded to the batch's rows
        and columns. ``np.pad`` returns a new array also where it adds
        nothing: whatever the caller writes into ``frame`` next, the
        transfer (and on the CPU backend, where a ring slot's first
        ``device_put`` may alias it, the piece) reads memory that
        nobody writes again."""
        h, w = frame.shape[:2]
        for sessions, r0, r1, ring, pieces in self._chips:
            if session not in sessions:
                continue
            rows = frame[min(r0, h - 1):r1]
            piece = np.pad(
                rows, ((0, r1 - r0 - rows.shape[0]),
                       (0, self._shape[2] - w), (0, 0)), mode="edge")
            staged, slot = ring.stage(piece[None])
            old = pieces[session - sessions[0]]
            if old is not None:
                self._replaced.append(old[1])
            pieces[session - sessions[0]] = staged, StagingTicket(ring, slot)

    def reset(self, session: int) -> None:
        """A recycled slot's pieces become zeros on the device."""
        self.put(session, np.zeros((1, 1, 3), np.uint8))

    def stage(self, frames) -> Tuple[jax.Array, np.ndarray, list]:
        """``frames``: a frame (unpadded is fine) or None per session.
        Returns the device batch, which sessions had None (their pieces
        ride again) and the tickets of the pieces replaced since the last
        call, to release once this dispatch is harvested: every earlier
        step, which may have read them, has been by then."""
        reuse_prev = np.array([f is None for f in frames])
        for n in np.flatnonzero(~reuse_prev):
            self.put(n, np.asarray(frames[n], np.uint8))
        shards = [own[0][0] if len(own) == 1
                  else jnp.concatenate([piece for piece, _ in own])
                  for *_, own in self._chips]
        tickets, self._replaced = self._replaced, []
        return (jax.make_array_from_single_device_arrays(
            self._shape, self._sharding, shards), reuse_prev, tickets)


def make_mesh(
    devices=None,
    stripe_axis: Optional[int] = None,
) -> Mesh:
    """Build a ("session", "stripe") mesh over the given (or all) devices.

    ``stripe_axis`` defaults to 2 when the device count is even so both mesh
    axes are exercised, else 1 (pure session parallelism).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if stripe_axis is None:
        stripe_axis = 2 if (n % 2 == 0 and n > 1) else 1
    if n % stripe_axis:
        raise ValueError(f"{n} devices not divisible by stripe_axis={stripe_axis}")
    arr = np.asarray(devices).reshape(n // stripe_axis, stripe_axis)
    return Mesh(arr, ("session", "stripe"))


def parse_mesh_spec(spec: str, devices=None) -> Mesh:
    """Build a mesh from the ``tpu_mesh`` setting, e.g. ``"session:4"`` or
    ``"session:4,stripe:2"``. Axis sizes must multiply to ≤ the available
    device count; missing axes default to 1."""
    if devices is None:
        devices = jax.devices()
    sizes = {"session": 1, "stripe": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, num = part.partition(":")
        name = name.strip()
        if name not in sizes:
            raise ValueError(f"unknown mesh axis {name!r} (session|stripe)")
        sizes[name] = int(num)
    total = sizes["session"] * sizes["stripe"]
    if total < 1 or total > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {total} devices; {len(devices)} available")
    arr = np.asarray(devices[:total]).reshape(sizes["session"], sizes["stripe"])
    return Mesh(arr, ("session", "stripe"))


def make_batched_step(mesh: Mesh, stripe_h: int):
    """Jitted sharded step: encode one frame for every session in the batch.

    fn(frames, prev, qy, qc, qsel) with
      frames/prev [N, H, W, 3] uint8  — sharded (session, stripe) on (N, H);
      qy/qc       [nq, 8, 8] float32  — replicated quant tables;
      qsel        [N, S] int32        — per-session per-stripe table index.
    Returns (yq, cbq, crq, damage, new_prev, session_bits, total_bits):
      coefficient planes and damage sharded like their inputs, ``new_prev``
      for the next tick (donated chain), per-session nonzero-coefficient
      counts [N] (the rate-control feedback, psum over "stripe"), and the
      replicated global total (psum over "session" too).
    """
    n_session, n_stripe = mesh.shape["session"], mesh.shape["stripe"]

    def local_step(frames, prev, qy, qc, qsel):
        enc = functools.partial(_encode_body, stripe_h=stripe_h)
        yq, cbq, crq, damage, new_prev = jax.vmap(
            enc, in_axes=(0, 0, None, None, 0))(frames, prev, qy, qc, qsel)
        nz = (
            (yq != 0).sum(axis=(1, 2, 3))
            + (cbq != 0).sum(axis=(1, 2, 3))
            + (crq != 0).sum(axis=(1, 2, 3))
        ).astype(jnp.int32)
        # A session's stripes live on different chips along "stripe": the
        # per-session coded-size estimate is the ICI psum across that axis.
        session_bits = jax.lax.psum(nz, "stripe")
        total_bits = jax.lax.psum(session_bits.sum(), "session")
        return yq, cbq, crq, damage, new_prev, session_bits, total_bits

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            P("session", "stripe"),  # frames
            P("session", "stripe"),  # prev
            P(),                     # qy
            P(),                     # qc
            P("session", "stripe"),  # qsel
        ),
        out_specs=(
            P("session", "stripe"),  # yq
            P("session", "stripe"),  # cbq
            P("session", "stripe"),  # crq
            P("session", "stripe"),  # damage
            P("session", "stripe"),  # new_prev
            P("session"),            # session_bits
            P(),                     # total_bits
        ),
    )
    return jax.jit(sharded, donate_argnums=(1,)), (n_session, n_stripe)


def make_batched_entropy_step(mesh: Mesh, pad_h: int, pad_w: int,
                              stripe_h: int):
    """Sharded multi-session step that carries encode *through* device
    entropy coding: one mesh dispatch yields wire-ready packed bitstreams
    for every session.

    Stripes are independent JPEGs (DC prediction resets per stripe,
    device_entropy.scan_geometry), so each device entropy-codes its local
    height shard with a packer built for the *local* geometry — no
    cross-device bitstream stitching is needed; only the scalar rate
    feedback crosses the ICI (psum over "stripe" then "session").

    Returns (jitted_fn, meta): fn(frames, prev, qy, qc, qsel) →
      packed [N, stripe_ax, mw + cap_words] uint32 — per session per height
          shard: 4*S_local metadata words (nbytes/base/overflow/damage,
          see jpeg.split_meta) then the compacted stripe bitstreams;
      new_prev, yq, cbq, crq — sharded, stay on device (the coefficient
          planes are only materialized for rare overflow fallbacks);
      session_bytes [N] int32 — coded bytes per session (rate feedback);
      total_bytes  [] int32 — replicated global sum.
    meta = (S_local, mw, cap_words, packer) for host-side assembly.
    """
    from ..encoder.device_entropy import DeviceEntropyPacker

    n_stripe_ax = mesh.shape["stripe"]
    if pad_h % (n_stripe_ax * stripe_h):
        raise ValueError("pad_h must divide into stripe_ax × stripe_h bands")
    h_local = pad_h // n_stripe_ax
    packer = DeviceEntropyPacker(h_local, pad_w, stripe_h)
    s_local = h_local // stripe_h
    mw = 4 * s_local
    cap = packer.cap_words

    def local_step(frames, prev, qy, qc, qsel):
        # the phases are named as the solo step names them (ops/phases.py;
        # _encode_body brings colour, damage and transform): scopes are
        # metadata and change no byte. The two psums lie in no phase
        enc = functools.partial(_encode_body, stripe_h=stripe_h)
        yq, cbq, crq, damage, new_prev = jax.vmap(
            enc, in_axes=(0, 0, None, None, 0))(frames, prev, qy, qc, qsel)
        with jax.named_scope("entropy"):
            words, nbytes, base, ovf = jax.vmap(packer._pack_fn)(
                yq, cbq, crq)
        session_bytes = jax.lax.psum(
            nbytes.sum(axis=1).astype(jnp.int32), "stripe")
        total_bytes = jax.lax.psum(session_bytes.sum(), "session")
        with jax.named_scope("entropy"):
            # session_bytes rides the fetched head (one extra word) so the
            # host never pays a second D2H round trip for rate feedback
            head = jnp.concatenate([
                nbytes.astype(jnp.uint32),
                base.astype(jnp.uint32),
                ovf.astype(jnp.uint32),
                damage.astype(jnp.uint32),
                session_bytes[:, None].astype(jnp.uint32),
            ], axis=1)                                # [N_local, mw + 1]
            packed = jnp.concatenate([head, words], axis=1)[:, None, :]
        return (packed, new_prev, yq, cbq, crq, session_bytes, total_bytes)

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            P("session", "stripe"),        # frames [N, H, W, 3]
            P("session", "stripe"),        # prev
            P(),                           # qy
            P(),                           # qc
            P("session", "stripe"),        # qsel [N, S_total]
        ),
        out_specs=(
            P("session", "stripe", None),  # packed [N, stripe_ax, mw+cap]
            P("session", "stripe"),        # new_prev
            P("session", "stripe"),        # yq
            P("session", "stripe"),        # cbq
            P("session", "stripe"),        # crq
            P("session"),                  # session_bytes
            P(),                           # total_bytes
        ),
    )
    return jax.jit(sharded, donate_argnums=(1,)), (s_local, mw, cap, packer)


class BatchedSessionEncoder:
    """Frame-batched multi-session encoder (BASELINE config 5 skeleton).

    Holds the sharded previous-frame state on device and dispatches one
    mesh-wide step per tick. Geometry constraints: ``height`` must divide
    evenly into ``mesh stripe axis × stripe_h`` bands and ``n_sessions``
    into the session axis.
    """

    def __init__(
        self,
        mesh: Mesh,
        n_sessions: int,
        width: int,
        height: int,
        stripe_h: int = 64,
        quality: int = 40,
        paintover_quality: int = 90,
    ) -> None:
        from ..ops.quant import quality_scaled_tables

        n_sess_ax = mesh.shape["session"]
        n_stripe_ax = mesh.shape["stripe"]
        if n_sessions % n_sess_ax:
            raise ValueError(
                f"{n_sessions} sessions not divisible by session axis {n_sess_ax}")
        if height % (n_stripe_ax * stripe_h):
            raise ValueError(
                f"height {height} not divisible by stripe axis {n_stripe_ax}"
                f" × stripe_h {stripe_h}")
        if width % 16:
            raise ValueError("width must be a multiple of 16 (4:2:0 MCUs)")
        self.mesh = mesh
        self.n_sessions = n_sessions
        self.width, self.height, self.stripe_h = width, height, stripe_h
        self.n_stripes = height // stripe_h

        ly, lc = quality_scaled_tables(quality)
        py, pc = quality_scaled_tables(paintover_quality)
        self._qy = jnp.stack([jnp.asarray(ly, jnp.float32),
                              jnp.asarray(py, jnp.float32)])
        self._qc = jnp.stack([jnp.asarray(lc, jnp.float32),
                              jnp.asarray(pc, jnp.float32)])

        self._step, _ = make_batched_step(mesh, stripe_h)
        frame_sharding = NamedSharding(mesh, P("session", "stripe"))
        self._frame_sharding = frame_sharding
        self._prev = jax.device_put(
            jnp.zeros((n_sessions, height, width, 3), jnp.uint8), frame_sharding)

    def step(self, frames: np.ndarray, qsel: Optional[np.ndarray] = None):
        """Encode one frame per session; returns
        (yq, cbq, crq, damage, session_bits, total_bits)."""
        if qsel is None:
            qsel = np.zeros((self.n_sessions, self.n_stripes), np.int32)
        frames_d = jax.device_put(
            jnp.asarray(frames, jnp.uint8), self._frame_sharding)
        yq, cbq, crq, damage, self._prev, session_bits, total_bits = self._step(
            frames_d, self._prev, self._qy, self._qc,
            jnp.asarray(qsel, jnp.int32))
        return yq, cbq, crq, damage, session_bits, total_bits


@dataclass
class _MeshPending:
    """One in-flight mesh dispatch (device handles + dispatch-time state)."""

    prefix: Any                 # async-fetching head+payload-guess slice
    packed: Any                 # full device buffer (refetch on miss)
    yq: Any                     # coefficient planes (overflow fallback only)
    cbq: Any
    crq: Any
    paint_candidate: np.ndarray
    reuse_prev: np.ndarray
    first: np.ndarray
    stride: int
    tickets: list               # staged pieces this dispatch replaced

    @property
    def step_out(self):
        """The step's own output (the coordinator's ready watch stamps
        when it is ready): not the prefix a slice program cuts from it."""
        return self.packed


class MeshStripeEncoder:
    """Multi-session JPEG-stripe encoder over a device mesh: one sharded
    dispatch per tick carries every session's frame through color convert,
    DCT, quantization AND device entropy coding, returning wire-ready 0x03
    stripe payloads per session (BASELINE config 5, completed end-to-end).

    Role: N solo ``JpegStripeEncoder``s collapsed into one SPMD program —
    sessions are data-parallel on the "session" mesh axis, each frame's
    height is sharded on the "stripe" axis, and damage gating / paint-over
    history run vectorized on host across the whole batch.
    """

    def __init__(
        self,
        mesh: Mesh,
        n_sessions: int,
        width: int,
        height: int,
        stripe_h: int = 64,
        quality: int = 40,
        paintover_quality: int = 90,
        use_paint_over_quality: bool = True,
        paint_over_trigger_frames: int = 15,
        damage_threshold: int = 0,
    ) -> None:
        from ..encoder.jfif import jfif_headers
        from ..ops.quant import quality_scaled_tables

        n_sess_ax = mesh.shape["session"]
        self.n_stripe_ax = mesh.shape["stripe"]
        if n_sessions % n_sess_ax:
            raise ValueError(
                f"{n_sessions} sessions not divisible by session axis {n_sess_ax}")
        if stripe_h % 16:
            raise ValueError("stripe_h must be a multiple of 16 (4:2:0 MCUs)")
        band = self.n_stripe_ax * stripe_h
        self.width, self.height = width, height
        self.pad_w = -(-width // 16) * 16
        self.pad_h = -(-height // band) * band
        self.stripe_h = stripe_h
        self.n_stripes = self.pad_h // stripe_h
        self.n_sessions = n_sessions
        self.mesh = mesh
        self.damage_threshold = int(damage_threshold)
        self.use_paint_over_quality = bool(use_paint_over_quality)
        self.paint_over_trigger_frames = int(paint_over_trigger_frames)

        ly, lc = quality_scaled_tables(quality)
        py, pc = quality_scaled_tables(paintover_quality)
        self._qy = jnp.stack([jnp.asarray(ly, jnp.float32),
                              jnp.asarray(py, jnp.float32)])
        self._qc = jnp.stack([jnp.asarray(lc, jnp.float32),
                              jnp.asarray(pc, jnp.float32)])
        self._headers = tuple(
            jfif_headers(self.pad_w, stripe_h, qy_np, qc_np, subsampling="420")
            for qy_np, qc_np in ((ly, lc), (py, pc)))

        self._step, (self.s_local, self._mw, self._cap, self._packer) = \
            make_batched_entropy_step(mesh, self.pad_h, self.pad_w, stripe_h)
        #: first-use compile signal of this lane's program (the sessions'
        #: capture loops read it through their coordinator facade)
        self.compile_watch = CompileWatch()
        self._frame_sharding = plane_sharding(mesh)
        self._qsel_sharding = plane_sharding(mesh)
        self._prev = jax.device_put(
            jnp.zeros((n_sessions, self.pad_h, self.pad_w, 3), jnp.uint8),
            self._frame_sharding)

        S = self.n_stripes
        self._static = np.zeros((n_sessions, S), np.int64)
        self._painted = np.zeros((n_sessions, S), bool)
        self._first = np.ones(n_sessions, bool)
        self._staging = LaneStaging(
            self._frame_sharding, n_sessions, self.pad_h, self.pad_w)
        #: adaptive D2H prefix (words per (session, shard) fetched besides
        #: metadata); a miss costs one extra read of the missing slice
        self._guess = self._packer.bucket_words(8192)
        self.stripes_emitted_total = 0        # and those the host coded:
        self.host_fallback_stripes_total = 0
        #: fetch/concat split of the latest harvest wall, with per-shard
        #: fetch attribution (the coordinator's flight-recorder feed)
        self.last_harvest_stages: Optional[dict] = None
        #: when the latest dispatch launched its step (``time.monotonic``):
        #: staging lies before it, the launch after (the same feed)
        self.last_launch_at: Optional[float] = None

    # -- control -----------------------------------------------------------

    def force_keyframe(self, session: int) -> None:
        """Next frame emits every stripe of one session (viewer join)."""
        self._first[session] = True
        self._static[session] = 0
        self._painted[session] = False

    def reset_session(self, session: int) -> None:
        """Recycle a slot for a new session: fresh damage history AND a
        zeroed prev frame so no stale pixels leak across occupants.

        force_keyframe alone is NOT enough the day an inter profile
        rides the mesh: the previous occupant's
        pixels would persist in the prev/reference planes and in the
        staged pieces an idle tick presents again."""
        self.force_keyframe(session)
        self._staging.reset(session)
        self._prev = jax.device_put(
            jnp.asarray(self._prev).at[session].set(0),
            self._frame_sharding)

    def lower_step(self):
        """The lane's step, lowered for this encoder's geometry and mesh:
        what observability/device_phases.py compiles (from the cache,
        where the lane has served) to name a trace's operations by phase.
        Shapes only: nothing runs, and no state of the encoder is read
        that a dispatch under way could be replacing."""
        frames = jax.ShapeDtypeStruct(
            (self.n_sessions, self.pad_h, self.pad_w, 3), jnp.uint8,
            sharding=self._frame_sharding)
        qsel = jax.ShapeDtypeStruct(
            (self.n_sessions, self.n_stripes), jnp.int32,
            sharding=self._qsel_sharding)
        tables = jax.ShapeDtypeStruct(self._qy.shape, self._qy.dtype)
        return self._step.lower(frames, frames, tables, tables, qsel)

    # -- per-tick ----------------------------------------------------------

    def dispatch(self, frames) -> "_MeshPending":
        """Dispatch one mesh step for all sessions and start the async D2H
        prefix fetch; pair with :meth:`harvest`. Keeping one dispatch in
        flight while harvesting the previous one hides the device
        round-trip exactly like the solo PipelinedJpegEncoder does.

        ``frames``: a length-N sequence of frames (unpadded is fine) or
        None: a slot with None presents its staged frame again, which
        damage gating then suppresses.
        """
        frames_d, reuse_prev, tickets = self._staging.stage(frames)

        paint_candidate = (
            self.use_paint_over_quality
            & (self._static >= self.paint_over_trigger_frames)
            & ~self._painted)
        paint_candidate &= ~reuse_prev[:, None] & ~self._first[:, None]
        first = self._first.copy()
        # a keyframe request on a slot with no frame this tick stays armed
        self._first &= reuse_prev
        # optimistic mark (cleared again by damage at harvest): frames
        # dispatched before this one harvests must not re-trigger the
        # same paint-over
        self._painted |= paint_candidate

        qsel = jax.device_put(
            jnp.asarray(paint_candidate.astype(np.int32)),
            self._qsel_sharding)
        self.last_launch_at = time.monotonic()
        with self.compile_watch.first_use("step"):
            packed, self._prev, yq, cbq, crq, _sb, _total = self._step(
                frames_d, self._prev, self._qy, self._qc, qsel)

        stride = self._mw + 1 + min(self._guess, self._cap)
        prefix = packed[:, :, :stride]
        prefix.copy_to_host_async()
        return _MeshPending(
            prefix=prefix, packed=packed, yq=yq, cbq=cbq, crq=crq,
            paint_candidate=paint_candidate, reuse_prev=reuse_prev,
            first=first, stride=stride, tickets=tickets)

    def fetch_ready(self, p: "_MeshPending") -> bool:
        """True when the eagerly-started prefix fetch has landed — the
        coordinator's in-flight window harvests without blocking then."""
        return bool(p.prefix.is_ready())

    def harvest(self, p: "_MeshPending") -> Tuple[List[List], np.ndarray]:
        """Complete one dispatched step: returns (stripes_per_session,
        session_coded_bytes). Must be called in dispatch order.

        Sets :attr:`last_harvest_stages` — the fetch/concat split of the
        harvest wall with per-stripe-shard fetch attribution — which the
        coordinator folds into each frame's flight-recorder span."""
        from ..encoder.jpeg import StripeOutput, split_meta

        t_h0 = time.perf_counter()
        for t in p.tickets:
            t.release()
        host, per_shard_ms = fetch_sharded_prefix(p.prefix)
        fetch_ms = sum(per_shard_ms.values())
        head = self._mw + 1

        damaged = np.zeros((self.n_sessions, self.n_stripes), bool)
        session_bytes = np.zeros(self.n_sessions, np.int64)
        metas = {}
        max_total = 0
        for n in range(self.n_sessions):
            session_bytes[n] = int(host[n, 0, self._mw])
            for k in range(self.n_stripe_ax):
                nbytes, base, ovf, damage = split_meta(
                    host[n, k, :self._mw], self.s_local)
                metas[(n, k)] = (nbytes, base, ovf)
                total = int(base[-1]) + (int(nbytes[-1]) + 3) // 4
                max_total = max(max_total, total)
                gs = slice(k * self.s_local, (k + 1) * self.s_local)
                damaged[n, gs] = damage > self.damage_threshold

        damaged[p.first] = True
        damaged[p.reuse_prev] = False
        emit = damaged | p.paint_candidate
        is_paint = p.paint_candidate
        self._static = np.where(damaged, 0, self._static + 1)
        # paint marks were set optimistically at dispatch; damage clears
        self._painted = np.where(damaged, False, self._painted)

        # start every miss-refetch before blocking on any (parallel RPCs)
        refetch = {}
        for n in range(self.n_sessions):
            if not emit[n].any():
                continue
            for k in range(self.n_stripe_ax):
                gs0 = k * self.s_local
                if not emit[n, gs0:gs0 + self.s_local].any():
                    continue
                nbytes, base, ovf = metas[(n, k)]
                total = int(base[-1]) + (int(nbytes[-1]) + 3) // 4
                if total > p.stride - head:
                    sl = p.packed[n, k, head:head + total]
                    sl.copy_to_host_async()
                    refetch[(n, k)] = sl

        out: List[List[StripeOutput]] = []
        for n in range(self.n_sessions):
            stripes: List[StripeOutput] = []
            if emit[n].any():
                for k in range(self.n_stripe_ax):
                    gs0 = k * self.s_local
                    if not emit[n, gs0:gs0 + self.s_local].any():
                        continue
                    nbytes, base, ovf = metas[(n, k)]
                    total = int(base[-1]) + (int(nbytes[-1]) + 3) // 4
                    if (n, k) in refetch:
                        t_rf = time.perf_counter()
                        words = np.asarray(refetch[(n, k)])
                        rf_ms = (time.perf_counter() - t_rf) * 1000.0
                        fetch_ms += rf_ms
                        per_shard_ms[k] = per_shard_ms.get(k, 0.0) + rf_ms
                    else:
                        words = host[n, k, head:head + total]
                    stripes += self._shard_stripes(
                        n, k, words, nbytes, base, ovf,
                        emit[n], is_paint[n], p.yq, p.cbq, p.crq)
            out.append(stripes)

        self._guess = max(self._packer.bucket_words(max(max_total * 2, 8192)),
                          self._guess // 2)
        total_ms = (time.perf_counter() - t_h0) * 1000.0
        self.last_harvest_stages = {
            "fetch_ms": fetch_ms,
            "concat_ms": max(0.0, total_ms - fetch_ms),
            "per_shard_fetch_ms": [
                round(per_shard_ms.get(k, 0.0), 3)
                for k in range(self.n_stripe_ax)],
        }
        return out, session_bytes

    def encode_frames(self, frames) -> Tuple[List[List], np.ndarray]:
        """Synchronous dispatch + harvest (tests, simple callers)."""
        return self.harvest(self.dispatch(frames))

    def _shard_stripes(self, n, k, words, nbytes, base, ovf,
                       emit, is_paint, yq, cbq, crq):
        from ..encoder.device_entropy import stuff_bytes, words_to_stripe_bytes
        from ..encoder.jfif import EOI
        from ..encoder.jpeg import StripeOutput, _entropy_encode_420

        raw = words_to_stripe_bytes(words, base, nbytes)
        yrows, crows = self.stripe_h // 8, self.stripe_h // 16
        out = []
        for s in range(self.s_local):
            g = k * self.s_local + s
            if not emit[g]:
                continue
            self.stripes_emitted_total += 1
            if ovf[s]:  # pathological stripe: host-code its coefficients
                self.host_fallback_stripes_total += 1
                scan = _entropy_encode_420(
                    np.asarray(yq[n, g * yrows:(g + 1) * yrows]),
                    np.asarray(cbq[n, g * crows:(g + 1) * crows]),
                    np.asarray(crq[n, g * crows:(g + 1) * crows]))
            else:
                scan = stuff_bytes(raw[s])
            qidx = 1 if is_paint[g] else 0
            out.append(StripeOutput(
                y_start=g * self.stripe_h,
                height=self.stripe_h,
                jpeg=self._headers[qidx] + scan + EOI,
                is_paintover=bool(is_paint[g])))
        return out
