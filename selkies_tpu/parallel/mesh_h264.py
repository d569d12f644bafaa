"""Multi-session H.264 encode over a ("session", "stripe") device mesh.

Round-3 verdict item 3: the mesh path was hard-gated to JPEG while the
config-4 memo sold an H.264-on-mesh projection. This module makes the
H.264 profile a real mesh citizen: sessions are data-parallel on the
"session" axis and each frame's height is sharded on stripe boundaries
on the "stripe" axis — legal because every stripe is an independent
video sequence (its own SPS/PPS/IDR chain and VideoDecoder client-side,
reference selkies-core.js:2925-2968), so motion estimation, the
reconstruction chain and the sparse level pack all stay shard-local.
Only nothing crosses the ICI per tick; the per-stripe CAVLC runs on the
host thread pool exactly as the solo path does (encoder/h264.py).

IDR handling keeps the dispatch SPMD-uniform: a joining session must
not force whole-batch keyframes or a divergent program, so the step
comes in two compiled flavors — a steady-state P-only program, and a
"mixed" program that additionally computes the Intra16x16 encode for
every stripe and SELECTS per stripe between intra and inter outputs.
The host dispatches the mixed program only on ticks where some stripe
needs an IDR (join/reset/entropy-resync); intra levels routinely exceed
int8, which the sparse pack already reports per stripe as overflow, so
the host recovers exact IDR levels from the flat16 rows it keeps on
device — the same fallback the solo encoder uses.
"""

from __future__ import annotations

import functools
import logging
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..encoder import device_cavlc as dcav
from ..encoder import h264_device as dev
from ..encoder.h264 import H264Stripe, encode_picture_nals_np, make_pps, make_sps
from ..encoder.h264 import _entropy_pool
from ..runtime import CompileWatch, pallas_interpret
from .mesh import LaneStaging, fetch_sharded_prefix, plane_sharding

logger = logging.getLogger("selkies_tpu.parallel.h264")

MB = 16


def _merge_idr(enc_p: dev.StripeEncodeOut, enc_i: dev.StripeEncodeOut,
               idr) -> dev.StripeEncodeOut:
    """Per-stripe select between the inter and intra encodes.

    ``idr``: [S] bool/int. Every StripeEncodeOut field carries the stripe
    dim first, so a broadcasted where merges the two programs' outputs.
    """
    def sel(a, b):
        flag = idr.reshape((idr.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(flag != 0, a, b)

    return dev.StripeEncodeOut(*[sel(a, b) for a, b in zip(enc_i, enc_p)])


def make_h264_mesh_step(mesh: Mesh, pad_h: int, pad_w: int, stripe_h: int,
                        *, search: int = dev.SEARCH, cap_frac: int = 4,
                        me: str = "xla", with_idr: bool = False,
                        entropy: str = "sparse",
                        max_stripe_bytes: int = 0):
    """Build the jitted sharded multi-session H.264 step.

    Returns (fn, s_local): fn(frames, prev_y, prev_cb, prev_cr, ref_y,
    ref_cb, ref_cr, paint, idr, qp, paint_qp) →
      (buf [N, stripe_ax, L], flat16 [N, S, words], prev planes, refs).

    frames [N, pad_h, pad_w, 3] uint8, sharded P("session", "stripe");
    plane state shards the same way; paint/idr are [N, S] int32 sharded
    on ("session", "stripe"). ``me`` here defaults to the XLA chunked
    search; :class:`MeshH264Encoder` passes the compiled Pallas kernel
    unless the run asked for interpreter mode.

    ``entropy="device"`` runs CAVLC shard-local (encoder/device_cavlc.py)
    so ``buf`` carries per-stripe bit-exact P-slice payloads instead of
    sparse levels — multi-session steady state then needs ZERO host
    entropy threads; IDR/overflow stripes still recover from flat16.
    """
    n_stripe_ax = mesh.shape["stripe"]
    if pad_h % (n_stripe_ax * stripe_h):
        raise ValueError("pad_h must divide into stripe_ax × stripe_h bands")
    h_local = pad_h // n_stripe_ax
    s_local = h_local // stripe_h

    def one(rgb, py1, pcb1, pcr1, ry1, rcb1, rcr1, paint1, idr1,
            qp, paint_qp):
        y, cb, cr = dev.prepare_planes(rgb, h_local, pad_w)
        enc, damage, update, nry, nrcb, nrcr = dev._frame_p_core(
            y, cb, cr, py1, pcb1, pcr1, ry1, rcb1, rcr1,
            paint1, qp, paint_qp, n_stripes=s_local, sh=stripe_h,
            search=search, me=me)
        if with_idr:
            ys = y.reshape(s_local, stripe_h, pad_w)
            cbs = cb.reshape(s_local, stripe_h // 2, pad_w // 2)
            crs = cr.reshape(s_local, stripe_h // 2, pad_w // 2)
            qps = jnp.broadcast_to(qp, (s_local,))
            enc_i = jax.vmap(dev.encode_stripe_idr)(ys, cbs, crs, qps)
            enc = _merge_idr(enc, enc_i, idr1)
            damage = damage | (idr1 != 0)
            update = update | (idr1 != 0)
            sel = (idr1 != 0)[:, None, None]
            nry = jnp.where(
                sel, enc_i.recon_y, nry.reshape(s_local, stripe_h, pad_w)
            ).reshape(h_local, pad_w)
            nrcb = jnp.where(
                sel, enc_i.recon_cb,
                nrcb.reshape(s_local, stripe_h // 2, pad_w // 2)
            ).reshape(h_local // 2, pad_w // 2)
            nrcr = jnp.where(
                sel, enc_i.recon_cr,
                nrcr.reshape(s_local, stripe_h // 2, pad_w // 2)
            ).reshape(h_local // 2, pad_w // 2)
        flat16, _ = dev._pack_levels(enc, damage, update)
        if entropy == "device":
            # shard-local CAVLC: IDR stripes are masked out of the pack
            # (their merged intra levels are not P-slice material) and
            # recover from flat16 on the host, like overflow
            upd_p = update & (idr1 == 0)
            # ``one`` runs under jax.vmap (local_step): a per-session
            # predicate would turn the pack's tier ``cond`` into a
            # ``select`` and run both output sizes on every frame
            buf = dcav.pack_p_frame(
                enc.mv, enc.luma, enc.chroma_dc, enc.chroma_ac,
                damage, upd_p, mb_w=pad_w // MB, mb_h=stripe_h // MB,
                max_stripe_bytes=max_stripe_bytes, tiered=False)
        else:
            buf = dev._pack_sparse(flat16, damage, update,
                                   cap_frac=cap_frac)
        # the fetched byte-prefix of this content-compacted buffer (head
        # + bitmap + compacted cells) is cut by a program of its own
        # (MeshH264Encoder._fetch_prefix), so this step — minutes of
        # compile with device CAVLC inside — is built once per
        # ``with_idr``, not once per prefix bucket
        return buf, flat16, y, cb, cr, nry, nrcb, nrcr

    def local_step(frames, prev_y, prev_cb, prev_cr,
                   ref_y, ref_cb, ref_cr, paint, idr, qp, paint_qp):
        buf, flat16, y, cb, cr, nry, nrcb, nrcr = jax.vmap(
            one, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None, None)
        )(frames, prev_y, prev_cb, prev_cr, ref_y, ref_cb, ref_cr,
          paint, idr, qp, paint_qp)
        return (buf[:, None, :], flat16, y, cb, cr, nry, nrcb, nrcr)

    plane = P("session", "stripe")
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(plane, plane, plane, plane, plane, plane, plane,
                  plane, plane, P(), P()),
        out_specs=(
            P("session", "stripe", None),   # buf [N, stripe_ax, L]
            P("session", "stripe", None),   # flat16 [N, S, words]
            plane, plane, plane,            # prev planes
            plane, plane, plane,            # refs
        ),
    )
    return jax.jit(sharded, donate_argnums=(1, 2, 3, 4, 5, 6)), s_local


@dataclass
class _MeshH264Pending:
    prefix: Any                   # async-fetching [N, stripe_ax, prefix]
    buf: Any                      # full packed buffer (undershoot refetch)
    flat16: Any                   # [N, S, words] exact levels (device)
    idr: np.ndarray               # [N, S] bool — dispatched as IDR
    paint: np.ndarray             # [N, S] bool
    reuse_prev: np.ndarray        # [N] bool
    qp: np.ndarray                # [N, S] int — qp each stripe coded at
    tickets: list                 # staged pieces this dispatch replaced

    @property
    def step_out(self):
        """The step's own output that the pending keeps (the
        coordinator's ready watch stamps when it is ready)."""
        return self.flat16


class MeshH264Encoder:
    """N solo H264StripeEncoders collapsed into one SPMD program.

    Mirrors MeshStripeEncoder's shape (dispatch/harvest/facade-friendly
    control surface) with the solo H264StripeEncoder's per-stripe host
    state (frame_num, idr_pic_id, damage/paint history, CAVLC pool).
    """

    def __init__(self, mesh: Mesh, n_sessions: int, width: int, height: int,
                 *, stripe_h: int = 64, qp: int = 26, paint_over_qp: int = 18,
                 use_paint_over_quality: bool = True,
                 paint_over_trigger_frames: int = 15,
                 search: int = dev.SEARCH, me: Optional[str] = None,
                 entropy: str = "device") -> None:
        n_sess_ax = mesh.shape["session"]
        self.n_stripe_ax = mesh.shape["stripe"]
        if n_sessions % n_sess_ax:
            raise ValueError(
                f"{n_sessions} sessions not divisible by session axis "
                f"{n_sess_ax}")
        if stripe_h % MB:
            raise ValueError("stripe_h must be a multiple of 16")
        if width % 2 or height % 2:
            raise ValueError("frame dimensions must be even")
        band = self.n_stripe_ax * stripe_h
        self.width, self.height = width, height
        self.pad_w = -(-width // MB) * MB
        self.pad_h = -(-height // band) * band
        self.stripe_h = stripe_h
        self.n_stripes = self.pad_h // stripe_h
        self.n_sessions = n_sessions
        self.mesh = mesh
        self.qp = int(np.clip(qp, 0, 51))
        self.paint_over_qp = int(np.clip(paint_over_qp, 0, 51))
        self.use_paint_over_quality = bool(use_paint_over_quality)
        self.paint_over_trigger = int(paint_over_trigger_frames)
        self.search = search
        if me is None:
            # the served default is the solo encoder's (dev.ME). A run
            # that asked for Pallas interpreter mode (tests/conftest.py)
            # gets the XLA search instead: the interpreter's expansion
            # of the kernel under vmap+shard_map compiles for minutes on
            # the CPU test mesh.
            me = "xla" if pallas_interpret() else dev.ME
        self.me = me

        n = (stripe_h // MB) * (self.pad_w // MB)
        self._shapes = [((n, 2), 2 * n), ((n, 16, 4, 4), 256 * n),
                        ((n, 4, 4), 16 * n), ((n, 2, 2, 2), 8 * n),
                        ((n, 2, 4, 4, 4), 128 * n)]
        self._stripe_words = sum(s for _, s in self._shapes)
        self.s_local = self.pad_h // self.n_stripe_ax // stripe_h
        self._cap_frac = 8
        self._pad_words, self._n_cells, self._cap_cells = \
            dev.sparse_geometry(self._stripe_words, self._cap_frac)
        #: entropy tier (docs/entropy.md): "device" packs CAVLC shard-
        #: local so steady state needs no host entropy threads; "host"
        #: ships sparse levels (the pre-ISSUE-1 path)
        if entropy not in ("device", "host"):
            raise ValueError(f"entropy must be device|host, got {entropy!r}")
        self.entropy = entropy
        if entropy == "device":
            self._cavlc_msb = dcav.default_max_stripe_bytes(
                self.pad_w // MB, stripe_h // MB)
            self._fixed_bytes = dcav.HEAD_BYTES * self.s_local
            self._buf_bytes = self._fixed_bytes \
                + self.s_local * self._cavlc_msb
            self._prefix = self._bucket(
                self._fixed_bytes + self.s_local * (4 << 10))
        else:
            self._cavlc_msb = 0
            self._fixed_bytes = 4 * self.s_local \
                + self.s_local * (self._n_cells // 8)
            self._buf_bytes = self._fixed_bytes \
                + self._cap_cells * self.s_local * dev.CELL
            #: per-(session, shard) fetch prefix over the content-
            #: compacted buffer (same layout as the solo encoder); an
            #: undershoot falls back to flat16 rows and grows the bucket
            self._prefix = self._bucket(
                self._fixed_bytes + self.s_local * (8 << 10))

        self._steps: Dict[bool, Any] = {}
        #: first-use compile signal of this lane's programs (the sessions'
        #: capture loops read it through their coordinator facade)
        self.compile_watch = CompileWatch()

        plane = plane_sharding(mesh)
        self._plane_sharding = plane
        self._frame_sharding = plane
        z = functools.partial(jax.device_put)
        self._prev_y = z(jnp.zeros((n_sessions, self.pad_h, self.pad_w),
                                   jnp.uint8), plane)
        self._prev_cb = z(jnp.zeros(
            (n_sessions, self.pad_h // 2, self.pad_w // 2), jnp.uint8), plane)
        self._prev_cr = z(jnp.zeros_like(self._prev_cb), plane)
        self._ref_y = z(jnp.zeros_like(self._prev_y), plane)
        self._ref_cb = z(jnp.zeros_like(self._prev_cb), plane)
        self._ref_cr = z(jnp.zeros_like(self._prev_cr), plane)

        S = self.n_stripes
        self._need_idr = np.ones((n_sessions, S), bool)
        self._frame_num = np.zeros((n_sessions, S), np.int64)
        self._idr_pic_id = np.zeros((n_sessions, S), np.int64)
        self._static = np.zeros((n_sessions, S), np.int64)
        self._painted = np.zeros((n_sessions, S), bool)
        self._staging = LaneStaging(
            plane, n_sessions, self.pad_h, self.pad_w)
        self._sps_pps: Dict[int, bytes] = {}
        #: fetch/concat split of the latest harvest wall with per-shard
        #: fetch attribution (the coordinator's flight-recorder feed)
        self.last_harvest_stages: Optional[dict] = None
        #: when the latest dispatch launched its step (``time.monotonic``)
        self.last_launch_at: Optional[float] = None
        #: stripes recovered through the flat16 host coder (overflow /
        #: prefix undershoot; IDR resyncs excluded) — observability
        self.host_fallback_stripes_total = 0
        #: sessions whose frame was withheld by whole-frame containment:
        #: in-flight successor ticks predicted off the withheld frame's
        #: references are withheld too, until the full-IDR resync tick
        self._withheld = np.zeros(n_sessions, bool)
        #: session indices whose stripe jobs FAILED in the latest
        #: harvest (not containment carry-over) — the coordinator charges
        #: these slots' health so repeated encoder-internal failures walk
        #: the slot into quarantine + migration like injected faults
        self.last_failed_sessions: frozenset = frozenset()

    @property
    def n_shards(self) -> int:
        """Chips one frame's stripe bands are sharded across (the SFE
        stripe axis; 1 = whole frame on one chip)."""
        return self.n_stripe_ax

    # -- control -----------------------------------------------------------

    def force_keyframe(self, session: int) -> None:
        self._need_idr[session] = True
        self._static[session] = 0
        self._painted[session] = False

    def reset_session(self, session: int) -> None:
        """Recycle a slot: fresh history AND zeroed planes so no pixels
        leak across occupants (the inter refs would otherwise carry
        them — the known hazard for mesh inter)."""
        self.force_keyframe(session)
        self._frame_num[session] = 0
        self._staging.reset(session)
        self._withheld[session] = False
        put = functools.partial(jax.device_put)
        for name in ("_prev_y", "_prev_cb", "_prev_cr",
                     "_ref_y", "_ref_cb", "_ref_cr"):
            arr = getattr(self, name)
            setattr(self, name, put(
                jnp.asarray(arr).at[session].set(0), self._plane_sharding))

    # -- helpers -----------------------------------------------------------

    def _bucket(self, nbytes: int) -> int:
        """Fetch-prefix bound quantized PER STRIPE: the payload share
        above the fixed head rounds up to s_local × a power-of-two
        per-stripe budget (≥1 KB). The set of compiled prefix shapes is
        then a function of per-stripe content alone — growing the SFE
        shard count shrinks s_local instead of multiplying distinct
        executables, and every lane of a bucket walks the same ladder
        (ISSUE 15)."""
        per = 1 << 10
        need = max(0, int(nbytes) - self._fixed_bytes)
        while per * self.s_local < need:
            per <<= 1
        return min(self._fixed_bytes + per * self.s_local, self._buf_bytes)

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("prefix",))
    def _fetch_prefix(buf, *, prefix: int):
        """[N, stripe_ax, L] → [N, stripe_ax, prefix], shard-local."""
        return buf[:, :, :prefix]

    def _step_for(self, with_idr: bool):
        key = with_idr
        fn = self._steps.get(key)
        if fn is None:
            fn, _ = make_h264_mesh_step(
                self.mesh, self.pad_h, self.pad_w, self.stripe_h,
                search=self.search, me=self.me, with_idr=with_idr,
                cap_frac=self._cap_frac,
                entropy="device" if self.entropy == "device" else "sparse",
                max_stripe_bytes=self._cavlc_msb)
            self._steps[key] = fn
        return fn

    def _sps_pps_for(self, h: int) -> bytes:
        if h not in self._sps_pps:
            self._sps_pps[h] = (
                make_sps(self.width, h, coded_height=self.stripe_h)
                + make_pps())
        return self._sps_pps[h]

    # -- per-tick ----------------------------------------------------------

    def dispatch(self, frames) -> _MeshH264Pending:
        """One sharded step for all sessions; pair with :meth:`harvest`.

        ``frames``: a length-N sequence of frames or None (a None slot
        presents its staged frame again; damage gating suppresses it).
        """
        frames_d, reuse_prev, tickets = self._staging.stage(frames)

        # a withheld session's client never received the content already
        # staged for it (whole-frame containment dropped it), so
        # an idle re-present is NOT a no-op for it: run the armed
        # full-frame IDR resync now instead of waiting for fresh damage
        reuse_prev &= ~self._withheld

        idr = self._need_idr & ~reuse_prev[:, None]
        paint = (self.use_paint_over_quality
                 & (self._static >= self.paint_over_trigger)
                 & ~self._painted & ~idr)
        paint &= ~reuse_prev[:, None]
        # optimistic arming (cleared by damage at harvest) — in-flight
        # ticks must not re-trigger
        self._painted |= paint
        self._need_idr &= reuse_prev[:, None]

        qp_arr = np.where(paint, self.paint_over_qp, self.qp)
        with_idr = bool(idr.any())
        fn = self._step_for(with_idr)
        paint_d = jax.device_put(jnp.asarray(paint.astype(np.int32)),
                                 self._plane_sharding)
        idr_d = jax.device_put(jnp.asarray(idr.astype(np.int32)),
                               self._plane_sharding)
        self.last_launch_at = time.monotonic()
        with self.compile_watch.first_use((with_idr, self._prefix)):
            (buf, flat16, self._prev_y, self._prev_cb, self._prev_cr,
             self._ref_y, self._ref_cb, self._ref_cr) = fn(
                frames_d, self._prev_y, self._prev_cb, self._prev_cr,
                self._ref_y, self._ref_cb, self._ref_cr,
                paint_d, idr_d, jnp.int32(self.qp),
                jnp.int32(self.paint_over_qp))
            prefix = self._fetch_prefix(buf, prefix=self._prefix)
        prefix.copy_to_host_async()
        return _MeshH264Pending(
            prefix=prefix, buf=None, flat16=flat16, idr=idr,
            paint=paint, reuse_prev=reuse_prev, qp=qp_arr, tickets=tickets)

    def fetch_ready(self, p: _MeshH264Pending) -> bool:
        """True when the eagerly-started prefix fetch has landed — the
        coordinator's in-flight window harvests without blocking then."""
        return bool(p.prefix.is_ready())

    def harvest(self, p: _MeshH264Pending
                ) -> Tuple[List[List[H264Stripe]], np.ndarray]:
        """Entropy-code one dispatched tick. Returns (stripes per session,
        coded bytes per session). Must be called in dispatch order.

        Sets :attr:`last_harvest_stages` — the fetch/concat split of the
        harvest wall with per-stripe-shard fetch attribution — which the
        coordinator folds into each frame's flight-recorder span."""
        t_h0 = time.perf_counter()
        for t in p.tickets:
            t.release()
        # [N, stripe_ax, prefix]: materialized shard by shard so the D2H
        # wall is attributable per SFE stripe shard
        host, per_shard_ms = fetch_sharded_prefix(p.prefix)
        fetch_ms = sum(per_shard_ms.values())
        S, sl = self.n_stripes, self.s_local
        CELL = dev.CELL
        cavlc = self.entropy == "device"

        damage = np.zeros((self.n_sessions, S), bool)
        ovf = np.zeros((self.n_sessions, S), bool)
        counts = np.zeros((self.n_sessions, S), np.int64)
        t_bits = np.zeros((self.n_sessions, S), np.int64)
        base_words = np.zeros((self.n_sessions, S), np.int64)
        for k in range(self.n_stripe_ax):
            gs = slice(k * sl, (k + 1) * sl)
            if cavlc:
                for n in range(self.n_sessions):
                    tb, bw, dmg, ov = dcav.parse_cavlc_head(host[n, k], sl)
                    t_bits[n, gs] = tb
                    base_words[n, gs] = bw
                    damage[n, gs] = dmg
                    ovf[n, gs] = ov
            else:
                head = host[:, k, :4 * sl].reshape(self.n_sessions, sl, 4)
                counts[:, gs] = head[:, :, 0].astype(np.int64) \
                    + (head[:, :, 1].astype(np.int64) << 8)
                damage[:, gs] = head[:, :, 2] != 0
                ovf[:, gs] = head[:, :, 3] != 0

        damage[p.reuse_prev] = False
        emit = damage | p.paint | p.idr
        self._static = np.where(damage, 0, self._static + 1)
        self._painted = np.where(damage, False, self._painted)

        # per shard: device-CAVLC payload words (bit-exact slice bits) or
        # content-compacted sparse cells, back to back after the fixed
        # head. An undershoot (content past the fetched prefix), a
        # per-stripe overflow, or an IDR stripe (its merged intra levels
        # are not P-slice material; |level| > 127 routinely in sparse
        # mode) recovers from the exact flat16 rows; reads start before
        # any blocks.
        used = np.minimum(counts, self._cap_cells) * CELL
        grew = False
        for n in range(self.n_sessions):
            for k in range(self.n_stripe_ax):
                gs = slice(k * sl, (k + 1) * sl)
                if not emit[n, gs].any():
                    continue
                if cavlc:
                    # clip to the device's per-stripe word capacity: an
                    # overflow stripe records unclipped t_bits but
                    # compacts at most V words, and overshooting here
                    # would pin the grow-only prefix at its cap
                    wc = np.minimum((t_bits[n, gs] + 31) // 32,
                                    self._cavlc_msb // 4)
                    needed = self._fixed_bytes \
                        + 4 * int(base_words[n, gs][-1] + wc[-1])
                else:
                    needed = self._fixed_bytes + int(used[n, gs].sum())
                if needed > host.shape[-1]:
                    ovf[n, gs] |= emit[n, gs]
                    if not grew:
                        self._prefix = self._bucket(needed + needed // 2)
                        grew = True
        host_path = ovf | (cavlc & p.idr)
        # overflow / prefix-undershoot stripes recovered through the
        # flat16 host coder (IDR resyncs are by-construction, not faults)
        self.host_fallback_stripes_total += int((ovf & emit).sum())
        exact: Dict[Tuple[int, int], Any] = {}
        for n in range(self.n_sessions):
            for g in range(S):
                if emit[n, g] and host_path[n, g]:
                    row = p.flat16[n, g]
                    row.copy_to_host_async()
                    exact[(n, g)] = row

        mb_w = self.pad_w // MB
        mb_h = self.stripe_h // MB
        jobs = []
        for n in range(self.n_sessions):
            for g in range(S):
                if not emit[n, g]:
                    continue
                k, s = g // sl, g % sl
                if cavlc and not host_path[n, g]:
                    # device already entropy-coded the stripe; the job is
                    # slice-header glue only
                    pb, nbits = dcav.payload_slice(
                        host[n, k], sl, base_words[n, k * sl:(k + 1) * sl],
                        t_bits[n, k * sl:(k + 1) * sl], s)
                    jobs.append((n, g, False, int(p.qp[n, g]),
                                 ("bits", pb, nbits)))
                    continue
                if host_path[n, g]:
                    t_rf = time.perf_counter()
                    row = np.asarray(exact[(n, g)]).astype(np.int32)
                    rf_ms = (time.perf_counter() - t_rf) * 1000.0
                    fetch_ms += rf_ms
                    per_shard_ms[k] = per_shard_ms.get(k, 0.0) + rf_ms
                else:
                    bitmap = host[n, k, 4 * sl:self._fixed_bytes] \
                        .reshape(sl, self._n_cells // 8)[s]
                    bits = np.unpackbits(bitmap, bitorder="little")
                    idx = np.flatnonzero(bits[:self._n_cells])
                    gs0 = k * sl
                    start = self._fixed_bytes \
                        + int(used[n, gs0:g].sum())
                    cells = host[n, k, start:start + used[n, g]] \
                        .view(np.int8).astype(np.int32) \
                        .reshape(-1, CELL)
                    dense = np.zeros(self._pad_words, np.int32)
                    dense.reshape(-1, CELL)[idx[:len(cells)]] = cells
                    row = dense[:self._stripe_words]
                parts, pos = [], 0
                for shape, size in self._shapes:
                    parts.append(row[pos:pos + size].reshape(shape))
                    pos += size
                jobs.append((n, g, bool(p.idr[n, g]), int(p.qp[n, g]),
                             ("levels", parts)))

        def run_one(job):
            n, g, is_key, qp, work = job
            if work[0] == "bits":
                _, pb, nbits = work
                return dcav.assemble_p_slice(
                    pb, nbits, qp, int(self._frame_num[n, g]))
            mv, luma, luma_dc, chroma_dc, chroma_ac = work[1]
            if is_key:
                return encode_picture_nals_np(
                    mv, luma, luma_dc, chroma_dc, chroma_ac,
                    is_idr=True, mb_w=mb_w, mb_h=mb_h, qp=qp, frame_num=0,
                    idr_pic_id=int(self._idr_pic_id[n, g]))
            return encode_picture_nals_np(
                mv, luma, luma_dc, chroma_dc, chroma_ac,
                is_idr=False, mb_w=mb_w, mb_h=mb_h, qp=qp,
                frame_num=int(self._frame_num[n, g]))

        def safe_one(job):
            try:
                return run_one(job)
            except Exception as exc:
                return exc

        payloads = list(_entropy_pool().map(safe_one, jobs)) \
            if len(jobs) > 1 else [safe_one(j) for j in jobs]

        # whole-frame containment (ISSUE 15): a failed stripe job must
        # never tear the access unit. Sibling stripes of the same frame
        # are withheld WITH it — their device reference planes already
        # advanced, so emitting them while skipping the failed one would
        # silently drift every later P frame — and the whole session
        # resyncs with a full IDR on its next tick instead. Successor
        # ticks already in flight when the failure surfaces predicted
        # off the withheld references too, so the session STAYS withheld
        # until the tick that was dispatched as a full-frame IDR.
        prev_withheld = self._withheld.copy()
        failed_sessions = set()
        for job, payload in zip(jobs, payloads):
            if isinstance(payload, Exception):
                n, g = job[0], job[1]
                logger.error("mesh CAVLC failed for session %d stripe %d; "
                             "frame withheld, forcing whole-frame IDR "
                             "resync", n, g, exc_info=payload)
                failed_sessions.add(n)
        for n in failed_sessions:
            self._need_idr[n] = True
            self._withheld[n] = True
        self.last_failed_sessions = frozenset(failed_sessions)
        # the resync tick (dispatched all-IDR) releases the withhold —
        # unless it failed too, in which case the next one re-arms
        release = prev_withheld & p.idr.all(axis=1)
        for n in failed_sessions:
            release[n] = False
        self._withheld &= ~release

        out: List[List[H264Stripe]] = [[] for _ in range(self.n_sessions)]
        coded = np.zeros(self.n_sessions, np.int64)
        for job, payload in zip(jobs, payloads):
            n, g, is_key, qp, _ = job
            if n in failed_sessions or (prev_withheld[n] and not release[n]):
                continue
            y0 = g * self.stripe_h
            h = min(self.stripe_h, self.height - y0)
            if h <= 0:
                continue
            if is_key:
                payload = self._sps_pps_for(h) + payload
                self._frame_num[n, g] = 1
                self._idr_pic_id[n, g] = (self._idr_pic_id[n, g] + 1) % 16
                self._need_idr[n, g] = False
                self._static[n, g] = 0
                self._painted[n, g] = False
            else:
                self._frame_num[n, g] = (self._frame_num[n, g] + 1) % 16
            coded[n] += len(payload)
            out[n].append(H264Stripe(
                y_start=y0, width=self.width, height=h,
                annexb=payload, is_key=is_key))
        total_ms = (time.perf_counter() - t_h0) * 1000.0
        self.last_harvest_stages = {
            "fetch_ms": fetch_ms,
            "concat_ms": max(0.0, total_ms - fetch_ms),
            "per_shard_fetch_ms": [
                round(per_shard_ms.get(k, 0.0), 3)
                for k in range(self.n_stripe_ax)],
        }
        return out, coded

    def encode_frames(self, frames) -> Tuple[List[List[H264Stripe]],
                                             np.ndarray]:
        """Synchronous dispatch + harvest (tests, simple callers)."""
        return self.harvest(self.dispatch(frames))
