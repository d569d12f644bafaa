"""tpuenc v1: H.264 Constrained-Baseline striped encoder.

Capability parity with the reference's ``x264enc-striped`` / ``x264enc``
pixelflux modes (CaptureSettings output_mode=1, selkies.py:2919-2963;
client decoders selkies-core.js:2925-2968): each horizontal stripe is an
independent H.264 video sequence with its own SPS/PPS/IDR chain, so the
client can run one WebCodecs ``VideoDecoder`` per stripe and only damaged
stripes are ever encoded or shipped.

Split of work (TPU-first, SURVEY.md §7 step 6):
  * device (encoder/h264_device.py): color/4:2:0, exhaustive ME, transforms,
    quant, and the exact decoder-arithmetic reconstruction loop;
  * host (native/cavlc.cpp): CAVLC entropy coding + NAL packaging of the
    device's level arrays;
  * here: stripe/GOP orchestration, damage gating, paint-over escalation
    (low-QP P frames — no IDR needed, unlike the reference's burst
    keyframes), SPS/PPS generation, reference-plane state.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..native import cavlc_lib
from ..runtime import CompileWatch
from . import device_cavlc as dcav
from . import h264_device as dev

logger = logging.getLogger("selkies_tpu.encoder.h264")

MB = 16

_POOL = None


def _entropy_pool():
    """Shared thread pool for per-stripe CAVLC (the C coder releases the
    GIL, so stripes of one frame entropy-code concurrently)."""
    global _POOL
    if _POOL is None:
        import concurrent.futures
        import os
        _POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 4),
            thread_name_prefix="cavlc")
    return _POOL


# ---------------------------------------------------------------------------
# SPS / PPS


class _BitWriter:
    def __init__(self) -> None:
        self.bits: List[int] = []

    def u(self, value: int, n: int) -> None:
        for i in range(n - 1, -1, -1):
            self.bits.append((value >> i) & 1)

    def ue(self, v: int) -> None:
        vp1 = v + 1
        n = vp1.bit_length() - 1
        self.u(0, n)
        self.u(vp1, n + 1)

    def se(self, v: int) -> None:
        self.ue(-2 * v if v <= 0 else 2 * v - 1)

    def rbsp(self) -> bytes:
        bits = self.bits + [1]
        while len(bits) % 8:
            bits.append(0)
        out = bytearray()
        for i in range(0, len(bits), 8):
            b = 0
            for bit in bits[i:i + 8]:
                b = (b << 1) | bit
            out.append(b)
        # emulation prevention
        esc = bytearray()
        zeros = 0
        for b in out:
            if zeros >= 2 and b <= 3:
                esc.append(3)
                zeros = 0
            esc.append(b)
            zeros = zeros + 1 if b == 0 else 0
        return bytes(esc)


def _nal(nal_type: int, rbsp: bytes, ref_idc: int = 3) -> bytes:
    return b"\x00\x00\x00\x01" + bytes(((ref_idc << 5) | nal_type,)) + rbsp


def make_sps(width: int, height: int, *, coded_height: Optional[int] = None,
             level_idc: int = 40, full_range: bool = True) -> bytes:
    """Constrained-Baseline SPS for a (possibly cropped) 4:2:0 frame.

    ``coded_height`` (a MB multiple ≥ height) must match the rows the
    slices actually code — the uniform stripe grid encodes full
    ``stripe_h`` rows even for a partial last stripe, and an SPS declaring
    fewer MB rows than the slice codes is an invalid bitstream
    (libavcodec: "first_mb_in_slice overflow")."""
    mb_w = (width + 15) // 16
    mb_h = ((coded_height or height) + 15) // 16
    crop_r = (mb_w * 16 - width) // 2
    crop_b = (mb_h * 16 - height) // 2
    bw = _BitWriter()
    bw.u(66, 8)          # profile_idc: Baseline
    bw.u(0b11000000, 8)  # constraint_set0+1 (constrained baseline)
    bw.u(level_idc, 8)
    bw.ue(0)             # sps id
    bw.ue(0)             # log2_max_frame_num_minus4 → 4-bit frame_num
    bw.ue(2)             # pic_order_cnt_type
    bw.ue(1)             # max_num_ref_frames
    bw.u(0, 1)           # gaps_in_frame_num_value_allowed
    bw.ue(mb_w - 1)
    bw.ue(mb_h - 1)
    bw.u(1, 1)           # frame_mbs_only
    bw.u(1, 1)           # direct_8x8_inference
    if crop_r or crop_b:
        bw.u(1, 1)
        bw.ue(0)
        bw.ue(crop_r)
        bw.ue(0)
        bw.ue(crop_b)
    else:
        bw.u(0, 1)
    # VUI: declare BT.601 + range so the browser matches our color matrix
    bw.u(1, 1)           # vui_parameters_present
    bw.u(0, 1)           # aspect_ratio_info_present
    bw.u(0, 1)           # overscan_info_present
    bw.u(1, 1)           # video_signal_type_present
    bw.u(5, 3)           # video_format: unspecified
    bw.u(1 if full_range else 0, 1)
    bw.u(1, 1)           # colour_description_present
    bw.u(6, 8)           # primaries: SMPTE 170M
    bw.u(6, 8)           # transfer
    bw.u(6, 8)           # matrix: BT.601
    bw.u(0, 1)           # chroma_loc_info_present
    bw.u(0, 1)           # timing_info_present
    bw.u(0, 1)           # nal_hrd
    bw.u(0, 1)           # vcl_hrd
    bw.u(0, 1)           # pic_struct_present
    bw.u(0, 1)           # bitstream_restriction
    return _nal(7, bw.rbsp())


def make_pps() -> bytes:
    bw = _BitWriter()
    bw.ue(0)     # pps id
    bw.ue(0)     # sps id
    bw.u(0, 1)   # entropy_coding_mode: CAVLC
    bw.u(0, 1)   # bottom_field_pic_order_in_frame_present
    bw.ue(0)     # num_slice_groups_minus1
    bw.ue(0)     # num_ref_idx_l0_default_active_minus1
    bw.ue(0)     # num_ref_idx_l1_default_active_minus1
    bw.u(0, 1)   # weighted_pred
    bw.u(0, 2)   # weighted_bipred_idc
    bw.se(0)     # pic_init_qp_minus26 (slice writer assumes 26)
    bw.se(0)     # pic_init_qs_minus26
    bw.se(0)     # chroma_qp_index_offset (qpc_for assumes 0)
    bw.u(1, 1)   # deblocking_filter_control_present (slices disable it)
    bw.u(0, 1)   # constrained_intra_pred
    bw.u(0, 1)   # redundant_pic_cnt_present
    return _nal(8, bw.rbsp())


# ---------------------------------------------------------------------------
# host entropy dispatch


def encode_picture_nals(out: dev.StripeEncodeOut, *, is_idr: bool,
                        mb_w: int, mb_h: int, qp: int, frame_num: int,
                        idr_pic_id: int = 0) -> bytes:
    """Run the native CAVLC coder over one stripe's device outputs."""
    return encode_picture_nals_np(
        np.ascontiguousarray(np.asarray(out.mv), np.int32),
        np.ascontiguousarray(np.asarray(out.luma), np.int32),
        np.ascontiguousarray(np.asarray(out.luma_dc), np.int32),
        np.ascontiguousarray(np.asarray(out.chroma_dc), np.int32),
        np.ascontiguousarray(np.asarray(out.chroma_ac), np.int32),
        is_idr=is_idr, mb_w=mb_w, mb_h=mb_h, qp=qp,
        frame_num=frame_num, idr_pic_id=idr_pic_id)


def encode_picture_nals_np(mv, luma, luma_dc, chroma_dc, chroma_ac, *,
                           is_idr: bool, mb_w: int, mb_h: int, qp: int,
                           frame_num: int, idr_pic_id: int = 0,
                           deblock: bool = False) -> bytes:
    """CAVLC over host-resident coefficient arrays (already fetched).

    ``deblock`` writes disable_deblocking_filter_idc=0 into P slice
    headers (decoder runs the in-loop filter). STAGED, default off: the
    encoder's device reconstruction does not yet mirror the filter —
    the spec's per-macroblock filtering order carries a 3×3-corner
    sequential dependency that defeats the straightforward
    all-vertical-then-all-horizontal vectorization, so an exact
    TPU-shaped formulation (wavefront or corner-fixup) is round-5 work
    (BASELINE.md "Quality vs x264" decision 2). Until then, enabling
    this flag drifts encoder refs from decoder output.
    """
    lib = cavlc_lib()
    if lib is None:
        raise RuntimeError("native CAVLC coder unavailable")
    cap = 1 << 22
    buf = np.empty(cap, np.uint8)
    n = lib.h264_encode_picture(
        1 if is_idr else 0, mb_w, mb_h, qp, frame_num & 0xF, idr_pic_id,
        np.ascontiguousarray(mv, np.int32),
        np.ascontiguousarray(luma, np.int32),
        np.ascontiguousarray(luma_dc, np.int32),
        np.ascontiguousarray(chroma_dc, np.int32),
        np.ascontiguousarray(chroma_ac, np.int32),
        buf, cap, 1 if deblock else 0)
    if n < 0:
        raise RuntimeError("CAVLC output exceeded capacity")
    return bytes(buf[:n])


# ---------------------------------------------------------------------------
# stripe orchestration


@dataclass
class H264Stripe:
    y_start: int
    width: int          # coded (cropped) width
    height: int         # coded (cropped) height of this stripe
    annexb: bytes
    is_key: bool


@dataclass
class _StripeState:
    y0: int             # luma row offset (unpadded coordinates)
    h: int              # unpadded stripe height
    pad_h: int          # MB-aligned height
    frame_num: int = 0
    idr_pic_id: int = 0
    need_idr: bool = True
    static_frames: int = 0
    painted_over: bool = False


class H264StripeEncoder:
    """Striped (or full-frame) H.264 encoder with damage gating.

    ``fullframe=True`` reproduces the reference's ``x264enc`` mode: one
    stripe covering the whole frame. The server ships it as 0x00
    full-frame packets (the wire routing lives in the encoder adapter's
    ``wire_fullframe`` flag, not here — reference h264_fullframe,
    selkies.py:2937, wire demux selkies-core.js 0x00 path).
    """

    #: how many harvested frames the fetch-prefix guess remembers. A
    #: frame's bits follow how far the content moved since the frame
    #: before (a scroll past the ±search range codes residual for every
    #: block), so sizes alternate, and a guess from the last frame alone
    #: undershot one frame in four of a 1080p scroll; each undershoot's
    #: re-read runs the pipe dry, and the burst that refills it makes the
    #: next big frame (PERF.md, PR 29). Two seconds of frames: a prefix
    #: too long costs microseconds, one too short a hundred milliseconds.
    PREFIX_MEMORY_FRAMES = 64

    def __init__(self, width: int, height: int, *, stripe_height: int = 64,
                 qp: int = 26, paint_over_qp: int = 18,
                 paint_over_trigger_frames: int = 15,
                 search: int = 12, fullframe: bool = False,
                 cap_frac: int = 8,
                 entropy: str = "device") -> None:
        if width % 2 or height % 2:
            raise ValueError("frame dimensions must be even")
        if stripe_height % MB:
            raise ValueError("stripe_height must be a multiple of 16")
        self.width = width
        self.height = height
        self.qp = int(np.clip(qp, 0, 51))
        self.paint_over_qp = int(np.clip(paint_over_qp, 0, 51))
        self.paint_over_trigger = paint_over_trigger_frames
        self.search = search
        self.pad_w = (width + MB - 1) // MB * MB
        sh = height if fullframe else stripe_height
        sh = (sh + MB - 1) // MB * MB
        self.stripe_h = sh
        self.stripes: List[_StripeState] = []
        y = 0
        while y < height:
            h = min(sh, height - y)
            self.stripes.append(_StripeState(y0=y, h=h, pad_h=sh))
            y += h
        #: uniform stripe grid: total padded height is S × stripe_h so the
        #: whole frame encodes as one vmapped device dispatch
        self.n_stripes = len(self.stripes)
        self.pad_h = self.n_stripes * sh
        self._sps_pps: Dict[int, bytes] = {}
        #: first-use compile signal for this encoder's programs (the
        #: capture loop's wedge detector reads it through the wrappers)
        self.compile_watch = CompileWatch()

        # device state chains (donated through each dispatch)
        self._prev_y = jnp.zeros((self.pad_h, self.pad_w), jnp.uint8)
        self._prev_cb = jnp.zeros((self.pad_h // 2, self.pad_w // 2),
                                  jnp.uint8)
        self._prev_cr = jnp.zeros_like(self._prev_cb)
        self._ref_y = jnp.zeros_like(self._prev_y)
        self._ref_cb = jnp.zeros_like(self._prev_cb)
        self._ref_cr = jnp.zeros_like(self._prev_cr)

        n = (sh // MB) * (self.pad_w // MB)
        self._shapes = [((n, 2), 2 * n), ((n, 16, 4, 4), 256 * n),
                        ((n, 4, 4), 16 * n), ((n, 2, 2, 2), 8 * n),
                        ((n, 2, 4, 4, 4), 128 * n)]
        self._stripe_words = sum(s for _, s in self._shapes)

        # block-sparse transfer geometry (dev._pack_sparse): fixed head +
        # bitmap prefix, then content-sized compacted cells. The fetch
        # prefix adapts to the previous frame's content (pipeline.py's
        # bucket strategy) so a mostly-static desktop ships a few KB.
        # cap_frac=8 was chosen on a remote-attached development
        # device, where it beat 4 and 32 end to end; the choice has NOT
        # been re-measured on a directly attached chip (ROADMAP), and
        # changing it is a perf change.
        self._cap_frac = cap_frac
        self._pad_words, self._n_cells, self._cap_cells = \
            dev.sparse_geometry(self._stripe_words, cap_frac)

        #: entropy tier for P frames (docs/entropy.md): "device" packs
        #: bit-exact CAVLC payloads on TPU (encoder/device_cavlc.py) so
        #: the fetch is the bitstream itself (36.5 kB a frame of a 1080p
        #: scroll: ledger, PR 29) and steady state needs no host entropy
        #: threads; "host", the degradation ladder's rung, ships the
        #: block-sparse levels and runs native CAVLC.  IDR and overflow
        #: stripes use the host path in both modes.
        if entropy not in ("device", "host"):
            raise ValueError(f"entropy must be device|host, got {entropy!r}")
        self.entropy = entropy
        #: fetch prefix: how much of the packed buffer a P frame brings to
        #: the host in the transfer started at dispatch. harvest keeps
        #: _sparse_guess at the bucket of 1.5x the largest needed bytes
        #: among the last PREFIX_MEMORY_FRAMES frames (_recent_needed),
        #: and _choose_prefix turns it into a prefix. Where the
        #: slice is a program of its own (dispatch on the device tier:
        #: dev.fetch_prefix) every bucket from _prefix_small to _buf_bytes
        #: is a tier, so the prefix holds the whole frame and harvest
        #: re-reads nothing. Where the slice is compiled into the step
        #: (the host-entropy step) a new size would recompile it, so
        #: that rung keeps two stable sizes: _prefix_small for
        #: static/quiet content (the worst-case head every frame would
        #: cost 10-30x the D2H bytes of an idle desktop) and
        #: _host_step_prefix for busy content (a frame past it is
        #: re-read like any other undershoot).
        if entropy == "device":
            self._cavlc_msb = dcav.default_max_stripe_bytes(
                self.pad_w // MB, sh // MB)
            self._fixed_bytes = dcav.HEAD_BYTES * self.n_stripes
            self._buf_bytes = self._fixed_bytes \
                + self.n_stripes * self._cavlc_msb
            # CAVLC payloads run ~4-6x smaller than the sparse cells
            self._sparse_guess = self._bucket(self._fixed_bytes + (16 << 10))
        else:
            self._cavlc_msb = 0
            self._fixed_bytes = 4 * self.n_stripes \
                + self.n_stripes * (self._n_cells // 8)
            self._buf_bytes = self._fixed_bytes \
                + self.n_stripes * self._cap_cells * dev.CELL
            self._sparse_guess = self._bucket(
                self._fixed_bytes + (64 << 10))
            # worst-case full-damage content at streaming QPs runs
            # ~1/20 of the pixel count in sparse cells (scroll source)
            self._host_step_prefix = self._bucket(
                self._fixed_bytes
                + max(96 << 10, self.pad_h * self.pad_w // 20))
        self._prefix_small = self._bucket(self._fixed_bytes + 4096)
        self._recent_needed: deque = deque(maxlen=self.PREFIX_MEMORY_FRAMES)

        #: observability (ISSUE 1 satellite): host entropy wall time and
        #: D2H re-read bytes, accumulated per harvested frame so the
        #: pipeline / bench can report per-frame gauges
        self.host_entropy_ms_total = 0.0
        self.d2h_refetch_bytes_total = 0
        #: stripes whose entropy coding failed and forced an IDR resync —
        #: repeated growth here is the signal the degradation ladder acts
        #: on (ISSUE 2: rung device -> host -> jpeg)
        self.entropy_errors_total = 0
        #: P frames harvested from the device-CAVLC pack, and those of
        #: them that ran the lowest rung of its output stage: the index
        #: the device branched on, read from the same t_bits. And what the
        #: stage paid for against what it carried: output words of the
        #: rungs the frames took, and payload words that landed in them
        self.cavlc_frames_total = 0
        self.cavlc_low_tier_frames_total = 0
        self.cavlc_tier_words_total = 0
        self.cavlc_payload_words_total = 0
        #: and those of them whose fetched prefix held the whole payload,
        #: so that harvest made no undershoot re-read
        self.prefix_hit_frames_total = 0

    def _choose_prefix(self, every_bucket: bool = False) -> int:
        """The next P frame's fetch prefix, from the estimate harvest
        maintains (_sparse_guess: the bucket of 1.5x the recent frames'
        largest needed bytes). ``every_bucket``: the slice is a program of its
        own, so the guess itself is the prefix; else one of the two
        sizes compiled into the step."""
        if self._sparse_guess <= self._prefix_small:
            return self._prefix_small
        return self._sparse_guess if every_bucket else self._host_step_prefix

    def _prefix_tiers(self) -> List[int]:
        """Every size ``_choose_prefix(every_bucket=True)`` can return."""
        tiers = [self._prefix_small]
        while tiers[-1] < self._buf_bytes:
            tiers.append(self._bucket(2 * tiers[-1]))
        return tiers

    def _bucket(self, nbytes: int) -> int:
        """Power-of-two fetch prefix (bounds distinct slice executables)."""
        n = 4096
        while n < nbytes:
            n <<= 1
        return min(n, self._buf_bytes)

    # -- helpers -----------------------------------------------------------

    def _sps_pps_for(self, st: _StripeState) -> bytes:
        key = st.h
        if key not in self._sps_pps:
            self._sps_pps[key] = (
                make_sps(self.width, st.h, coded_height=self.stripe_h)
                + make_pps())
        return self._sps_pps[key]

    # -- encode ------------------------------------------------------------

    def dispatch(self, rgb) -> "_H264Pending":
        """One dense device dispatch for the whole frame (every stripe);
        pair with :meth:`harvest`. Damage detection, reference-plane
        selection, and sparse level packing all happen inside the single
        jit program — the host's only per-frame read is the packed buffer,
        whose copy to the host starts here."""
        rgb = jnp.asarray(rgb)

        is_idr = any(st.need_idr for st in self.stripes)
        if is_idr:
            # optimistic clear so pipelined dispatch-ahead frames don't
            # re-IDR; entropy failure at harvest re-arms the flag
            for st in self.stripes:
                st.need_idr = False
        paint = np.zeros(self.n_stripes, np.int8)
        if not is_idr:
            for i, st in enumerate(self.stripes):
                # candidacy from *previous* frames' history; optimistic
                # mark so in-flight frames don't re-trigger (cleared again
                # by damage at harvest)
                if (st.static_frames >= self.paint_over_trigger
                        and not st.painted_over):
                    paint[i] = 1
                    st.painted_over = True

        # the device tier's slice is dev.fetch_prefix, a program of its
        # own: there the prefix follows the content bucket by bucket
        cavlc = self.entropy == "device"
        prefix = None if is_idr else self._choose_prefix(every_bucket=cavlc)
        # the executable: IDR, the device tier's P step (with all its
        # slice programs), or the host tier's by the prefix inside it
        program = "idr" if is_idr else ("p", self.entropy,
                                        None if cavlc else prefix)
        with self.compile_watch.first_use(program) as cold:
            if is_idr:
                (flat8, flat16, self._prev_y, self._prev_cb, self._prev_cr,
                 self._ref_y, self._ref_cb, self._ref_cr) = \
                    dev.encode_frame_idr_rgb(
                        rgb, self._prev_y, self._prev_cb, self._prev_cr,
                        self._ref_y, self._ref_cb, self._ref_cr,
                        jnp.int32(self.qp),
                        pad_h=self.pad_h, pad_w=self.pad_w,
                        n_stripes=self.n_stripes, sh=self.stripe_h)
                buf, fetch_arr = None, flat16
            elif cavlc:
                # on-device CAVLC: the fetch prefix is head + bit-exact
                # P-slice payloads (device_cavlc.py); flat16 stays device-
                # resident for overflow/IDR-resync re-reads
                (buf, flat16, self._prev_y, self._prev_cb, self._prev_cr,
                 self._ref_y, self._ref_cb, self._ref_cr) = \
                    dev.encode_frame_p_cavlc_rgb(
                        rgb, self._prev_y, self._prev_cb, self._prev_cr,
                        self._ref_y, self._ref_cb, self._ref_cr,
                        jnp.asarray(paint, jnp.int32),
                        jnp.int32(self.qp), jnp.int32(self.paint_over_qp),
                        pad_h=self.pad_h, pad_w=self.pad_w,
                        n_stripes=self.n_stripes, sh=self.stripe_h,
                        search=self.search,
                        max_stripe_bytes=self._cavlc_msb,
                        me=dev.ME)
                if cold:
                    # with the step, every slice program the content can
                    # select later: none is left to compile in a stream
                    for tier in self._prefix_tiers():
                        dev.fetch_prefix(buf, prefix=tier)
                fetch_arr = dev.fetch_prefix(buf, prefix=prefix)
            else:
                # the whole per-frame program — planes, encode, pack, and
                # the fetch-prefix slice — is ONE dispatch
                (buf, fetch_arr, flat16,
                 self._prev_y, self._prev_cb, self._prev_cr,
                 self._ref_y, self._ref_cb, self._ref_cr) = \
                    dev.encode_frame_p_rgb(
                        rgb, self._prev_y, self._prev_cb, self._prev_cr,
                        self._ref_y, self._ref_cb, self._ref_cr,
                        jnp.asarray(paint, jnp.int32),
                        jnp.int32(self.qp), jnp.int32(self.paint_over_qp),
                        pad_h=self.pad_h, pad_w=self.pad_w,
                        n_stripes=self.n_stripes, sh=self.stripe_h,
                        # two-tier prefix: static content ships the small
                        # head, busy content the sized one — two compiled
                        # programs, no per-bucket recompile churn; undershoot
                        # re-reads from buf
                        search=self.search, prefix=prefix,
                        cap_frac=self._cap_frac, me=dev.ME)
        fetch_arr.copy_to_host_async()
        qp_arr = np.where(paint != 0, self.paint_over_qp, self.qp)
        return _H264Pending(fetch=fetch_arr, flat16=flat16, is_idr=is_idr,
                            paint=paint, qp=qp_arr, buf=buf,
                            cavlc=(not is_idr and cavlc),
                            head_len=0 if is_idr else int(fetch_arr.shape[0]))

    def _recover_undershoot(self, p: "_H264Pending", host, needed: int):
        """Prediction-miss recovery shared by the sparse and device-CAVLC
        transfers: the frame after content got busier (rare, and counted
        as no prefix hit) re-reads the right bucket from the full device
        buffer, with the slice program of that tier; the read queues
        behind every step already dispatched.  The guess takes this frame
        in, so the next dispatches' prefix holds its like."""
        if needed > len(host):
            full = dev.fetch_prefix(p.buf, prefix=self._bucket(needed))
            full.copy_to_host_async()
            host = np.asarray(full)
            self.d2h_refetch_bytes_total += host.nbytes
        self._recent_needed.append(needed)
        largest = max(self._recent_needed)
        self._sparse_guess = self._bucket(
            max(largest + largest // 2, self._fixed_bytes + 4096))
        return host

    def _refetch_overflow_rows(self, p: "_H264Pending", damage, ovf):
        """Exact flat16 re-reads for overflow stripes, all started before
        any blocking (rare: |level| beyond the packed range)."""
        refetch = {}
        need_rows = [i for i in range(self.n_stripes)
                     if ovf[i] and (damage[i] or p.paint[i])]
        if len(need_rows) > 2:
            # ONE read of the frame's exact levels instead of a read a
            # stripe
            rows_host = np.asarray(p.flat16)
            self.d2h_refetch_bytes_total += rows_host.nbytes
            refetch = {i: rows_host[i] for i in need_rows}
        else:
            for i in need_rows:
                sl = p.flat16[i]
                sl.copy_to_host_async()
                refetch[i] = sl
                self.d2h_refetch_bytes_total += 2 * self._stripe_words
        return refetch

    def harvest(self, p: "_H264Pending",
                host: Optional[np.ndarray] = None) -> List[H264Stripe]:
        """Entropy-code one dispatched frame (host CAVLC over the fetched
        levels). Must be called in dispatch order. ``host`` supplies the
        already-fetched bytes when a pipeline owns the transfer."""
        if host is None:
            host = np.asarray(p.fetch)
        S = self.n_stripes
        mb_w = self.pad_w // MB
        mb_h = self.stripe_h // MB
        t_bits = base_words = None
        if p.is_idr:
            levels16 = host
            damage = np.ones(S, bool)
            ovf = np.zeros(S, bool)
        elif p.cavlc:
            # device-CAVLC transfer: head + bit-exact slice payloads
            levels16 = None
            t_bits, base_words, damage, ovf = dcav.parse_cavlc_head(host, S)
            # mirror the device's per-stripe word clip: an overflowing
            # stripe records its unclipped t_bits but compacts at most V
            # words, and an unclipped estimate here would force a
            # full-buffer refetch exactly on busy content
            wc = np.minimum((t_bits + 31) // 32, self._cavlc_msb // 4)
            rungs = dcav.tier_words(self._cavlc_msb, mb_w * mb_h)
            rung = int(dcav.tier_index(t_bits, rungs))
            self.cavlc_frames_total += 1
            self.cavlc_low_tier_frames_total += rung == len(rungs) - 1
            self.cavlc_tier_words_total += S * rungs[rung]
            self.cavlc_payload_words_total += int(wc.sum())
            needed = self._fixed_bytes + 4 * int(base_words[-1] + wc[-1])
            self.prefix_hit_frames_total += needed <= len(host)
            host = self._recover_undershoot(p, host, needed)
            refetch = self._refetch_overflow_rows(p, damage, ovf)
        else:
            levels16 = None
            head = host[:4 * S].reshape(S, 4)
            counts = head[:, 0].astype(np.int64) \
                + (head[:, 1].astype(np.int64) << 8)
            damage = head[:, 2] != 0
            ovf = head[:, 3] != 0
            used = np.minimum(counts, self._cap_cells) * dev.CELL
            needed = self._fixed_bytes + int(used.sum())
            host = self._recover_undershoot(p, host, needed)
            bitmaps = host[4 * S:self._fixed_bytes] \
                .reshape(S, self._n_cells // 8)
            starts = np.concatenate(
                [[0], np.cumsum(used)[:-1]]) + self._fixed_bytes
            refetch = self._refetch_overflow_rows(p, damage, ovf)

        out: List[H264Stripe] = []
        jobs: List[tuple] = []
        for i, st in enumerate(self.stripes):
            if p.is_idr:
                emit, is_key = True, True
                st.static_frames = 0
                st.painted_over = False
            elif damage[i]:
                emit, is_key = True, False
                st.static_frames = 0
                st.painted_over = False
            elif p.paint[i]:
                emit, is_key = True, False
                st.static_frames += 1
            else:
                emit = False
                st.static_frames += 1
            if not emit:
                continue

            if not p.is_idr and p.cavlc and not ovf[i]:
                # device already entropy-coded this stripe: the host job
                # is header/exp-Golomb glue only (no per-MB work)
                pb, nbits = dcav.payload_slice(host, S, base_words,
                                               t_bits, i)
                jobs.append((i, st, is_key, int(p.qp[i]),
                             ("bits", pb, nbits)))
                continue
            if p.is_idr:
                row = levels16[i].astype(np.int32)
            elif ovf[i]:
                row = np.asarray(refetch[i]).astype(np.int32)
            else:
                # rebuild the dense row from bitmap + compacted cells
                bits = np.unpackbits(bitmaps[i], bitorder="little")
                idx = np.flatnonzero(bits[:self._n_cells])
                cells = host[starts[i]:starts[i] + used[i]] \
                    .view(np.int8).astype(np.int32).reshape(-1, dev.CELL)
                dense = np.zeros(self._pad_words, np.int32)
                dense.reshape(-1, dev.CELL)[idx[:len(cells)]] = cells
                row = dense[:self._stripe_words]
            parts = []
            pos = 0
            for shape, size in self._shapes:
                parts.append(row[pos:pos + size].reshape(shape))
                pos += size
            mv, luma, luma_dc, chroma_dc, chroma_ac = parts
            jobs.append((i, st, is_key, int(p.qp[i]),
                         ("levels", mv, luma, luma_dc, chroma_dc,
                          chroma_ac)))

        def run_one(job):
            i, st, is_key, qp, work = job
            if work[0] == "bits":
                _, pb, nbits = work
                return dcav.assemble_p_slice(pb, nbits, qp, st.frame_num)
            _, mv, luma, luma_dc, chroma_dc, chroma_ac = work
            if is_key:
                nals = encode_picture_nals_np(
                    mv, luma, luma_dc, chroma_dc, chroma_ac,
                    is_idr=True, mb_w=mb_w, mb_h=mb_h, qp=qp,
                    frame_num=0, idr_pic_id=st.idr_pic_id)
                return self._sps_pps_for(st) + nals
            return encode_picture_nals_np(
                mv, luma, luma_dc, chroma_dc, chroma_ac,
                is_idr=False, mb_w=mb_w, mb_h=mb_h, qp=qp,
                frame_num=st.frame_num)

        def safe_one(job):
            try:
                return run_one(job)
            except Exception as exc:       # surfaced per stripe below
                return exc

        # the C coder releases the GIL: stripes entropy-code in parallel
        # (pixelflux does the same with per-stripe C++ threads)
        t_entropy0 = time.perf_counter()
        if len(jobs) > 1:
            payloads = list(_entropy_pool().map(safe_one, jobs))
        else:
            payloads = [safe_one(job) for job in jobs]
        self.host_entropy_ms_total += \
            (time.perf_counter() - t_entropy0) * 1000.0
        for job, payload in zip(jobs, payloads):
            i, st, is_key, qp, _ = job
            if isinstance(payload, Exception):
                # the device ref already advanced to a reconstruction the
                # decoder will never see — resynchronize with an IDR
                # instead of drifting every following P frame
                self.entropy_errors_total += 1
                logger.error("entropy coding failed for stripe %d; "
                             "forcing IDR resync", i, exc_info=payload)
                st.need_idr = True
                continue
            if is_key:
                st.frame_num = 1
                st.idr_pic_id = (st.idr_pic_id + 1) % 16
                st.need_idr = False
            else:
                st.frame_num = (st.frame_num + 1) % 16
            out.append(H264Stripe(
                y_start=st.y0, width=self.width, height=st.h,
                annexb=payload, is_key=is_key))
        return out

    def encode_frame(self, rgb) -> List[H264Stripe]:
        """RGB (H, W, 3) uint8 → encoded stripes (only damaged/paint-over)."""
        return self.harvest(self.dispatch(rgb))

    def request_keyframe(self) -> None:
        """Force IDR on every stripe (client join / PIPELINE_RESETTING)."""
        for st in self.stripes:
            st.need_idr = True

    def stripe_ref(self, i: int):
        """Host copies of stripe i's reference planes (conformance oracle)."""
        sh = self.stripe_h
        y = np.asarray(self._ref_y[i * sh:(i + 1) * sh])
        cb = np.asarray(self._ref_cb[i * sh // 2:(i + 1) * sh // 2])
        cr = np.asarray(self._ref_cr[i * sh // 2:(i + 1) * sh // 2])
        return y, cb, cr

    def lower_step(self):
        """The served P step, lowered for this encoder's geometry and
        options: what observability/device_phases.py compiles (from the
        cache, where the stream has run) to name a trace's operations by
        phase. Nothing runs and no state of the encoder is touched."""
        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        rgb = jax.ShapeDtypeStruct((self.height, self.width, 3), jnp.uint8)
        planes = [like(p) for p in (self._prev_y, self._prev_cb,
                                    self._prev_cr, self._ref_y,
                                    self._ref_cb, self._ref_cr)]
        paint = jax.ShapeDtypeStruct((self.n_stripes,), jnp.int32)
        common = dict(pad_h=self.pad_h, pad_w=self.pad_w,
                      n_stripes=self.n_stripes, sh=self.stripe_h,
                      search=self.search, me=dev.ME)
        if self.entropy == "device":
            return dev.encode_frame_p_cavlc_rgb.lower(
                rgb, *planes, paint, i32, i32,
                max_stripe_bytes=self._cavlc_msb, **common)
        return dev.encode_frame_p_rgb.lower(
            rgb, *planes, paint, i32, i32, prefix=self._choose_prefix(),
            cap_frac=self._cap_frac, **common)


@dataclass
class _H264Pending:
    """One in-flight H.264 dispatch."""

    fetch: object               # async-fetching buffer (sparse u8 for P,
    flat16: object              # i16 for IDR); exact levels for re-reads
    is_idr: bool
    paint: np.ndarray
    qp: np.ndarray
    buf: object = None          # full sparse device buffer (undershoot)
    head_len: int = 0
    cavlc: bool = False             # buffer holds device-CAVLC payloads


