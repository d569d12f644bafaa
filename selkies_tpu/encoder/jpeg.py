"""tpuenc JPEG-stripe profile.

The frame is split into horizontal stripes (the reference's unit of spatial
parallelism and of client-side decode — SURVEY.md §2.7); one jit-compiled
device dispatch per frame produces quantized, zigzagged DCT coefficients for
every stripe plus a per-stripe damage measure, and the host entropy-codes and
ships only the stripes that changed ("damage gating", the TPU answer to the
reference's XDamage-driven skip: always dispatch dense work on device, mask on
host — SURVEY.md §7 hard part 4).

Paint-over: after ``paint_over_trigger_frames`` consecutive static frames a
stripe is re-emitted once at the high paint-over quality (same behavior as
pixelflux's quality escalation, consumed via CaptureSettings at
reference selkies.py:2919-2963).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.color import rgb_to_ycbcr, subsample_420
from ..ops.dct import block_dct2, blockify
from ..ops.phases import phase
from ..ops.quant import ZIGZAG, quality_scaled_tables
from . import entropy_py
from .h264_device import StagingRing
from .jfif import EOI, jfif_headers
from ..native import entropy_lib
from ..runtime import CompileWatch
from .jpeg_tables import std_tables


@dataclass(frozen=True)
class StripeOutput:
    """One encoded stripe ready for protocol packing."""

    y_start: int
    height: int
    jpeg: bytes
    is_paintover: bool


def _encode_body(frame, prev, qy, qc, qsel, *, stripe_h: int,
                 wm_scaled=None, alpha_inv=None):
    """One whole-frame encode dispatch.

    Args:
      frame: [H, W, 3] uint8 RGB (H multiple of stripe_h, W multiple of 16).
      prev:  [H, W, 3] uint8 previous frame (for damage detection); donated.
      qy/qc: [nq, 8, 8] float32 quant tables (normal, paint-over, ...).
      qsel:  [S] int32 per-stripe table index.
      wm_scaled/alpha_inv: optional watermark overlay (premultiplied RGB
        [H, W, 3] u16 and inverse alpha [H, W, 1] u16) blended on device —
        the pixelflux watermark feature (reference selkies.py:2959-2962).
    Returns:
      yq  [H/8,  W/8,  64] int16 zigzag coefficients,
      cbq [H/16, W/16, 64] int16,
      crq [H/16, W/16, 64] int16,
      damage [S] int32 max abs pixel delta per stripe,
      frame (to become the caller's new ``prev`` without a host round-trip).
    """
    h, w, _ = frame.shape
    s = h // stripe_h

    # the scopes name the step's phases in the compiled program's
    # metadata (ops/phases.py): they change no arithmetic
    with jax.named_scope("colour"):
        if wm_scaled is not None:
            blended = (frame.astype(jnp.uint32) * alpha_inv.astype(jnp.uint32)
                       + wm_scaled.astype(jnp.uint32) + 127) // 255
            frame = blended.astype(jnp.uint8)

    with jax.named_scope("damage"):
        diff = jnp.abs(frame.astype(jnp.int16) - prev.astype(jnp.int16))
        damage = diff.reshape(s, stripe_h * w * 3).max(axis=1) \
            .astype(jnp.int32)

    with jax.named_scope("colour"):
        y, cb, cr = rgb_to_ycbcr(frame)
        cb = subsample_420(cb)
        cr = subsample_420(cr)

    zz = jnp.asarray(ZIGZAG)

    @phase("transform")
    def component(plane, tables, rows_per_stripe):
        blocks = blockify(plane) - 128.0            # [by, bx, 8, 8]
        coeffs = block_dct2(blocks)
        by = blocks.shape[0]
        row_stripe = jnp.arange(by) // rows_per_stripe
        recip = 1.0 / tables                        # [nq, 8, 8]
        row_recip = recip[qsel[row_stripe]]         # [by, 8, 8]
        q = jnp.round(coeffs * row_recip[:, None]).astype(jnp.int16)
        return jnp.take(q.reshape(by, q.shape[1], 64), zz, axis=-1)

    yq = component(y, qy, stripe_h // 8)
    cbq = component(cb, qc, stripe_h // 16)
    crq = component(cr, qc, stripe_h // 16)
    return yq, cbq, crq, damage, frame


_device_encode = functools.partial(
    jax.jit,
    static_argnames=("stripe_h",),
    donate_argnames=("prev",),
)(_encode_body)


@functools.lru_cache(maxsize=32)
def _device_pipeline(pad_h: int, pad_w: int, stripe_h: int,
                     watermark: bool = False):
    """Shared (packer, jitted step) per frame geometry.

    Keyed like :func:`device_entropy.scan_geometry` so reconnects/resizes to
    an already-seen resolution reuse the compiled executable instead of
    retracing a fresh per-instance closure (a multi-second stall on the
    shared event loop otherwise)."""
    from .device_entropy import DeviceEntropyPacker

    packer = DeviceEntropyPacker(pad_h, pad_w, stripe_h)
    packer_fn = packer._pack_fn
    n_stripes = pad_h // stripe_h

    @functools.partial(jax.jit, donate_argnames=("prev",))
    def step(frame, prev, qy, qc, qsel, wm_scaled=None, alpha_inv=None):
        yq, cbq, crq, damage, new_prev = _encode_body(
            frame, prev, qy, qc, qsel, stripe_h=stripe_h,
            wm_scaled=wm_scaled if watermark else None,
            alpha_inv=alpha_inv if watermark else None)
        with jax.named_scope("entropy"):
            words, nbytes, base, ovf = packer_fn(yq, cbq, crq)
            # One fetchable buffer per frame: 4*S words of metadata
            # followed by the packed bitstream, so the host harvests a
            # frame with a single D2H read whatever a read's fixed cost is
            # (see pipeline.PipelinedJpegEncoder).
            head = jnp.concatenate([
                nbytes.astype(jnp.uint32),
                base.astype(jnp.uint32),
                ovf.astype(jnp.uint32),
                damage.astype(jnp.uint32),
            ])
            packed = jnp.concatenate([head, words])
        return packed, new_prev, yq, cbq, crq

    return packer, step


META_WORDS_PER_STRIPE = 4  # nbytes, base_words, overflow, damage


def split_meta(head_np: np.ndarray, n_stripes: int):
    """Parse the 4*S metadata words at the front of a packed step buffer."""
    s = n_stripes
    nbytes = head_np[0:s].astype(np.int64)
    base = head_np[s:2 * s].astype(np.int64)
    ovf = head_np[2 * s:3 * s] != 0
    damage = head_np[3 * s:4 * s].astype(np.int64)
    return nbytes, base, ovf, damage


def _entropy_encode_420(y: np.ndarray, cb: np.ndarray, cr: np.ndarray) -> bytes:
    lib = entropy_lib()
    if lib is None:
        return entropy_py.encode_scan_420(y, cb, cr)
    dc_l, ac_l, dc_c, ac_c = std_tables()
    # worst case ~16 bits/coeff plus stuffing headroom
    cap = (y.size + cb.size + cr.size) * 4 + 4096
    out = np.empty(cap, dtype=np.uint8)
    n = lib.jpeg_encode_scan_420(
        np.ascontiguousarray(y), np.ascontiguousarray(cb),
        np.ascontiguousarray(cr),
        y.shape[0], y.shape[1],
        dc_l.code_arr, dc_l.len_arr, ac_l.code_arr, ac_l.len_arr,
        dc_c.code_arr, dc_c.len_arr, ac_c.code_arr, ac_c.len_arr,
        out, cap,
    )
    if n < 0:
        return entropy_py.encode_scan_420(y, cb, cr)
    return out[:n].tobytes()


class JpegStripeEncoder:
    """Stateful per-display JPEG-stripe encoder (tpuenc v0).

    Equivalent role to one pixelflux ``ScreenCapture`` encode context in the
    reference; constructed per display by the capture manager.

    ``entropy="device"`` (default) runs Huffman coding on the TPU too
    (:mod:`.device_entropy`), so per-frame D2H is just the compressed
    bitstream; ``entropy="host"`` pulls coefficient planes back and codes
    them with the native/Python coder (oracle and fallback path).
    """

    def __init__(
        self,
        width: int,
        height: int,
        stripe_height: int = 64,
        quality: int = 40,
        paintover_quality: int = 90,
        use_paint_over_quality: bool = True,
        paint_over_trigger_frames: int = 15,
        damage_threshold: int = 0,
        entropy: str = "device",
        watermark_path: str = "",
        watermark_location: int = -1,
    ) -> None:
        if stripe_height % 16:
            raise ValueError("stripe_height must be a multiple of 16 (4:2:0 MCUs)")
        if entropy not in ("device", "host"):
            raise ValueError(f"unknown entropy mode {entropy!r}")
        self.width = width
        self.height = height
        # Padded geometry: width to 16 (MCU), height to a stripe multiple.
        self.pad_w = -(-width // 16) * 16
        self.pad_h = -(-height // stripe_height) * stripe_height
        self.stripe_h = stripe_height
        self.n_stripes = self.pad_h // stripe_height
        self.damage_threshold = int(damage_threshold)
        self.use_paint_over_quality = use_paint_over_quality
        self.paint_over_trigger_frames = int(paint_over_trigger_frames)
        self.entropy = entropy

        self.set_quality(quality, paintover_quality)

        #: overflowed stripes that fell back to host entropy coding —
        #: sustained growth means the device packing budget is wrong for
        #: this content and the degradation ladder's host rung is cheaper
        self.host_fallback_stripes_total = 0
        self.stripes_emitted_total = 0      # the whole they are a share of

        #: first-use compile signal for this encoder's step (read by
        #: the capture loop's wedge detector through the wrappers)
        self.compile_watch = CompileWatch()
        self._prev = jnp.zeros((self.pad_h, self.pad_w, 3), dtype=jnp.uint8)
        self._static_frames = np.zeros(self.n_stripes, dtype=np.int64)
        self._painted = np.zeros(self.n_stripes, dtype=bool)
        self._first_frame = True
        #: donated H2D staging lane (ISSUE 12): the synchronous
        #: encode_frame path (host-entropy rung of the degradation
        #: ladder included) double-buffers its uploads through the same
        #: ring the async pipeline uses, instead of allocating per frame
        self._staging = StagingRing(depth=2)
        self._staging_ticket: Optional[tuple] = None
        self._wm_scaled, self._alpha_inv = self._load_watermark(
            watermark_path, watermark_location)

        if entropy == "device":
            self._packer, self._step = _device_pipeline(
                self.pad_h, self.pad_w, self.stripe_h,
                watermark=self._wm_scaled is not None)

    # -- configuration -----------------------------------------------------

    def _load_watermark(self, path: str, location: int):
        """Build the full-frame premultiplied overlay (pixelflux watermark
        parity, reference selkies.py:2959-2962). Locations: 0 TL, 1 TR,
        2 BL, 3 BR (default), 4 center, 5 middle-left, 6 middle-right."""
        if not path:
            return None, None
        try:
            from PIL import Image

            img = np.asarray(Image.open(path).convert("RGBA"), np.uint16)
        except Exception:
            import logging

            logging.getLogger("selkies_tpu.encoder").warning(
                "watermark %s unreadable; disabled", path)
            return None, None
        wh, ww = img.shape[:2]
        wh, ww = min(wh, self.pad_h), min(ww, self.pad_w)
        img = img[:wh, :ww]
        m = 16  # margin
        positions = {
            0: (m, m),
            1: (m, self.pad_w - ww - m),
            2: (self.pad_h - wh - m, m),
            3: (self.pad_h - wh - m, self.pad_w - ww - m),
            4: ((self.pad_h - wh) // 2, (self.pad_w - ww) // 2),
            5: ((self.pad_h - wh) // 2, m),
            6: ((self.pad_h - wh) // 2, self.pad_w - ww - m),
        }
        y0, x0 = positions.get(int(location), positions[3])
        y0, x0 = max(0, y0), max(0, x0)
        # clamp to the space remaining at the placement (a mark near the
        # frame edge is cropped, never a broadcast error)
        wh = min(wh, self.pad_h - y0)
        ww = min(ww, self.pad_w - x0)
        if wh <= 0 or ww <= 0:
            return None, None
        img = img[:wh, :ww]
        # integer alpha blend: out = (frame*(255-a) + rgb*a + 127) // 255
        a = img[:, :, 3:4]
        wm_scaled = np.zeros((self.pad_h, self.pad_w, 3), np.uint16)
        wm_scaled[y0:y0 + wh, x0:x0 + ww] = img[:, :, :3] * a
        alpha_inv = np.full((self.pad_h, self.pad_w, 1), 255, np.uint16)
        alpha_inv[y0:y0 + wh, x0:x0 + ww] = 255 - a
        return jnp.asarray(wm_scaled), jnp.asarray(alpha_inv)

    def set_quality(self, quality: int, paintover_quality: Optional[int] = None):
        self.quality = int(quality)
        if paintover_quality is not None:
            self.paintover_quality = int(paintover_quality)
        ly, lc = quality_scaled_tables(self.quality)
        py, pc = quality_scaled_tables(self.paintover_quality)
        self._qy_np = (ly, py)
        self._qc_np = (lc, pc)
        self._qy = jnp.stack([jnp.asarray(ly, jnp.float32), jnp.asarray(py, jnp.float32)])
        self._qc = jnp.stack([jnp.asarray(lc, jnp.float32), jnp.asarray(pc, jnp.float32)])
        self._headers: Dict[int, bytes] = {}

    def _stripe_headers(self, qidx: int) -> bytes:
        hdr = self._headers.get(qidx)
        if hdr is None:
            hdr = jfif_headers(
                self.pad_w, self.stripe_h,
                self._qy_np[qidx], self._qc_np[qidx], subsampling="420",
            )
            self._headers[qidx] = hdr
        return hdr

    # -- per-frame ---------------------------------------------------------

    def _pad(self, frame: np.ndarray) -> np.ndarray:
        if frame.shape[0] == self.pad_h and frame.shape[1] == self.pad_w:
            return frame
        return np.pad(
            frame,
            ((0, self.pad_h - frame.shape[0]), (0, self.pad_w - frame.shape[1]), (0, 0)),
            mode="edge",
        )

    def _paint_candidates(self) -> np.ndarray:
        """Paint-over candidacy from *previous* frames' history, so the quant
        table index can ride the same dispatch as the frame."""
        return (
            self.use_paint_over_quality
            & (self._static_frames >= self.paint_over_trigger_frames)
            & ~self._painted
        )

    def _decide_emits(self, damaged: np.ndarray, paint_candidate: np.ndarray):
        """Update damage history; return (emit, is_paint) flag arrays."""
        if self._first_frame:
            damaged = np.ones_like(damaged)
            self._first_frame = False
        emit = np.zeros(self.n_stripes, dtype=bool)
        is_paint = np.zeros(self.n_stripes, dtype=bool)
        for s in range(self.n_stripes):
            if damaged[s]:
                self._static_frames[s] = 0
                self._painted[s] = False
                emit[s] = True
                is_paint[s] = bool(paint_candidate[s])  # quantized w/ HQ table
            else:
                self._static_frames[s] += 1
                if paint_candidate[s]:
                    emit[s] = True
                    is_paint[s] = True
                    self._painted[s] = True
        return emit, is_paint

    def _assemble(self, emit, is_paint, scans) -> List[StripeOutput]:
        out: List[StripeOutput] = []
        for s in range(self.n_stripes):
            if not emit[s]:
                continue
            qidx = 1 if is_paint[s] else 0
            out.append(
                StripeOutput(
                    y_start=s * self.stripe_h,
                    height=self.stripe_h,
                    jpeg=self._stripe_headers(qidx) + scans[s] + EOI,
                    is_paintover=bool(is_paint[s]),
                )
            )
        return out

    @staticmethod
    def total_packed_words(base_np: np.ndarray, nbytes_np: np.ndarray) -> int:
        """Packed-word count of the whole frame (last stripe's base + span)."""
        return int(base_np[-1]) + (int(nbytes_np[-1]) + 3) // 4

    def _scans_from_packed(
        self, words_np, base_np, nbytes_np, ovf_np, emit, yq, cbq, crq,
    ) -> List[bytes]:
        """Per-stripe entropy scans from the device-packed word buffer;
        overflowed stripes fall back to host-coding their coefficients."""
        from .device_entropy import stuff_bytes, words_to_stripe_bytes

        yrows, crows = self.stripe_h // 8, self.stripe_h // 16
        raw = words_to_stripe_bytes(words_np, base_np, nbytes_np)
        scans: List[bytes] = [b""] * self.n_stripes
        for s in range(self.n_stripes):
            if not emit[s]:
                continue
            self.stripes_emitted_total += 1
            if ovf_np[s]:  # pathological stripe: host-code its coeffs
                self.host_fallback_stripes_total += 1
                scans[s] = _entropy_encode_420(
                    np.asarray(yq[s * yrows:(s + 1) * yrows]),
                    np.asarray(cbq[s * crows:(s + 1) * crows]),
                    np.asarray(crq[s * crows:(s + 1) * crows]))
            else:
                scans[s] = stuff_bytes(raw[s])
        return scans

    def _stage_frame(self, frame: np.ndarray):
        """Stage one padded host frame through the donated ring.

        encode_frame is synchronous (the previous frame was fully
        fetched before this call), so the previous ticket is released
        here and the two slots ping-pong."""
        self._staging.release(self._staging_ticket)
        staged, self._staging_ticket = self._staging.stage(frame)
        return staged

    def encode_frame(self, frame: np.ndarray) -> List[StripeOutput]:
        """Encode one [H, W, 3] uint8 RGB frame; returns changed stripes only."""
        frame = self._pad(np.asarray(frame, dtype=np.uint8))
        paint_candidate = self._paint_candidates()
        qsel = jnp.asarray(paint_candidate.astype(np.int32))
        yrows = self.stripe_h // 8
        crows = self.stripe_h // 16

        if self.entropy == "device":
            with self.compile_watch.first_use("step"):
                packed, new_prev, yq, cbq, crq = self._step(
                    self._stage_frame(frame), self._prev, self._qy,
                    self._qc, qsel, self._wm_scaled, self._alpha_inv)
            self._prev = new_prev
            mw = META_WORDS_PER_STRIPE * self.n_stripes
            head_np = np.asarray(packed[:mw])
            nbytes_np, base_np, ovf_np, damage_np = split_meta(
                head_np, self.n_stripes)
            emit, is_paint = self._decide_emits(
                damage_np > self.damage_threshold, paint_candidate)
            scans: List[bytes] = [b""] * self.n_stripes
            if emit.any():
                total = self.total_packed_words(base_np, nbytes_np)
                bucket = self._packer.bucket_words(total)
                words_np = np.asarray(packed[mw:mw + bucket])
                scans = self._scans_from_packed(
                    words_np, base_np, nbytes_np, ovf_np, emit, yq, cbq, crq)
            return self._assemble(emit, is_paint, scans)

        with self.compile_watch.first_use("encode"):
            yq, cbq, crq, damage, new_prev = _device_encode(
                self._stage_frame(frame), self._prev, self._qy, self._qc,
                qsel, stripe_h=self.stripe_h,
                wm_scaled=self._wm_scaled, alpha_inv=self._alpha_inv,
            )
        self._prev = new_prev
        yq, cbq, crq, damage = (np.asarray(a) for a in (yq, cbq, crq, damage))
        emit, is_paint = self._decide_emits(
            damage > self.damage_threshold, paint_candidate)
        scans = [
            _entropy_encode_420(
                yq[s * yrows:(s + 1) * yrows],
                cbq[s * crows:(s + 1) * crows],
                crq[s * crows:(s + 1) * crows],
            ) if emit[s] else b""
            for s in range(self.n_stripes)
        ]
        return self._assemble(emit, is_paint, scans)

    def force_keyframe(self) -> None:
        """Make the next frame emit every stripe (client (re)connect)."""
        self._first_frame = True
        self._static_frames[:] = 0
        self._painted[:] = False

    def lower_step(self):
        """The served device-entropy step, lowered for this encoder's
        geometry: what observability/device_phases.py compiles (from the
        cache, where the stream has run) to name a trace's operations by
        phase. Nothing runs and no state of the encoder is touched."""
        def like(a):
            return None if a is None else jax.ShapeDtypeStruct(a.shape,
                                                               a.dtype)

        qsel = jax.ShapeDtypeStruct((self.n_stripes,), jnp.int32)
        return self._step.lower(
            like(self._prev), like(self._prev), like(self._qy),
            like(self._qc), qsel, like(self._wm_scaled),
            like(self._alpha_inv))
