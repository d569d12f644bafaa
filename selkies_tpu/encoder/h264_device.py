"""Device-side H.264 stripe encode step (tpuenc v1).

Replaces the reference's x264/NVENC encode stage (pixelflux striped x264;
legacy gstwebrtc_app.py:260-770 encoder zoo) with a jit-compiled JAX
pipeline.  TPU-first structure — every macroblock is processed in parallel;
there are NO sequential prediction chains on device:

* IDR stripes use Intra16x16 DC prediction with every MB in its own slice,
  which makes the prediction the constant 128 (all neighbors unavailable,
  §8.3.3) — exact, conformant, and embarrassingly parallel.  The per-MB
  slice-header cost is a few bytes and only paid on keyframes.
* P stripes are inter-only (P_16x16, one integer-pel MV per MB searched
  exhaustively on device).  MV *prediction* (median) only affects bitstream
  MVD bits, so it lives in the host entropy coder, not on device.
* The reconstruction loop (dequant → inverse transform → clip) runs on
  device with the exact decoder arithmetic from ops/h264_transform.py, so
  the reference frames match a conformant decoder bit-for-bit.

Each stripe is an independent video sequence (the client runs one
VideoDecoder per stripe Y — reference selkies-core.js:2925-2968), so ME
never crosses stripe boundaries.

Outputs are quantized level arrays + MVs; the host C++ coder (cavlc.cpp)
turns them into Annex-B NAL units.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import h264_transform as ht
from ..ops.color import rgb_to_ycbcr, subsample_420
from ..ops.motion import (full_search_mc, full_search_mc_scan,
                          full_search_mv, mc_chroma, mc_luma,
                          pad_replicate)
from ..ops.pallas_me import me_mc_stripes
from ..ops.phases import phase

MB = 16
SEARCH = 12
#: the motion search the encoders dispatch: the VMEM-resident kernel
#: (ops/pallas_me.py). "xla" (chunked) and "scan" are its references
#: (tests/test_h264_batch.py::test_me_backends_agree)
ME = "pallas"


class StripeEncodeOut(NamedTuple):
    """Device outputs for one stripe (n = number of MBs, raster order).

    Luma 4×4 blocks are indexed (row-major 4×4 grid within the MB); the
    host coder reorders to the spec's 8×8-then-raster scan.
    """
    mv: jnp.ndarray            # (n, 2) int32 (dy, dx); zeros for IDR
    luma: jnp.ndarray          # (n, 16, 4, 4) int32 quantized levels
    luma_dc: jnp.ndarray       # (n, 4, 4) int32 (IDR only; zeros for P)
    chroma_dc: jnp.ndarray     # (n, 2, 2, 2) int32
    chroma_ac: jnp.ndarray     # (n, 2, 4, 4, 4) int32 (position 0 zeroed)
    recon_y: jnp.ndarray       # (H, W) uint8
    recon_cb: jnp.ndarray      # (H/2, W/2) uint8
    recon_cr: jnp.ndarray      # (H/2, W/2) uint8


def _mb_blocks(plane: jnp.ndarray, mb: int = MB) -> jnp.ndarray:
    """(H, W) → (n_mb, mb//4 * mb//4, 4, 4), raster MBs, raster 4×4s."""
    h, w = plane.shape
    nby, nbx = h // mb, w // mb
    g = mb // 4
    v = plane.reshape(nby, mb, nbx, mb).swapaxes(1, 2)     # (nby,nbx,mb,mb)
    v = v.reshape(nby * nbx, g, 4, g, 4).swapaxes(2, 3)    # (n,g,g,4,4)
    return v.reshape(nby * nbx, g * g, 4, 4)


def _mb_unblocks(blocks: jnp.ndarray, h: int, w: int, mb: int = MB
                 ) -> jnp.ndarray:
    """Inverse of :func:`_mb_blocks`."""
    nby, nbx = h // mb, w // mb
    g = mb // 4
    v = blocks.reshape(nby * nbx, g, g, 4, 4).swapaxes(2, 3)
    v = v.reshape(nby, nbx, mb, mb).swapaxes(1, 2)
    return v.reshape(h, w)


#: x264-style decimation weights per 4×4 coefficient position: the cost
#: of a LONE |level|==1 coefficient there (x264 decimate_table4 indexed
#: by the reverse-zigzag leading run, mapped back to (row, col)). High
#: frequencies are expensive (they force coding every run before them),
#: low frequencies nearly free. Clustered coefficients over-count with
#: this per-position sum — i.e. the approximation only KEEPS more.
_DECIMATE_W = np.array([[0, 0, 0, 0],
                        [0, 0, 0, 1],
                        [0, 0, 1, 2],
                        [0, 1, 2, 3]], np.int32)


def _decimate_score(z):
    """Per-block x264-style decimation score; (..., 4, 4) → (...)."""
    a = jnp.abs(z)
    w = jnp.asarray(_DECIMATE_W)
    # any |level|>1 prices the block out of decimation (score 9 each)
    per = jnp.where(a > 1, 9, jnp.where(a == 1, w, 0))
    return per.sum(axis=(-2, -1))


def _encode_luma_residual(res_blocks, qp, intra, decimate: bool = False):
    """4×4 transform+quant and exact decoder-side reconstruction.

    res_blocks: (n, 16, 4, 4) int32 residual.
    Returns (levels, recon_res) — both (n, 16, 4, 4) int32.

    ``decimate`` (inter only) drops a macroblock's whole luma residual
    when its x264-style score is < 6 — the "single small coefficient"
    noise that costs cbp+run bits but buys no visible quality (x264
    x264_macroblock_probe_skip / decimate path). The round-4 quality
    gate measured isolated ±1 coefficients as a dominant bit cost on
    near-static desktop content. The zeroed levels feed the
    reconstruction below, so encoder refs stay decoder-exact.
    """
    w = ht.forward_dct4(res_blocks)
    z = ht.quant4(w, qp, intra=intra)
    if decimate and not intra:
        mb_score = _decimate_score(z).sum(axis=-1)        # (n,)
        keep = (mb_score >= 6)[:, None, None, None]
        z = jnp.where(keep, z, 0)
    d = ht.dequant4(z, qp)
    r = ht.inverse_dct4(d)
    return z, r


def _encode_luma_i16(res_blocks, qp):
    """Intra16x16 luma path: Hadamard DC + AC-only 4×4 levels.

    res_blocks: (n, 16, 4, 4).  Returns (z_dc (n,4,4), z_ac (n,16,4,4),
    recon_res (n,16,4,4)).
    """
    w = ht.forward_dct4(res_blocks)                    # (n,16,4,4)
    dc = w[..., 0, 0].reshape(-1, 4, 4)                # raster DC grid
    y = ht.hadamard4_fwd(dc)
    z_dc = ht.quant_dc16(y, qp)
    d_dc = ht.dequant_dc16(z_dc, qp)                   # (n,4,4), = 4·W scale
    z_ac = ht.quant4(w, qp, intra=True)
    z_ac = z_ac.at[..., 0, 0].set(0)
    d = ht.dequant4(z_ac, qp)
    d = d.at[..., 0, 0].set(d_dc.reshape(-1, 16))
    r = ht.inverse_dct4(d)
    return z_dc, z_ac, r


def _encode_chroma(res_blocks, qpc, intra, decimate: bool = False):
    """Chroma path (always DC 2×2 Hadamard + AC blocks).

    res_blocks: (n, 4, 4, 4) one component, 4 4×4 blocks per MB (2×2 grid).
    Returns (z_dc (n,2,2), z_ac (n,4,4,4), recon_res (n,4,4,4)).

    ``decimate`` drops the component's AC levels when their per-MB
    score is ≤ 3 (x264 uses < 7 over both components combined; each
    component separately at half that is the conservative split). DC
    always survives — it carries the visible tint.
    """
    w = ht.forward_dct4(res_blocks)                    # (n,4,4,4)
    dc = w[..., 0, 0].reshape(-1, 2, 2)
    y = ht.hadamard2_fwd(dc)
    z_dc = ht.quant_dc2(y, qpc)
    d_dc = ht.dequant_dc2(z_dc, qpc)
    z_ac = ht.quant4(w, qpc, intra=intra)
    z_ac = z_ac.at[..., 0, 0].set(0)
    if decimate and not intra:
        score = _decimate_score(z_ac).sum(axis=-1)     # (n,)
        keep = (score > 3)[:, None, None, None]
        z_ac = jnp.where(keep, z_ac, 0)
    d = ht.dequant4(z_ac, qpc)
    d = d.at[..., 0, 0].set(d_dc.reshape(-1, 4))
    r = ht.inverse_dct4(d)
    return z_dc, z_ac, r


def _clip8(x):
    return jnp.clip(x, 0, 255).astype(jnp.uint8)


@jax.jit
def encode_stripe_idr(y, cb, cr, qp) -> StripeEncodeOut:
    """IDR stripe: I16x16/DC with per-MB slices (pred ≡ 128).

    ``qp`` is traced (one compile covers every QP — paint-over and rate
    control change it per frame).
    """
    qpc = ht.qpc_for(qp)
    h, w = y.shape
    n = (h // MB) * (w // MB)

    res_y = _mb_blocks(y.astype(jnp.int32) - 128)
    z_dc, z_ac, r = _encode_luma_i16(res_y, qp)
    recon_y = _clip8(_mb_unblocks(r + 128, h, w))

    outs_c = []
    recons_c = []
    for plane in (cb, cr):
        res = _mb_blocks(plane.astype(jnp.int32) - 128, mb=MB // 2)
        zc_dc, zc_ac, rc = _encode_chroma(res, qpc, intra=True)
        outs_c.append((zc_dc, zc_ac))
        recons_c.append(_clip8(_mb_unblocks(rc + 128, h // 2, w // 2,
                                            mb=MB // 2)))

    return StripeEncodeOut(
        mv=jnp.zeros((n, 2), jnp.int32),
        luma=z_ac,
        luma_dc=z_dc,
        chroma_dc=jnp.stack([outs_c[0][0], outs_c[1][0]], axis=1),
        chroma_ac=jnp.stack([outs_c[0][1], outs_c[1][1]], axis=1),
        recon_y=recon_y,
        recon_cb=recons_c[0],
        recon_cr=recons_c[1],
    )


@jax.jit
def encode_stripe_p_pred(y, cb, cr, mv_grid, pred_y, pred_cb, pred_cr,
                         qp) -> StripeEncodeOut:
    """P stripe transform/quant/recon given precomputed ME predictions
    (the production path runs ME for all stripes in one Pallas kernel —
    ops/pallas_me.py — and feeds the winners here)."""
    qpc = ht.qpc_for(qp)
    h, w = y.shape

    res_y = _mb_blocks(y.astype(jnp.int32) - pred_y.astype(jnp.int32))
    z_l, r = _encode_luma_residual(res_y, qp, intra=False, decimate=True)
    recon_y = _clip8(
        _mb_unblocks(r, h, w) + pred_y.astype(jnp.int32))

    outs_c = []
    recons_c = []
    for plane, pred in ((cb, pred_cb), (cr, pred_cr)):
        res = _mb_blocks(plane.astype(jnp.int32) - pred.astype(jnp.int32),
                         mb=MB // 2)
        zc_dc, zc_ac, rc = _encode_chroma(res, qpc, intra=False,
                                          decimate=True)
        outs_c.append((zc_dc, zc_ac))
        recons_c.append(_clip8(
            _mb_unblocks(rc, h // 2, w // 2, mb=MB // 2)
            + pred.astype(jnp.int32)))

    n = (h // MB) * (w // MB)
    return StripeEncodeOut(
        mv=mv_grid.reshape(n, 2),
        luma=z_l,
        luma_dc=jnp.zeros((n, 4, 4), jnp.int32),
        chroma_dc=jnp.stack([outs_c[0][0], outs_c[1][0]], axis=1),
        chroma_ac=jnp.stack([outs_c[0][1], outs_c[1][1]], axis=1),
        recon_y=recon_y,
        recon_cb=recons_c[0],
        recon_cr=recons_c[1],
    )


def _stripe_view(plane, n_stripes, sh):
    return plane.reshape(n_stripes, sh, plane.shape[-1])


def _collapse_mv_ties(cur, ref, ref_cb, ref_cr, mv,
                      pred_y, pred_cb, pred_cr, *, search: int):
    """Re-point SAD-tied macroblocks at the stripe's dominant motion.

    The exhaustive search breaks SAD ties toward small |mv| per MB in
    isolation. On desktop content that checkerboards flat regions
    between mv=0 and the true motion, so the host coder's P_Skip runs
    never form and every such MB pays mb_type+mvd+cbp syntax — measured
    ~12x the bits of x264 superfast at equal PSNR on scrolling text
    (tools/quality_measure.py, the round-4 quality gate). x264 solves
    this with rate-aware MV costs inside the search; the TPU-shaped
    equivalent is this whole-stripe post-pass: find the stripe's most
    common winner, and move every MB whose SAD at that offset EQUALS
    its winner's SAD (a true tie — quality is untouched) onto it. The
    MV field then collapses to long uniform runs that skip/mvd-predict
    to almost nothing. Pure XLA, so every ME backend shares it.

    cur/ref: (h, w) uint8; ref_cb/ref_cr: (hc, wc) uint8.
    """
    h, w = cur.shape
    hc, wc = ref_cb.shape
    nby, nbx = h // MB, w // MB
    n = 2 * search + 1

    ridx = (mv[..., 0] + search) * n + (mv[..., 1] + search)
    counts = (ridx.reshape(-1, 1)
              == jnp.arange(n * n, dtype=jnp.int32)[None, :]).sum(0)
    dom = jnp.argmax(counts).astype(jnp.int32)      # first max = lowest idx
    ddy = dom // n - search
    ddx = dom % n - search

    # luma prediction at the dominant offset: one dynamic-base slice of
    # the replicate-padded window (a fast DMA, not a gather)
    win = pad_replicate(ref, search)
    ref_dom = jax.lax.dynamic_slice(
        win, (search + ddy, search + ddx), (h, w))
    cur_i = cur.astype(jnp.int32)
    sad_dom = jnp.abs(cur_i - ref_dom.astype(jnp.int32)) \
        .reshape(nby, MB, nbx, MB).sum(axis=(1, 3))
    sad_best = jnp.abs(cur_i - pred_y.astype(jnp.int32)) \
        .reshape(nby, MB, nbx, MB).sum(axis=(1, 3))
    take = sad_dom <= sad_best                       # == : a true tie

    mv_new = jnp.where(take[..., None],
                       jnp.stack([ddy, ddx]).astype(jnp.int32)[None, None],
                       mv)
    take_px = jnp.repeat(jnp.repeat(take, MB, 0), MB, 1)
    pred_y2 = jnp.where(take_px, ref_dom.astype(jnp.uint8), pred_y)

    # chroma at the dominant offset (§8.4.2.2.2: integer luma mv →
    # {0,4}-eighth bilinear); arithmetic >> and & match the per-offset
    # path in ops/motion.py chroma_pred
    rc = search // 2 + 1
    iy, ix = ddy >> 1, ddx >> 1
    yf, xf = (ddy & 1) * 4, (ddx & 1) * 4
    out_c = []
    for cp in (ref_cb, ref_cr):
        cpad = pad_replicate(cp.astype(jnp.int32), rc + 1)
        a = jax.lax.dynamic_slice(
            cpad, (rc + 1 + iy, rc + 1 + ix), (hc + 1, wc + 1))
        tl = a[:hc, :wc]
        tr = a[:hc, 1:]
        bl = a[1:, :wc]
        br = a[1:, 1:]
        acc = ((8 - xf) * (8 - yf) * tl + xf * (8 - yf) * tr
               + (8 - xf) * yf * bl + xf * yf * br + 32) >> 6
        out_c.append(acc.astype(jnp.uint8))
    cb2 = MB // 2
    take_cx = jnp.repeat(jnp.repeat(take, cb2, 0), cb2, 1)
    pred_cb2 = jnp.where(take_cx, out_c[0], pred_cb)
    pred_cr2 = jnp.where(take_cx, out_c[1], pred_cr)
    return mv_new, pred_y2, pred_cb2, pred_cr2


def _frame_p_core(y, cb, cr, prev_y, prev_cb, prev_cr,
                  ref_y, ref_cb, ref_cr, paint, qp, paint_qp,
                  *, n_stripes: int, sh: int, search: int,
                  me: str = "pallas"):
    """Shared body of the dense whole-frame P encode: every stripe in ONE
    dispatch.

    Per-stripe dispatches pay the fixed dispatch cost 17 times a frame.
    Here stripes ride a vmap axis, damage detection runs in the
    same program, and undamaged stripes keep their old reference planes
    via an on-device select, so the host makes exactly one fetch.
    """
    S = n_stripes
    ys = _stripe_view(y, S, sh)
    pys = _stripe_view(prev_y, S, sh)
    pcbs = _stripe_view(prev_cb, S, sh // 2)
    pcrs = _stripe_view(prev_cr, S, sh // 2)
    rys = _stripe_view(ref_y, S, sh)
    rcbs = _stripe_view(ref_cb, S, sh // 2)
    rcrs = _stripe_view(ref_cr, S, sh // 2)
    cbs = _stripe_view(cb, S, sh // 2)
    crs = _stripe_view(cr, S, sh // 2)

    # the scopes name the step's phases in the compiled program's
    # metadata (observability/device_phases.py reads them back): metadata
    # only, the arithmetic and the bitstream are what they were
    with jax.named_scope("damage"):
        damage = jax.vmap(
            lambda a, b, c, d, e, f:
            jnp.any(a != b) | jnp.any(c != d) | jnp.any(e != f)
        )(ys, pys, cbs, pcbs, crs, pcrs)

        update = damage | (paint != 0)
        qps = jnp.where(paint != 0, paint_qp, qp)            # [S]

    # ME for every stripe in ONE VMEM-resident kernel (ops/pallas_me.py),
    # then the per-stripe transform/quant/recon rides a vmap. The XLA
    # searches are the kernel's references (``me`` is "xla" or "scan").
    with jax.named_scope("motion"):
        if me == "pallas":
            mv, pred_y, pred_cb, pred_cr = me_mc_stripes(
                ys, rys, rcbs, rcrs, search=search)
        else:
            fn = full_search_mc_scan if me == "scan" else full_search_mc
            mv, pred_y, pred_cb, pred_cr = jax.vmap(
                functools.partial(fn, mb=MB, search=search)
            )(ys, rys, rcbs, rcrs)
        # SAD-tied MBs re-point at each stripe's dominant motion so skip
        # runs form (same quality, far fewer syntax bits — see
        # _collapse_mv_ties); shared across every ME backend
        mv, pred_y, pred_cb, pred_cr = jax.vmap(
            functools.partial(_collapse_mv_ties, search=search)
        )(ys, rys, rcbs, rcrs, mv, pred_y, pred_cb, pred_cr)
    with jax.named_scope("transform"):
        enc = jax.vmap(encode_stripe_p_pred)(
            ys, cbs, crs, mv, pred_y, pred_cb, pred_cr, qps)

        sel = update[:, None, None]
        new_ref_y = jnp.where(sel, enc.recon_y, rys).reshape(y.shape)
        new_ref_cb = jnp.where(sel, enc.recon_cb, rcbs).reshape(cb.shape)
        new_ref_cr = jnp.where(sel, enc.recon_cr, rcrs).reshape(cr.shape)

    return enc, damage, update, new_ref_y, new_ref_cb, new_ref_cr


#: sparse pack geometry: levels are grouped into 16-element cells; a
#: per-cell nonzero bitmap + the compacted nonzero cells are the transfer
CELL = 16


def sparse_geometry(stripe_words: int,
                    cap_frac: int = 4) -> "tuple[int, int, int]":
    """(padded_words, n_cells, cap_cells) for one stripe's flat16 row."""
    pad_words = -(-stripe_words // (CELL * 8)) * (CELL * 8)
    n_cells = pad_words // CELL
    cap = max(1, n_cells // cap_frac)
    return pad_words, n_cells, cap


@phase("entropy")
def _pack_sparse(flat16, damage, update, cap_frac: int = 4):
    """Block-sparse device pack of the level buffer (P frames).

    Most 16-element cells of the coefficient buffer are all-zero at
    streaming QPs, and the dense levels are 3.3 MB/frame at 1080p to
    move D2H. Ship a per-cell bitmap plus only the nonzero cells,
    compacted back-to-back across stripes so the host can fetch a
    prefix sized by the actual content:

      head   [S, 4]  u8  — count_lo, count_hi, damage, overflow
      bitmap [S, n_cells/8] u8 — LSB-first cell-nonzero bits
      cells  [total ≤ S*cap*CELL] u8 — int8 cell values, stripes
             back-to-back in bitmap order

    Overflow (cell count > cap, or |level| > 127) falls back to the
    exact flat16 row for that stripe, like the dense path's tail flags.
    """
    S, W = flat16.shape
    pad_words, n_cells, cap = sparse_geometry(W, cap_frac)
    blk = jnp.pad(flat16, ((0, 0), (0, pad_words - W))) \
        .reshape(S, n_cells, CELL)
    nzb = (blk != 0).any(-1) & update[:, None]            # [S, B]
    count = nzb.sum(axis=1).astype(jnp.int32)             # [S]
    # nonzero cells first, original order preserved (stable sort)
    order = jnp.argsort(~nzb, axis=1, stable=True)[:, :cap]
    cells16 = jnp.take_along_axis(blk, order[:, :, None], axis=1)
    range_ovf = (jnp.abs(cells16) > 127).any(axis=(1, 2))
    ovf = range_ovf | (count > cap)
    cells8 = jnp.clip(cells16, -127, 127).astype(jnp.int8)

    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    bitmap = (nzb.reshape(S, n_cells // 8, 8).astype(jnp.int32)
              * weights[None, None, :]).sum(-1).astype(jnp.uint8)

    # compact used cells back-to-back across stripes
    used = jnp.minimum(count, cap) * CELL                 # bytes per stripe
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(used)[:-1]])
    total_cap = S * cap * CELL
    j = jnp.arange(total_cap, dtype=jnp.int32)
    sidx = jnp.clip(jnp.searchsorted(starts, j, side="right") - 1, 0, S - 1)
    within = j - starts[sidx]
    valid = within < used[sidx]
    flat_cells = cells8.reshape(S, cap * CELL)
    gathered = flat_cells[sidx, jnp.clip(within, 0, cap * CELL - 1)]
    cells_out = jnp.where(valid, gathered, jnp.int8(0))

    head = jnp.stack([
        (count & 0xFF).astype(jnp.uint8),
        ((count >> 8) & 0xFF).astype(jnp.uint8),
        damage.astype(jnp.uint8),
        ovf.astype(jnp.uint8),
    ], axis=1)                                            # [S, 4]
    return jnp.concatenate([
        head.reshape(-1),
        bitmap.reshape(-1),
        cells_out.view(jnp.uint8),
    ])


@functools.partial(jax.jit,
                   static_argnames=("pad_h", "pad_w", "n_stripes", "sh",
                                    "search", "cap_frac", "prefix", "me"),
                   donate_argnames=("prev_y", "prev_cb", "prev_cr",
                                    "ref_y", "ref_cb", "ref_cr"))
def encode_frame_p_rgb(rgb, prev_y, prev_cb, prev_cr,
                       ref_y, ref_cb, ref_cr, paint, qp, paint_qp,
                       *, pad_h: int, pad_w: int, n_stripes: int, sh: int,
                       search: int = SEARCH, cap_frac: int = 4,
                       prefix: int = 0, me: str = "pallas"):
    """The host-entropy rung's whole per-frame P program in ONE dispatch:
    RGB→planes, damage, ME/MC, transform/quant/recon, sparse pack
    (:func:`_pack_sparse`; flat16 backs per-stripe overflow re-reads),
    and the fetch-prefix slice: ``prefix`` > 0 additionally returns
    ``buf[:prefix]``."""
    y, cb, cr = prepare_planes(rgb, pad_h, pad_w)
    enc, damage, update, new_ref_y, new_ref_cb, new_ref_cr = _frame_p_core(
        y, cb, cr, prev_y, prev_cb, prev_cr, ref_y, ref_cb, ref_cr,
        paint, qp, paint_qp, n_stripes=n_stripes, sh=sh, search=search,
        me=me)
    flat16, _ = _pack_levels(enc, damage, update)
    buf = _pack_sparse(flat16, damage, update, cap_frac=cap_frac)
    head = buf[:prefix] if prefix else buf
    return (buf, head, flat16, y, cb, cr,
            new_ref_y, new_ref_cb, new_ref_cr)


@functools.partial(jax.jit, static_argnames=("prefix",))
def fetch_prefix(buf, *, prefix: int):
    """The first ``prefix`` bytes of a packed step buffer — the part the
    host fetches. A program of its own (compiles in milliseconds) so the
    step that produced ``buf`` is compiled ONCE whatever prefix tier the
    content selects: the device-CAVLC step costs minutes to compile
    (tests/test_chip_compile.py), and with the slice inside it every
    tier change recompiled it mid-stream."""
    return buf[:prefix]


@functools.partial(jax.jit,
                   static_argnames=("pad_h", "pad_w", "n_stripes", "sh",
                                    "search", "max_stripe_bytes", "me"),
                   donate_argnames=("prev_y", "prev_cb", "prev_cr",
                                    "ref_y", "ref_cb", "ref_cr"))
def encode_frame_p_cavlc_rgb(rgb, prev_y, prev_cb, prev_cr,
                             ref_y, ref_cb, ref_cr, paint, qp, paint_qp,
                             *, pad_h: int, pad_w: int, n_stripes: int,
                             sh: int, search: int = SEARCH,
                             max_stripe_bytes: int = 0,
                             me: str = "pallas"):
    """P encode with ON-DEVICE CAVLC: the whole per-frame program — planes,
    damage, ME/MC, transform/quant/recon and entropy coding — in ONE
    dispatch (the fetch-prefix slice is :func:`fetch_prefix`).  The host
    fetches per-stripe bit-exact P-slice payloads
    (encoder/device_cavlc.py) instead of the block-sparse level buffer,
    so the D2H transfer is the bitstream's size; flat16 stays on device
    for overflow/resync."""
    from . import device_cavlc as dcav

    y, cb, cr = prepare_planes(rgb, pad_h, pad_w)
    enc, damage, update, new_ref_y, new_ref_cb, new_ref_cr = _frame_p_core(
        y, cb, cr, prev_y, prev_cb, prev_cr, ref_y, ref_cb, ref_cr,
        paint, qp, paint_qp, n_stripes=n_stripes, sh=sh, search=search,
        me=me)
    flat16, _ = _pack_levels(enc, damage, update)
    S = n_stripes
    buf = dcav.pack_p_frame(
        enc.mv.reshape(S, -1, 2),
        enc.luma.reshape(S, -1, 16, 4, 4),
        enc.chroma_dc.reshape(S, -1, 2, 2, 2),
        enc.chroma_ac.reshape(S, -1, 2, 4, 4, 4),
        damage, update, mb_w=pad_w // MB, mb_h=sh // MB,
        max_stripe_bytes=max_stripe_bytes)
    return (buf, flat16, y, cb, cr, new_ref_y, new_ref_cb, new_ref_cr)


@functools.partial(jax.jit, static_argnames=("pad_h", "pad_w",
                                             "n_stripes", "sh"),
                   donate_argnames=("prev_y", "prev_cb", "prev_cr",
                                    "ref_y", "ref_cb", "ref_cr"))
def encode_frame_idr_rgb(rgb, prev_y, prev_cb, prev_cr,
                         ref_y, ref_cb, ref_cr, qp,
                         *, pad_h: int, pad_w: int, n_stripes: int,
                         sh: int):
    """IDR counterpart of :func:`encode_frame_p_rgb` (one dispatch)."""
    y, cb, cr = prepare_planes(rgb, pad_h, pad_w)
    return encode_frame_idr(y, cb, cr, prev_y, prev_cb, prev_cr,
                            ref_y, ref_cb, ref_cr, qp,
                            n_stripes=n_stripes, sh=sh)


@functools.partial(jax.jit, static_argnames=("n_stripes", "sh"),
                   donate_argnames=("prev_y", "prev_cb", "prev_cr",
                                    "ref_y", "ref_cb", "ref_cr"))
def encode_frame_idr(y, cb, cr, prev_y, prev_cb, prev_cr,
                     ref_y, ref_cb, ref_cr, qp,
                     *, n_stripes: int, sh: int):
    """Dense whole-frame IDR encode (all stripes refresh; one dispatch).

    The body of :func:`encode_frame_idr_rgb`, a program of its own inside
    it. IDR levels can exceed int8, so the host fetches flat16 (keyframes
    are rare — connect, reset, PLI).
    """
    S = n_stripes
    ys = _stripe_view(y, S, sh)
    cbs = _stripe_view(cb, S, sh // 2)
    crs = _stripe_view(cr, S, sh // 2)
    qps = jnp.broadcast_to(qp, (S,))

    with jax.named_scope("transform"):
        enc = jax.vmap(encode_stripe_idr)(ys, cbs, crs, qps)
        new_ref_y = enc.recon_y.reshape(y.shape)
        new_ref_cb = enc.recon_cb.reshape(cb.shape)
        new_ref_cr = enc.recon_cr.reshape(cr.shape)
    damage = jnp.ones((S,), bool)
    flat16, flat8 = _pack_levels(enc, damage, damage)
    return flat8, flat16, y, cb, cr, new_ref_y, new_ref_cb, new_ref_cr


@phase("entropy")
def _pack_levels(enc: StripeEncodeOut, damage, update):
    """Device-side packing of one frame's level arrays for a single fetch.

    flat16: [S, words] int16 exact concat of (mv, luma, luma_dc, chroma_dc,
    chroma_ac) per stripe. flat8: the same clipped to int8 (halves the
    transfer; levels at streaming QPs rarely leave [-127, 127]) with a
    per-stripe tail of (damage, overflow) flags — overflowed stripes are
    re-read from flat16.
    """
    S = enc.mv.shape[0]
    parts = [enc.mv.reshape(S, -1), enc.luma.reshape(S, -1),
             enc.luma_dc.reshape(S, -1), enc.chroma_dc.reshape(S, -1),
             enc.chroma_ac.reshape(S, -1)]
    flat16 = jnp.concatenate(parts, axis=1).astype(jnp.int16)
    ovf = (jnp.abs(flat16.astype(jnp.int32)) > 127).any(axis=1)
    tail = jnp.stack([damage.astype(jnp.int8), ovf.astype(jnp.int8)],
                     axis=1)
    flat8 = jnp.concatenate(
        [jnp.clip(flat16, -127, 127).astype(jnp.int8), tail], axis=1)
    return flat16, flat8


@functools.partial(jax.jit, donate_argnames=("slot",))
def _stage_into(slot, frame):
    """H2D staging step for one ring slot.

    ``slot`` is the retiring ring buffer (donated): XLA may write the
    freshly transferred ``frame`` into its device memory instead of
    allocating, so a ring of N slots bounds staging memory at N frames
    no matter how many frames stream through. The elementwise merge is
    the cheapest op that makes the output *computed* (eligible to alias
    the donated operand) rather than a pass-through of the transfer
    buffer.
    """
    return frame | (slot & 0)


class StagingRing:
    """Double-buffered (depth>=2) H2D staging lane with donated slots.

    The pipelined encoders stage each host frame through here before
    dispatch: while the device encodes the frame staged into slot A, the
    host's next upload lands in slot B, so H2D transfer overlaps compute
    and donation can never serialize two consecutive dispatches against
    the same buffer.

    Donation hazard: a slot handed to ``_stage_into`` is *deleted* at
    call time — any later host read of that array would crash. ``stage``
    therefore refuses to donate a slot whose ticket is still held by an
    in-flight frame and falls back to a fresh allocation (counted in
    ``stalls_total``) — correctness never depends on the caller sizing
    the ring right, only peak memory does. tests/test_pipeline_async.py
    pins the guard.
    """

    def __init__(self, depth: int = 2, device=None) -> None:
        self.depth = max(2, int(depth))
        #: where frames land: a lane's chip, or None for the default
        #: device (the solo encoders)
        self._device = device
        #: shape/dtype-keyed slot lists — a resize simply starts a new
        #: lane; stale lanes are dropped
        self._slots: "list[object]" = [None] * self.depth
        self._busy = [False] * self.depth
        self._shape = None
        self._next = 0
        #: lane generation: tickets carry it so a ticket issued before a
        #: shape change can never free (and thus re-donate) the NEW
        #: lane's same-index slot while it is still in flight
        self._generation = 0
        self.stalls_total = 0
        self.staged_total = 0

    @property
    def in_use(self) -> int:
        return sum(self._busy)

    def stage(self, frame) -> "tuple[jnp.ndarray, Optional[tuple]]":
        """Stage one host frame; returns (device_array, ticket).

        ticket is None when the ring stalled (every slot still in
        flight) and a fresh unmanaged buffer was allocated instead.
        Release the ticket via :meth:`release` once the consuming frame
        has been harvested.
        """
        frame = jax.device_put(frame, self._device)
        key = (frame.shape, frame.dtype)
        if key != self._shape:
            # geometry change: abandon old slots (freed by GC) and
            # restart the lane — donation needs shape-stable buffers.
            # Outstanding tickets become stale via the generation bump.
            self._shape = key
            self._slots = [None] * self.depth
            self._busy = [False] * self.depth
            self._next = 0
            self._generation += 1
        idx = self._next
        if self._busy[idx]:
            # use-after-donate guard: a busy slot's occupant is still
            # referenced by an in-flight frame — donating it would
            # delete a buffer someone may read. Prefer ANY free slot
            # (so one leaked slot costs capacity, never the whole
            # lane); with every slot busy, allocate fresh instead.
            free = next((i for i in range(self.depth)
                         if not self._busy[i]), None)
            if free is None:
                self.stalls_total += 1
                return frame, None
            idx = free
        if self._slots[idx] is None:
            staged = frame
        else:
            staged = _stage_into(self._slots[idx], frame)
        self._slots[idx] = staged
        self._busy[idx] = True
        self._next = (idx + 1) % self.depth
        self.staged_total += 1
        return staged, (self._generation, idx)

    def release(self, ticket: "Optional[tuple]") -> None:
        """Mark a slot's contents consumed (safe to donate again).
        Tickets from a retired lane (pre-shape-change) are no-ops."""
        if ticket is not None:
            gen, idx = ticket
            if gen == self._generation:
                self._busy[idx] = False

    def release_all(self) -> None:
        """Teardown path: a closed pipeline holds no live readers, so
        every slot becomes donatable — a restarted encoder must never
        inherit a phantom-busy ring."""
        self._busy = [False] * self.depth


class StagingTicket:
    """One staged frame's hold on its ring slot, released once however
    many paths (harvest, a failed dispatch, close) come to release it."""

    __slots__ = ("_ring", "_ticket")

    def __init__(self, ring: StagingRing, ticket: "Optional[tuple]") -> None:
        self._ring = ring
        self._ticket = ticket

    def release(self) -> None:
        if self._ticket is not None:
            self._ring.release(self._ticket)
            self._ticket = None


@phase("colour")
def prepare_planes(rgb: jnp.ndarray, pad_h: int, pad_w: int):
    """RGB (H, W, 3) → padded uint8 (Y, Cb, Cr) planes.

    Pads to MB multiples by edge replication (the padded region is cropped
    away by the SPS frame_cropping fields).
    """
    h, w = rgb.shape[:2]
    if (pad_h, pad_w) != (h, w):
        rgb = jnp.pad(rgb, ((0, pad_h - h), (0, pad_w - w), (0, 0)),
                      mode="edge")
    yf, cbf, crf = rgb_to_ycbcr(rgb)
    y = _clip8(jnp.round(yf).astype(jnp.int32))
    cb = _clip8(jnp.round(subsample_420(cbf)).astype(jnp.int32))
    cr = _clip8(jnp.round(subsample_420(crf)).astype(jnp.int32))
    return y, cb, cr
