"""Pallas motion-search kernel: exhaustive ME + exact MC in VMEM.

The XLA formulations of the H.264 motion search (ops/motion.py) are
HBM-traffic-bound: every candidate offset re-reads the current and
reference planes from HBM, so even the chunk-batched form measured
~30 ms/frame at 1080p (625 offsets × ~100 MB/chunk of traffic). One
stripe's entire search window — current luma (64×1920), padded reference
(88×1944), chroma — is ~0.6 MB, a trivial VMEM fit, so this kernel runs
the complete search per stripe with the planes resident on-chip:

  * grid = (n_stripes,); each program owns one stripe;
  * pass 1: static unroll over dx, ``fori_loop`` over dy; per offset the
    shifted reference is a VMEM slice, SAD per 16×16 block is a reshape
    row-sum + lane-group sum, and only a (nby, nbx) best/rank pair is
    carried;
  * tie-breaking is *rank-based*: every offset carries its index in the
    |dy|+|dx|-sorted order used by ops/motion.py, and ties keep the
    lower rank — bit-identical winners to the exhaustive XLA search
    regardless of evaluation order;
  * pass 2 re-walks the offsets and, predicated on "this offset won at
    least one block" (``@pl.when``), builds the winning luma prediction
    and the §8.4.2.2.2-exact chroma bilinear by masked select — a frame
    with few distinct motions pays for few updates.

The public entry :func:`me_mc_stripes` takes stripe-batched planes
(S, H, W) and returns (mv, pred_y, pred_cb, pred_cr) with the same
semantics as ``vmap(full_search_mc)``. The kernel is compiled for the
attached device unless the run asked for interpreter mode
(``runtime.pallas_interpret``: the ``tpu_interpret`` setting, which
tests/conftest.py sets so the CPU test mesh exercises the same code path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import pallas_interpret
from .motion import _offsets, pad_replicate

MB = 16


def _rank_table(search: int) -> np.ndarray:
    """rank[dy+search, dx+search] = index in the sorted offset order."""
    offs = _offsets(search)
    n = 2 * search + 1
    rank = np.zeros((n, n), np.int32)
    for r, (dy, dx) in enumerate(offs):
        rank[dy + search, dx + search] = r
    return rank


def _me_mc_kernel(ranks_ref, cur_ref, ref_ref, cb_ref, cr_ref,
                  rank_out, py_out, pcb_out, pcr_out,
                  best_sad, best_rank, *, search: int, h: int,
                  w: int, hc: int, wc: int):
    nby, nbx = h // MB, w // MB
    n_dy = 2 * search + 1
    cur = cur_ref[0].astype(jnp.int32)                    # (h, w)

    # lane-group indicator (w, nbx): Mosaic cannot reshape-split the lane
    # dim, so the 16-lane column sum rides the MXU instead
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (w, nbx), 0) // MB
    grp_ids = jax.lax.broadcasted_iota(jnp.int32, (w, nbx), 1)
    col_ind = (col_ids == grp_ids).astype(jnp.float32)

    # ---- pass 1: SAD-only sweep, carry (best_sad, best_rank) ----------
    big = jnp.int32(1 << 30)
    best_sad[:nby, :nbx] = jnp.full((nby, nbx), big, jnp.int32)
    best_rank[:nby, :nbx] = jnp.full((nby, nbx), big, jnp.int32)

    # int32 once: Mosaic's dynamic rotate only handles 32-bit lanes
    win_all = ref_ref[0].astype(jnp.int32)                # (h+2s, w+2s)

    def body(dyi, _):
        # ONE dynamic row shift per dy, realized as a circular roll
        # (Mosaic cannot prove unaligned dynamic sublane slices; the
        # compiled rotate takes the dynamic amount as unsigned, hence
        # the positive shift ≡ -dyi mod rows). h + 2·search window rows
        # mean no wrapped garbage enters the [0:h) slice. The dx axis
        # is handled by static lane slices of the rolled window, and
        # all n_dy row-sum grids ride ONE MXU matmul (M = n_dy·nby)
        # instead of n_dy M=nby slivers.
        rolled = pltpu.roll(win_all, win_all.shape[0] - dyi, 0)[:h]
        rows_all = jnp.concatenate(
            [jnp.abs(cur - rolled[:, dxi:dxi + w])
             .reshape(nby, MB, w).sum(axis=1)
             for dxi in range(n_dy)], axis=0)            # (n_dy·nby, w)
        # HIGHEST: row sums reach 4080, past bf16's exact-integer range;
        # the MXU's default bf16 operand rounding would drift near-tie
        # winners between backends (same hazard as ops/motion.py:88 and
        # the round-2 device-entropy corruption)
        sads_all = jnp.dot(rows_all.astype(jnp.float32), col_ind,
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
        for dxi in range(n_dy):
            sad = sads_all[dxi * nby:(dxi + 1) * nby].astype(jnp.int32)
            rank = ranks_ref[dyi, dxi]
            bs = best_sad[:nby, :nbx]
            br = best_rank[:nby, :nbx]
            take = (sad < bs) | ((sad == bs) & (rank < br))
            best_sad[:nby, :nbx] = jnp.where(take, sad, bs)
            best_rank[:nby, :nbx] = jnp.where(take, rank, br)
        return 0

    jax.lax.fori_loop(0, n_dy, body, 0)

    win_rank = best_rank[:nby, :nbx]
    rank_out[0] = win_rank

    # ---- pass 2: exact predictions for winning offsets only -----------
    rc = search // 2 + 1
    cbsz = MB // 2

    def _expand_inds(rows_n, cols_n, cell):
        # block mask (nby, nbx) → pixel mask (rows_n, cols_n) via two
        # indicator matmuls (jnp.repeat lowers to reshapes Mosaic
        # rejects; the MXU does this for free)
        r_blk = jax.lax.broadcasted_iota(jnp.int32, (rows_n, nby), 0) // cell
        r_tgt = jax.lax.broadcasted_iota(jnp.int32, (rows_n, nby), 1)
        c_blk = jax.lax.broadcasted_iota(jnp.int32, (nbx, cols_n), 1) // cell
        c_tgt = jax.lax.broadcasted_iota(jnp.int32, (nbx, cols_n), 0)
        return ((r_blk == r_tgt).astype(jnp.float32),
                (c_blk == c_tgt).astype(jnp.float32))

    rexp_y, cexp_y = _expand_inds(h, w, MB)
    rexp_c, cexp_c = _expand_inds(hc, wc, cbsz)

    def expand_mask(take, rexp, cexp):
        t = take.astype(jnp.float32)
        px = jnp.dot(jnp.dot(rexp, t, preferred_element_type=jnp.float32),
                     cexp, preferred_element_type=jnp.float32)
        return px != 0

    cb_all = cb_ref[0].astype(jnp.int32)
    cr_all = cr_ref[0].astype(jnp.int32)

    def body2(dyi, _):
        # Gate whole dy rows on "some block's winner lives in this row":
        # the rolls + 25 per-dx mask/update bodies below were measured at
        # ~5.3 of the kernel's 8.3 ms/frame when run unconditionally,
        # while typical desktop motion has 1-2 winning dy rows, not 25.
        # The membership test is 25 vector compares of the (nby, nbx)
        # winner grid — noise next to one skipped roll. (A pass-1 SMEM
        # winner-flag scratch was tried first; scratch carried between
        # two fori_loops faults Mosaic inside lax.scan programs.)
        row_hit = jnp.zeros((nby, nbx), jnp.bool_)
        for dxi in range(n_dy):
            row_hit = row_hit | (win_rank == ranks_ref[dyi, dxi])

        @pl.when(jnp.any(row_hit))
        def _(dyi=dyi):
            rolled = pltpu.roll(win_all, win_all.shape[0] - dyi, 0)[:h]
            dy = dyi - search
            iy = dy >> 1
            yf = (dy & 1) * 4
            y0 = rc + 1 + iy
            cb_roll = pltpu.roll(cb_all, cb_all.shape[0] - y0, 0)
            cr_roll = pltpu.roll(cr_all, cr_all.shape[0] - y0, 0)
            for dxi in range(n_dy):
                dx = dxi - search
                rank = ranks_ref[dyi, dxi]
                take = win_rank == rank                  # (nby, nbx)
                # chroma lane geometry, xf folded in statically
                # (§8.4.2.2.2: integer luma mv → {0,4}-eighth weights)
                ix = dx >> 1
                xf = (dx & 1) * 4
                x0 = rc + 1 + ix

                @pl.when(jnp.any(take))
                def _(take=take, dxi=dxi, x0=x0, xf=xf,
                      rolled=rolled, cb_roll=cb_roll, cr_roll=cr_roll,
                      yf=yf):
                    tpx = expand_mask(take, rexp_y, cexp_y)
                    py_out[0] = jnp.where(
                        tpx, rolled[:, dxi:dxi + w].astype(jnp.uint8),
                        py_out[0])

                    def ctap(roll_c, off):
                        a = roll_c[off:off + hc, x0:x0 + wc]
                        if xf == 0:
                            return a * 8
                        return (a * (8 - xf)
                                + roll_c[off:off + hc,
                                         x0 + 1:x0 + 1 + wc] * xf)

                    ncb = ((8 - yf) * ctap(cb_roll, 0)
                           + yf * ctap(cb_roll, 1) + 32) >> 6
                    ncr = ((8 - yf) * ctap(cr_roll, 0)
                           + yf * ctap(cr_roll, 1) + 32) >> 6
                    tcx = expand_mask(take, rexp_c, cexp_c)
                    pcb_out[0] = jnp.where(tcx, ncb.astype(jnp.uint8),
                                           pcb_out[0])
                    pcr_out[0] = jnp.where(tcx, ncr.astype(jnp.uint8),
                                           pcr_out[0])

        return 0

    jax.lax.fori_loop(0, n_dy, body2, 0)


@functools.partial(jax.jit, static_argnames=("search", "interpret"))
def me_mc_stripes(cur, ref, ref_cb, ref_cr, *, search: int = 12,
                  interpret: bool | None = None):
    """Stripe-batched fused ME+MC via the VMEM-resident Pallas kernel.

    cur/ref: (S, h, w) uint8 luma; ref_cb/ref_cr: (S, h/2, w/2) uint8.
    Returns (mv (S, nby, nbx, 2) int32, pred_y, pred_cb, pred_cr uint8)
    with selection semantics identical to ``vmap(full_search_mc)``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    S, h, w = cur.shape
    hc, wc = ref_cb.shape[-2:]
    nby, nbx = h // MB, w // MB
    n_dy = 2 * search + 1
    rc = search // 2 + 1

    ref_pad = pad_replicate(ref, search)                  # (S, h+2s, w+2s)
    cbp = pad_replicate(ref_cb, rc + 1)
    crp = pad_replicate(ref_cr, rc + 1)
    ranks = jnp.asarray(_rank_table(search))

    kern = functools.partial(_me_mc_kernel, search=search, h=h, w=w,
                             hc=hc, wc=wc)
    # inside a shard_map (the mesh H.264 lanes) the outputs vary over the
    # same mesh axes as the planes; jax's varying-axes check wants that
    # said on every out_shape (empty outside a shard_map)
    vma = jax.typeof(cur).vma
    rank_w, py, pcb, pcr = pl.pallas_call(
        kern,
        grid=(S,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # ranks
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h + 2 * search, w + 2 * search),
                         lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hc + 2 * (rc + 1), wc + 2 * (rc + 1)),
                         lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hc + 2 * (rc + 1), wc + 2 * (rc + 1)),
                         lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, nby, nbx), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hc, wc), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hc, wc), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, nby, nbx), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((S, h, w), jnp.uint8, vma=vma),
            jax.ShapeDtypeStruct((S, hc, wc), jnp.uint8, vma=vma),
            jax.ShapeDtypeStruct((S, hc, wc), jnp.uint8, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((max(8, nby), max(128, nbx)), jnp.int32),
            pltpu.VMEM((max(8, nby), max(128, nbx)), jnp.int32),
        ],
        # 4K stripes (w=3840) need ~18 MB of scoped VMEM (the rolled
        # int32 window + the indicator constants); the default 16 MB
        # scope is conservative, not the physical limit
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        # the operation's name in a device trace (``me_mc_stripes.<n>``):
        # the benchmark's me_kernel_ms finds the kernel by it
        name="me_mc_stripes",
    )(ranks, cur, ref_pad, cbp, crp)
    mv = jnp.asarray(_offsets(search))[rank_w]            # (S, nby, nbx, 2)
    return mv, py, pcb, pcr
