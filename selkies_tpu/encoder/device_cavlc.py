"""Device-side (on-TPU) H.264 CAVLC entropy coding for P slices.

Why: the H.264 path's steady state paid a per-batch D2H read of the block-sparse
coefficient buffer, plus a per-session host CPU cost for the native CAVLC
coder (encoder/h264.py ``_entropy_pool``).  The JPEG path already proved
the fix (encoder/device_entropy.py): run entropy coding on device and
fetch only the compressed bits.  A full-damage 1080p scroll's P frame is
36.5 kB of bitstream (ledger, PR 29), far below the sparse levels: CAVLC
on device shrinks that transfer directly AND removes the per-session
host entropy threads (the "millions of users" scaling wall).

Unlike CABAC, every CAVLC context is *data-parallel*: the nC context of a
4×4 block is a function of its neighbors' totalCoeff — a pure count of
nonzeros, independent of any coded bit.  Skip runs, MV prediction and cbp
are likewise closed-form over the MV/level grids.  The only sequential
chain is the per-block level suffix_length adaptation, which spans ≤ 16
coefficients and unrolls into 16 vectorized steps.

Structure (mirrors device_entropy.py's slot-grid design):

  1. per-MB syntax (skip decision, mb_skip_run, mvd, cbp, mb_qp_delta)
     and per-residual-block CAVLC symbols are computed into fixed
     (bits, len) slot grids — each slot ≤ 32 bits;
  2. VLC tables (coeff_token / total_zeros / run_before, ITU-T H.264
     Tables 9-5..9-10, transcribed from native/cavlc.cpp) are looked up
     through a two-level one-hot matmul over one packed (code<<5|len)
     table — MXU-friendly, no scalar-core gathers;
  3. each *unit* (MB header, one residual block, or the stripe's
     trailing skip run) packs into ≤ ``UNIT_WORDS`` 32-bit words with a
     masked shift-and-sum contraction;
  4. units globalize into the per-stripe bitstream with the analytic
     cumsum-difference trick (no searchsorted), and stripes compact
     back-to-back at word granularity with a (t_bits, base, overflow)
     head so the host fetches ONE buffer.

The payload is the P slice *after* the slice header: the host prepends
the (qp, frame_num)-dependent header bits, appends rbsp_trailing, and
runs emulation-prevention escaping — O(bytes) vectorized glue, no per-MB
work.  Output is bit-exact with native/cavlc.cpp; overflow stripes
(|level| beyond the 28-bit escape, a unit past UNIT_WORDS, or a stripe
past ``max_stripe_bytes``) are flagged and fall back to the exact flat16
levels + host coder, exactly like the JPEG overflow tail.

IDR pictures keep the host coder: they are rare (connect/reset/PLI), use
per-MB slices, and their levels routinely exceed int8 anyway.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.phases import phase

MB = 16

#: 32-bit words per packed unit (512 bits).  The worst *legal* residual
#: block is ~476 bits (16 escape-coded levels + coeff_token + signs); a
#: MB header unit is ≤ ~90 bits.  Anything larger flags overflow.
UNIT_WORDS = 16

#: The output stage (word assembly + frame compaction) comes in a ladder of
#: sizes (:func:`tier_words`): the stripe capacity V = ``max_stripe_bytes
#: // 4`` words, then V / 4, V / 16, ... for as long as a rung still holds
#: ``TIER_FLOOR_BYTES_PER_MB`` bytes for each macroblock of a stripe. A
#: frame runs the smallest rung that its largest stripe fits, read from its
#: own ``t_bits`` (:func:`tier_index`): the scalar-core gathers that build
#: each output word cost what the rung holds, not what the frame produced.
TIER_RATIO = 4

#: Set from the served 1080p scroll (PERF.md, PR 33): stripes of 480
#: macroblocks, V = 32,768 words (273 B/MB). Its frames are 30-36 kB: the
#: mean stripe fills 450-530 words (4 B/MB), and the largest stripe of a
#: frame, which picks the rung, 860-880 in the median. The rungs there are
#: V, V / 4 = 32 KiB (68 B/MB: the frames after a standstill of the
#: machine, and the ~27 B/MB of streaming QPs) and V / 16 = 8 KiB = 2,048
#: words (17 B/MB): 99.0-99.7% of a window's P frames fit it, 87% would
#: fit half of it, and V / 64 (4.3 B/MB) holds the mean stripe only. The
#: floor is in bytes per macroblock so that a stripe of another size (the
#: full-frame profile: 8,160 macroblocks) gets its rungs from the same rule.
TIER_FLOOR_BYTES_PER_MB = 16

#: fixed per-stripe head: t_bits u32 LE, base_words u32 LE, damage, ovf,
#: 2 pad bytes
HEAD_BYTES = 12

# ---------------------------------------------------------------------------
# VLC tables (transcribed from native/cavlc.cpp — ITU-T H.264 §9.2)

_COEFF_TOKEN_LEN = np.array([
    [1, 0, 0, 0, 6, 2, 0, 0, 8, 6, 3, 0, 9, 8, 7, 5,
     10, 9, 8, 6, 11, 10, 9, 7, 13, 11, 10, 8, 13, 13, 11, 9,
     13, 13, 13, 10, 14, 14, 13, 11, 14, 14, 14, 13, 15, 15, 14, 14,
     15, 15, 15, 14, 16, 15, 15, 15, 16, 16, 16, 15, 16, 16, 16, 16,
     16, 16, 16, 16],
    [2, 0, 0, 0, 6, 2, 0, 0, 6, 5, 3, 0, 7, 6, 6, 4,
     8, 6, 6, 4, 8, 7, 7, 5, 9, 8, 8, 6, 11, 9, 9, 6,
     11, 11, 11, 7, 12, 11, 11, 9, 12, 12, 12, 11, 12, 12, 12, 11,
     13, 13, 13, 12, 13, 13, 13, 13, 13, 14, 13, 13, 14, 14, 14, 13,
     14, 14, 14, 14],
    [4, 0, 0, 0, 6, 4, 0, 0, 6, 5, 4, 0, 6, 5, 5, 4,
     7, 5, 5, 4, 7, 5, 5, 4, 7, 6, 6, 4, 7, 6, 6, 4,
     8, 7, 7, 5, 8, 8, 7, 6, 9, 8, 8, 7, 9, 9, 8, 8,
     9, 9, 9, 8, 10, 9, 9, 9, 10, 10, 10, 10, 10, 10, 10, 10,
     10, 10, 10, 10],
], np.int64)

_COEFF_TOKEN_BITS = np.array([
    [1, 0, 0, 0, 5, 1, 0, 0, 7, 4, 1, 0, 7, 6, 5, 3,
     7, 6, 5, 3, 7, 6, 5, 4, 15, 6, 5, 4, 11, 14, 5, 4,
     8, 10, 13, 4, 15, 14, 9, 4, 11, 10, 13, 12, 15, 14, 9, 12,
     11, 10, 13, 8, 15, 1, 9, 12, 11, 14, 13, 8, 7, 10, 9, 12,
     4, 6, 5, 8],
    [3, 0, 0, 0, 11, 2, 0, 0, 7, 7, 3, 0, 7, 10, 9, 5,
     7, 6, 5, 4, 4, 6, 5, 6, 7, 6, 5, 8, 15, 6, 5, 4,
     11, 14, 13, 4, 15, 10, 9, 4, 11, 14, 13, 12, 8, 10, 9, 8,
     15, 14, 13, 12, 11, 10, 9, 12, 7, 11, 6, 8, 9, 8, 10, 1,
     7, 6, 5, 4],
    [15, 0, 0, 0, 15, 14, 0, 0, 11, 15, 13, 0, 8, 12, 14, 12,
     15, 10, 11, 11, 11, 8, 9, 10, 9, 14, 13, 9, 8, 10, 9, 8,
     15, 14, 13, 13, 11, 14, 10, 12, 15, 10, 13, 12, 11, 14, 9, 12,
     8, 10, 13, 8, 13, 7, 9, 12, 9, 12, 11, 10, 5, 8, 7, 6,
     1, 4, 3, 2],
], np.int64)

_COEFF_TOKEN_CDC_LEN = np.array(
    [2, 0, 0, 0, 6, 1, 0, 0, 6, 6, 3, 0, 6, 7, 7, 6, 6, 8, 8, 7],
    np.int64)
_COEFF_TOKEN_CDC_BITS = np.array(
    [1, 0, 0, 0, 7, 1, 0, 0, 4, 6, 1, 0, 3, 3, 2, 5, 2, 3, 2, 0],
    np.int64)

_TOTAL_ZEROS_LEN = [
    [0],
    [1, 3, 3, 4, 4, 5, 5, 6, 6, 7, 7, 8, 8, 9, 9, 9],
    [3, 3, 3, 3, 3, 4, 4, 4, 4, 5, 5, 6, 6, 6, 6],
    [4, 3, 3, 3, 4, 4, 3, 3, 4, 5, 5, 6, 5, 6],
    [5, 3, 4, 4, 3, 3, 3, 4, 3, 4, 5, 5, 5],
    [4, 4, 4, 3, 3, 3, 3, 3, 4, 5, 4, 5],
    [6, 5, 3, 3, 3, 3, 3, 3, 4, 3, 6],
    [6, 5, 3, 3, 3, 2, 3, 4, 3, 6],
    [6, 4, 5, 3, 2, 2, 3, 3, 6],
    [6, 6, 4, 2, 2, 3, 2, 5],
    [5, 5, 3, 2, 2, 2, 4],
    [4, 4, 3, 3, 1, 3],
    [4, 4, 2, 1, 3],
    [3, 3, 1, 2],
    [2, 2, 1],
    [1, 1],
]
_TOTAL_ZEROS_BITS = [
    [0],
    [1, 3, 2, 3, 2, 3, 2, 3, 2, 3, 2, 3, 2, 3, 2, 1],
    [7, 6, 5, 4, 3, 5, 4, 3, 2, 3, 2, 3, 2, 1, 0],
    [5, 7, 6, 5, 4, 3, 4, 3, 2, 3, 2, 1, 1, 0],
    [3, 7, 5, 4, 6, 5, 4, 3, 3, 2, 2, 1, 0],
    [5, 4, 3, 7, 6, 5, 4, 3, 2, 1, 1, 0],
    [1, 1, 7, 6, 5, 4, 3, 2, 1, 1, 0],
    [1, 1, 5, 4, 3, 3, 2, 1, 1, 0],
    [1, 1, 1, 3, 3, 2, 2, 1, 0],
    [1, 0, 1, 3, 2, 1, 1, 1],
    [1, 0, 1, 3, 2, 1, 1],
    [0, 1, 1, 2, 1, 3],
    [0, 1, 1, 1, 1],
    [0, 1, 1, 1],
    [0, 1, 1],
    [0, 1],
]

_TZ_CDC_LEN = [[0], [1, 2, 3, 3], [1, 2, 2, 0], [1, 1, 0, 0]]
_TZ_CDC_BITS = [[0], [1, 1, 1, 0], [1, 1, 0, 0], [1, 0, 0, 0]]

_RUN_BEFORE_LEN = [
    [0],
    [1, 1],
    [1, 2, 2],
    [2, 2, 2, 2],
    [2, 2, 2, 3, 3],
    [2, 2, 3, 3, 3, 3],
    [2, 3, 3, 3, 3, 3, 3],
    [3, 3, 3, 3, 3, 3, 3, 4, 5, 6, 7, 8, 9, 10, 11],
]
_RUN_BEFORE_BITS = [
    [0],
    [1, 0],
    [1, 1, 0],
    [3, 2, 1, 0],
    [3, 2, 1, 1, 0],
    [3, 2, 3, 2, 1, 0],
    [3, 0, 1, 3, 2, 5, 4],
    [7, 6, 5, 4, 3, 2, 1, 1, 1, 1, 1, 1, 1, 1, 1],
]

#: coded_block_pattern me(v) mapping for Inter prediction (Table 9-4)
_CBP_INTER_BY_CODENUM = np.array([
    0, 16, 1, 2, 4, 8, 32, 3, 5, 10, 12, 15, 47, 7, 11, 13,
    14, 6, 9, 31, 35, 37, 42, 44, 33, 34, 36, 40, 39, 43, 45, 46,
    17, 18, 20, 24, 19, 21, 26, 28, 23, 27, 29, 30, 22, 25, 38, 41],
    np.int64)
_CBP_INTER_CODENUM = np.zeros(48, np.int32)
_CBP_INTER_CODENUM[_CBP_INTER_BY_CODENUM] = np.arange(48)

_ZIGZAG4 = np.array([0, 1, 4, 8, 5, 2, 3, 6, 9, 12, 13, 10, 7, 11, 14, 15],
                    np.int32)

#: spec z-scan emission order of luma 4×4 blocks, as raster index r*4+c
_LUMA_SCAN = np.array([0, 1, 4, 5, 2, 3, 6, 7, 8, 9, 12, 13, 10, 11, 14, 15],
                      np.int32)

# packed (bits<<5 | len) LUT regions — one 1024-entry table, looked up
# via two one-hot matmuls (values < 2^21, exact in f32 at HIGHEST)
_TOK_BASE = 0           # 3 × 68 coeff_token classes
_TOKC_BASE = 204        # 20 chroma-DC coeff_token
_TZ_BASE = 224          # 16 × 16 total_zeros
_TZC_BASE = 480         # 4 × 4 chroma-DC total_zeros
_RB_BASE = 496          # 8 × 15 run_before


@functools.lru_cache(maxsize=1)
def _packed_lut() -> np.ndarray:
    lut = np.zeros(1024, np.float32)

    def put(base, i, bits, length):
        lut[base + i] = (int(bits) << 5) | int(length)

    for cls in range(3):
        for i in range(68):
            put(_TOK_BASE + cls * 68, i, _COEFF_TOKEN_BITS[cls][i],
                _COEFF_TOKEN_LEN[cls][i])
    for i in range(20):
        put(_TOKC_BASE, i, _COEFF_TOKEN_CDC_BITS[i], _COEFF_TOKEN_CDC_LEN[i])
    for t in range(16):
        row_l, row_b = _TOTAL_ZEROS_LEN[t], _TOTAL_ZEROS_BITS[t]
        for tz in range(len(row_l)):
            put(_TZ_BASE + t * 16, tz, row_b[tz], row_l[tz])
    for t in range(4):
        row_l, row_b = _TZ_CDC_LEN[t], _TZ_CDC_BITS[t]
        for tz in range(len(row_l)):
            put(_TZC_BASE + t * 4, tz, row_b[tz], row_l[tz])
    for zl in range(8):
        row_l, row_b = _RUN_BEFORE_LEN[zl], _RUN_BEFORE_BITS[zl]
        for run in range(len(row_l)):
            put(_RB_BASE + zl * 15, run, row_b[run], row_l[run])
    return lut


def _lut1024(idx):
    """packed = table[idx] for idx ∈ [0, 1024) via one-hot matmuls.

    Same rationale (and the same Precision.HIGHEST requirement) as
    device_entropy._lut512: TPU scalar-core gathers cost ~10 ns/element,
    and the MXU's default f32 path rounds operands to bf16."""
    table = _packed_lut().reshape(32, 32)
    hi = idx >> 5
    lo = idx & 31
    rows = jnp.dot(jax.nn.one_hot(hi, 32, dtype=jnp.float32),
                   jnp.asarray(table),
                   precision=jax.lax.Precision.HIGHEST)
    picked = (rows * jax.nn.one_hot(lo, 32, dtype=jnp.float32)).sum(-1)
    return picked.astype(jnp.int32)


# ---------------------------------------------------------------------------
# exp-Golomb on device


def _ue_dev(v):
    """ue(v) → (bits u32, len i32); exact for v < 2^16 - 1."""
    vp1 = (v + 1).astype(jnp.int32)
    nb = jnp.zeros_like(vp1)
    for b in range(1, 17):       # integer bit_length-1, no float log2
        nb = nb + (vp1 >= (1 << b)).astype(jnp.int32)
    return vp1.astype(jnp.uint32), 2 * nb + 1


def _se_dev(v):
    m = jnp.where(v <= 0, -2 * v, 2 * v - 1)
    return _ue_dev(m)


# ---------------------------------------------------------------------------
# residual_block CAVLC symbols (§9.2), vectorized over blocks


def _code_blocks(scan, nC, n_coeff: int, chroma_dc: bool):
    """CAVLC symbols for B residual blocks.

    scan: [B, n_coeff] int32 coefficients in scan order; nC: [B] int32
    (ignored for chroma DC).  Returns (bits [B, NS] u32, lens [B, NS]
    i32, ovf [B] bool) with NS = 2*n_coeff + 2 slots laid out as
    [coeff_token, t1-signs, level_0.._{n-1} (reverse order),
    total_zeros, run_before_0.._{n-2}].  Lens include the token even for
    total == 0; callers gate whole blocks (cbp / skip) by zeroing lens.
    """
    B = scan.shape[0]
    K = n_coeff
    nz = scan != 0
    t = nz.sum(-1).astype(jnp.int32)

    # k-th nonzero from the END (reverse scan order) via suffix ranks
    suf = jnp.cumsum(nz[:, ::-1].astype(jnp.int32), axis=1)[:, ::-1]
    kk = jnp.arange(K, dtype=jnp.int32)
    sel = nz[:, :, None] & ((suf - 1)[:, :, None] == kk[None, None, :])
    vals_rev = (scan[:, :, None] * sel).sum(1).astype(jnp.int32)
    pos_rev = (jnp.arange(K, dtype=jnp.int32)[None, :, None] * sel).sum(1)

    # trailing ones: leading run of |v|==1 in rev order, capped at 3
    isone = jnp.abs(vals_rev) == 1
    lead = jnp.cumprod(isone.astype(jnp.int32), axis=1)
    t1 = lead[:, :min(3, K)].sum(1)

    # ---- coeff_token ------------------------------------------------------
    tok_idx = t * 4 + t1
    if chroma_dc:
        packed = _lut1024(_TOKC_BASE + tok_idx)
        token_bits = (packed >> 5).astype(jnp.uint32)
        token_len = packed & 31
    else:
        cls = jnp.where(nC < 2, 0, jnp.where(nC < 4, 1, 2))
        packed = _lut1024(_TOK_BASE + cls * 68 + tok_idx)
        flc = jnp.where(t == 0, 3, ((t - 1) << 2) | t1)
        token_bits = jnp.where(nC >= 8, flc,
                               packed >> 5).astype(jnp.uint32)
        token_len = jnp.where(nC >= 8, 6, packed & 31)

    # ---- trailing-one signs (one slot, MSB-first emission order) ----------
    within = kk[None, :] < t1[:, None]
    sign = ((vals_rev < 0) & within).astype(jnp.uint32)
    shift = jnp.clip(t1[:, None] - 1 - kk[None, :], 0, 31).astype(jnp.uint32)
    sign_bits = (sign << shift).sum(1).astype(jnp.uint32)

    # ---- levels (reverse order, sequential suffix_length over ≤K steps) ---
    sl = jnp.where((t > 10) & (t1 < 3), 1, 0).astype(jnp.int32)
    lvl_bits: List = []
    lvl_lens: List = []
    ovf = jnp.zeros((B,), bool)
    for k in range(K):
        v = vals_rev[:, k]
        mag = jnp.abs(v)
        lc = 2 * (mag - 1) + (v < 0).astype(jnp.int32)
        lc = lc - jnp.where((t1 == k) & (t1 < 3), 2, 0)
        emit = (kk[k] >= t1) & (k < t)

        # suffix_length == 0 encoding
        esc0 = lc >= 30
        b0 = jnp.where(lc < 14, 1,
                       jnp.where(~esc0, (1 << 4) | (lc - 14),
                                 (1 << 12) | ((lc - 30) & 0xFFF)))
        l0 = jnp.where(lc < 14, lc + 1, jnp.where(~esc0, 19, 28))
        o0 = lc >= 30 + 4096
        # suffix_length > 0 encoding
        th = 15 << sl
        esc1 = lc >= th
        b1 = jnp.where(~esc1, (1 << sl) | (lc & ((1 << sl) - 1)),
                       (1 << 12) | ((lc - th) & 0xFFF))
        l1 = jnp.where(~esc1, (lc >> sl) + 1 + sl, 28)
        o1 = lc >= th + 4096

        zero_sl = sl == 0
        bits_k = jnp.where(zero_sl, b0, b1)
        len_k = jnp.where(zero_sl, l0, l1)
        ovf = ovf | (emit & jnp.where(zero_sl, o0, o1))
        lvl_bits.append(jnp.where(emit, bits_k, 0).astype(jnp.uint32))
        lvl_lens.append(jnp.where(emit, len_k, 0))

        new_sl = jnp.maximum(sl, 1)
        new_sl = new_sl + ((mag > (3 << (new_sl - 1)))
                           & (new_sl < 6)).astype(jnp.int32)
        sl = jnp.where(emit, new_sl, sl)

    # ---- total_zeros ------------------------------------------------------
    tz = pos_rev[:, 0] + 1 - t
    max_coeff = 4 if chroma_dc else n_coeff
    emit_tz = (t > 0) & (t < max_coeff)
    if chroma_dc:
        tzi = _TZC_BASE + jnp.clip(t, 0, 3) * 4 + jnp.clip(tz, 0, 3)
    else:
        tzi = _TZ_BASE + jnp.clip(t, 0, 15) * 16 + jnp.clip(tz, 0, 15)
    packed = _lut1024(tzi)
    tz_bits = jnp.where(emit_tz, packed >> 5, 0).astype(jnp.uint32)
    tz_len = jnp.where(emit_tz, packed & 31, 0)

    # ---- run_before (reverse order; zeros_left_i = p_i - i closed form) ---
    rb_bits: List = []
    rb_lens: List = []
    for k in range(K - 1):
        zeros_left = pos_rev[:, k] - (t - 1 - k)
        run = pos_rev[:, k] - pos_rev[:, k + 1] - 1
        emit = (k <= t - 2) & (zeros_left > 0)
        zl = jnp.clip(zeros_left, 0, 7)
        packed = _lut1024(_RB_BASE + zl * 15 + jnp.clip(run, 0, 14))
        rb_bits.append(jnp.where(emit, packed >> 5, 0).astype(jnp.uint32))
        rb_lens.append(jnp.where(emit, packed & 31, 0))

    bits = jnp.stack(
        [token_bits, sign_bits] + lvl_bits + [tz_bits] + rb_bits, axis=1)
    lens = jnp.stack(
        [token_len, t1] + lvl_lens + [tz_len] + rb_lens, axis=1)
    return bits, lens.astype(jnp.int32), ovf


# ---------------------------------------------------------------------------
# unit pack + stripe globalization (device_entropy.py's word machinery)


def _pack_units(bits, lens, W: int):
    """[U, SLOTS] slot grids → ([U, W] u32 words MSB-first, Lb [U], ovf)."""
    cum = jnp.cumsum(lens, axis=1)
    off = cum - lens
    Lb = cum[:, -1]
    unit_ovf = Lb > 32 * W

    j0 = jnp.minimum(off >> 5, W - 1)
    pos = off & 31
    sh = 32 - pos - lens
    safe = jnp.where(lens > 0, bits, 0).astype(jnp.uint32)
    c0 = jnp.where(
        sh >= 0,
        safe << jnp.clip(sh, 0, 31).astype(jnp.uint32),
        safe >> jnp.clip(-sh, 0, 31).astype(jnp.uint32)).astype(jnp.uint32)
    c1 = jnp.where(
        sh < 0, safe << jnp.clip(32 + sh, 0, 31).astype(jnp.uint32),
        jnp.uint32(0)).astype(jnp.uint32)
    j1 = jnp.minimum(j0 + 1, W - 1)

    wk = jnp.arange(W, dtype=jnp.int32)[None, None, :]
    words = (jnp.where(j0[..., None] == wk, c0[..., None], 0)
             + jnp.where(j1[..., None] == wk, c1[..., None], 0)
             ).sum(axis=1, dtype=jnp.uint32)
    return words, Lb.astype(jnp.int32), unit_ovf


def _unit_spans(words_unit, Lb):
    """Where each unit's bits fall in its stripe's bitstream.

    words_unit: [S, U, W] u32; Lb: [S, U] i32 bit lengths (0 = empty
    unit).  Returns (cs0, cs1 [S, U*W] u32 — running sums of every unit
    word shifted to its bit offset, and of what that shift spills into
    the next word; g0, e [S, U] i32 — the stripe words each unit starts
    and ends in; t_bits [S] i32).  Nothing here depends on how many
    words the output holds."""
    S, U, W = words_unit.shape
    cumb = jnp.cumsum(Lb, axis=1)
    base = cumb - Lb
    t_bits = cumb[:, -1]

    g0 = base >> 5
    r = base & 31
    e = (base + Lb - 1) >> 5

    r3 = r[..., None]
    u0 = words_unit >> r3.astype(jnp.uint32)
    u1 = jnp.where(r3 == 0, jnp.uint32(0),
                   words_unit << (32 - r3).astype(jnp.uint32))
    cs0 = jnp.cumsum(u0.reshape(S, U * W), axis=1, dtype=jnp.uint32)
    cs1 = jnp.cumsum(u1.reshape(S, U * W), axis=1, dtype=jnp.uint32)
    return cs0, cs1, g0, e, t_bits


#: words a row of ``_last_unit``'s histogram holds: the chip's lane count,
#: so neither one-hot operand has a minor dimension the chip would pad
_HIST_LANES = 128


def _last_unit(g0, V: int):
    """For each of a stripe's first V words, the last unit that starts at
    or before it, [S, V] i32; units past word V - 1 count as starting in it.

    g0 [S, U] i32 is non-decreasing along U and starts at 0 (an exclusive
    running sum of bit lengths), so the last such unit is the number of
    units that start at or before the word, less one: a histogram of the
    start words and a running sum over it, with no scatter and no search.
    The histogram is ``_lut1024``'s trick turned round: the word index
    splits into a row and a lane, and the two one-hot operands meet over
    the units on the MXU. 0/1 is exact in bf16 and the f32 accumulator
    counts exactly up to 2^24 units."""
    S, U = g0.shape
    assert U < 1 << 24, U
    L = _HIST_LANES
    rows = -(-V // L)
    g0c = jnp.clip(g0, 0, V - 1)
    # units on the minor dimension of the rows' operand, lanes on the other's
    in_row = (g0c // L)[:, None, :] == jnp.arange(
        rows, dtype=jnp.int32)[None, :, None]                # [S, rows, U]
    in_lane = (g0c % L)[:, :, None] == jnp.arange(
        L, dtype=jnp.int32)[None, None, :]                   # [S, U, L]
    count = jax.lax.dot_general(
        in_row.astype(jnp.bfloat16), in_lane.astype(jnp.bfloat16),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # [S, rows, L]
    count = count.astype(jnp.int32).reshape(S, rows * L)[:, :V]
    return jnp.cumsum(count, axis=1) - 1


def _stripe_words(cs0, cs1, g0, e, V: int, W: int):
    """The first V words of each stripe's bitstream, [S, V] u32.

    Same analytic boundary construction as device_entropy (empty units
    are safe: a non-boundary unit never has bits past the word its
    successor starts in).  Every output word costs three scalar-core
    gathers, so the cost is V's, whatever the stripe produced: callers
    pick V (``pack_p_frame_words``).  Which unit a word is gathered from
    (``_last_unit``) costs the units' one-hot lanes on the vector unit:
    0.15 ms a served frame beside 1.05 of gathers at the lowest rung
    (PERF.md, PR 39)."""
    S = g0.shape[0]
    lastblk = _last_unit(g0, V)

    ge = (jnp.clip(g0, 0, (1 << 15) - 1) << 16) | (
        jnp.clip(e + 1, 0, (1 << 15) - 1))
    ge_b = jnp.take_along_axis(ge, lastblk, axis=1)
    g0b = ge_b >> 16
    e1b = ge_b & 0xFFFF
    w_ar = jnp.arange(V, dtype=jnp.int32)[None, :]

    jstar = jnp.where(e1b <= w_ar, W - 1,
                      jnp.minimum(w_ar - g0b, W - 1))
    s_at0 = jnp.take_along_axis(cs0, lastblk * W + jstar, axis=1)
    word0 = s_at0 - jnp.concatenate(
        [jnp.zeros((S, 1), jnp.uint32), s_at0[:, :-1]], axis=1)

    # the spill of word w-1: its last unit is lastblk one word to the
    # right (unit 0 at w = 0), so ge there is ge_b shifted, not a gather
    lastblk1 = jnp.concatenate(
        [jnp.zeros((S, 1), jnp.int32), lastblk[:, :-1]], axis=1)
    ge_b1 = jnp.concatenate([ge[:, :1], ge_b[:, :-1]], axis=1)
    g0b1 = ge_b1 >> 16
    e1b1 = ge_b1 & 0xFFFF
    jstar1 = jnp.where(e1b1 + 1 <= w_ar, W - 1,
                       jnp.clip(w_ar - 1 - g0b1, 0, W - 1))
    s_at1 = jnp.take_along_axis(cs1, lastblk1 * W + jstar1, axis=1)
    s_at1 = jnp.where(w_ar == 0, 0, s_at1)
    word1 = s_at1 - jnp.concatenate(
        [jnp.zeros((S, 1), jnp.uint32), s_at1[:, :-1]], axis=1)

    return word0 + word1


def _compact(words_stripe, wc, base_words):
    """Stripes back-to-back at word granularity: [S, V] -> [S * V] u32,
    stripe s's first wc[s] words at base_words[s], zeros after the last."""
    S, V = words_stripe.shape
    j = jnp.arange(S * V, dtype=jnp.int32)
    sidx = jnp.clip(
        jnp.searchsorted(base_words, j, side="right") - 1, 0, S - 1)
    src = sidx * V + jnp.clip(j - base_words[sidx], 0, V - 1)
    valid = j < (base_words[-1] + wc[-1])
    return jnp.where(valid, words_stripe.reshape(-1)[src], 0)


def tier_words(max_stripe_bytes: int, n_mb: int) -> Tuple[int, ...]:
    """The output stage's ladder for stripes of ``n_mb`` macroblocks: words
    a stripe, from the capacity down (``TIER_RATIO``,
    ``TIER_FLOOR_BYTES_PER_MB``)."""
    rungs = [max_stripe_bytes // 4]
    while 4 * (rungs[-1] // TIER_RATIO) >= TIER_FLOOR_BYTES_PER_MB * n_mb:
        rungs.append(rungs[-1] // TIER_RATIO)
    return tuple(rungs)


def tier_index(t_bits, rungs: Tuple[int, ...]):
    """The rung of ``rungs`` a frame with these per-stripe payload bits
    ([S], device or host array) takes: how many of the smaller rungs its
    largest stripe fits under, so 0 is the capacity and ``len(rungs) - 1``
    the low tier. Stripes outside the update mask pack nothing and read
    0, so the maximum is over the updated ones."""
    return (t_bits.max() <= 32 * np.asarray(rungs[1:], np.int32)).sum()


# ---------------------------------------------------------------------------
# P-slice payload pack (the tentpole entry point)


def default_max_stripe_bytes(mb_w: int, mb_h: int) -> int:
    """Per-stripe payload capacity: 256 B/MB of headroom (streaming QPs
    measure ~27 B/MB mean, paint-over ~4x that), pow2, ≥ 16 KB."""
    n = 16384
    while n < 256 * mb_w * mb_h:
        n <<= 1
    return n


def pack_p_frame_words(mv, luma, chroma_dc, chroma_ac, update, *,
                       mb_w: int, mb_h: int, max_stripe_bytes: int,
                       tiered: bool = True):
    """Device CAVLC over one P frame's level tensors.

    mv [S, n, 2] (dy, dx) int; luma [S, n, 16, 4, 4] (raster 4×4 grid);
    chroma_dc [S, n, 2, 2, 2]; chroma_ac [S, n, 2, 4, 4, 4] (position 0
    zeroed); update [S] bool — stripes outside the mask pack nothing.

    The output stage runs at the rung of :func:`tier_words` that the
    frame's own bits pick; ``tiered=False`` keeps the one capacity-sized
    body, for a caller that runs the pack under ``jax.vmap``, where a
    batched index makes ``switch`` a ``select`` that runs every branch.

    Returns (words [cap_words] u32 — per-stripe P-slice payloads (post
    slice header, MSB-first) compacted back-to-back word-aligned;
    t_bits [S] i32; base_words [S] i32; overflow [S] bool).
    """
    S = mv.shape[0]
    n = mb_w * mb_h
    V = max_stripe_bytes // 4
    W = UNIT_WORDS
    cap_words = S * V

    mv = mv.astype(jnp.int32)
    luma = luma.astype(jnp.int32)
    chroma_dc = chroma_dc.astype(jnp.int32)
    chroma_ac = chroma_ac.astype(jnp.int32)
    upd = update.astype(bool)

    # ---- per-block totalCoeff and cbp ------------------------------------
    lt = (luma != 0).sum((-1, -2)).astype(jnp.int32)         # [S, n, 16]
    cact = (chroma_ac != 0).sum((-1, -2)).astype(jnp.int32)  # [S, n, 2, 4]
    cdct = (chroma_dc != 0).sum((-1, -2)).astype(jnp.int32)  # [S, n, 2]

    nz88 = (lt > 0).reshape(S, n, 2, 2, 2, 2).any(axis=(3, 5))  # [S,n,2,2]
    w88 = jnp.asarray([[1, 2], [4, 8]], jnp.int32)
    cbp_luma = (nz88 * w88[None, None]).sum((-1, -2))
    has_cac = (cact > 0).any((-1, -2))
    has_cdc = (cdct > 0).any(-1)
    cbp_chroma = jnp.where(has_cac, 2, jnp.where(has_cdc, 1, 0))
    cbp = cbp_luma | (cbp_chroma << 4)
    any_coeff = cbp > 0                                      # [S, n]

    # ---- MV prediction, skip decision, mvd (§8.4.1) ----------------------
    mvg = mv.reshape(S, mb_h, mb_w, 2)
    zpad = functools.partial(jnp.pad, mode="constant")
    a = zpad(mvg, ((0, 0), (0, 0), (1, 0), (0, 0)))[:, :, :-1]   # left
    b = zpad(mvg, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]      # top
    c_tr = zpad(mvg, ((0, 0), (1, 0), (0, 1), (0, 0)))[:, :-1, 1:]
    d_tl = zpad(mvg, ((0, 0), (1, 0), (1, 0), (0, 0)))[:, :-1, :-1]
    col = jnp.arange(mb_w, dtype=jnp.int32)[None, None, :]
    row = jnp.arange(mb_h, dtype=jnp.int32)[None, :, None]
    a_av = col > 0
    b_av = row > 0
    ctr_av = (row > 0) & (col + 1 < mb_w)
    d_av = (row > 0) & (col > 0)
    c = jnp.where(ctr_av[..., None], c_tr,
                  jnp.where(d_av[..., None], d_tl, 0))
    c_av = ctr_av | d_av

    med = jnp.maximum(jnp.minimum(a, b),
                      jnp.minimum(jnp.maximum(a, b), c))
    only_a = a_av & ~b_av & ~c_av
    pred = jnp.where(only_a[..., None], a, med)              # [S,mh,mw,2]

    a_zero = (a == 0).all(-1)
    b_zero = (b == 0).all(-1)
    skip_mv = jnp.where((~a_av | ~b_av | a_zero | b_zero)[..., None],
                        0, pred)
    anyc_g = any_coeff.reshape(S, mb_h, mb_w)
    skip = ~anyc_g & (mvg == skip_mv).all(-1)
    coded = (~skip).reshape(S, n)

    mvd = ((mvg - pred) * 4).reshape(S, n, 2)                # qpel

    # ---- mb_skip_run + trailing run (prefix-max over raster order) -------
    idx = jnp.arange(n, dtype=jnp.int32)[None, :]
    masked = jnp.where(coded, idx, -1)
    run_max = jax.lax.associative_scan(jnp.maximum, masked, axis=1)
    prev_coded = jnp.concatenate(
        [jnp.full((S, 1), -1, jnp.int32), run_max[:, :-1]], axis=1)
    skip_run = idx - prev_coded - 1
    tail_run = n - 1 - run_max[:, -1]                        # [S]

    # ---- header unit slots [S, n, 6] -------------------------------------
    sr_b, sr_l = _ue_dev(skip_run)
    mx_b, mx_l = _se_dev(mvd[..., 1])                        # x first
    my_b, my_l = _se_dev(mvd[..., 0])
    cn = jnp.take(jnp.asarray(_CBP_INTER_CODENUM), cbp)
    cb_b, cb_l = _ue_dev(cn)
    one_u32 = jnp.ones_like(sr_b)
    hdr_bits = jnp.stack(
        [sr_b, one_u32, mx_b, my_b, cb_b, one_u32], axis=-1)
    hdr_lens = jnp.stack(
        [sr_l, jnp.ones_like(sr_l), mx_l, my_l, cb_l,
         any_coeff.astype(jnp.int32)], axis=-1)
    gate_mb = (coded & upd[:, None]).astype(jnp.int32)
    hdr_lens = hdr_lens * gate_mb[..., None]

    # ---- nC grids (neighbor totalCoeff; -1 = unavailable) ----------------
    def _nc_from_grid(grid):
        left = jnp.pad(grid, ((0, 0), (0, 0), (1, 0)),
                       constant_values=-1)[:, :, :-1]
        top = jnp.pad(grid, ((0, 0), (1, 0), (0, 0)),
                      constant_values=-1)[:, :-1]
        both = (left >= 0) & (top >= 0)
        return jnp.where(both, (left + top + 1) >> 1,
                         jnp.where(left >= 0, left,
                                   jnp.where(top >= 0, top, 0)))

    lgrid = lt.reshape(S, mb_h, mb_w, 4, 4).transpose(0, 1, 3, 2, 4) \
        .reshape(S, mb_h * 4, mb_w * 4)
    nc_l = _nc_from_grid(lgrid).reshape(S, mb_h, 4, mb_w, 4) \
        .transpose(0, 1, 3, 2, 4).reshape(S, n, 16)

    def _nc_chroma(totals):                                  # [S, n, 4]
        grid = totals.reshape(S, mb_h, mb_w, 2, 2) \
            .transpose(0, 1, 3, 2, 4).reshape(S, mb_h * 2, mb_w * 2)
        return _nc_from_grid(grid).reshape(S, mb_h, 2, mb_w, 2) \
            .transpose(0, 1, 3, 2, 4).reshape(S, n, 4)

    nc_cb = _nc_chroma(cact[:, :, 0])
    nc_cr = _nc_chroma(cact[:, :, 1])

    # ---- residual units ---------------------------------------------------
    zz = jnp.asarray(_ZIGZAG4)
    lscan = luma.reshape(S, n, 16, 16)[..., zz]              # [S,n,16,16]
    lu_bits, lu_lens, lu_ovf = _code_blocks(
        lscan.reshape(-1, 16), nc_l.reshape(-1), 16, False)
    NSL = 2 * 16 + 2
    lu_bits = lu_bits.reshape(S, n, 16, NSL)
    lu_lens = lu_lens.reshape(S, n, 16, NSL)
    b8 = jnp.asarray(
        [(r // 2) * 2 + (c // 2) for r in range(4) for c in range(4)],
        jnp.int32)
    lu_gate = ((cbp_luma[..., None] >> b8[None, None]) & 1) \
        * gate_mb[..., None]
    lu_lens = lu_lens * lu_gate[..., None]
    lu_ovf = (lu_ovf.reshape(S, n, 16) & (lu_gate > 0)).any((-1, -2))

    cdc_scan = chroma_dc.reshape(S, n, 2, 4)                 # raster = scan
    cd_bits, cd_lens, cd_ovf = _code_blocks(
        cdc_scan.reshape(-1, 4), None, 4, True)
    NSC = 2 * 4 + 2
    cd_bits = cd_bits.reshape(S, n, 2, NSC)
    cd_lens = cd_lens.reshape(S, n, 2, NSC)
    cd_gate = (cbp_chroma >= 1).astype(jnp.int32) * gate_mb
    cd_lens = cd_lens * cd_gate[..., None, None]
    cd_ovf = (cd_ovf.reshape(S, n, 2) & (cd_gate > 0)[..., None]) \
        .any((-1, -2))

    cac_scan = chroma_ac.reshape(S, n, 2, 4, 16)[..., zz[1:]]  # [S,n,2,4,15]
    nc_c = jnp.stack([nc_cb, nc_cr], axis=2)                 # [S, n, 2, 4]
    ca_bits, ca_lens, ca_ovf = _code_blocks(
        cac_scan.reshape(-1, 15), nc_c.reshape(-1), 15, False)
    NSA = 2 * 15 + 2
    ca_bits = ca_bits.reshape(S, n, 8, NSA)
    ca_lens = ca_lens.reshape(S, n, 8, NSA)
    ca_gate = (cbp_chroma == 2).astype(jnp.int32) * gate_mb
    ca_lens = ca_lens * ca_gate[..., None, None]
    ca_ovf = (ca_ovf.reshape(S, n, 8) & (ca_gate > 0)[..., None]) \
        .any((-1, -2))

    # ---- unit sequence: [hdr, luma×16 (z-scan), cdc×2, cac×8] per MB -----
    SLOT = NSL                                               # 34 = max

    def padslots(x, ns):
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, SLOT - ns)))

    lscan_order = jnp.asarray(_LUMA_SCAN)
    u_bits = jnp.concatenate([
        padslots(hdr_bits[:, :, None, :], 6),
        lu_bits[:, :, lscan_order],
        padslots(cd_bits, NSC),
        padslots(ca_bits, NSA),
    ], axis=2)                                               # [S, n, 27, SLOT]
    u_lens = jnp.concatenate([
        padslots(hdr_lens[:, :, None, :], 6),
        lu_lens[:, :, lscan_order],
        padslots(cd_lens, NSC),
        padslots(ca_lens, NSA),
    ], axis=2)

    tr_b, tr_l = _ue_dev(tail_run)
    tail_bits = jnp.zeros((S, 1, SLOT), jnp.uint32) \
        .at[:, 0, 0].set(tr_b)
    tail_lens = jnp.zeros((S, 1, SLOT), jnp.int32).at[:, 0, 0].set(
        tr_l * (tail_run > 0).astype(jnp.int32)
        * upd.astype(jnp.int32))

    U = n * 27 + 1
    all_bits = jnp.concatenate(
        [u_bits.reshape(S, n * 27, SLOT), tail_bits], axis=1)
    all_lens = jnp.concatenate(
        [u_lens.reshape(S, n * 27, SLOT), tail_lens], axis=1)

    # ---- pack + globalize + compact --------------------------------------
    words_u, Lb, unit_ovf = _pack_units(
        all_bits.reshape(S * U, SLOT), all_lens.reshape(S * U, SLOT), W)
    cs0, cs1, g0, e, t_bits = _unit_spans(
        words_u.reshape(S, U, W), Lb.reshape(S, U))
    wc = jnp.minimum((t_bits + 31) // 32, V)
    base_words = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(wc)[:-1].astype(jnp.int32)])

    def output_stage(v_out: int):
        def run(cs0, cs1, g0, e, wc, base_words):
            out = _compact(_stripe_words(cs0, cs1, g0, e, v_out, W),
                           wc, base_words)
            return jnp.pad(out, (0, cap_words - S * v_out))
        return run

    # same words on every rung: a frame takes a rung only if every
    # stripe's wc fits it, so S * v_out slots hold the whole frame and the
    # rest of the buffer is the zeros the capacity-sized body puts there
    rungs = tier_words(max_stripe_bytes, n) if tiered else (V,)
    words = jax.lax.switch(
        tier_index(t_bits, rungs), [output_stage(v) for v in rungs],
        cs0, cs1, g0, e, wc, base_words)

    # a slot may span at most 2 words (len ≤ 32); exp-Golomb header slots
    # are the only unbounded-by-table lengths and stay ≤ 31 bits for any
    # n_mb < 32767 — flag the stripe rather than corrupt if exceeded
    hdr_slot_ovf = (hdr_lens > 32).any((-1, -2))
    overflow = (lu_ovf | cd_ovf | ca_ovf | hdr_slot_ovf
                | (t_bits > 32 * V)
                | unit_ovf.reshape(S, U).any(-1)) & upd
    return words, t_bits, base_words, overflow


@phase("entropy")
def pack_p_frame(mv, luma, chroma_dc, chroma_ac, damage, update, *,
                 mb_w: int, mb_h: int, max_stripe_bytes: int,
                 tiered: bool = True):
    """Fetchable uint8 buffer: [S, HEAD_BYTES] head + big-endian payload.

    Head per stripe: t_bits u32 LE, base_words u32 LE, damage u8,
    overflow u8, 2 pad bytes.  Payload: the compacted words serialized
    MSB-first (big-endian), so byte i of a stripe's payload carries its
    bits 8i..8i+7."""
    words, t_bits, base_words, overflow = pack_p_frame_words(
        mv, luma, chroma_dc, chroma_ac, update,
        mb_w=mb_w, mb_h=mb_h, max_stripe_bytes=max_stripe_bytes,
        tiered=tiered)
    S = t_bits.shape[0]

    def le4(x):
        x = x.astype(jnp.uint32)
        return jnp.stack([(x >> (8 * i)) & 0xFF for i in range(4)],
                         axis=1).astype(jnp.uint8)

    head = jnp.concatenate([
        le4(t_bits), le4(base_words),
        damage.astype(jnp.uint8)[:, None],
        overflow.astype(jnp.uint8)[:, None],
        jnp.zeros((S, 2), jnp.uint8),
    ], axis=1)
    payload = jnp.stack([
        (words >> 24) & 0xFF, (words >> 16) & 0xFF,
        (words >> 8) & 0xFF, words & 0xFF,
    ], axis=-1).astype(jnp.uint8).reshape(-1)
    return jnp.concatenate([head.reshape(-1), payload])


# ---------------------------------------------------------------------------
# host-side glue: slice header + payload + trailing + EP escape → NAL


def parse_cavlc_head(host: np.ndarray, n_stripes: int):
    """(t_bits, base_words, damage, ovf) from a fetched head prefix."""
    h = np.asarray(host[:HEAD_BYTES * n_stripes], np.uint8) \
        .reshape(n_stripes, HEAD_BYTES)
    w = (1 << (8 * np.arange(4, dtype=np.int64)))
    t_bits = (h[:, 0:4].astype(np.int64) * w).sum(1)
    base_words = (h[:, 4:8].astype(np.int64) * w).sum(1)
    return t_bits, base_words, h[:, 8] != 0, h[:, 9] != 0


def _p_slice_header_bits(qp: int, frame_num: int) -> List[int]:
    """Bit list for the P slice header native/cavlc.cpp writes
    (deblocking disabled, single slice, first_mb 0)."""
    bits: List[int] = []

    def u(v, nb):
        for i in range(nb - 1, -1, -1):
            bits.append((v >> i) & 1)

    def ue(v):
        vp1 = v + 1
        nb = vp1.bit_length() - 1
        u(0, nb)
        u(vp1, nb + 1)

    def se(v):
        ue(-2 * v if v <= 0 else 2 * v - 1)

    ue(0)                       # first_mb_in_slice
    ue(5)                       # slice_type: P (all)
    ue(0)                       # pps id
    u(frame_num & 0xF, 4)
    u(0, 1)                     # num_ref_idx_active_override
    u(0, 1)                     # ref_pic_list_modification_l0
    u(0, 1)                     # adaptive_ref_pic_marking
    se(qp - 26)                 # slice_qp_delta
    ue(1)                       # disable_deblocking_filter_idc
    return bits


def _ep_escape(rbsp: np.ndarray) -> bytes:
    """Emulation-prevention escaping with the sequential reset semantics
    (an accepted escape restarts the zero-run count), vectorized over
    the rare candidate positions."""
    a = np.asarray(rbsp, np.uint8)
    if len(a) < 3:
        return a.tobytes()
    z = a == 0
    cand = np.flatnonzero(z[:-2] & z[1:-1] & (a[2:] <= 3)) + 2
    if cand.size == 0:
        return a.tobytes()
    accepted = []
    last = -10
    for j in cand:
        if j == last + 1:       # inserted 0x03 reset the zero run
            continue
        accepted.append(j)
        last = j
    return np.insert(a, accepted, 3).tobytes()


def assemble_p_slice(payload: np.ndarray, nbits: int, qp: int,
                     frame_num: int) -> bytes:
    """One Annex-B P-slice NAL from a device-packed payload.

    payload: uint8 big-endian bit buffer (≥ ceil(nbits/8) bytes, bits
    past ``nbits`` zero).  Bit-exact with h264_encode_picture's P path.
    """
    hdr = _p_slice_header_bits(qp, frame_num)
    k = len(hdr)
    npay = (nbits + 7) // 8
    pb = np.asarray(payload[:npay], np.uint8)
    total_bits = k + nbits + 1                  # + rbsp stop bit
    nbytes = (total_bits + 7) // 8
    out = np.zeros(nbytes + 1, np.uint8)
    hb = np.packbits(np.asarray(hdr, np.uint8))
    out[:len(hb)] = hb
    base, s = k // 8, k % 8
    if s == 0:
        out[base:base + npay] = pb
    else:
        out[base:base + npay] |= pb >> s
        out[base + 1:base + 1 + npay] |= (
            (pb.astype(np.uint16) << (8 - s)) & 0xFF).astype(np.uint8)
    stop = k + nbits
    out[stop >> 3] |= 0x80 >> (stop & 7)
    return (b"\x00\x00\x00\x01" + bytes(((3 << 5) | 1,))
            + _ep_escape(out[:nbytes]))


def payload_slice(host: np.ndarray, n_stripes: int,
                  base_words: np.ndarray, t_bits: np.ndarray,
                  i: int) -> Tuple[np.ndarray, int]:
    """(payload bytes, nbits) for stripe ``i`` of a fetched buffer."""
    start = HEAD_BYTES * n_stripes + int(base_words[i]) * 4
    nbits = int(t_bits[i])
    return host[start:start + ((nbits + 31) // 32) * 4], nbits
