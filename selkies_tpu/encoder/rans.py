"""rANS entropy coding prototype — the BASELINE config-3 decision spike.

Context (SURVEY.md §7 hard part 1): after the
JPEG-stripe latency data landed, the deferred decision was whether a
learned-codec/rANS profile should replace or join the Huffman scan. This
module is the measurement instrument for that gate: a correct,
round-trip-tested range-ANS coder over the *same* quantized, zigzagged
DCT planes the device pipeline emits, with per-frame adaptive symbol
models — i.e. the best entropy stage a config-3 profile could put behind
the existing transform, measured on identical inputs.

Model: the JPEG symbol decomposition ((run,size) pairs + raw value bits,
DC diffs per component with stripe-reset prediction) with per-frame
adaptive frequencies, 12-bit quantized, transmitted as a table header.
Value bits are interleaved raw (rANS codes only the modelled symbols, as
in JPEG: value bits are already near-uniform). This keeps the comparison
apples-to-apples: identical symbol stream, Huffman lengths vs adaptive
arithmetic lengths.

The coder is host/numpy (the gate measures *bits*, not device time; the
device-side cost model is in docs/config3_decision.md). 32-bit rANS,
16-bit renormalization, single stream per stripe so stripes stay
independently decodable like the JPEG scans they would replace.
"""

from __future__ import annotations

import struct
from typing import List, Tuple

import numpy as np

RANS_L = 1 << 16          # lower bound: with 16-bit renorm the state
                          # stays in [2^16, 2^32) — a u32 on the wire
PROB_BITS = 12            # quantized probability resolution
PROB_SCALE = 1 << PROB_BITS


# ------------------------------------------------------------ symbolization


def _bitlen(v: np.ndarray) -> np.ndarray:
    out = np.zeros_like(v)
    a = np.abs(v)
    nz = a > 0
    out[nz] = np.floor(np.log2(a[nz])).astype(v.dtype) + 1
    return out


def symbolize_block_plane(plane: np.ndarray,
                          dc_reset_every: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[N, 64] zigzag blocks → (symbols, value_bits, value_lens).

    Symbols (one alphabet, 512 wide):
      0..255    AC (run<<4 | size), run 0-15, size 1-10 (+ ZRL 0xF0, EOB 0x00)
      256..267  DC size 0-11
    DC prediction resets every ``dc_reset_every`` blocks (stripe bounds).
    """
    n = plane.shape[0]
    syms: List[int] = []
    vbits: List[int] = []
    vlens: List[int] = []
    pred = 0
    for i in range(n):
        if i % dc_reset_every == 0:
            pred = 0
        blk = plane[i]
        dc = int(blk[0])
        diff = dc - pred
        pred = dc
        size = int(_bitlen(np.asarray([diff]))[0])
        syms.append(256 + size)
        if size:
            raw = diff if diff > 0 else diff + (1 << size) - 1
            vbits.append(raw & ((1 << size) - 1))
            vlens.append(size)
        run = 0
        for k in range(1, 64):
            v = int(blk[k])
            if v == 0:
                run += 1
                continue
            while run >= 16:
                syms.append(0xF0)
                run -= 16
            size = int(_bitlen(np.asarray([v]))[0])
            syms.append((run << 4) | size)
            raw = v if v > 0 else v + (1 << size) - 1
            vbits.append(raw & ((1 << size) - 1))
            vlens.append(size)
            run = 0
        if run:
            syms.append(0x00)
    return (np.asarray(syms, np.int32), np.asarray(vbits, np.int64),
            np.asarray(vlens, np.int32))


# ------------------------------------------------------------------ model


def build_model(symbols: np.ndarray, alphabet: int = 268) -> np.ndarray:
    """Quantized per-frame frequency table: [alphabet] uint16 summing to
    PROB_SCALE, every present symbol ≥ 1."""
    counts = np.bincount(symbols, minlength=alphabet).astype(np.float64)
    present = counts > 0
    if not present.any():
        freqs = np.zeros(alphabet, np.int64)
        freqs[0] = PROB_SCALE
        return freqs.astype(np.uint16)
    scaled = counts * (PROB_SCALE / counts.sum())
    freqs = np.maximum(np.round(scaled).astype(np.int64), present.astype(np.int64))
    # exact renormalization to PROB_SCALE: trim/boost the largest entries
    while freqs.sum() != PROB_SCALE:
        delta = PROB_SCALE - int(freqs.sum())
        idx = int(np.argmax(freqs)) if delta < 0 else int(np.argmax(counts))
        step = max(1, abs(delta) // 2) * (1 if delta > 0 else -1)
        if freqs[idx] + step < 1:
            step = 1 - int(freqs[idx])
        freqs[idx] += step
    return freqs.astype(np.uint16)


def model_header(freqs: np.ndarray) -> bytes:
    """Sparse table serialization: u16 count, then (u16 sym, u16 freq)."""
    nz = np.flatnonzero(freqs)
    out = struct.pack("<H", len(nz))
    for s in nz:
        out += struct.pack("<HH", int(s), int(freqs[s]))
    return out


def parse_model_header(data: bytes, alphabet: int = 268
                       ) -> Tuple[np.ndarray, int]:
    if len(data) < 2:
        raise ValueError("malformed rANS stream: header truncated")
    (n,) = struct.unpack_from("<H", data)
    if 2 + 4 * n > len(data):
        raise ValueError("malformed rANS stream: model table truncated")
    freqs = np.zeros(alphabet, np.int64)
    pos = 2
    for _ in range(n):
        s, f = struct.unpack_from("<HH", data, pos)
        if s >= alphabet:
            raise ValueError(f"malformed rANS stream: symbol {s} outside "
                             f"alphabet {alphabet}")
        freqs[s] = f
        pos += 4
    if int(freqs.sum()) != PROB_SCALE:
        raise ValueError("malformed rANS stream: model does not sum to "
                         "PROB_SCALE")
    return freqs.astype(np.uint16), pos


# ------------------------------------------------------------------ coder


def rans_encode(symbols: np.ndarray, freqs: np.ndarray) -> bytes:
    """Single-stream 32-bit rANS, 16-bit renorm, encoded in reverse so the
    decoder reads forward."""
    cum = np.zeros(len(freqs) + 1, np.int64)
    np.cumsum(freqs, out=cum[1:])
    state = RANS_L
    out: List[int] = []                  # u16 words, reversed at the end
    x_max_base = ((RANS_L >> PROB_BITS) << 16)
    for s in symbols[::-1]:
        f = int(freqs[s])
        # renormalize: stream out low 16 bits while state too large
        x_max = x_max_base * f
        while state >= x_max:
            out.append(state & 0xFFFF)
            state >>= 16
        state = ((state // f) << PROB_BITS) + (state % f) + int(cum[s])
    header = struct.pack("<I", state)
    body = np.asarray(out[::-1], np.uint16).tobytes()
    return header + body


def rans_decode(data: bytes, freqs: np.ndarray, count: int) -> np.ndarray:
    cum = np.zeros(len(freqs) + 1, np.int64)
    np.cumsum(freqs, out=cum[1:])
    # slot → symbol lookup
    slot2sym = np.zeros(PROB_SCALE, np.int32)
    for s in np.flatnonzero(freqs):
        slot2sym[cum[s]:cum[s + 1]] = s
    if len(data) < 4:
        raise ValueError("malformed rANS stream: state header truncated")
    (state,) = struct.unpack_from("<I", data)
    words = np.frombuffer(data[4:len(data) - (len(data) - 4) % 2], np.uint16)
    wi = 0
    out = np.empty(count, np.int32)
    for i in range(count):
        slot = state & (PROB_SCALE - 1)
        s = int(slot2sym[slot])
        out[i] = s
        f = int(freqs[s])
        state = f * (state >> PROB_BITS) + slot - int(cum[s])
        while state < RANS_L:
            if wi >= len(words):
                raise ValueError("rans stream truncated")
            state = (state << 16) | int(words[wi])
            wi += 1
    return out


# ----------------------------------------------------------- value bits


def pack_value_bits(vbits: np.ndarray, vlens: np.ndarray) -> bytes:
    """MSB-first concatenation of the raw value-bit fields."""
    total = int(vlens.sum())
    buf = bytearray((total + 7) // 8)
    pos = 0
    for v, ln in zip(vbits.tolist(), vlens.tolist()):
        for b in range(ln - 1, -1, -1):
            if (v >> b) & 1:
                buf[pos >> 3] |= 0x80 >> (pos & 7)
            pos += 1
    return bytes(buf)


def unpack_value_bits(data: bytes, vlens: np.ndarray) -> np.ndarray:
    if int(vlens.sum() if len(vlens) else 0) > len(data) * 8:
        raise ValueError("malformed rANS stream: value bits truncated")
    out = np.empty(len(vlens), np.int64)
    pos = 0
    for i, ln in enumerate(vlens.tolist()):
        v = 0
        for _ in range(ln):
            bit = (data[pos >> 3] >> (7 - (pos & 7))) & 1
            v = (v << 1) | bit
            pos += 1
        out[i] = v
    return out


# --------------------------------------------------------------- profile


def encode_planes(yq: np.ndarray, cbq: np.ndarray, crq: np.ndarray,
                  blocks_per_stripe_y: int) -> bytes:
    """Full config-3 candidate bitstream for one frame's planes: adaptive
    model header + rANS symbol stream + raw value bits, per component
    class (luma / chroma) like JPEG's table split."""
    y2 = yq.reshape(-1, 64)
    c2 = np.concatenate([cbq.reshape(-1, 64), crq.reshape(-1, 64)])
    out = b""
    for plane, reset in ((y2, blocks_per_stripe_y),
                         (c2, max(1, blocks_per_stripe_y // 4))):
        syms, vbits, vlens = symbolize_block_plane(plane, reset)
        freqs = build_model(syms)
        stream = rans_encode(syms, freqs)
        values = pack_value_bits(vbits, vlens)
        hdr = model_header(freqs)
        out += struct.pack("<III", len(syms), len(stream), len(values))
        out += hdr + stream + values
    return out


def decode_planes(data: bytes, y_blocks: int, c_blocks: int,
                  blocks_per_stripe_y: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of encode_planes → ([y_blocks, 64], [c_blocks, 64])."""
    pos = 0
    planes = []
    for n_blocks, reset in ((y_blocks, blocks_per_stripe_y),
                            (c_blocks, max(1, blocks_per_stripe_y // 4))):
        if pos + 12 > len(data):
            raise ValueError("malformed rANS stream: plane header truncated")
        nsym, nstream, nvalues = struct.unpack_from("<III", data, pos)
        pos += 12
        if pos + nstream + nvalues > len(data):
            raise ValueError("malformed rANS stream: plane sizes exceed data")
        # a block emits ≤ 65 symbols (DC + 64 AC/EOB) and ≤ 65 values, so
        # an untrusted 32-bit count beyond that is an attack, not a frame —
        # without this bound a ~30-byte blob forces a multi-GB allocation
        # and a near-unbounded decode loop
        if nsym > n_blocks * 65 or nvalues > n_blocks * 65 * 8:
            raise ValueError("malformed rANS stream: counts exceed geometry")
        freqs, consumed = parse_model_header(data[pos:])
        pos += consumed
        syms = rans_decode(data[pos:pos + nstream], freqs, nsym)
        pos += nstream
        values_raw = data[pos:pos + nvalues]
        pos += nvalues
        # reconstruct blocks from the symbol stream
        vlens = []
        for s in syms.tolist():
            if s >= 256:
                vlens.append(s - 256)
            elif s not in (0x00, 0xF0):
                vlens.append(s & 15)
        vlens_arr = np.asarray([l for l in vlens if l > 0], np.int32)
        vals = unpack_value_bits(values_raw, vlens_arr)
        blocks = np.zeros((n_blocks, 64), np.int16)
        n_syms = len(syms)
        n_vals = len(vals)
        vi = 0
        si = 0
        pred = 0

        def _bad(what: str) -> ValueError:
            # corrupt/truncated input must surface as a clean decode
            # error, not an IndexError, before this coder ever fronts
            # untrusted wire data
            return ValueError(f"malformed rANS stream: {what} "
                              f"(block {b}, si={si}, vi={vi})")

        for b in range(n_blocks):
            if b % reset == 0:
                pred = 0
            if si >= n_syms:
                raise _bad("symbol stream exhausted at DC")
            s = int(syms[si]); si += 1
            size = s - 256
            if not 0 <= size <= 15:
                raise _bad(f"DC symbol {s} out of range")
            if size:
                if vi >= n_vals:
                    raise _bad("value stream exhausted at DC")
                raw = int(vals[vi]); vi += 1
                diff = raw if raw >= (1 << (size - 1)) \
                    else raw - (1 << size) + 1
            else:
                diff = 0
            pred += diff
            blocks[b, 0] = pred
            k = 1
            while k < 64:
                if si >= n_syms:
                    raise _bad("symbol stream exhausted mid-block")
                s = int(syms[si]); si += 1
                if s == 0x00:
                    break
                if s == 0xF0:
                    k += 16
                    continue
                if not 0 <= s <= 0xFF:
                    raise _bad(f"AC symbol {s} out of range")
                run, size = s >> 4, s & 15
                if size == 0:
                    raise _bad(f"AC symbol {s:#x} has zero size")
                k += run
                if k >= 64:
                    raise _bad(f"run overflows block ({k})")
                if vi >= n_vals:
                    raise _bad("value stream exhausted mid-block")
                raw = int(vals[vi]); vi += 1
                v = raw if raw >= (1 << (size - 1)) else raw - (1 << size) + 1
                blocks[b, k] = v
                k += 1
                if k == 64:
                    break
            # blocks that end exactly on coefficient 63 carry no EOB
        planes.append(blocks)
    return planes[0], planes[1]
