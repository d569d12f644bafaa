"""What a kernel's algorithm needs, from its shapes, and the chip's peaks.
Kept with the benchmark so that no later PR can move the yardstick."""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       "add it with its source; there is no default")
    return table[device_kind]


def me_ops_and_bytes(stripes: int, stripe_h: int, width: int,
                     search: int = 12) -> Tuple[float, float]:
    """Full-search motion estimation plus compensation over luma stripes
    ``[stripes, stripe_h, width]`` (``ops/pallas_me.me_mc_stripes``).

    Operations: every luma pixel is compared at each of (2*search+1)^2
    offsets, a difference and an accumulate each. Bytes: the planes read
    and written once: current and reference luma in, the reference's two
    4:2:0 chroma planes in, the predicted luma and chroma out, and one
    motion vector (two int32) per 16x16 macroblock out."""
    luma = stripes * stripe_h * width
    ops = (2 * search + 1) ** 2 * luma * 2.0
    chroma = 2 * (luma // 4)
    mvs = (luma // 256) * 2 * 4
    bytes_ = 2 * luma + chroma + luma + chroma + mvs
    return ops, float(bytes_)


def roofline_pct(ops: float, bytes_: float, seconds: float,
                 peak: Dict[str, float], ops_peak: str) -> Tuple[float, str]:
    """(share of the roofline in %, which bound it is): the least time the
    chip could take over the time it took."""
    t_ops = ops / peak[ops_peak]
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    least, bound = (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
    return 100.0 * least / seconds, bound
