"""Device-side cost attribution for the tpuenc H.264 path (config 2).

Per-stage chained-dispatch timings include every dispatch's fixed host
round trip, NOT only device compute. The estimator that cancels it is
the **batch-size sweep**: time the batched scan program
(dev.encode_frame_p_batch_rgb, one dispatch for B frames) at two batch
sizes and take the slope,

    device_ms_per_frame = (T(B2) - T(B1)) / (chain * (B2 - B1)),

which cancels every fixed per-dispatch and per-fetch cost. Stage
attribution comes from re-running the sweep with a stage stubbed out
(``--attribute``): slope(full) - slope(without ME) ≈ ME's in-context
cost, etc. Host CAVLC is timed directly (it is host work).

Outputs one JSON line:
  device_ms_per_frame / device_fps  — slope estimate (fixed costs cancel)
  dispatch_overhead_ms              — fixed cost per batch dispatch
  fetch_floor_ms                    — one D2H round trip on this link
  me_ms / pack_ms / transform_ms    — in-context stage slopes (--attribute)
  me_tflops                         — analytic SAD FLOPs / measured ME time
  cavlc_ms_frame                    — host entropy coding per frame
  cavlc_scaling                     — CAVLC wall time at 1/2/4/8 pool threads

The sweep/attribution runs the HOST-entropy profile (entropy="host"):
this tool decomposes the sparse-levels + host-CAVLC path, and its stage
stubs target dev._pack_sparse / the native coder. The streaming default
is the on-device CAVLC tier (encoder/device_cavlc.py, docs/entropy.md);
its device cost shows up in the separate cavlc_pack_ms slope below.

Run: ``python tools/h264_stages.py [--frames N] [--attribute]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

W, H = 1920, 1080


def _sweep(enc, src, b1: int, b2: int, chain: int, reps: int):
    """Slope + intercept of the batched program's wall time vs B."""
    import jax.numpy as jnp

    def run_chain(B):
        frames = jnp.stack([src.next_frame() for _ in range(B)])
        pends = enc.dispatch_batch(frames, fetch=False)     # compile
        np.asarray(pends[-1].batch_heads[0, :64])           # real sync
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(chain):
                pends = enc.dispatch_batch(frames, fetch=False)
            np.asarray(pends[-1].batch_heads[0, :64])       # one tiny fetch
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        return best

    floor = run_chain_floor(enc, src)
    t1, t2 = run_chain(b1), run_chain(b2)
    slope = (t2 - t1) / (chain * (b2 - b1))
    per_dispatch = max(0.0, (t1 - floor) / chain - b1 * slope)
    return slope, per_dispatch, floor, (t1, t2)


def run_chain_floor(enc, src):
    """One tiny fetch with zero extra dispatches = the D2H round trip."""
    import jax.numpy as jnp

    frames = jnp.stack([src.next_frame() for _ in range(2)])
    pends = enc.dispatch_batch(frames, fetch=False)
    np.asarray(pends[-1].batch_heads[0, :64])
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(pends[-1].batch_heads[0, 64:128])
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    return best


def measure(width: int = W, height: int = H, b1: int = 6, b2: int = 12,
            chain: int = 4, reps: int = 3, attribute: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from selkies_tpu.capture.synthetic import DeviceScrollSource
    from selkies_tpu.encoder import h264_device as dev
    from selkies_tpu.encoder.h264 import H264StripeEncoder

    enc = H264StripeEncoder(width, height, entropy="host")
    src = DeviceScrollSource(width, enc.pad_h)
    enc.encode_frame(src.next_frame())          # IDR + compiles
    enc.encode_frame(src.next_frame())

    slope, per_dispatch, floor, raw = _sweep(enc, src, b1, b2, chain, reps)
    out = {
        "device_ms_per_frame": round(slope, 2),
        "device_fps": round(1000.0 / slope, 1) if slope > 0 else None,
        "dispatch_overhead_ms": round(per_dispatch, 2),
        "fetch_floor_ms": round(floor, 2),
        "sweep_raw_ms": [round(v, 1) for v in raw],
        "method": (
            f"slope of one-dispatch batched scan at B={b1} vs B={b2} "
            f"(chain={chain}, best-of-{reps}); cancels per-dispatch RPC"),
    }

    if attribute:
        # stage slopes by stubbing one stage at a time. A fresh encoder
        # object does NOT bust the module-level jit cache — the batched
        # program was already compiled with identical static args — so
        # the caches are cleared around each stubbed variant (this is a
        # standalone tool; recompiles are its cost, not the product's).
        real_me, real_pack = dev.me_mc_stripes, dev._pack_sparse

        def me_stub(cur, ref, ref_cb, ref_cr, search=12, interpret=None):
            S, h, w = cur.shape
            mv = jnp.zeros((S, h // 16, w // 16, 2), jnp.int32)
            return mv, ref, ref_cb, ref_cr

        def pack_stub(flat16, damage, update, cap_frac=4):
            S, Wd = flat16.shape
            _, n_cells, cap = dev.sparse_geometry(Wd, cap_frac)
            total = 4 * S + S * (n_cells // 8) + S * cap * dev.CELL
            return jnp.zeros((total,), jnp.uint8)

        try:
            jax.clear_caches()
            dev.me_mc_stripes = me_stub
            e2 = H264StripeEncoder(width, height, entropy="host")
            s2 = DeviceScrollSource(width, e2.pad_h)
            e2.encode_frame(s2.next_frame())
            e2.encode_frame(s2.next_frame())
            no_me, _, _, _ = _sweep(e2, s2, b1, b2, chain, reps)
        finally:
            dev.me_mc_stripes = real_me
        try:
            jax.clear_caches()
            dev._pack_sparse = pack_stub
            e3 = H264StripeEncoder(width, height, entropy="host")
            s3 = DeviceScrollSource(width, e3.pad_h)
            e3.encode_frame(s3.next_frame())
            e3.encode_frame(s3.next_frame())
            no_pack, _, _, _ = _sweep(e3, s3, b1, b2, chain, reps)
        finally:
            dev._pack_sparse = real_pack
            jax.clear_caches()

        me_ms = max(0.0, slope - no_me)
        pack_ms = max(0.0, slope - no_pack)
        out["me_ms"] = round(me_ms, 2)
        out["pack_ms"] = round(pack_ms, 2)
        out["transform_ms"] = round(max(0.0, slope - me_ms - pack_ms), 2)

        # analytic SAD FLOPs (abs-diff+sums+indicator matmul) / ME time
        S, sh = enc.n_stripes, enc.stripe_h
        n_off = (2 * enc.search + 1) ** 2
        nby, nbx = sh // 16, enc.pad_w // 16
        flops = n_off * S * (2 * nby * sh * enc.pad_w
                             + 2 * nby * enc.pad_w * nbx)
        out["me_tflops"] = round(flops / (me_ms / 1000.0) / 1e12, 2) \
            if me_ms > 0 else None

    # device-CAVLC tier: in-context slope of the streaming default's
    # batched program minus the host-tier program (both one-dispatch
    # scans; the difference is the device entropy pack net of the
    # sparse pack it replaces)
    try:
        jax.clear_caches()
        e4 = H264StripeEncoder(width, height)           # entropy="device"
        s4 = DeviceScrollSource(width, e4.pad_h)
        e4.encode_frame(s4.next_frame())
        e4.encode_frame(s4.next_frame())
        dev_slope, _, _, _ = _sweep(e4, s4, b1, b2, chain, reps)
        out["device_entropy_ms_per_frame"] = round(dev_slope, 2)
        out["cavlc_pack_ms"] = round(dev_slope - slope, 2)
    except Exception as e:
        out["device_entropy_error"] = repr(e)
    finally:
        jax.clear_caches()

    # host CAVLC: one frame fetched, then entropy-only timing; also its
    # scaling over pool sizes (headroom for 4K / multi-session)
    import concurrent.futures

    import selkies_tpu.encoder.h264 as h264mod

    pend = enc.dispatch(src.next_frame(), fetch=True)
    host = np.asarray(pend.fetch)
    scaling = {}
    saved_pool = h264mod._POOL
    try:
        for workers in (1, 2, 4, 8):
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="cavlc")
            h264mod._POOL = pool
            # re-encode the same fetched frame; harvest mutates
            # frame_num state, so rewind it between timings
            t0 = time.perf_counter()
            stripes = enc.harvest(pend, host=host)
            dt = (time.perf_counter() - t0) * 1000.0
            scaling[workers] = round(dt, 2)
            for st in enc.stripes:
                st.frame_num = (st.frame_num - 1) % 16
            pool.shutdown(wait=False)
    finally:
        h264mod._POOL = saved_pool
    out["cavlc_ms_frame"] = scaling[8]
    out["cavlc_scaling"] = scaling
    out["stripes_out"] = len(stripes)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=W)
    ap.add_argument("--height", type=int, default=H)
    ap.add_argument("--b1", type=int, default=6)
    ap.add_argument("--b2", type=int, default=12)
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--attribute", action="store_true",
                    help="also slope-attribute ME/pack/transform (slow)")
    args = ap.parse_args()
    out = measure(width=args.width, height=args.height, b1=args.b1,
                  b2=args.b2, chain=args.chain, reps=args.repeats,
                  attribute=args.attribute)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
