"""Multi-session encode benchmark (BASELINE config 5, single-chip slice).

Measures aggregate 1080p encode throughput with N desktop sessions on the
available devices, two ways:

  1. time-shared: each session runs its own pipelined solo encoder and the
     round-robin scheduler keeps the device queue full (round-1 mode);
  2. mesh-batched: every session's frame rides ONE sharded
     MeshStripeEncoder dispatch (the tpu_mesh product path) — on a
     multi-chip slice sessions are data-parallel over the "session" mesh
     axis; on one chip the batch amortizes per-dispatch overhead.

Plus the scaling-story series (ISSUE 14): a short swarm churn storm
(tools/swarm_run.py, device-free scheduler path) contributes
``sessions_per_chip``, ``fairness_jain_index``, and ``eviction_ms_p95``
so one run reports multi-tenant packing next to raw encoder throughput.

Refuses to run without a TPU (``runtime.require_tpu``). Prints ONE JSON
line with the better aggregate as the headline value, both breakdowns and
the device JAX reports; exits non-zero if any phase raised.
"""

from __future__ import annotations

import json
import sys
import time

N_SESSIONS = 4
W, H = 1920, 1080
WARMUP_FRAMES = 24
BENCH_FRAMES = 400           # across all sessions
MAX_SECONDS = 90.0


def bench_mesh() -> dict:
    """Mesh-batched aggregate: one sharded dispatch per tick for all N.

    The mesh geometry honors the full ``session:N,stripe:M`` form of the
    ``tpu_mesh`` setting (env ``SELKIES_TPU_MESH``) instead of
    hardcoding the stripe axis to 1 (ISSUE 15 satellite) — so this
    bench runs on real 2-D meshes: M > 1 stripe-shards every session's
    frame across chips on top of the session data-parallelism."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from selkies_tpu.parallel import MeshStripeEncoder, parse_mesh_spec

    devices = jax.devices()
    n_dev = len(devices)
    spec = os.environ.get("SELKIES_TPU_MESH", "") or f"session:{n_dev}"
    mesh = parse_mesh_spec(spec, devices)
    n_sess_ax = mesh.shape["session"]
    per_chip = max(1, N_SESSIONS // n_sess_ax)
    n_sessions = per_chip * n_sess_ax
    enc = MeshStripeEncoder(mesh, n_sessions, W, H)

    # device-resident scrolling batch: full damage every tick, no H2D cost,
    # same "scroll" content as the solo bench (noise would quadruple the
    # bitstream and measure the D2H link instead of the encoder)
    from selkies_tpu.capture.synthetic import SyntheticSource

    base = np.stack([
        np.pad(SyntheticSource(W, H, pattern="scroll", seed=i)._bg,
               ((0, enc.pad_h - H), (0, enc.pad_w - W), (0, 0)), mode="edge")
        for i in range(n_sessions)])
    batch = jnp.asarray(base)
    roll = jax.jit(lambda b: jnp.roll(b, -8, axis=1))

    for _ in range(3):
        batch = roll(batch)
        enc.encode_frames(batch)

    frames = 0
    total_bytes = 0
    d2h_bytes = 0
    fetch_ms = []
    dispatch_ms = []
    pack_ms = []
    ticks = max(1, BENCH_FRAMES // n_sessions)
    from collections import deque

    def dispatch_timed(b):
        # per-shard stage truth (ISSUE 13 satellite): the mesh path's
        # dispatch/fetch/pack decomposition, same stage names as the
        # solo flight recorder so MULTICHIP and BENCH rows compare
        t0 = time.perf_counter()
        p = enc.dispatch(b)
        dispatch_ms.append((time.perf_counter() - t0) * 1000.0)
        return p

    def harvest_timed(p):
        # per-shard fetch truth (ISSUE 1 satellite — MULTICHIP files
        # carried no transfer numbers): wall time until the dispatched
        # tick's prefix is host-readable, and its aggregate byte size
        nonlocal d2h_bytes
        t0 = time.perf_counter()
        p.prefix.block_until_ready()
        t1 = time.perf_counter()
        fetch_ms.append((t1 - t0) * 1000.0)
        d2h_bytes += int(np.prod(p.prefix.shape)) * p.prefix.dtype.itemsize
        out = enc.harvest(p)
        pack_ms.append((time.perf_counter() - t1) * 1000.0)
        return out

    start = time.perf_counter()
    pending = deque()
    for _ in range(ticks):
        if time.perf_counter() - start > MAX_SECONDS / 2:
            break
        batch = roll(batch)
        pending.append(dispatch_timed(batch))  # overlap: 2 steps in flight
        if len(pending) >= 3:
            out, _bytes = harvest_timed(pending.popleft())
            frames += sum(1 for s in out if s)
            total_bytes += sum(len(st.jpeg) for s in out for st in s)
    while pending:
        out, _bytes = harvest_timed(pending.popleft())
        frames += sum(1 for s in out if s)
        total_bytes += sum(len(st.jpeg) for s in out for st in s)
    elapsed = time.perf_counter() - start
    fps = frames / elapsed if elapsed > 0 else 0.0
    fetch_sorted = sorted(fetch_ms) or [0.0]

    def p(vals, q):
        s = sorted(vals) or [0.0]
        return round(s[min(len(s) - 1, int(len(s) * q / 100))], 2)

    return {
        # per-shard stage breakdown (tick-granular: one dispatch covers
        # every shard's sessions, so per-frame cost is value/n_sessions)
        "mesh_stage_breakdown": {
            "dispatch": {"p50_ms": p(dispatch_ms, 50),
                         "p95_ms": p(dispatch_ms, 95)},
            "fetch_wait": {"p50_ms": p(fetch_ms, 50),
                           "p95_ms": p(fetch_ms, 95)},
            "pack": {"p50_ms": p(pack_ms, 50),
                     "p95_ms": p(pack_ms, 95)},
        },
        "mesh_aggregate_fps": round(fps, 2),
        "mesh_sessions": n_sessions,
        # the devices the mesh actually spans (a SELKIES_TPU_MESH spec
        # may use fewer than the host has) — per-chip derivations from
        # this output must divide by this, not the host inventory
        "mesh_devices": int(mesh.devices.size),
        "mesh_spec": (f"session:{n_sess_ax},"
                      f"stripe:{mesh.shape['stripe']}"),
        "mesh_frames": frames,
        "mesh_mean_frame_kb": round(total_bytes / max(frames, 1) / 1024, 1),
        "mesh_fetch_ms_p50": round(
            fetch_sorted[len(fetch_sorted) // 2], 2),
        "mesh_fetch_ms_p95": round(
            fetch_sorted[min(len(fetch_sorted) - 1,
                             int(len(fetch_sorted) * 0.95))], 2),
        "mesh_d2h_bytes_per_frame": round(d2h_bytes / max(frames, 1)),
    }


def sfe_drive(enc, frames_target: int, budget_s: float) -> dict:
    """Shared SFE drive discipline for one single-session
    ``MeshH264Encoder`` (used by ``bench_sfe_scaling`` here AND
    bench.py's ``_bench_4k_sfe``, so the two reported series can never
    diverge): device-resident scrolling source, IDR + steady-state
    warmup ticks, then a 2-deep dispatch/harvest window. Returns
    fps/frames plus the harvest stage samples."""
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as np

    from selkies_tpu.capture.synthetic import SyntheticSource

    assert enc.n_sessions == 1
    base = np.pad(
        SyntheticSource(enc.width, enc.height, pattern="scroll")._bg,
        ((0, enc.pad_h - enc.height), (0, enc.pad_w - enc.width), (0, 0)),
        mode="edge")
    batch = jax.device_put(jnp.asarray(base[None]), enc._frame_sharding)
    roll = jax.jit(lambda b: jnp.roll(b, -8, axis=1))
    enc.encode_frames(batch)          # IDR tick (mixed-program compile)
    batch = roll(batch)
    enc.encode_frames(batch)          # steady-state P compile

    frames = 0
    concat_ms, fetch_ms = [], []
    pending = deque()
    start = time.perf_counter()

    def harvest_one():
        nonlocal frames
        enc.harvest(pending.popleft())
        frames += 1
        st = enc.last_harvest_stages or {}
        concat_ms.append(st.get("concat_ms", 0.0))
        fetch_ms.append(st.get("fetch_ms", 0.0))

    while frames < frames_target and \
            time.perf_counter() - start < budget_s:
        batch = roll(batch)
        pending.append(enc.dispatch(batch))  # >=2 sharded batches in flight
        if len(pending) >= 2:
            harvest_one()
    while pending:
        harvest_one()
    elapsed = time.perf_counter() - start
    from selkies_tpu.parallel.coordinator import _p50
    return {
        "fps": round(frames / elapsed, 2) if elapsed > 0 else 0.0,
        "frames": frames,
        "concat_ms_p50": _p50(concat_ms, 2),
        "fetch_ms_p50": _p50(fetch_ms, 2),
    }


def bench_sfe_scaling(width: int = 3840, height: int = 2160,
                      shard_counts=(1, 2, 4), frames_target: int = 96,
                      budget_per_shard: float = MAX_SECONDS / 6) -> dict:
    """Split-frame encoding scaling (ISSUE 15 acceptance): ONE 4K H.264
    session's frames stripe-sharded across 1 / 2 / 4 chips
    (`MeshH264Encoder` over ``session:1,stripe:M``), identical content
    and drive discipline per shard count (2-deep dispatch/harvest
    window), so the fps series isolates the ICI shard speedup. The
    acceptance bar is >=1.7x at 2 shards over the 1-shard baseline with
    a near-linear trend to 4. (Geometry parameterized so the code path
    smoke-tests at toy sizes on CPU hosts.)"""
    import jax

    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    devices = jax.devices()
    series = {}
    concat = {}
    for shards in shard_counts:
        if shards > len(devices):
            continue
        mesh = parse_mesh_spec(f"session:1,stripe:{shards}",
                               devices[:shards])
        d = sfe_drive(MeshH264Encoder(mesh, 1, width, height),
                      frames_target, budget_per_shard)
        series[str(shards)] = d["fps"]
        concat[str(shards)] = d["concat_ms_p50"]
    if not series:
        return {}
    out = {
        "sfe_scaling": series,
        "sfe_concat_ms_p50": concat,
        "fourk_sfe_fps": max(series.values()),
        "sfe_shards_best": max(
            (int(k) for k, v in series.items()
             if v == max(series.values())), default=1),
    }
    if "1" in series and "2" in series and series["1"] > 0:
        out["sfe_speedup_2shard"] = round(series["2"] / series["1"], 2)
    if "1" in series and "4" in series and series["1"] > 0:
        out["sfe_speedup_4shard"] = round(series["4"] / series["1"], 2)
    return out


def bench_swarm() -> dict:
    """Scheduler-plane churn metrics (docs/scaling.md): a bounded swarm
    storm through the real ws_handler with device-free lanes — measures
    packing, fairness, and eviction latency, not codec throughput (the
    mesh/solo sections above own that)."""
    import asyncio
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.swarm_run import swarm_run

    try:
        r = asyncio.run(swarm_run(
            n_clients=64, duration_s=6.0, seed=0, concurrency=48,
            slots_per_lane=8, max_lanes=4, encoder="fake",
            sick_slot=True))
    except Exception as e:
        return {"swarm_error": repr(e)}
    return {
        "sessions_per_chip": r["sessions_per_chip"],
        "fairness_jain_index": r["fairness_jain_index"],
        "eviction_ms_p95": r["eviction_ms_p95"],
        "swarm_clients": r["swarm_clients"],
        "swarm_sessions_peak": r["sessions_peak"],
        "swarm_frames": r["frames_delivered_total"],
        "swarm_migrations": r["migrations"],
        "swarm_leak_free": bool(r["alive"]),
    }


def main() -> int:
    from selkies_tpu.runtime import enable_compile_cache, require_tpu

    device = require_tpu()
    print("device:", json.dumps(device), file=sys.stderr)
    enable_compile_cache()
    import jax.numpy as jnp

    from selkies_tpu.capture.synthetic import DeviceScrollSource
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    sessions = []
    for i in range(N_SESSIONS):
        base = JpegStripeEncoder(W, H)
        sessions.append((
            PipelinedJpegEncoder(base, depth=4, fetch_group=4),
            DeviceScrollSource(W, H, seed=i),
            base,
        ))

    def padded(base, frame):
        if frame.shape[0] == base.pad_h:
            return frame
        return jnp.pad(
            frame, ((0, base.pad_h - frame.shape[0]),
                    (0, base.pad_w - frame.shape[1]), (0, 0)), mode="edge")

    for i in range(WARMUP_FRAMES):
        enc, src, base = sessions[i % N_SESSIONS]
        enc.submit(padded(base, src.next_frame()))
        enc.poll()
    for enc, _, _ in sessions:
        enc.flush()

    done = 0
    total_bytes = 0
    submitted = 0
    start = time.perf_counter()
    while submitted < BENCH_FRAMES and \
            time.perf_counter() - start < MAX_SECONDS:
        enc, src, base = sessions[submitted % N_SESSIONS]
        enc.submit(padded(base, src.next_frame()))
        submitted += 1
        for _seq, stripes in enc.poll():
            done += 1
            total_bytes += sum(len(s.jpeg) for s in stripes)
    for enc, _, _ in sessions:
        for _seq, stripes in enc.flush():
            done += 1
            total_bytes += sum(len(s.jpeg) for s in stripes)
    elapsed = time.perf_counter() - start

    fps = done / elapsed if elapsed > 0 else 0.0
    try:
        mesh = bench_mesh()
    except Exception as e:          # e.g. a prod SELKIES_TPU_MESH spec
        mesh = {"mesh_aggregate_fps": 0.0,  # too big for this bench host
                "mesh_sessions": 0, "mesh_error": repr(e)}
    try:
        # ISSUE 15 acceptance series: fps vs SFE shard count at 4K
        sfe = bench_sfe_scaling()
    except Exception as e:          # the headline must survive a sub-bench
        sfe = {"sfe_error": repr(e)}
    # headline: the better mode, with per-session figures computed against
    # THAT mode's session count (mesh may batch more sessions on big slices)
    if mesh["mesh_aggregate_fps"] > fps:
        best, best_sessions = mesh["mesh_aggregate_fps"], mesh["mesh_sessions"]
        mode = "mesh"
    else:
        best, best_sessions = fps, N_SESSIONS
        mode = "solo"
    result = {
        "metric": "tpuenc_jpeg_multisession_aggregate_fps",
        "device": device,
        "value": round(best, 2),
        "unit": "fps",
        "mode": mode,
        "sessions": best_sessions,
        "per_session_fps": round(best / best_sessions, 2),
        "vs_baseline": round(best / (60.0 * best_sessions), 3),
        "solo_sessions": N_SESSIONS,
        "solo_aggregate_fps": round(fps, 2),
        "solo_frames": done,
        "solo_d2h_bytes_per_frame": round(
            sum(e.stats()["d2h_bytes_per_frame"] * max(e.stats()["frames"], 1)
                for e, _, _ in sessions)
            / max(sum(e.stats()["frames"] for e, _, _ in sessions), 1)),
        "elapsed_s": round(elapsed, 2),
        "mean_frame_kb": round(total_bytes / max(done, 1) / 1024, 1),
        **mesh,
        **sfe,
        **bench_swarm(),
    }
    print(json.dumps(result))
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        print("FAILED phases:", ", ".join(failed), file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
