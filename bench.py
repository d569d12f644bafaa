"""Headline benchmark: 1080p streaming-encode throughput on one chip.

Mirrors the reference's headline claim — 60 fps @ 1920×1080 desktop encode
(reference docs/README.md:12, docs/design.md:11; BASELINE.md) — against the
tpuenc JPEG-stripe profile with device-side entropy coding, run through the
pipelined (dispatch/D2H-overlapped) encoder exactly as the streaming server
drives it: per frame, the damage/size metadata and the packed bitstream are
fetched to the host and assembled into per-stripe JPEGs.

Also measures the rest of the BASELINE matrix on the same chip:
  * p50/p95 glass-to-glass (capture handoff → stripes decodable on the
    client side of the wire) — the declared BASELINE latency metric;
  * tpuenc H.264 1080p (config 2) through the dense one-dispatch device
    encode + host CAVLC;
  * 4K JPEG single-chip (config 4's single-chip share; the cross-chip
    stripe-sharded path is validated by __graft_entry__.dryrun_multichip).

Frames come from a device-resident scrolling source (every stripe damaged
every frame — the no-shortcuts worst case for damage gating). The source
materializes frames on device with a jitted roll, so the number is the
encoder's and not the capture upload's.

Refuses to run without a TPU (``runtime.require_tpu``): a number from
another backend is not a device number. Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "fps", "vs_baseline": N,
   "device": {"platform", "kind", "count"}, ...}
vs_baseline is the ratio against the reference's 60 fps 1080p target.
Exits non-zero if any phase raised (its ``*_error`` key says which).

Nothing this script prints has been re-measured on a directly attached
chip since the constants below were chosen; PERF.md records what has.
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_FPS = 60.0  # reference headline: 60 fps @ 1080p
W, H = 1920, 1080
WARMUP_FRAMES = 24
BENCH_FRAMES = 300
MAX_SECONDS = 90.0
# chosen for a remote-attached development device; not re-measured on a
# directly attached chip (changing them is a perf change, ROADMAP)
PIPELINE_DEPTH = 12   # frames in flight ahead of the D2H harvest
FETCH_GROUP = 4       # frames per D2H read


def _pipelined_jpeg_fps(width, height, frames, seconds, depth=PIPELINE_DEPTH,
                        fetch_group=FETCH_GROUP):
    import jax.numpy as jnp

    from selkies_tpu.capture.synthetic import DeviceScrollSource
    from selkies_tpu.encoder.jpeg import JpegStripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedJpegEncoder

    base = JpegStripeEncoder(width, height)
    src = DeviceScrollSource(width, height)
    enc = PipelinedJpegEncoder(base, depth=depth, fetch_group=fetch_group)

    def padded(frame):
        if frame.shape[0] == base.pad_h and frame.shape[1] == base.pad_w:
            return frame
        return jnp.pad(
            frame,
            ((0, base.pad_h - frame.shape[0]),
             (0, base.pad_w - frame.shape[1]), (0, 0)),
            mode="edge")

    for _ in range(WARMUP_FRAMES):  # includes compile
        enc.submit(padded(src.next_frame()))
        for _ in enc.poll():
            pass
    for _ in enc.flush():
        pass

    done = 0
    total_bytes = 0
    start = time.perf_counter()
    submitted = 0
    while submitted < frames:
        enc.submit(padded(src.next_frame()))
        submitted += 1
        for _seq, stripes in enc.poll():
            done += 1
            total_bytes += sum(len(s.jpeg) for s in stripes)
        if time.perf_counter() - start > seconds:
            break
    for _seq, stripes in enc.flush():
        done += 1
        total_bytes += sum(len(s.jpeg) for s in stripes)
    elapsed = time.perf_counter() - start
    fps = done / elapsed if elapsed > 0 else 0.0
    return fps, done, elapsed, total_bytes, enc.stats()


def _h264_d2h_baseline() -> dict:
    """Short host-entropy-path stint: the sparse-level-buffer transfer
    the device-CAVLC tier replaces — so the reduction acceptance
    criterion is measured against a live number, not BENCH history."""
    from selkies_tpu.capture.synthetic import DeviceScrollSource
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

    B = 12
    enc = H264StripeEncoder(W, H, entropy="host")
    pipe = PipelinedH264Encoder(enc, depth=3 * B, batch=B)
    src = DeviceScrollSource(W, enc.pad_h)
    enc.encode_frame(src.next_frame())
    enc.encode_frame(src.next_frame())
    for _ in range(2):                       # compile + prefix settle
        pipe.submit_batch(src.next_batch(B))
        for _ in pipe.poll(flush_partial=False):
            pass
    for _ in pipe.flush():
        pass
    pipe.d2h_bytes_total = 0
    pipe.frames_completed = 0
    enc.d2h_refetch_bytes_total = 0
    enc.host_entropy_ms_total = 0.0
    deadline = time.perf_counter() + MAX_SECONDS / 8
    while pipe.frames_completed < 60 and time.perf_counter() < deadline:
        pipe.submit_batch(src.next_batch(B))
        for _ in pipe.poll(flush_partial=False):
            pass
    for _ in pipe.flush():
        pass
    st = pipe.stats()
    return {
        "h264_d2h_bytes_per_frame_host_baseline":
            round(st["d2h_bytes_per_frame"]),
        "h264_host_entropy_ms_per_frame_baseline":
            round(st["host_entropy_ms_per_frame"], 2),
    }


def bench_h264() -> dict:
    """Config 2: tpuenc H.264 1080p via the dense one-dispatch device
    encode (ME/transform/quant/recon + on-device CAVLC entropy packing
    — encoder/device_cavlc.py; the host only glues slice headers),
    pipelined with grouped D2H reads."""
    import jax.numpy as jnp

    from selkies_tpu.capture.synthetic import DeviceScrollSource
    from selkies_tpu.encoder.h264 import H264StripeEncoder
    from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

    BATCH = 12
    enc = H264StripeEncoder(W, H)
    # the P-frame reference chain rides a lax.scan inside ONE device
    # program per batch (dev.encode_frame_p_batch_rgb) and the source
    # emits the whole batch in one program, so the fixed per-dispatch
    # cost is paid ~2x per 12 frames instead of ~4x per frame
    pipe = PipelinedH264Encoder(enc, depth=3 * BATCH, batch=BATCH)
    src = DeviceScrollSource(W, enc.pad_h)

    for _ in range(2):
        enc.encode_frame(src.next_frame())  # IDR + single-frame compile
    for _ in range(2):                      # batch-program compile
        pipe.submit_batch(src.next_batch(BATCH))
    for _ in pipe.flush():
        pass
    pipe.d2h_bytes_total = 0                 # exclude warmup/IDR transfers
    pipe.frames_completed = 0
    enc.d2h_refetch_bytes_total = 0
    enc.host_entropy_ms_total = 0.0
    done, nb = 0, 0
    start = time.perf_counter()
    while done < 300 and time.perf_counter() - start < MAX_SECONDS / 3:
        pipe.submit_batch(src.next_batch(BATCH))
        for _seq, out in pipe.poll(flush_partial=False):
            done += 1
            nb += sum(len(s.annexb) for s in out)
    for _seq, out in pipe.flush():
        done += 1
        nb += sum(len(s.annexb) for s in out)
    elapsed = time.perf_counter() - start
    fps = done / elapsed if elapsed > 0 else 0.0

    # Device-side truth: chain-slope over the
    # already-compiled batched program. Chained dispatches + ONE tiny
    # fetch; the difference between 4-deep and 2-deep chains cancels
    # the fetch round trip, leaving (dispatch_rpc + B*frame)*2 — so
    # frame_ms here is a slight OVERestimate (includes ~1/B of the
    # dispatch RPC), i.e. device_fps is conservative.
    import numpy as _np

    def chain_ms(n_chains, reps=3):
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n_chains):
                pends = enc.dispatch_batch(src.next_batch(BATCH),
                                           fetch=False)
            _np.asarray(pends[-1].batch_heads[0, :64])
            best = min(best, (time.perf_counter() - t0) * 1000.0)
        return best

    t2, t4 = chain_ms(2), chain_ms(4)
    dev_ms = max(0.0, (t4 - t2) / (2 * BATCH))
    st = pipe.stats()
    out = {
        "h264_1080p_fps": round(fps, 2),
        "h264_batch": BATCH,
        "h264_entropy": enc.entropy,
        "h264_mean_frame_kb": round(nb / max(done, 1) / 1024, 1),
        # ISSUE 12: the dispatch/fetch-floor claim measured per round —
        # dispatch launch cost, host time blocked on D2H, and proof that
        # >=2 batches actually rode the device concurrently
        "dispatch_p50_ms": st.get("dispatch_p50_ms", 0.0),
        "fetch_wait_p50_ms": st.get("fetch_wait_p50_ms", 0.0),
        "inflight_batches_max": st.get("inflight_batches_max", 0),
        # ISSUE 1 satellites: the bottleneck claim measured, not inferred
        "h264_d2h_bytes_per_frame": round(st["d2h_bytes_per_frame"]),
        "h264_host_entropy_ms_per_frame":
            round(st["host_entropy_ms_per_frame"], 2),
        "h264_frames_dropped": st.get("frames_dropped", 0),
        "h264_entropy_errors": st.get("entropy_errors", 0),
        "h264_device_ms_per_frame": round(dev_ms, 2),
        "h264_device_fps": round(1000.0 / dev_ms, 1) if dev_ms > 0 else None,
        "h264_device_note": (
            "chain-slope of the one-dispatch batched program; cancels "
            "fetch+fixed costs, includes ~1/B of dispatch RPC "
            "(conservative). tools/h264_stages.py has the full method."),
    }
    try:
        out.update(_h264_d2h_baseline())
    except Exception as e:                   # baseline must not kill config 2
        out["h264_d2h_baseline_error"] = repr(e)
    return out


def _bench_4k_sfe(width=3840, height=2160, max_shards=4,
                  frames_target=120, seconds=MAX_SECONDS / 4) -> dict:
    """Split-frame encoding (ISSUE 15): ONE 4K frame's stripe bands
    sharded across the stripe mesh axis (`MeshH264Encoder`, shard-local
    device CAVLC, host slice concat), driven with a 2-deep
    dispatch/harvest window like the coordinator's SFE lanes — the
    drive discipline is bench_multi.sfe_drive, shared with the
    `sfe_scaling` series so the two can never diverge. On one chip this
    measures the mesh-path overhead floor; on a multi-chip slice
    `fourk_sfe_fps` should scale near-linearly with shard count."""
    import jax

    import bench_multi
    from selkies_tpu.parallel import parse_mesh_spec
    from selkies_tpu.parallel.mesh_h264 import MeshH264Encoder

    devices = jax.devices()
    shards = min(len(devices), max_shards)
    mesh = parse_mesh_spec(f"session:1,stripe:{shards}", devices[:shards])
    enc = MeshH264Encoder(mesh, 1, width, height)
    d = bench_multi.sfe_drive(enc, frames_target, seconds)
    return {
        "fourk_sfe_fps": d["fps"],
        "fourk_sfe_shards": shards,
        "fourk_sfe_frames": d["frames"],
        "fourk_sfe_concat_ms_p50": d["concat_ms_p50"],
        "fourk_sfe_fetch_ms_p50": d["fetch_ms_p50"],
        "fourk_sfe_host_fallback_stripes": enc.host_fallback_stripes_total,
    }


def bench_4k() -> dict:
    """Config 4: 4K JPEG + 4K H.264 throughput, single-chip AND the
    split-frame-encoding lane.

    The v5e-4 target (30 fps) rides the stripe-axis mesh shard
    (parallel/, validated by __graft_entry__.dryrun_multichip); the
    `fourk_sfe_*` fields measure that path live (ISSUE 15) so the
    speedup over the single-chip `fourk_h264_fps` shows in one BENCH
    round."""
    fps, done, elapsed, total, jst = _pipelined_jpeg_fps(
        3840, 2160, 120, MAX_SECONDS / 4)
    out = {
        "fourk_jpeg_fps": round(fps, 2),
        "fourk_mean_frame_kb": round(total / max(done, 1) / 1024, 1),
        "fourk_d2h_bytes_per_frame": round(jst["d2h_bytes_per_frame"]),
    }
    try:
        from selkies_tpu.capture.synthetic import DeviceScrollSource
        from selkies_tpu.encoder.h264 import H264StripeEncoder
        from selkies_tpu.encoder.pipeline import PipelinedH264Encoder

        B = 8
        enc = H264StripeEncoder(3840, 2160)
        src = DeviceScrollSource(3840, enc.pad_h)
        pipe = PipelinedH264Encoder(enc, depth=3 * B, batch=B)
        enc.encode_frame(src.next_frame())
        enc.encode_frame(src.next_frame())
        for _ in range(3):                   # compile + prefix settle
            pipe.submit_batch(src.next_batch(B))
            for _ in pipe.poll(flush_partial=False):
                pass
        for _ in pipe.flush():
            pass
        done = 0
        start = time.perf_counter()
        while done < 150 and time.perf_counter() - start < MAX_SECONDS / 4:
            pipe.submit_batch(src.next_batch(B))
            for _seq, _o in pipe.poll(flush_partial=False):
                done += 1
        for _seq, _o in pipe.flush():
            done += 1
        el = time.perf_counter() - start
        out["fourk_h264_fps"] = round(done / el, 2) if el > 0 else 0.0
    except Exception as e:
        out["fourk_h264_error"] = repr(e)
    try:
        # ISSUE 15: the SFE lane measured next to the single-chip number
        out.update(_bench_4k_sfe())
    except Exception as e:
        out["fourk_sfe_error"] = repr(e)
    return out


def bench_glass_to_glass() -> dict:
    """p50/p95 capture→client-decodable latency through the REAL server:
    DataStreamingServer + websocket client on loopback; the client ACKs
    every frame and PIL-decodes one stripe per frame as the stand-in for
    the browser's ImageDecoder."""
    import asyncio
    import io

    import numpy as np
    from PIL import Image

    from selkies_tpu.protocol import unpack_binary, VideoStripe
    from selkies_tpu.server.app import StreamingApp
    from selkies_tpu.server.data_server import DataStreamingServer
    from selkies_tpu.settings import Settings

    from selkies_tpu.capture.synthetic import SyntheticSource
    from selkies_tpu.server.data_server import default_encoder_factory

    #: wire frame id → (capture-handoff time, harvest time). The wrapper
    #: mirrors the capture loop's id assignment exactly: ids are handed
    #: to non-empty results in poll order, which is submission order.
    #: The harvest stamp splits the end-to-end number into the encode
    #: share (dispatch → levels on host) vs the serve/transport share.
    fid_times = {}

    class TimedEncoder:
        def __init__(self, inner):
            self.inner = inner
            self._t = {}
            self._next_fid = 1

        def try_submit(self, frame):
            seq = self.inner.try_submit(frame)
            if seq is not None:
                self._t[seq] = time.monotonic()
            return seq

        submit = try_submit

        def poll(self):
            out = self.inner.poll()
            now = time.monotonic()
            for seq, stripes in out:
                t = self._t.pop(seq, None)
                if stripes and t is not None:
                    fid_times[self._next_fid] = (t, now)
                    self._next_fid += 1
            return out

        def flush(self):
            return self.inner.flush()

        def pop_trace(self, seq):
            # flight-recorder passthrough: without it the served-path
            # stage breakdown would lose the encoder-side intervals
            pt = getattr(self.inner, "pop_trace", None)
            return pt(seq) if pt else None

        def force_keyframe(self):
            self.inner.force_keyframe()

        def stats(self):
            st = getattr(self.inner, "stats", None)
            return st() if st else {}

        def close(self):
            close = getattr(self.inner, "close", None)
            if close:
                close()

    made = []      # every encoder the server built (reconfigures rebuild)

    def encoder_factory(w, h, settings, overrides=None):
        enc = TimedEncoder(default_encoder_factory(w, h, settings,
                                                   overrides))
        made.append(enc)
        return enc

    def source_factory(w, h, fps, x=0, y=0):
        return SyntheticSource(w, h, fps, pattern="scroll")

    lat_ms = []

    async def run():
        import websockets
        import websockets.asyncio.server as ws_server

        settings = Settings(argv=[], env={"SELKIES_PORT": "0"})
        app = StreamingApp(settings)
        server = DataStreamingServer(
            settings, app=app, source_factory=source_factory,
            encoder_factory=encoder_factory, host="127.0.0.1")
        app.data_server = server
        server._stop_event = asyncio.Event()
        srv = await ws_server.serve(server.ws_handler, "127.0.0.1", 0,
                                    compression=None, max_size=None)
        port = srv.sockets[0].getsockname()[1]

        async with websockets.connect(f"ws://127.0.0.1:{port}") as ws:
            await ws.recv()             # MODE
            await ws.recv()             # server_settings
            await ws.send('SETTINGS,{"displayId": "primary", '
                          '"initialClientWidth": 1920, '
                          '"initialClientHeight": 1080, '
                          '"framerate": 30}')
            seen = set()
            deadline = time.monotonic() + 30.0
            while len(lat_ms) < 140 and time.monotonic() < deadline:
                try:
                    m = await asyncio.wait_for(ws.recv(), 10)
                except asyncio.TimeoutError:
                    break
                if not isinstance(m, bytes):
                    continue
                f = unpack_binary(m)
                if not isinstance(f, VideoStripe):
                    continue
                if f.frame_id in seen:
                    continue
                seen.add(f.frame_id)
                # decode one stripe as the browser-ImageDecoder stand-in;
                # latency = capture handoff → stripe decodable client-side
                t_recv = time.monotonic()
                Image.open(io.BytesIO(f.payload)).load()
                t_dec = time.monotonic()
                stamps = fid_times.get(f.frame_id)
                if stamps is not None:
                    t0, t_harvest = stamps
                    lat_ms.append(((t_dec - t0) * 1000.0,
                                   (t_harvest - t0) * 1000.0,
                                   (t_recv - t_harvest) * 1000.0,
                                   (t_dec - t_recv) * 1000.0))
                await ws.send(f"CLIENT_FRAME_ACK {f.frame_id}")
        # driver gauges BEFORE stop() closes the encoders (ISSUE 12:
        # the served path must show >=2 batches in flight, not just the
        # standalone pipeline stints)
        for enc in made:
            try:
                enc_stats.append(enc.stats())
            except Exception:
                pass
        # flight-recorder stage breakdown (ISSUE 13): the ROADMAP item 1
        # criterion measured per stage on the REAL served path
        rec_summary.update(server.recorder.summary("primary"))
        await server.stop()
        rec_open[0] = server.recorder.open_spans()
        srv.close()

    enc_stats: list = []
    rec_summary: dict = {}
    rec_open = [None]
    asyncio.run(run())
    # the first frames pay jit warmup + display reconfigure churn
    samples = lat_ms[20:] if len(lat_ms) > 40 else lat_ms
    if not samples:
        return {"p50_glass_to_glass_ms": None}
    arr = np.asarray(samples)   # [total, encode, serve, client_decode]

    def pct(col, q):
        vals = np.sort(arr[:, col])
        return round(float(vals[min(len(vals) - 1,
                                    int(len(vals) * q / 100))]), 1)

    busiest = max(enc_stats, key=lambda s: s.get("frames", 0), default={})
    # per-stage p50/p95 for all eight stages (ISSUE 13 satellite): the
    # flight recorder measured the REAL path, so the ROADMAP item 1
    # criterion (encode_only vs device ms/frame) is a single bench field
    # with its decomposition alongside
    stage_fields = {}
    for stage, v in (rec_summary.get("stages") or {}).items():
        stage_fields[f"served_{stage}_p50_ms"] = v["p50_ms"]
        stage_fields[f"served_{stage}_p95_ms"] = v["p95_ms"]
    for k in ("glass_to_glass_p50_ms", "glass_to_glass_p95_ms",
              "encode_only_p50_ms", "encode_only_p95_ms"):
        if k in rec_summary:
            stage_fields[f"recorder_{k}"] = rec_summary[k]
    stage_fields["served_frames_traced"] = rec_summary.get("frames", 0)
    stage_fields["served_frames_acked"] = rec_summary.get("acked", 0)
    # must be 0 after stop(): the recorder's span-leak invariant
    stage_fields["served_trace_open_spans"] = rec_open[0]
    return {
        "p50_glass_to_glass_ms": pct(0, 50),
        "p95_glass_to_glass_ms": pct(0, 95),
        **stage_fields,
        # ISSUE 12 acceptance evidence from the SERVED path: the async
        # driver's in-flight window and the dispatch/fetch-wait medians
        # behind encode_only_p50_ms
        "inflight_batches_max": busiest.get("inflight_batches_max", 0),
        "served_dispatch_p50_ms": busiest.get("dispatch_p50_ms", 0.0),
        "served_fetch_wait_p50_ms": busiest.get("fetch_wait_p50_ms", 0.0),
        # stage decomposition: the encode stage is
        # capture handoff → levels on host (device dispatch + D2H); serve
        # is host assembly + websocket; decode is the client-side share
        "encode_only_p50_ms": pct(1, 50),
        "encode_only_p95_ms": pct(1, 95),
        "serve_p50_ms": pct(2, 50),
        "client_decode_p50_ms": pct(3, 50),
        "latency_samples": len(arr),
    }


def main() -> int:
    from selkies_tpu.runtime import enable_compile_cache, require_tpu

    device = require_tpu()
    print("device:", json.dumps(device), file=sys.stderr)
    enable_compile_cache()
    # median-of-N protocol: the headline is the median of three shorter
    # runs with the spread published alongside
    runs = []
    total_bytes = done = 0
    jpeg_stats = {}
    for _ in range(3):
        fps, d, _el, tb, jpeg_stats = _pipelined_jpeg_fps(
            W, H, BENCH_FRAMES // 3, MAX_SECONDS / 4)
        runs.append(round(fps, 2))
        done += d
        total_bytes += tb
    med = sorted(runs)[1]
    result = {
        "metric": "tpuenc_jpeg_1080p_encode_fps",
        "device": device,
        "value": med,
        "unit": "fps",
        "vs_baseline": round(med / BASELINE_FPS, 3),
        "runs": runs,
        "spread": round(max(runs) - min(runs), 2),
        "frames": done,
        "mean_frame_kb": round(total_bytes / max(done, 1) / 1024, 1),
        # per-frame transfer + host-entropy gauges (ISSUE 1 satellite:
        # BENCH bottleneck claims must be measured, not inferred)
        "jpeg_d2h_bytes_per_frame":
            round(jpeg_stats.get("d2h_bytes_per_frame", 0)),
        "jpeg_host_entropy_ms_per_frame":
            round(jpeg_stats.get("host_entropy_ms_per_frame", 0), 2),
        # robustness accounting (ISSUE 2 satellite): dropped/errored
        # frames and host entropy fallbacks are results, not log noise —
        # a throughput headline that silently dropped frames is a lie
        "jpeg_frames_dropped": jpeg_stats.get("frames_dropped", 0),
        "jpeg_host_fallback_stripes":
            jpeg_stats.get("host_fallback_stripes", 0),
        # ISSUE 12 satellites on the headline path too
        "jpeg_dispatch_p50_ms": jpeg_stats.get("dispatch_p50_ms", 0.0),
        "jpeg_fetch_wait_p50_ms": jpeg_stats.get("fetch_wait_p50_ms", 0.0),
        "jpeg_inflight_batches_max":
            jpeg_stats.get("inflight_batches_max", 0),
    }
    try:
        result.update(bench_glass_to_glass())
    except Exception as e:  # the headline number must survive a sub-bench
        result["glass_to_glass_error"] = repr(e)
    try:
        result.update(bench_h264())
    except Exception as e:
        result["h264_error"] = repr(e)
    try:
        result.update(bench_4k())
    except Exception as e:
        result["fourk_error"] = repr(e)
    print(json.dumps(result))
    failed = sorted(k for k in result if k.endswith("_error"))
    if failed:
        print("FAILED phases:", ", ".join(failed), file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
