#!/usr/bin/env python3
"""One run of one benchmark cell on the TPU this machine holds.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Serves (``selkies_tpu.server.main.build`` + ``serve``), warms, measures for
``--seconds``, stops the server with nothing in flight, decodes and compares
what the clients received, and prints one JSON object as the last line of
standard output. ``--trace 0`` reports the cell's end-to-end metrics with the
profiler off; ``--trace 1`` takes a few seconds of ``jax.profiler`` trace in
the window and reports the per-layer metrics and a breakdown.

Anything but a TPU with the chips the cell asks for is refused (exit 2),
except under ``--rehearsal WxH``: the harness rehearsed on the CPU at a tiny
size, Pallas in interpret mode. A rehearsal names the CPU in its last line
and prints no device metric.

Every run also prints, on earlier lines and under ``window`` in the last
line (a key the driver ignores), the 95th percentile of the latency and
which regime of the encode pipeline the window ran in.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()       # set-up is counted from here

import argparse
import asyncio
import json
import os
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", default="",
                    help="WxH: rehearse the harness on the CPU at this size")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: run the configuration's control (control.py)")
    return ap.parse_args(argv)


def device_info(chips: int, rehearsal: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark: no TPU: jax reports {info}; a number "
                         "from another backend is not a device number")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax "
                         f"reports {len(devs)}")
    return info


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


async def run_cell(args, cell, device, rehearsal) -> Dict[str, Any]:
    from benchmark import cells, check, trace
    from benchmark.harness import CacheEvents, Run, say

    cache = CacheEvents()
    env_extra = dict(cell.config["control"]["env"]) if args.control else {}
    run = Run(cell, args.seed, args.seconds, bool(args.trace), rehearsal,
              env_extra=env_extra)
    run.device = device
    t_imports = time.monotonic()
    server_task = await run.boot()
    t_boot = time.monotonic()
    try:
        await run.join()
        t_join = time.monotonic()
        run.counters["compile_cache_misses"] = float(cache.misses)
        say(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
            f"warm-up {run.counters['warmup_s']:.2f} s")
        await run.measure()
        setup_s = run.window[0] - T_PROCESS
        say(f"set-up {setup_s:.2f} s: imports and device {t_imports - T_PROCESS:.2f}"
            f", build and boot warm-up {t_boot - t_imports:.2f}, clients "
            f"join and steady state {t_join - t_boot:.2f}")
        await run.drain()
        wrong = run.health()
        run.collect_spans()
        e2e = run.end_to_end()
        for c in run.clients.values():
            await c.close()
    finally:
        server_task.cancel()
        try:
            await asyncio.wait_for(server_task, 60)
        except (asyncio.CancelledError, asyncio.TimeoutError):
            pass
    open_spans = run.server.recorder.open_spans()
    peak = memory_peak_bytes()

    # -- what the generator and the capture loop did, on earlier lines ----
    for s in run.sources:
        late = s.tick_lateness_ms(*run.window)
        if late:
            say(f"source {s.number} ({run.display_of_source.get(s.number)}): "
                f"{len(late)} capture calls in the window, late against "
                f"their {run.fps:g} Hz ticks by p50 "
                f"{sorted(late)[len(late) // 2]:.3f} ms, max {max(late):.3f} ms")
    for did, (n, of) in run.tracing_disagrees().items():
        if of and hasattr(run.source_of(did), "read_index"):
            say(f"{did}: the recorder's capture mark names another content "
                f"step than the picture shows in {n} of {of} frames")
    for line in run.stalls():
        say(line)
    say(f"changes due in the window: {e2e['attempted']}; never shown: "
        f"{e2e['never_shown']}; complete frames: {e2e['frames']}; "
        f"open spans after stop: {open_spans}")
    window = run.regime() if "metrics" in e2e else {}
    if window:
        # the tail and the pipeline's regime, in every run: no bound fits
        # the first, and the second says what the bounded numbers mean
        say(f"latency p95 over the same changes: "
            f"{window['latency_p95_ms']:.3f} ms (no bound: PERF.md)")
        fetch_wait = window["fetch_wait_p50_ms"]
        say(f"regime: {window['frames_in_flight']:.2f} frames in flight "
            f"(latency_p50 x delivered_fps), fetch_wait p50 "
            f"{'not read' if fetch_wait is None else format(fetch_wait, '.3f') + ' ms'}"
            f", encoder's inflight_batches "
            f"{window['inflight_batches']}; the configuration's band "
            f"{window['band']}: {window['regime']}" + {
                "other": "  <-- another regime than the bounds were "
                         "measured in",
                "traced": " (the band is of untraced windows: held to none)",
            }.get(window["regime"], ""))
        say("latency p50 by the second the change fell due: " + " ".join(
            format(v, ".1f") for v in window["latency_p50_by_second_ms"]))

    # -- correct: the clients' pictures against the desktop ---------------
    t_cmp = time.monotonic()
    numbers, compared, psnr = run.compare()
    limits = cell.limits()
    numbers = {k: v for k, v in numbers.items() if k in limits}
    ok, rows = check.verdict(numbers, limits, compared)
    say(f"compared {compared} frames in {time.monotonic() - t_cmp:.2f} s; "
        f"luma PSNR against the source {psnr:.2f} dB (not compared)")
    failed = e2e["never_shown"]
    if wrong:
        say("guarantees broken: " + "; ".join(wrong))
        failed = e2e["attempted"]
    correct = bool(ok and not wrong and "metrics" in e2e
                   and e2e["attempted"] > 0)

    out: Dict[str, Any] = {"correct": correct,
                           "attempted": e2e["attempted"], "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    device = dict(device, memory_peak_bytes=peak)
    if args.trace:
        found: Dict[str, float] = {}
        for m in cell.per_layer:
            spec = cells.layer_metric_spec(m["name"])
            value = cells.module("readers", spec["reader"]).read(
                run, spec.get("args", {}))
            if value is not None:
                found[m["name"]] = value
        out["metrics"] = found
        if run.profile is not None and not rehearsal:
            busy = trace.busy_s(run.profile)
            device["busy_s"] = sum(busy.values()) / max(1, len(busy))
            device["window_s"] = trace.window_s(run.profile)
            out["breakdown"] = {
                "device_ops": trace.top_device_ops(run.profile),
                "idle_gaps": trace.idle_gaps(run.profile)}
    else:
        found = dict(e2e.get("metrics", {}), setup_s=setup_s)
        out["metrics"] = {m["name"]: found[m["name"]]
                          for m in cell.end_to_end if m["name"] in found}
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in out["metrics"].items()}
    out["device"] = device
    if rehearsal:
        out["rehearsal"] = True
    out["window"] = window
    out["compared"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        say(f"compared {k}: {v:.6g} (limit {lim:g})"
            f"{'' if v <= lim else '  <-- over'}")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    rehearsal = None
    if args.rehearsal:
        w, h = args.rehearsal.lower().split("x")
        rehearsal = (int(w), int(h))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["SELKIES_TPU_INTERPRET"] = "true"
    from benchmark.cells import load_cell

    cell = load_cell(args.workload)
    device = device_info(cell.chips, rehearsal is not None)
    # everything the server writes stays inside the checkout
    os.environ.setdefault("SELKIES_UPLOAD_DIR",
                          os.path.join(HERE, ".work", "uploads"))
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    out = asyncio.run(run_cell(args, cell, device, rehearsal))
    print(json.dumps(out), flush=True)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main()
    # the TPU client's teardown has crashed a finished process before
    # (PR 22: SIGSEGV after the success line); the server is stopped, the
    # clients are closed and everything is flushed, so leave without it
    os._exit(code)
