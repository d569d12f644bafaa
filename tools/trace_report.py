#!/usr/bin/env python3
"""Summarize a flight-recorder trace: per-stage percentiles + slowest frames.

Input is the Chrome trace-event JSON the server serves at
``/debug/trace`` on the metrics port (Perfetto-loadable; see
docs/observability.md). This CLI renders the same capture as text: a
per-stage p50/p95/p99 table per display, and the top-k slowest frames
with their stage timelines — the quick "where did the time go" answer
without opening a UI.

Usage::

    python tools/trace_report.py --url http://localhost:8000/debug/trace?s=30
    python tools/trace_report.py --file trace.json --top 10
    curl -s localhost:8000/debug/trace | python tools/trace_report.py

The stage glossary (capture/stage/dispatch/fetch_wait/pack/queue/send/
ack) is in docs/observability.md.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List


def load(url: str = "", path: str = "") -> Dict[str, Any]:
    if url:
        from urllib.request import urlopen

        with urlopen(url, timeout=10.0) as r:
            return json.load(r)
    if path:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    return json.load(sys.stdin)


def _pct(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, int(len(s) * q / 100.0))]


def build_frames(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Regroup the flat event list into per-frame records: each frame is
    the set of X slices sharing (pid, tid, args.frame_id)."""
    frames: Dict[Any, Dict[str, Any]] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("cat", "frame") != "frame":
            continue            # the threads' rows are not frames
        args = ev.get("args", {})
        # the recorder stamps a unique span token per frame; fall back
        # to (pid, tid, frame_id) for captures from older exports
        key = ((ev.get("pid"), "span", args["span"])
               if "span" in args
               else (ev.get("pid"), ev.get("tid"), args.get("frame_id")))
        fr = frames.setdefault(key, {
            "display": args.get("display", f"pid{ev.get('pid')}"),
            "frame_id": args.get("frame_id", -1),
            "terminal": args.get("terminal", "?"),
            "stages": {},
            "t0": float("inf"),
            "t1": float("-inf"),
        })
        fr["stages"][ev["name"]] = ev.get("dur", 0.0) / 1000.0
        fr["t0"] = min(fr["t0"], ev.get("ts", 0.0))
        fr["t1"] = max(fr["t1"], ev.get("ts", 0.0) + ev.get("dur", 0.0))
        fr["terminal"] = args.get("terminal", fr["terminal"])
    out = list(frames.values())
    for fr in out:
        fr["total_ms"] = max(0.0, (fr["t1"] - fr["t0"]) / 1000.0)
    return out


#: canonical stage order for tables/timelines (unknown stages append)
STAGE_ORDER = ("capture", "stage", "dispatch", "fetch_wait", "pack",
               "queue", "send", "ack")


def _stage_sorted(names) -> List[str]:
    known = [s for s in STAGE_ORDER if s in names]
    return known + sorted(n for n in names if n not in STAGE_ORDER)


def render(trace: Dict[str, Any], top: int = 5) -> str:
    frames = build_frames(trace)
    lines: List[str] = []
    other = trace.get("otherData", {})
    lines.append(f"frames: {len(frames)}   open spans at export: "
                 f"{other.get('open_spans', '?')}")
    by_display: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for fr in frames:
        by_display[fr["display"]].append(fr)

    for display, frs in sorted(by_display.items()):
        lines.append(f"\n== display {display} ({len(frs)} frames) ==")
        acked = [f["total_ms"] for f in frs if f["terminal"] == "acked"]
        if acked:
            lines.append(
                f"glass-to-glass  p50 {_pct(acked, 50):8.2f} ms   "
                f"p95 {_pct(acked, 95):8.2f} ms   "
                f"p99 {_pct(acked, 99):8.2f} ms   ({len(acked)} acked)")
        stage_vals: Dict[str, List[float]] = defaultdict(list)
        for fr in frs:
            for stage, ms in fr["stages"].items():
                stage_vals[stage].append(ms)
        lines.append(f"{'stage':<12}{'p50 ms':>10}{'p95 ms':>10}"
                     f"{'p99 ms':>10}{'n':>8}")
        for stage in _stage_sorted(stage_vals):
            vals = stage_vals[stage]
            lines.append(f"{stage:<12}{_pct(vals, 50):>10.2f}"
                         f"{_pct(vals, 95):>10.2f}"
                         f"{_pct(vals, 99):>10.2f}{len(vals):>8}")
        terminals: Dict[str, int] = defaultdict(int)
        for fr in frs:
            terminals[fr["terminal"]] += 1
        lines.append("terminals: " + ", ".join(
            f"{k}={v}" for k, v in sorted(terminals.items())))

        slowest = sorted(frs, key=lambda f: f["total_ms"],
                         reverse=True)[:top]
        if slowest:
            lines.append(f"\nslowest {len(slowest)} frames:")
            for fr in slowest:
                timeline = "  ".join(
                    f"{s}={fr['stages'][s]:.2f}"
                    for s in _stage_sorted(fr["stages"]))
                lines.append(
                    f"  frame {fr['frame_id']:>6}  "
                    f"total {fr['total_ms']:8.2f} ms  "
                    f"[{fr['terminal']}]  {timeline}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", default="",
                   help="fetch the trace from a /debug/trace endpoint")
    p.add_argument("--file", default="",
                   help="read a saved trace JSON (default: stdin)")
    p.add_argument("--top", type=int, default=5,
                   help="slowest frames to detail per display")
    args = p.parse_args(argv)
    try:
        trace = load(args.url, args.file)
    except Exception as e:
        print(f"could not load trace: {e!r}", file=sys.stderr)
        return 2
    print(render(trace, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
