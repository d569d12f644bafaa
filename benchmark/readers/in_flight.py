"""Frames in flight between due and shown, by Little's law: the median
latency of a change times the frames delivered a second. It says which
regime the encode pipeline ran the window in (PERF.md): the same program
settles at 9.5 or at 13 of them."""


def read(run, args):
    m = run.metrics
    if not m:
        return None
    return m["latency_p50_ms"] * m["delivered_fps"] / 1000.0
