"""One kernel's device time per call (``args.report`` = ``ms``) or its share
of the roofline (``roofline``), from the profiler trace. The configuration
gives the kernel's shapes (``kernels.<name>``); the count of operations and
bytes is ``roofline.<args.count>``; the peaks are ``peaks.json``'s. A cell
whose configuration has no such kernel reads nothing."""

from .. import roofline, trace
from ..harness import say


def read(run, args):
    shapes = run.cell.config.get("kernels", {}).get(args["kernel"])
    if run.profile is None or shapes is None:
        return None
    ms = trace.kernel_ms_per_call(run.profile, args["kernel"])
    if ms is None or args.get("report", "ms") == "ms":
        return ms
    ops, bytes_ = getattr(roofline, args["count"])(**shapes)
    pct, bound = roofline.roofline_pct(
        ops, bytes_, ms / 1000.0, roofline.peaks(run.device["kind"]),
        args["ops_peak"])
    say(f"{args['kernel']}: {ops:.4g} ops, {bytes_:.4g} bytes, "
        f"{ms:.4f} ms per call: {pct:.4f}% of the roofline, {bound}-bound")
    return pct
