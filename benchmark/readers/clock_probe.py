"""The device probe's clock pairs (``selkies_tpu/observability/
device_probe.py``): how long a one-add program waited behind what the
device held (``read``), and the offset between the profiler's clock and
``time.monotonic`` (``align``, for the readers that lay a device trace over
the program's own timelines).

``read`` gives a percentile of ``t_ready - t_enqueued`` over the probes seen
ready in the window. A rehearsal has no device to queue on, and a program
without the probe has no pairs: None for both."""

import re
import statistics

from ..harness import say
from ..metrics import percentile

PROBE = re.compile(r"^jit_selkies_clock_probe\(")


def pairs(run, t0, t1):
    rec = run.server.recorder
    get = getattr(rec, "clock_pairs", None)
    return get(t0, t1) if get is not None else []


def read(run, args):
    if run.rehearsal:
        return None
    rows = pairs(run, *run.window)
    if not rows:
        return None
    return percentile([(b - a) * 1000.0 for _dev, a, b in rows],
                      float(args.get("percentile", 50)))


def align(run):
    """{device number: seconds to add to a trace time (ns / 1e9) to get
    ``time.monotonic``}, or None with fewer than three probes in the traced
    seconds of a device. Cached on the run.

    Each execution of the probe in the trace ends at the instant its host
    pair was seen ready, but for the wake-up of the blocked thread: the
    smallest ``t_ready - device_end`` is the offset. Executions and pairs
    are matched by order and spacing: the session began within a little of
    ``run.window[0]`` plus the mix's ``trace.start_s``, and probes are
    250 ms apart."""
    if hasattr(run, "_clock_offsets"):
        return run._clock_offsets
    run._clock_offsets = None
    prof = run.profile
    if prof is None:
        return None
    # where the session began: the harness notes when it asked for it (a
    # window traced a second time began later); else the mix's lead
    guess = getattr(run, "trace_asked_at", None)
    if guess is None:
        conf = run.cell.traffic.get("trace", {})
        guess = run.window[0] + min(float(conf.get("start_s", 2.0)),
                                    run.seconds / 4)
    w0, w1 = prof.window()
    host = pairs(run, guess - 1.0, guess + (w1 / 1e9) + 2.0)
    out = {}
    for dev, mods in prof.modules.items():
        ends = sorted((s + d) / 1e9 for n, s, d in mods
                      if PROBE.match(n) and w0 <= s and s + d <= w1)
        ready = sorted(b for hd, _a, b in host
                       if hd == dev or len(prof.modules) == 1)
        if len(ends) < 3 or len(ready) < 3:
            say(f"clock pairs: {len(ends)} probe executions in device "
                f"{dev}'s traced seconds, {len(ready)} host pairs: too few")
            return None
        best = None
        for h in ready:                      # which pair is the first end's?
            a = h - ends[0]
            if not guess - 0.25 <= a <= guess + 1.0:
                continue
            res = []
            for e in ends:
                near = min(ready, key=lambda r: abs(r - (a + e)))
                if abs(near - (a + e)) < 0.02:
                    res.append(near - e)
            if len(res) >= 3:
                score = (len(res), -(max(res) - min(res)))
                if best is None or score > best[0]:
                    best = (score, res)
        if best is None:
            say(f"clock pairs: no match for device {dev}'s probes")
            return None
        res = best[1]
        offset = min(res)
        over = sorted((r - offset) * 1000.0 for r in res)
        say(f"clock pairs, device {dev}: {len(res)} of {len(ends)} probe "
            f"executions matched; trace time 0 is monotonic {offset:.6f} "
            f"({(offset - guess) * 1000.0:+.3f} ms from where the session "
            f"was asked for); the pairs disagree by median "
            f"{statistics.median(over):.3f} ms, second smallest "
            f"{over[1]:.3f} ms, largest {over[-1]:.3f} ms above the smallest")
        out[dev] = offset
    run._clock_offsets = out or None
    return run._clock_offsets
