"""How late the program's ready watch stamps a step's end, in ms: a
percentile of ``t_ready`` less the end of the step's execution in the device
trace, over the frames stamped in the traced seconds.

The program stamps, on ``time.monotonic``, the instant each launched step's
output became ready (``selkies_tpu/observability/device_probe.py``
``ReadyWatch``); a frame's span carries it as the end of its ``device_run``
stage. The device trace has the same instant on the profiler's clock: the
end of an execution of the cell's ``step_program`` on the ``XLA Modules``
line. ``clock_probe.align`` moves the second onto the first's clock (the
probe's pairs: nothing new), each stamp is paired with the execution that
ended nearest it (steps lie 8-17 ms apart; a stamp with none within
``NEAR_MS`` is left out and counted), and the difference is the wake-up of
the thread that blocked for the output: if the interpreter holds that
thread back, it shows here and nowhere else. On several devices a step's
end is its latest end over the devices (the output is ready when every
shard is).

The clocks' offset is a lower bound by its nature (a host never sees a
result before the device has it): where a stamp lies nearer its execution
than the probe's nearest pair did, the offset is the stamp's, so no lag is
negative and the number is the lag above the smallest seen, as the pairs'
own disagreement is.

A stamp that the program clipped (``device_run`` ends where ``dispatch``
or ``fetch_wait`` ended: the watch woke after the driver had the frame)
says nothing of when it landed: left out and counted. None untraced, in a
rehearsal (no device), from a program that writes no ``device_run``, and
with no clock (fewer than three probes in the traced seconds)."""

import bisect
import re

from ..harness import say
from ..metrics import percentile
from . import clock_probe

#: a stamp pairs with an execution that ended within this of it
NEAR_MS = 2.0


def stamps(run):
    """[t_ready] of the window's frames, unclipped ones only, and how many
    were clipped."""
    out, clipped = [], 0
    for tr in run.spans:
        iv = tr.spans.get("device_run")
        if iv is None:
            continue
        d, f = tr.spans.get("dispatch"), tr.spans.get("fetch_wait")
        if (d is not None and iv[1] <= d[1]) or \
                (f is not None and iv[1] >= f[1]):
            clipped += 1
        else:
            out.append(iv[1])
    return sorted(out), clipped


def nearest(ends, t, within_s):
    """The one of the sorted ``ends`` nearest ``t`` if it lies within
    ``within_s`` of it, else None."""
    i = bisect.bisect_left(ends, t)
    near = min(ends[max(0, i - 1):i + 1], key=lambda e: abs(e - t),
               default=None)
    return near if near is not None and abs(near - t) <= within_s else None


def step_ends(run, offsets):
    """Sorted ends of the step program's executions on ``time.monotonic``;
    over several devices each step's latest end."""
    program = run.cell.config.get("step_program")
    if not program:
        return []
    pat = re.compile(r"^jit_" + re.escape(program) + r"\(")
    w0, w1 = run.profile.window()
    per_dev = [sorted(
        off + (s + d) / 1e9 for n, s, d in run.profile.modules.get(dev, [])
        if pat.match(n) and s >= w0 and s + d <= w1)
        for dev, off in offsets.items()]
    if not per_dev or not all(per_dev):
        return []
    first, *rest = per_dev
    return [max([end] + [e for e in (nearest(ends, end, 0.004)
                                     for ends in rest) if e is not None])
            for end in first]


def lags_ms(ready, ends):
    """(lag of each stamp that has an execution within ``NEAR_MS``, how many
    have none)."""
    paired = [(r, nearest(ends, r, NEAR_MS / 1000.0)) for r in ready]
    return ([(r - e) * 1000.0 for r, e in paired if e is not None],
            sum(1 for _r, e in paired if e is None))


def read(run, args):
    if run.rehearsal or run.profile is None:
        return None
    ready, clipped = stamps(run)
    if not ready and not clipped:
        return None                 # a program without the ready watch
    offsets = clock_probe.align(run)
    if not offsets:
        say("ready stamps: not read: no clock for the trace")
        return None
    ends = step_ends(run, offsets)
    if not ends:
        say("ready stamps: not read: no execution of the step program in "
            "the traced seconds")
        return None
    # the stamps of the traced seconds (a little inside: a step cut by the
    # window's edge has no execution to pair with)
    ready = [r for r in ready if ends[0] - 0.001 <= r <= ends[-1] + 0.001]
    lags, alone = lags_ms(ready, ends)
    if not lags:
        say(f"ready stamps: not read: none of {len(ready)} stamps in the "
            f"traced seconds lies within {NEAR_MS:g} ms of an execution")
        return None
    lead = min(0.0, min(lags))      # a stamp nearer than the probe's nearest
    lags = sorted(x - lead for x in lags)
    say(f"ready stamps: {len(lags)} of {len(ready)} stamps in the traced "
        f"seconds paired with one of {len(ends)} executions of the step; "
        f"{alone} with none within {NEAR_MS:g} ms, left out; {clipped} "
        f"clipped stamps in the window, left out; t_ready less the "
        f"execution's end: p50 {percentile(lags, 50):.3f} ms, p95 "
        f"{percentile(lags, 95):.3f}, max {lags[-1]:.3f}"
        + (f"; the nearest stamp lies {-lead:.3f} ms nearer its execution "
           f"than the probe's nearest pair: the offset is the stamp's"
           if lead < 0 else ""))
    return percentile(lags, float(args.get("percentile", 50)))
