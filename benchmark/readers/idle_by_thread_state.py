"""Share of the traced window, in %, in which no operation ran on the
device **and** a thread of the program was in one of ``args.states`` (the
flight recorder's thread track, ``selkies_tpu/observability/tracing.py``);
``args.unmarked`` instead: and the thread had no state marked (it wanted
to run and could not, or ran code no state covers).

The device's busy intervals come from the profiler trace on its own clock;
the clock pairs (``clock_probe.align``) move them onto ``time.monotonic``,
the thread track's. Over one thread's states and ``unmarked`` the shares
add up to ``trace_idle``'s number.

Which thread drives the device is data: ``args.threads`` lists the names a
program may give it (the solo driver's ``tpuenc-async``, a mesh lane's
``mesh-encode``), and the first of them that left a track in the traced
seconds is read; the run says which (an earlier line, and
``run.driving_thread``), and says so where more than one left a track (the
first listed is read then). ``args.thread``, one name, is read as a list of
one. On several devices a share is the mean over the devices: one thread
drives them all, and each device's idle intervals are laid over its track.
None without a trace, without a track from any of the threads, or without
three probes in the traced seconds; the run says on an earlier line which
of the three it was (``not read: ...``), once for the five metrics that
share a track, so that a line without them says why."""

from .. import trace
from ..harness import say
from . import clock_probe


def idle_intervals(prof, dev):
    """[(start, end)] in ns: the window less the busy intervals."""
    w0, w1 = prof.window()
    out, at = [], w0
    for s, e in trace.busy_intervals(prof, dev):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if w1 > at:
        out.append((at, w1))
    return out


def split(idle, track):
    """Seconds of ``idle`` ([(t0, t1)] on the track's clock, sorted) per
    state of ``track`` ([(thread, state, t0, t1)], sorted, not overlapping);
    what no row covers is under ``None``."""
    out = {None: sum(b - a for a, b in idle)}
    i = 0
    for _thread, state, s0, s1 in track:
        while i < len(idle) and idle[i][1] <= s0:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < s1:
            ov = min(s1, idle[j][1]) - max(s0, idle[j][0])
            if ov > 0:
                out[state] = out.get(state, 0.0) + ov
                out[None] -= ov
            j += 1
    return out


def by_state(run, threads):
    """{state or None: share of the traced window in %} of the first of
    ``threads`` that left a track, the mean over devices; cached on the
    run."""
    threads = tuple(threads)
    cache = run.__dict__.setdefault("_idle_by_state", {})
    if threads in cache:
        return cache[threads]
    cache[threads] = None
    prof = run.profile
    track_of = getattr(run.server.recorder, "thread_track", None)
    if prof is None or track_of is None:
        say("idle by thread state: not read: "
            + ("no trace" if prof is None
               else "no track (the recorder keeps no thread track)"))
        return None
    offsets = clock_probe.align(run)
    if not offsets:
        say("idle by thread state: not read: fewer than three probes "
            "matched in the traced seconds of a device")
        return None
    w0, w1 = prof.window()
    first = min(offsets.values())
    left = [t for t in threads
            if track_of(t, first + w0 / 1e9, first + w1 / 1e9)]
    if not left:
        say(f"idle by thread state: not read: no track (nothing from "
            f"{' or '.join(map(repr, threads))} in the traced seconds)")
        return None
    thread = run.driving_thread = left[0]
    say(f"thread track: the device's driver is read from {thread!r}"
        + (f"; {', '.join(map(repr, left[1:]))} left a track too (the "
           f"first listed is read)" if len(left) > 1 else ""))
    total = {}
    for dev, off in offsets.items():
        idle = [(off + a / 1e9, off + b / 1e9)
                for a, b in idle_intervals(prof, dev)]
        track = track_of(thread, off + w0 / 1e9, off + w1 / 1e9)
        if not track:
            say(f"idle by thread state: not read: no track (nothing from "
                f"{thread!r} in device {dev}'s traced seconds)")
            return None
        part = split(idle, track)
        whole = split([(off + w0 / 1e9, off + w1 / 1e9)], track)
        span = (w1 - w0) / 1e9
        say(f"{thread} over the traced seconds, idle device or not: "
            + ", ".join(f"{k or 'no state'} {100.0 * v / span:.2f}%"
                        for k, v in sorted(whole.items(),
                                           key=lambda kv: -kv[1])))
        for k, v in part.items():
            total[k] = total.get(k, 0.0) + v
        long = sorted(idle, key=lambda g: g[0] - g[1])[:10]
        for a, b in long:
            inside = split([(a, b)], track)
            say(f"device {dev}: idle gap of {(b - a) * 1e3:.2f} ms, "
                f"{a - off - w0 / 1e9:.3f} s into the traced seconds; "
                f"{thread} was in: " + ", ".join(
                    f"{k or 'no state'} {v * 1e3:.2f} ms" for k, v in
                    sorted(inside.items(), key=lambda kv: -kv[1])
                    if v > 5e-6))
    window = (w1 - w0) / 1e9 * len(offsets)
    cache[threads] = {k: 100.0 * v / window for k, v in total.items()}
    say(f"idle by state of {thread}: " + ", ".join(
        f"{k or 'no state'} {v:.2f}%" for k, v in sorted(
            cache[threads].items(), key=lambda kv: -kv[1])))
    return cache[threads]


def read(run, args):
    if run.profile is None:
        return None
    shares = by_state(run, args.get("threads") or [args["thread"]])
    if shares is None:
        return None
    if args.get("unmarked"):
        return shares.get(None, 0.0)
    return sum(shares.get(s, 0.0) for s in args["states"])
