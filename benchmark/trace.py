"""From a ``jax.profiler`` trace to numbers: device-busy time, the time of
a program and of a kernel, the idle gaps and what the host did in them.

What a TPU trace holds (looked at by hand on a v5e, PR 24): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
execution of a compiled program, named ``jit_<function>(<fingerprint>)``,
and whose line ``XLA Ops`` has one event per HLO operation, named by its
whole HLO text (``%me_mc_stripes.1 = (s32[17,4,120]...``: a Pallas call
carries its kernel function's name). Times are nanoseconds from the start of
the profiling session, and the device goes on being traced while
``stop_trace`` collects, so events are clipped to the traced window.

The host tracer stays off. Switched on, even at the level that records only
``TraceAnnotation`` spans, it stalls the served pipeline: the JPEG cell's
steps then come in bursts of four with 0.3 s holes between them and a
third of its frames are lost (my chip runs, PR 24: 326 and 331 of 480 frames
with it, 472 without; device busy 10% with it, 67% without). So the traced
window is placed by the host's clock: the session starts when ``start_trace``
is called (its first 50 ms are its own set-up), and the window is the sleep
that follows it.

The reduction works on a :class:`Profile`, a plain structure that is read
from an ``.xplane.pb`` on the chip and from a small recorded extract
(``fixtures/``) in the tests.
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]            # name, start ns, duration ns
WINDOW_SPAN = "bench.trace_window"     # the one entry of Profile.host
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


@dataclass
class Profile:
    #: per device number: executions of compiled programs
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    #: per device number: HLO operations, by their short name
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    #: the traced window on the session's clock (see ``capture``)
    host: List[Event] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({
            "modules": {str(k): v for k, v in self.modules.items()},
            "ops": {str(k): v for k, v in self.ops.items()},
            "host": self.host})

    @staticmethod
    def from_json(text: str) -> "Profile":
        d = json.loads(text)

        def evs(rows) -> List[Event]:
            return [(str(n), float(s), float(t)) for n, s, t in rows]
        return Profile(
            modules={int(k): evs(v) for k, v in d["modules"].items()},
            ops={int(k): evs(v) for k, v in d["ops"].items()},
            host=evs(d["host"]))

    # -- the traced window -------------------------------------------------
    def window(self) -> Tuple[float, float]:
        """(start, end) in ns: the benchmark's own span round the traced
        seconds; failing that, first to last device event."""
        for name, s, d in self.host:
            if name == WINDOW_SPAN:
                return s, s + d
        ev = [e for d in self.modules.values() for e in d]
        if not ev:
            raise ValueError("an empty trace has no window")
        return min(s for _n, s, _d in ev), max(s + d for _n, s, d in ev)

    def cut(self, t0: float, t1: float) -> "Profile":
        """The events that lie wholly inside [t0, t1]."""
        def inside(rows):
            return [(n, s, d) for n, s, d in rows if s >= t0 and s + d <= t1]
        return Profile({k: inside(v) for k, v in self.modules.items()},
                       {k: inside(v) for k, v in self.ops.items()},
                       [e for e in self.host
                        if e[0] == WINDOW_SPAN or (e[1] >= t0
                                                   and e[1] + e[2] <= t1)])


def short_op_name(hlo_text: str) -> str:
    """``%fusion.24 = u32[557056]{...} fusion(...)`` -> ``fusion.24``."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text[:40]


def load_xplane(path: str) -> Profile:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    prof = Profile()
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    prof.modules[dev] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
                elif line.name == "XLA Ops":
                    prof.ops[dev] = [
                        (short_op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
    return prof


async def capture(work_dir: str, seconds: float) -> Profile:
    """Trace ``seconds`` of the running process (device only, see above),
    reduce the trace, and remove the files."""
    import time

    import jax

    shutil.rmtree(work_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    t_session = time.monotonic()
    await asyncio.to_thread(jax.profiler.start_trace, work_dir,
                            profiler_options=opts)
    t0 = time.monotonic()
    try:
        await asyncio.sleep(seconds)
    finally:
        t1 = time.monotonic()
        await asyncio.to_thread(jax.profiler.stop_trace)
    found = sorted(glob.glob(os.path.join(
        work_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    prof = await asyncio.to_thread(load_xplane, found[-1])
    prof.host = [(WINDOW_SPAN, (t0 - t_session) * 1e9, (t1 - t0) * 1e9)]
    shutil.rmtree(work_dir, ignore_errors=True)
    return prof


# -- reductions --------------------------------------------------------------

def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(prof: Profile, dev: int) -> List[Tuple[float, float]]:
    """When an operation ran on the device: the union of its programs' and
    its operations' intervals, clipped to the traced window."""
    w0, w1 = prof.window()
    rows = prof.modules.get(dev, []) + prof.ops.get(dev, [])
    return union_ns((max(s, w0), min(s + d, w1)) for _n, s, d in rows
                    if s + d > w0 and s < w1)


def busy_s(prof: Profile) -> Dict[int, float]:
    return {dev: sum(e - s for s, e in busy_intervals(prof, dev)) / 1e9
            for dev in sorted(prof.modules)}


def window_s(prof: Profile) -> float:
    w0, w1 = prof.window()
    return (w1 - w0) / 1e9


def program_ms_per_step(prof: Profile, program: str) -> Optional[float]:
    """Device time of the executions of the program called ``program``
    (``jit_<program>(...)``), per execution, over every device."""
    pat = re.compile(r"^jit_" + re.escape(program) + r"\(")
    w0, w1 = prof.window()
    durs = [d for rows in prof.modules.values() for n, s, d in rows
            if pat.match(n) and s >= w0 and s + d <= w1]
    return sum(durs) / len(durs) / 1e6 if durs else None


def kernel_ms_per_call(prof: Profile, kernel: str) -> Optional[float]:
    """Device time of the operation called ``kernel`` (``kernel`` or
    ``kernel.<n>``), per call."""
    pat = re.compile(r"^" + re.escape(kernel) + r"(\.\d+)?$")
    w0, w1 = prof.window()
    durs = [d for rows in prof.ops.values() for n, s, d in rows
            if pat.match(n) and s >= w0 and s + d <= w1]
    return sum(durs) / len(durs) / 1e6 if durs else None


def top_device_ops(prof: Profile, k: int = 10) -> List[List]:
    """[[name, seconds]] of the operations that took most device time."""
    w0, w1 = prof.window()
    tot: Dict[str, float] = {}
    for rows in prof.ops.values():
        for n, s, d in rows:
            if s >= w0 and s + d <= w1:
                tot[n] = tot.get(n, 0.0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def program_of(module_name: str) -> str:
    """``jit_step(1234)`` -> ``step``."""
    m = re.match(r"^jit_(.*)\(\d+\)$", module_name)
    return m.group(1) if m else module_name


def idle_gaps(prof: Profile, k: int = 10) -> List[List]:
    """[[what the device was waiting for, seconds]] of the longest gaps in
    which nothing ran on a device. The host is not traced (see the top of
    this file), so a gap is named by what ended it: the program the host
    launched next, which is what it had yet to get to. Spans inside the
    program, on this clock, are the next ``tracing`` issue's."""
    w0, w1 = prof.window()
    gaps: List[Tuple[float, float, int]] = []
    for dev in prof.modules:
        at = w0
        for s, e in busy_intervals(prof, dev):
            if s > at:
                gaps.append((at, s, dev))
            at = max(at, e)
        if w1 > at:
            gaps.append((at, w1, dev))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1, dev in gaps[:k]:
        nxt = [(s, n) for n, s, _d in prof.modules.get(dev, [])
               if g1 - 1e3 <= s < w1]
        label = ("before " + program_of(min(nxt)[1])) if nxt else \
            "until the window's end"
        out.append([label, (g1 - g0) / 1e9])
    return out
