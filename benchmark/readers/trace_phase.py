"""Device time per execution of the cell's step program that went to one
phase of the step (``args.phase``: ``colour``, ``damage``, ``motion``,
``transform``, ``entropy``, or ``other`` for what lies in no scope), in ms.

The trace names operations by their HLO names (``fusion.24``); which phase
an operation belongs to comes from the program itself, which wraps its
phases in ``jax.named_scope`` and gives, on demand, {operation: phase} for
the step an encoder serves (``selkies_tpu/observability/device_phases.py``:
the step is lowered and loaded from the compile cache, seconds). The
encoder asked is the one that served: what the first display held as the
window closed (``run.served_encoder``), a solo driver or a mesh lane's
facade alike; nothing is built here. It is asked once the server has
stopped and ``memory_peak_bytes`` is read: ``lower_step`` reads shapes only.
Operations are counted where they ran inside an execution of
``step_program``; an operation that holds others (a loop) counts only its
own time. None without a trace, and where the served encoder offers no
``lower_step`` or the program names no phases."""

import bisect
import re

from ..harness import say


def _phase_map(run):
    try:
        from selkies_tpu.observability import device_phases
    except ImportError:
        return None
    served = getattr(run, "served_encoder", None)
    return None if served is None else device_phases.step_phases(served)


def self_times(ops):
    """[(name, start, own ns)] of one device's operations: an operation
    that lies inside another takes its time away from it."""
    out, stack = [], []           # stack rows: [name, start, end, own]
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, d])
    while stack:
        top = stack.pop()
        out.append((top[0], top[1], max(0.0, top[3])))
    return out


def by_phase(run):
    """{phase: ms per step}, and under ``"_step"`` the step's own time per
    execution; cached on the run."""
    if hasattr(run, "_phase_ms"):
        return run._phase_ms
    run._phase_ms = None
    prof = run.profile
    program = run.cell.config.get("step_program")
    if prof is None or not program:
        return None
    pat = re.compile(r"^jit_" + re.escape(program) + r"\(")
    w0, w1 = prof.window()
    spans_of = {dev: sorted((s, s + d) for n, s, d in mods
                            if pat.match(n) and s >= w0 and s + d <= w1)
                for dev, mods in prof.modules.items()}
    steps = sum(len(v) for v in spans_of.values())
    if not steps:
        return None
    phases = _phase_map(run)
    if not phases:
        say("device phases: the encoder that served "
            f"({type(getattr(run, 'served_encoder', None)).__name__}) names "
            "none for its step")
        return None
    n_ops = 0
    step_ns = 0.0
    tot, per_op = {}, {}
    for dev, spans in spans_of.items():
        step_ns += sum(e - s for s, e in spans)
        starts = [s for s, _e in spans]
        inside = []
        for n, s, d in prof.ops.get(dev, []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= spans[i][1]:
                inside.append((n, s, d))
        for n, _s, own in self_times(inside):
            ph = phases.get(n, "other")
            tot[ph] = tot.get(ph, 0.0) + own
            per_op[(ph, n)] = per_op.get((ph, n), 0.0) + own
            n_ops += 1
    out = {ph: ns / steps / 1e6 for ph, ns in tot.items()}
    out["_step"] = step_ns / steps / 1e6
    say(f"device phases over {steps} executions of {program} "
        f"({out['_step']:.3f} ms each, {n_ops // steps} operations): "
        + ", ".join(f"{ph} {ms:.3f} ms" for ph, ms in sorted(
            ((k, v) for k, v in out.items() if k != "_step"),
            key=lambda kv: -kv[1]))
        + f"; together {sum(v for k, v in out.items() if k != '_step'):.3f}")
    for (ph, n), ns in sorted(per_op.items(), key=lambda kv: -kv[1])[:16]:
        say(f"  {n}: {ph}, {ns / steps / 1e6:.3f} ms per step")
    run._phase_ms = out
    return out


def read(run, args):
    if run.profile is None:
        return None
    ms = by_phase(run)
    if ms is None:
        return None
    return ms.get(args["phase"], 0.0)
