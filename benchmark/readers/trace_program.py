"""Device time per execution of the cell's step program, from the profiler
trace. The configuration names the program (``step_program``: the function
behind ``jit_<name>(...)`` in the trace's ``XLA Modules`` line)."""

from .. import trace


def read(run, args):
    program = run.cell.config.get(args.get("config_key", "step_program"))
    if run.profile is None or not program:
        return None
    return trace.program_ms_per_step(run.profile, program)
