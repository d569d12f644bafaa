"""The longest stall of one kind that began in the window, in ms, from the
program's stall watch (``selkies_tpu/observability/stall_watch.py``):
``interpreter`` (a thread that only sleeps woke late: some thread kept the
interpreter's lock, and every thread waited) or ``loop`` (the event loop's
heartbeat stopped while threads ran). 0 where none was recorded; None where
the program has no stall watch."""

from ..harness import say


def read(run, args):
    get = getattr(run.server.recorder, "stalls", None)
    if get is None:
        return None
    rows = [(b - a) * 1000.0 for kind, a, b in get(*run.window)
            if kind == args["kind"]]
    if rows:
        say(f"stall watch: {len(rows)} stalls of kind {args['kind']} began "
            f"in the window: " + ", ".join(f"{ms:.1f}" for ms in rows[:12])
            + " ms")
    return max(rows, default=0.0)
