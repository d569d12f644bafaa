"""Share of the traced window, in %, in which no operation ran on the
device; over several chips the mean, with each device on an earlier line."""

from .. import trace
from ..harness import say


def read(run, args):
    if run.profile is None:
        return None
    busy = trace.busy_s(run.profile)
    if not busy:
        return None
    window = trace.window_s(run.profile)
    for dev, b in busy.items():
        say(f"device {dev}: busy {b:.4f} s of {window:.4f} s traced: "
            f"idle {100.0 * (1 - b / window):.2f}%")
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / window)
