#!/usr/bin/env python3
"""Run a cell's control on the chip: the program with its own coarser
quantiser switched on (the configuration's ``control.env``), at the cell's
own size and load, on several seeds. Every run has to come out NOT correct,
by the number the control is there to fail. The benchmark's own runs never
run this; the builder does, once, when a limit is set (PERF.md has the
readings), and `tests/perfbench` keeps it at a size a test run can hold.

    python benchmark/control.py --workload <name> --seeds 1,2,3 --seconds 8

One child process per seed, one after another: this process stays off JAX,
so each child has the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearsal", default="")
    args = ap.parse_args()
    failed_to_fail = 0
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds", str(args.seconds),
               "--trace", "0", "--control", "1"]
        if args.rehearsal:
            cmd += ["--rehearsal", args.rehearsal]
        p = subprocess.run(cmd, capture_output=True, text=True)
        if p.returncode != 0 or not p.stdout.strip():
            print(f"seed {seed}: the control crashed (exit {p.returncode}): "
                  f"it has failed, and sets no upper end\n{p.stderr[-800:]}")
            continue
        out = json.loads(p.stdout.strip().splitlines()[-1])
        print(f"seed {seed}: correct={out['correct']} " + " ".join(
            f"{k}={v['value']:.6g}/{v['limit']:g}"
            for k, v in out["compared"].items()), flush=True)
        failed_to_fail += bool(out["correct"])
    print(f"controls that passed as correct: {failed_to_fail}")
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
