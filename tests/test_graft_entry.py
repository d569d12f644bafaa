"""The driver-facing multi-chip dry run completes in a CPU child.

``dryrun_multichip`` is CPU-only by construction: it starts a child
interpreter pinned to ``JAX_PLATFORMS=cpu`` with ``n`` virtual devices, so
it never competes for a chip its caller may hold. This test runs it the
way the driver does — from a parent that requests no platform at all —
and asserts it completes.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_completes_in_cpu_child():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # the entry point must pin the CPU itself
    code = ("import sys; sys.path.insert(0, %r); "
            "import __graft_entry__ as g; g.dryrun_multichip(8)" % REPO)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
