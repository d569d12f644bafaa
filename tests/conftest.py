"""Test configuration: a JAX CPU backend with 8 virtual devices.

All tests run on the CPU (the chip is reached only through chip_smoke.py
and the bench scripts); multi-chip sharding tests use the 8 virtual
devices as a simulated mesh, per the test strategy in SURVEY.md §4.

Pallas interpreter mode is asked for HERE (``SELKIES_TPU_INTERPRET``, the
env form of the ``tpu_interpret`` setting) — the program never falls into
it on its own, so a kernel that reaches a backend without a compiler for
it fails instead of silently interpreting.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["SELKIES_TPU_INTERPRET"] = "true"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
