"""Sanitizer builds of the native components (SURVEY.md §5 race detection).

The reference relies on by-construction safety plus external tooling; here
the native build system itself carries the instrumentation option
(``SELKIES_NATIVE_SANITIZE`` for the lazily built libs, ``SANITIZE=`` for
the Makefile shims), and this test actually EXECUTES the JPEG entropy
coder under AddressSanitizer and cross-checks its bitstream against the
pure-Python oracle.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _libasan() -> str:
    try:
        out = subprocess.run(
            ["g++", "-print-file-name=libasan.so"],
            capture_output=True, text=True, timeout=30, check=True,
        ).stdout.strip()
    except (subprocess.SubprocessError, FileNotFoundError):
        return ""
    return out if os.path.isabs(out) and os.path.exists(out) else ""


# jax must stay unimported here: the ASAN __cxa_throw interceptor check
# fails inside jaxlib's uninstrumented nanobind, which has nothing to do
# with our code — so the child mirrors _entropy_encode_420's ctypes call
# instead of importing selkies_tpu.encoder.jpeg
CHILD = r"""
import numpy as np
from selkies_tpu.native import entropy_lib
from selkies_tpu.encoder import entropy_py
from selkies_tpu.encoder.jpeg_tables import std_tables

lib = entropy_lib()
assert lib is not None, "sanitized entropy lib failed to build"
rng = np.random.default_rng(7)
# [block_rows, block_cols, 64] zigzagged coefficient planes (4:2:0)
y = rng.integers(-128, 128, (4, 4, 64), dtype=np.int16)
cb = rng.integers(-64, 64, (2, 2, 64), dtype=np.int16)
cr = rng.integers(-64, 64, (2, 2, 64), dtype=np.int16)
dc_l, ac_l, dc_c, ac_c = std_tables()
cap = (y.size + cb.size + cr.size) * 4 + 4096
out = np.empty(cap, dtype=np.uint8)
n = lib.jpeg_encode_scan_420(
    np.ascontiguousarray(y), np.ascontiguousarray(cb),
    np.ascontiguousarray(cr), y.shape[0], y.shape[1],
    dc_l.code_arr, dc_l.len_arr, ac_l.code_arr, ac_l.len_arr,
    dc_c.code_arr, dc_c.len_arr, ac_c.code_arr, ac_c.len_arr,
    out, cap)
assert n > 0, n
got = out[:n].tobytes()
want = entropy_py.encode_scan_420(y, cb, cr)
assert got == want, "sanitized coder diverged from the python oracle"
print("SANITIZED_OK", len(got))
"""


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_entropy_coder_runs_clean_under_asan(tmp_path):
    libasan = _libasan()
    if not libasan:
        pytest.skip("libasan.so not installed")
    env = dict(os.environ)
    env["SELKIES_NATIVE_SANITIZE"] = "address"
    env["LD_PRELOAD"] = libasan
    # leak checking would flag the Python interpreter itself, not our lib
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    proc = subprocess.run(
        [sys.executable, "-c", CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "SANITIZED_OK" in proc.stdout
    import glob
    assert glob.glob(os.path.join(              # cached under its own name
        REPO, "selkies_tpu", "native", "_libselkies_entropy_address.*.so"))


@pytest.mark.skipif(shutil.which("make") is None or shutil.which("cc") is None,
                    reason="no make/cc")
def test_interposer_builds_with_sanitize_flag(tmp_path):
    src = os.path.join(REPO, "native", "interposer")
    build = tmp_path / "interposer"
    shutil.copytree(src, build)
    proc = subprocess.run(
        ["make", "-B", "SANITIZE=address"], cwd=build,  # -B: a prebuilt .so
        capture_output=True, text=True, timeout=120,    # ships in the repo
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    so = build / "selkies_joystick_interposer.so"
    assert so.exists()
    syms = subprocess.run(["nm", "-D", str(so)], capture_output=True,
                          text=True, timeout=30).stdout
    assert "__asan" in syms  # instrumentation actually present
