"""Process-wide runtime policy (selkies_tpu/runtime.py), the atomic native
build, and the scripts that must refuse to run without a TPU."""

import glob
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import pytest

from selkies_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """What enable_compile_cache() sets on jax.config, without setting it
    (a test run must not start writing a persistent cache)."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_honours_operator_variable(monkeypatch, tmp_path,
                                                 config_updates):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path / "elsewhere"))
    assert runtime.enable_compile_cache() == str(tmp_path / "elsewhere")
    # JAX reads its own variable: the helper names no directory in code
    assert not [k for k, _ in config_updates if k.endswith("cache_dir")]


def test_compile_cache_defaults_to_fixed_in_repo_path(monkeypatch,
                                                      config_updates):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert runtime.compile_cache_dir() == want      # fixed: no pid, no time
    assert [v for k, v in config_updates if k.endswith("cache_dir")] == [want]


def test_interpret_mode_is_asked_for_never_fallen_into(monkeypatch):
    assert runtime.pallas_interpret()       # conftest.py asked for it
    monkeypatch.delenv(runtime.INTERPRET_ENV)
    assert not runtime.pallas_interpret()   # no backend-sniffing default
    monkeypatch.setenv(runtime.INTERPRET_ENV, "0")
    assert not runtime.pallas_interpret()


def test_compile_watch_times_only_a_programs_first_use():
    watch = runtime.CompileWatch()
    assert watch.compiling_for_s() == 0.0
    entered, leave = threading.Event(), threading.Event()

    def dispatch():
        with watch.first_use("p"):
            entered.set()
            leave.wait(5)

    for expect_timed in (True, False):      # cold call, then the warm one
        entered.clear(), leave.clear()
        t = threading.Thread(target=dispatch)
        t.start()
        assert entered.wait(5)
        time.sleep(0.05)
        timed = watch.compiling_for_s()
        assert (0.0 < timed < runtime.COMPILE_GRACE_S) is expect_timed
        leave.set()
        t.join()
        assert watch.compiling_for_s() == 0.0

    with watch.first_use("batch"):          # nested: an inner exit does
        with watch.first_use("idr"):        # not pop the outer mark
            pass
        assert watch.compiling_for_s() > 0.0
    assert watch.compiling_for_s() == 0.0
    with pytest.raises(ValueError):         # a failed first call stays cold
        with watch.first_use("q"):
            raise ValueError
    with watch.first_use("q"):
        assert watch.compiling_for_s() > 0.0
    assert runtime.CompileWatch().compiling_for_s() == 0.0  # per encoder


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_measurement_scripts_refuse_a_cpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, script)],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        ok = json.loads(last).get("ok")
    except (ValueError, AttributeError):
        ok = None
    assert ok is not True                   # never the success line


NATIVE_DIR = os.path.join(REPO, "selkies_tpu", "native")

#: loads a COPY of the native package's loader from the directory it is
#: given — the loader builds beside itself, so the copy is its own world
LOAD_COPY = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location(
    "native_copy", sys.argv[1] + "/__init__.py")
native = importlib.util.module_from_spec(spec)
spec.loader.exec_module(native)
"""


def _copy_native(tmp_path, *sources):
    for name in ("__init__.py",) + sources:
        shutil.copy(os.path.join(NATIVE_DIR, name), tmp_path / name)


def test_atomic_native_build_survives_concurrent_builders(tmp_path):
    """Two processes race to build the same library into an empty
    directory (six xdist workers do at import): both load a complete
    library, one file remains, no temp litter."""
    _copy_native(tmp_path, "entropy.cpp")
    builder = LOAD_COPY + """
lib = native.entropy_lib()
assert lib is not None, native._ENTROPY.error
assert lib.jpeg_encode_scan_420 is not None
"""
    procs = [subprocess.Popen([sys.executable, "-c", builder, str(tmp_path)],
                              cwd=REPO, stderr=subprocess.PIPE, text=True)
             for _ in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
    assert len(glob.glob(str(tmp_path / "_libselkies_entropy.*.so"))) == 1
    assert not glob.glob(str(tmp_path / "*.tmp"))


def test_native_build_failure_is_loud_for_require(tmp_path):
    _copy_native(tmp_path)
    (tmp_path / "entropy.cpp").write_text("this is not C++\n")
    spec = importlib.util.spec_from_file_location(
        "native_copy", str(tmp_path / "__init__.py"))
    native = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(native)
    assert native.entropy_lib() is None     # fallback path: None + logged
    assert "native build" in str(native._ENTROPY.error)
    with pytest.raises(RuntimeError, match="required but unavailable"):
        native.require("entropy")
