"""Tests of this directory run side by side, one file to a worker, and more
than one of them rehearses a traced run. The benchmark keeps its trace in one
place in its checkout (``benchmark/.work/trace``) and clears it before and
after: right for one run at a time, a race between two. Here every test gets
a trace directory of its own."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def a_trace_directory_of_its_own(tmp_path, monkeypatch):
    from benchmark import trace

    real = trace.capture

    async def capture(_work_dir, seconds):
        return await real(str(tmp_path / "trace"), seconds)

    monkeypatch.setattr(trace, "capture", capture)
