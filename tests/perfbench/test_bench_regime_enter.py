"""``regime.enter``: how a configuration says which regime of its pipeline
the bounds were measured in and how the harness tells. The harness looks
once steady state is reached; a stream in the stated regime is measured at
once, one in another is run dry and filled again, a few times at most."""

import asyncio
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ENTER = {"stage": "pipe_wait", "max_p50_ms": 40.0}
LOOK_S, STOP_S, SETTLE_S, REFILLS = 0.2, 0.1, 0.1, 3


@pytest.fixture(autouse=True)
def quick(monkeypatch):
    """The harness's own seconds, cut to tenths for a test."""
    from benchmark import harness

    for name in ("LOOK_S", "STOP_S", "SETTLE_S", "REFILLS"):
        monkeypatch.setattr(harness, name, globals()[name])


class _Client:
    def __init__(self):
        self.killed, self.t0 = None, time.monotonic()

    def frames_seen(self):
        return int((time.monotonic() - self.t0) * 200)


class _Recorder:
    """Frames leave ``pipe_wait`` a hundred times a second; how long each
    waited there is the regime's: ``deep_fills`` fills of the pipeline come
    out deep (100 ms), every later one shallow (4 ms)."""

    def __init__(self, source, deep_fills):
        self.source, self.deep_fills = source, deep_fills

    def _completed(self):
        now = time.monotonic()
        wait = 0.100 if self.source.fills < self.deep_fills else 0.004
        return [SimpleNamespace(spans={
            "pipe_wait": (now - k / 100 - wait, now - k / 100)})
            for k in range(100)]


class _Source:
    """Counts how often the desktop was stopped and set going again."""

    def __init__(self):
        self.fills, self._stopped = 0, False

    @property
    def stopped(self):
        return self._stopped

    @stopped.setter
    def stopped(self, value):
        if self._stopped and not value:
            self.fills += 1
        self._stopped = value


def _run(deep_fills, rehearsal=None):
    from benchmark.cells import Cell
    from benchmark.harness import Run

    conf = {"width": 64, "height": 64,
            "regime": {"frames_in_flight": [1, 2], "enter": ENTER}}
    cell = Cell("c", 1, "conf", "mix", conf, {"steady": {"frames": 5}}, [], [])
    run = Run(cell, 1, 1.0, False, rehearsal)
    run.width, run.height = 64, 64
    run.clients = {"primary": _Client()}
    run.sources = [_Source()]
    run.server = SimpleNamespace(recorder=_Recorder(run.sources[0],
                                                    deep_fills))
    t0 = time.monotonic()
    asyncio.run(run._enter_regime(ENTER))
    return run, time.monotonic() - t0


def test_a_stream_in_the_stated_regime_is_measured_at_once(capsys):
    run, took = _run(deep_fills=0)
    assert run.sources[0].fills == 0 and took < 0.1
    assert run.counters["regime_rolls"] == 0
    assert "look 1: pipe_wait p50 over the last 0.2 s 4.00 ms" \
        in capsys.readouterr().err


@pytest.mark.parametrize("deep_fills", [1, 2])
def test_a_stream_in_another_regime_is_run_dry_and_filled_again(
        capsys, deep_fills):
    run, took = _run(deep_fills)
    assert run.sources[0].fills == deep_fills == run.counters["regime_rolls"]
    assert not run.sources[0].stopped
    # each refill: the stop, then settle_s and look_s of stream
    assert took >= deep_fills * (STOP_S + SETTLE_S + LOOK_S)
    err = capsys.readouterr().err
    assert err.count("not in it") == deep_fills and err.count(": in it") == 1


def test_a_stream_that_never_gets_there_is_measured_as_it_is(capsys):
    run, _took = _run(deep_fills=99)
    assert run.sources[0].fills == REFILLS == run.counters["regime_rolls"]
    assert not run.sources[0].stopped
    assert capsys.readouterr().err.count("not in it") == REFILLS + 1


def test_a_rehearsal_refills_once_at_most():
    run, _took = _run(deep_fills=99, rehearsal=(64, 64))
    assert run.sources[0].fills == 1


def test_a_stage_no_frame_has_reads_none_and_counts_as_not_in_it():
    run, _took = _run(deep_fills=0)
    assert run._stage_p50("no_such_stage", 1.0) is None


def test_the_h264_configuration_says_how_its_regime_is_told_and_jpeg_none():
    from benchmark.cells import load_cell

    enter = load_cell("h264-1080p60.scroll").config["regime"]["enter"]
    assert enter["stage"] == "pipe_wait" and enter["what"]
    # between the two regimes' readings (3-5 ms and 100-110 ms)
    assert 10 <= enter["max_p50_ms"] <= 80
    assert "enter" not in load_cell("jpeg-1080p60.scroll").config["regime"]
