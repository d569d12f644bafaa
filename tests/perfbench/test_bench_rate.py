"""A cell's rate reaches the session: the client asks for it in its SETTINGS
as a browser that picked a rate does (the mix's ``client.framerate`` where
it says what its client asks for, else the configuration's ``framerate``),
the server says what the session runs at, and a run whose session runs at
another rate ends. Also
what took the place of the look before the window (``regime.enter``, gone
with PR 41): a joined stream is measured at once, and a traced window is
held to no band."""

import asyncio
import dataclasses
import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.cells import Cell, load_cell  # noqa: E402
from benchmark.client import Client  # noqa: E402
from benchmark.harness import Run  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def a_cell(rate, displays=("primary",)):
    conf = {"width": 64, "height": 64, "framerate": rate,
            "displays": list(displays),
            "regime": {"frames_in_flight": [1, 2]}}
    return Cell("c", 1, "conf", "mix", conf, {"steady": {"frames": 5, "seconds": 0.1}}, [], [])


def a_run(rate, served, source_fps=None, displays=("primary",), trace=False):
    run = Run(a_cell(rate, displays), 1, 1.0, trace, None)
    run.server = SimpleNamespace(display_clients={
        d: SimpleNamespace(bp=SimpleNamespace(framerate=served))
        for d in displays})
    run.sources = [SimpleNamespace(number=k, fps=source_fps or served,
                                   joined=lambda: None)
                   for k in range(len(displays))]
    return run


# -- the arithmetic of the check, on a stand-in server -----------------------

@pytest.mark.parametrize("rate", [30, 60, 120])
def test_the_rate_read_back_is_the_configurations(capsys, rate):
    run = a_run(rate, float(rate))
    run.read_session_rates()
    assert run.session_fps == {"primary": float(rate)}
    assert f"primary {rate}" in capsys.readouterr().err


@pytest.mark.parametrize("rate, served, source_fps", [
    (120, 60.0, None),       # SETTINGS never reached the session
    (30, 60.0, None),
    (60, 60.0, 30.0),        # the state says 60, the loop was started at 30
], ids=["asked-120-ran-60", "asked-30-ran-60", "loop-at-another-rate"])
def test_a_run_whose_session_ran_at_another_rate_ends(rate, served,
                                                      source_fps):
    run = a_run(rate, served, source_fps)
    with pytest.raises(RuntimeError, match="not the cell that is named"):
        run.read_session_rates()


def test_the_mix_says_what_its_client_asks_for_else_the_configuration():
    cell = a_cell(60)
    assert Run(cell, 1, 1.0, False, None).fps == 60.0
    cell.traffic["client"] = {"framerate": 120}
    assert Run(cell, 1, 1.0, False, None).fps == 120.0
    del cell.config["framerate"]
    cell.traffic.pop("client")
    assert Run(cell, 1, 1.0, False, None).fps == 60.0     # the server's own
    # the files: scroll120's client asks for 120 of a deployment whose
    # default is 60; scroll's asks for the configuration's
    new, old = load_cell("h264-1080p120.scroll"), \
        load_cell("h264-1080p60.scroll")
    assert Run(new, 1, 1.0, False, None).fps == 120.0
    assert Run(old, 1, 1.0, False, None).fps == 60.0
    assert Run(load_cell("jpeg-1080p60.scroll"), 1, 1.0, False, None).fps \
        == 60.0


def test_every_display_of_a_lane_is_asked():
    run = a_run(60, 60.0, displays=("d0", "d1", "d2", "d3"))
    run.server.display_clients["d2"].bp.framerate = 30.0
    with pytest.raises(RuntimeError, match="d2"):
        run.read_session_rates()
    del run.server.display_clients["d2"]
    with pytest.raises(RuntimeError, match="names no rate"):
        run.read_session_rates()


def test_the_client_asks_for_the_rate_in_settings_and_none_where_none():
    sent = []

    class Ws:
        def __init__(self):
            self.said = iter(["MODE websockets",
                              json.dumps({"type": "server_settings"})])

        async def recv(self):
            return next(self.said)

        async def send(self, text):
            sent.append(text)

        def __aiter__(self):
            return self

        async def __anext__(self):
            raise StopAsyncIteration

    async def connect(*_a, **_kw):
        return Ws()

    import websockets

    real, websockets.connect = websockets.connect, connect
    try:
        for rate in (120.0, None):
            c = Client(1, "primary", 64, 48, rate)
            asyncio.run(c.connect())
    finally:
        websockets.connect = real
    bodies = [json.loads(t.split(",", 1)[1]) for t in sent]
    assert bodies[0] == {"displayId": "primary", "initialClientWidth": 64,
                         "initialClientHeight": 48, "framerate": 120}
    assert "framerate" not in bodies[1]


# -- through the real server, at a size a test run can hold ------------------

def served(rate):
    """Boot the real server on the JPEG configuration with ``framerate``
    set to ``rate``, join as the harness joins, and stop: the run as it
    stood when steady state was reached (or what ``join`` raised)."""
    cell = load_cell("jpeg-1080p60.scroll")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, framerate=rate),
        traffic=dict(cell.traffic, steady={"frames": 10, "seconds": 1.0}))
    run = Run(cell, 5, 1.0, False, (256, 144))

    async def go():
        task = await run.boot()
        try:
            await run.join()
        finally:
            for s in run.sources:
                s.stopped = True
            await asyncio.sleep(0.3)
            for c in run.clients.values():
                await c.close()
            task.cancel()
            try:
                await asyncio.wait_for(task, 60)
            except (asyncio.CancelledError, asyncio.TimeoutError):
                pass
    asyncio.run(go())
    return run


@pytest.mark.parametrize("rate", [30, 120])
def test_the_server_runs_the_session_at_the_rate_the_client_asked(rate):
    run = served(rate)
    assert run.session_fps == {"primary": float(rate)}
    src = run.sources[0]
    assert src.fps == float(rate)
    # and its capture loop ticks at it: the last second of calls
    calls = [t for t in src._calls if t >= src._calls[-1] - 1.0]
    assert 0.7 * rate <= len(calls) <= 1.15 * rate, len(calls)


def test_a_rate_the_server_clamps_is_not_the_configurations():
    """Upstream's range is 8-120: a configuration that says 144 is served
    at 120, and the run says so and ends instead of measuring it."""
    with pytest.raises(RuntimeError, match="framerate 144.*120"):
        served(144)


def test_a_configurations_mixes_each_have_their_band():
    run = a_run(60, 60.0)
    run.server.recorder = SimpleNamespace()
    run.cell.config["regime"] = {
        "frames_in_flight": [6, 8],
        "frames_in_flight_by_traffic": {"mix": [1, 2]}}
    run.metrics = {"latency_p50_ms": 100.0, "delivered_fps": 15.0}
    run.latencies_ms = [100.0]
    w = run.regime()
    assert w["band"] == [1, 2] and w["regime"] == "expected"
    run.cell.traffic_name = "the-other-mix"
    w = run.regime()
    assert w["band"] == [6, 8] and w["regime"] == "other"


# -- no look before the window; a traced window is held to no band -----------

@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(CONFIGS) if f.endswith(".json")))
def test_no_configuration_says_how_to_enter_a_regime(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        regime = json.load(f)["regime"]
    assert {"what", "frames_in_flight"} <= set(regime) <= {
        "what", "frames_in_flight", "frames_in_flight_by_traffic"}
    for lo, hi in [regime["frames_in_flight"]] + list(
            regime.get("frames_in_flight_by_traffic", {}).values()):
        assert 0 < lo < hi
    assert "untraced" in regime["what"]


def test_a_joined_stream_is_measured_at_once(monkeypatch):
    """Steady state, the session's rate, and the window: nothing looks at
    the stream, stops the desktop or waits in between."""
    run = a_run(60, 60.0)

    class Joined:
        killed = None
        t_settings = 0.0

        def __init__(self, *_a):
            self.t0 = time.monotonic()

        async def connect(self):
            pass

        def frames_seen(self):
            return 1 + int((time.monotonic() - self.t0) * 500)

    monkeypatch.setattr(harness, "Client", Joined)
    run.port = 0
    t0 = time.monotonic()
    asyncio.run(run.join())
    assert time.monotonic() - t0 < 0.5
    assert not any(getattr(s, "stopped", False) for s in run.sources)
    assert run.session_fps == {"primary": 60.0}
    assert set(run.counters) <= {"warmup_s"}


@pytest.mark.parametrize("trace, in_flight, want", [
    (False, 1.5, "expected"), (False, 2.5, "other"), (True, 2.5, "traced"),
    (True, 1.5, "traced"), (False, 5.0, "other")])
def test_the_band_is_of_untraced_windows(trace, in_flight, want):
    run = a_run(60, 60.0, trace=trace)
    # a band of another mix of the same configuration is not this cell's
    run.cell.config["regime"]["frames_in_flight_by_traffic"] = {
        "another-mix": [4, 6]}
    run.server.recorder = SimpleNamespace()
    run.metrics = {"latency_p50_ms": 100.0, "delivered_fps": in_flight * 10}
    run.latencies_ms = [100.0]
    run.latencies_by_second = {0: [90.0, 100.0, 110.0], 1: [100.0]}
    run.session_fps = {"primary": 60.0}
    w = run.regime()
    assert w["regime"] == want and w["band"] == [1, 2]
    assert w["frames_in_flight"] == pytest.approx(in_flight)
    assert w["latency_p50_by_second_ms"] == [100.0, 100.0]
    assert w["session_fps"] == {"primary": 60.0}
    assert set(w) == {"latency_p95_ms", "frames_in_flight",
                      "fetch_wait_p50_ms", "inflight_batches", "band",
                      "session_fps", "latency_p50_by_second_ms",
                      "stalled_s", "regime"}


# -- a traced run whose seconds held a long standstill is traced once more ---

@pytest.mark.parametrize("held_s, window_s, traces", [
    (0.0, 12.0, 1), (0.9, 12.0, 1), (1.4, 12.0, 2), (1.4, 1.2, 1)],
    ids=["quiet", "under-the-line", "over-it", "no-room-left"])
def test_a_traced_window_that_stood_still_is_traced_once_more(
        monkeypatch, capsys, held_s, window_s, traces):
    from benchmark import trace as trace_mod

    taken = []

    async def capture(_dir, seconds):
        taken.append(time.monotonic())
        await asyncio.sleep(seconds)
        return trace_mod.Profile(
            modules={0: [("jit_step(1)", 0.0, 1e6)]}, ops={0: []},
            host=[(trace_mod.WINDOW_SPAN, 0.0, seconds * 1e9)])

    monkeypatch.setattr(trace_mod, "capture", capture)
    cell = a_cell(60)
    cell.traffic["trace"] = {"start_s": 0.05, "seconds": 0.2}
    run = Run(cell, 1, window_s, True, None)
    run.sources = []

    def stalls(t0, t1):
        # the first traced seconds held the standstill; the second none
        return [("interpreter", t0 + 0.01, t0 + 0.01 + held_s)] \
            if len(taken) == 1 and held_s else []

    run.server = SimpleNamespace(recorder=SimpleNamespace(stalls=stalls),
                                 display_clients={})
    run.displays = []

    async def go():
        # the window itself is not waited for: only the traces are
        task = asyncio.create_task(run.measure())
        await asyncio.sleep(1.0)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
    asyncio.run(go())
    assert len(taken) == traces
    assert run.trace_asked_at == pytest.approx(taken[-1], abs=0.05)
    err = capsys.readouterr().err
    assert ("traced once more" in err) == (traces == 2)
