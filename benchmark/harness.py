"""One run of one cell: serve, warm, measure, stop, compare.

The system under test is ``selkies_tpu.server.main.build`` + ``serve`` and
the websocket endpoint of its ``DataStreamingServer``: what ``selkies-tpu``
itself boots. The benchmark hands it frames (``server.source_factory``),
joins as a websocket client, and reads its flight recorder. Boot and health
helpers are copied from ``chip_smoke.py`` (PERF.md's verdict table).
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import check, metrics
from .cells import BENCH_DIR, Cell, module
from .client import H264_STRIPE, Client, Frame
from .sources.desktop import CallLog

#: the server's flight recorder keeps this many finished spans: every span
#: of set-up and of the window has to be there still when the frames are
#: attributed after it (60 a second and display; the default is 4096)
RECORDER_CAPACITY = 1 << 16
#: a traced run has one trace a side to give its per-layer line: where the
#: traced seconds held an ``interpreter`` stall (the machine standing still)
#: of more than this, they are traced once more and the first is logged
RETRACE_STALL_S = 1.0


def say(*parts: Any) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CacheEvents:
    """JAX's persistent-compile-cache hits and misses, as the smoke's
    ``CacheEvents`` counts them."""

    def __init__(self) -> None:
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class _NoWarmUp:
    """Stands in for ``server.main.WarmUp`` in a rehearsal."""

    def __init__(self, _settings) -> None:
        import threading

        self.done, self.error, self.seconds = threading.Event(), None, 0.0
        self.done.set()


class Run:
    """Everything one run knows; what the per-layer readers are handed."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 rehearsal: Optional[Tuple[int, int]],
                 bench_dir: str = BENCH_DIR,
                 env_extra: Optional[Dict[str, str]] = None) -> None:
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace = trace
        self.rehearsal, self.bench_dir = rehearsal, bench_dir
        self.env_extra = dict(env_extra or {})
        conf = cell.config
        self.width, self.height = rehearsal or (conf["width"], conf["height"])
        #: the rate the cell's client asks for in its SETTINGS, and what the
        #: server has to say the session ran at (``session_fps``): the
        #: mix's where it says what its client asks for (a browser's user
        #: picks one inside the server's range), else the configuration's
        self.fps = float(cell.traffic.get("client", {}).get(
            "framerate", conf.get("framerate", 60)))
        self.session_fps: Dict[str, float] = {}
        self.displays: List[str] = list(conf.get("displays", ["primary"]))
        self.log = CallLog()
        self.sources: List[Any] = []
        self.clients: Dict[str, Client] = {}
        self.counters: Dict[str, float] = {}
        self.window: Tuple[float, float] = (0.0, 0.0)
        self.spans: List[Any] = []          # recorder traces begun in window
        self.profile = None                 # trace.Profile of a traced run
        self.server = None
        self.display_of_source: Dict[int, str] = {}
        self.gate_closed: Dict[str, int] = {}
        self.encoder_stats: Dict[str, Dict[str, Any]] = {}
        #: the encoder the first display held as the window closed: what
        #: served, solo driver or mesh lane facade (``trace_phase`` asks it
        #: for its step's phases)
        self.served_encoder: Any = None
        self.latencies_ms: List[float] = []  # of every change due in window
        #: the same latencies, by the whole second of the window in which
        #: the change fell due (what a standstill costs, and for how long)
        self.latencies_by_second: Dict[int, List[float]] = {}
        self.trace_asked_at: Optional[float] = None
        self.metrics: Dict[str, float] = {}  # end to end, of the window
        self.loop_late_s = 0.0
        self._attributed: Dict[str, int] = {}
        self._tops: Dict[str, "TopBand"] = {}

    # -- boot --------------------------------------------------------------
    def _source_factory(self) -> Callable:
        gen = module("sources", self.cell.traffic["generator"],
                     self.bench_dir)
        params = dict(self.cell.traffic.get("params", {}))

        def factory(width, height, fps, x=0, y=0):
            number = len(self.sources)
            src = gen.Source(width, height, fps, number, self.log,
                             self.seed + number, params)
            self.sources.append(src)
            return src
        return factory

    async def boot(self):
        from selkies_tpu.observability.tracing import FlightRecorder
        from selkies_tpu.server import main as server_main
        from selkies_tpu.settings import Settings

        env = {"SELKIES_PORT": str(free_port()),
               "SELKIES_WEB_PORT": str(free_port()),
               "SELKIES_METRICS_PORT": str(free_port())}
        env.update(self.cell.config.get("env", {}))
        env.update(self.env_extra)
        real_warmup = server_main.WarmUp
        if self.rehearsal:
            # the boot warm-up compiles 1920x1080 whatever joins: a
            # rehearsal at a tiny size cannot wait for that on a CPU
            env["SELKIES_TPU_INTERPRET"] = "true"
            server_main.WarmUp = _NoWarmUp
        try:
            server = server_main.build(Settings(argv=[], env=env))
        finally:
            server_main.WarmUp = real_warmup
        server.host = "127.0.0.1"
        server.source_factory = self._source_factory()
        server.recorder = FlightRecorder(capacity=RECORDER_CAPACITY)
        self.server = server
        self.port = int(env["SELKIES_PORT"])
        task = asyncio.create_task(server_main.serve(server))
        deadline = time.monotonic() + 60
        while server._server is None:
            if task.done():
                task.result()
                raise RuntimeError("server exited during boot")
            if time.monotonic() > deadline:
                raise RuntimeError("server did not listen in 60 s")
            await asyncio.sleep(0.02)
        ok = await asyncio.to_thread(server.warmup.done.wait, 1500.0)
        if not ok or server.warmup.error is not None:
            raise RuntimeError(f"boot warm-up failed: {server.warmup.error!r}")
        self.counters["warmup_s"] = float(server.warmup.seconds)
        return task

    async def join(self) -> None:
        """One client per display, one after another (each join reflows the
        layout), each asking for the cell's rate in its SETTINGS (``self.fps``),
        then wait for steady state: every client has had the mix's number of
        frames and its seconds have passed since the last join. The stream
        is not stopped again before the window: after a stop of half a
        second 6 windows of 18 filled in the old pipeline's deeper regime,
        without it 1 of 18 (PERF.md, PR 27)."""
        steady = self.cell.traffic.get("steady", {})
        for did in self.displays:
            c = Client(self.port, did, self.width, self.height, self.fps)
            await c.connect()
            self.clients[did] = c
            await self._until(lambda c=c: c.frames_seen() >= 1, 1500.0,
                              f"{did}: first frame")
        for s in self.sources:
            # every client holds its first frame: a mix that reaches its
            # steady state by its own traffic begins it here
            s.joined()
        t_joined = time.monotonic()
        want = int(steady.get("frames", 20))
        base = {d: c.frames_seen() for d, c in self.clients.items()}
        await self._until(
            lambda: all(c.frames_seen() - base[d] >= want
                        for d, c in self.clients.items())
            and time.monotonic() - t_joined >= float(steady.get("seconds", 2)),
            300.0, "steady state")
        self.read_session_rates()
        if self.cell.config.get("env", {}).get("SELKIES_TPU_MESH"):
            # the server skips its boot warm-up under tpu_mesh: a lane's
            # programs compile (or load) when the first client joins it
            first = self.clients[self.displays[0]]
            self.counters["warmup_s"] = \
                first.frames[0].t_last - first.t_settings

    def read_session_rates(self) -> None:
        """The rate each display's session runs at, as the server says it
        (``DisplayState.bp.framerate``: what its capture loop ticks at and
        its backpressure counts in). A session at another rate than the
        cell's client asked for is another cell: the run ends here."""
        for did in self.displays:
            st = self.server.display_clients.get(did)
            rate = getattr(getattr(st, "bp", None), "framerate", None)
            if rate is None:
                raise RuntimeError(f"{did}: the server names no rate for it")
            self.session_fps[did] = float(rate)
        say("session rate, as the server says it: " + ", ".join(
            f"{d} {r:g}" for d, r in self.session_fps.items())
            + f" (asked for in SETTINGS: {self.fps:g})")
        off = {d: r for d, r in self.session_fps.items() if r != self.fps}
        # and what each capture loop handed its source when it started
        off.update({f"source {s.number}": s.fps for s in self.sources
                    if s.fps != self.fps})
        if off:
            raise RuntimeError(
                f"the cell's client asked for framerate {self.fps:g} and "
                f"the server ran {off}: not the cell that is named")

    async def _until(self, cond: Callable[[], bool], timeout_s: float,
                     what: str) -> None:
        deadline = time.monotonic() + timeout_s
        while not cond():
            for c in self.clients.values():
                if c.killed:
                    raise RuntimeError(f"server said {c.killed!r}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"timed out waiting for {what}")
            await asyncio.sleep(0.02)

    # -- the window --------------------------------------------------------
    async def measure(self) -> None:
        for s in self.sources:
            s.anchor()
        w0 = time.monotonic()
        self.window = (w0, w0 + self.seconds)
        if self.trace:
            from . import trace as trace_mod

            conf = self.cell.traffic.get("trace", {})
            lead = min(float(conf.get("start_s", 2.0)), self.seconds / 4)
            span = min(float(conf.get("seconds", 3.0)), self.seconds / 2)
            await asyncio.sleep(lead)
            work = os.path.join(self.bench_dir, ".work", "trace")
            self.trace_asked_at = time.monotonic()
            self.profile = await trace_mod.capture(work, span)
            # on the check's machines an ``interpreter`` stall is the
            # machine standing still
            held = sum(b - a for a, b in self.stalls_by_kind(
                self.trace_asked_at, time.monotonic()).get("interpreter", []))
            if held > RETRACE_STALL_S and \
                    time.monotonic() + span + 1.0 < self.window[1]:
                busy = trace_mod.busy_s(self.profile)
                say(f"traced seconds held {held:.2f} s of interpreter stall "
                    f"(over {RETRACE_STALL_S:g} s): device busy "
                    f"{sum(busy.values()) / max(1, len(busy)):.3f} s of "
                    f"{trace_mod.window_s(self.profile):.3f}; kept here, not "
                    f"read: the window is traced once more")
                self.trace_asked_at = time.monotonic()
                self.profile = await trace_mod.capture(work, span)
        watch = asyncio.create_task(self._watch_gate())
        await asyncio.sleep(max(0.0, self.window[1] - time.monotonic()))
        watch.cancel()
        for did in self.displays:
            st = self.server.display_clients.get(did)
            encoder = getattr(st, "encoder", None)
            if self.served_encoder is None:
                self.served_encoder = encoder
            stats = getattr(encoder, "stats", None)
            if stats is not None:
                self.encoder_stats[did] = dict(stats())

    async def _watch_gate(self) -> None:
        """Twenty times a second: is each display's backpressure gate open,
        and did this loop get its turn on time? A closed gate or a blocked
        loop is why a capture was never made; neither shows in a span."""
        last = time.monotonic()
        while True:
            await asyncio.sleep(0.05)
            now = time.monotonic()
            self.loop_late_s = max(self.loop_late_s, now - last - 0.05)
            last = now
            for did in self.displays:
                st = self.server.display_clients.get(did)
                if st is not None and not st.bp.send_enabled:
                    self.gate_closed[did] = self.gate_closed.get(did, 0) + 1

    def stalls(self) -> List[str]:
        """Earlier lines: the longest hole between capture calls and between
        arriving stripes in the window, the loop's worst lateness, and how
        long each gate was seen closed."""
        w0, w1 = self.window
        out = []
        for s in self.sources:
            c = [t for t in s._calls if w0 <= t < w1]
            if len(c) > 1:
                gaps = np.diff(c)
                k = int(np.argmax(gaps))
                out.append(f"source {s.number}: longest hole between capture "
                           f"calls {gaps[k] * 1000:.1f} ms, {c[k] - w0:.2f} s "
                           f"into the window")
        for did, c in self.clients.items():
            t = [f.t_last for f in c.frames if w0 <= f.t_last < w1]
            if len(t) > 1:
                gaps = np.diff(t)
                k = int(np.argmax(gaps))
                out.append(f"{did}: longest hole between complete frames "
                           f"{gaps[k] * 1000:.1f} ms, {t[k] - w0:.2f} s into "
                           f"the window; gate seen closed "
                           f"{self.gate_closed.get(did, 0) * 0.05:.2f} s")
        out.append(f"event loop: worst lateness of a 50 ms timer "
                   f"{self.loop_late_s * 1000:.1f} ms")
        for kind, rows in self.stalls_by_kind().items():
            a, b = max(rows, key=lambda r: r[1] - r[0])
            out.append(f"stall watch: {len(rows)} stalls of kind {kind} began "
                       f"in the window, {sum(b - a for a, b in rows):.3f} s "
                       f"together, the longest {(b - a) * 1000:.1f} ms, "
                       f"{a - w0:.2f} s into it")
        for did, st in self.encoder_stats.items():
            out.append(f"{did}: encoder says " + json.dumps(
                {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in st.items()
                 if isinstance(v, (int, float, str, bool))})[:400])
        return out

    def stalls_by_kind(self, t0: Optional[float] = None,
                       t1: Optional[float] = None
                       ) -> Dict[str, List[Tuple[float, float]]]:
        """The program's stall watch (PERF.md section 3): (t0, t1) of the
        stalls that began in [t0, t1] (the window, where none is given), by
        kind; {} where the program has no stall watch or recorded none."""
        get = getattr(self.server.recorder, "stalls", None)
        span = self.window if t0 is None else (t0, t1)
        out: Dict[str, List[Tuple[float, float]]] = {}
        for kind, a, b in (get(*span) if get else ()):
            out.setdefault(kind, []).append((a, b))
        return out

    async def drain(self) -> None:
        """Past the window: keep receiving until every change due in it is
        shown or the mix's grace is over; then stop the desktop and let the
        pipeline run dry, so that the server stops with nothing in flight
        (PR 22: SIGSEGV at exit with dispatches pending)."""
        grace = float(self.cell.traffic.get("drain_s", 3.0))
        # the first look decodes the top band of every frame so far and
        # holds the loop (the server's too) meanwhile: the grace is time in
        # which frames flow, so it starts after it
        t0 = time.monotonic()
        pending = self._shown_so_far()
        t_end = time.monotonic() + grace
        say(f"drain: reading the window's frames held the loop "
            f"{time.monotonic() - t0:.2f} s; then up to {grace:g} s for the "
            f"last changes to be shown")
        while time.monotonic() < t_end:
            if all(lat is not None for lat in pending):
                break
            await asyncio.sleep(0.1)
            pending = self._shown_so_far()
        for s in self.sources:
            s.stopped = True
        quiet = time.monotonic()
        seen = sum(len(c.frames) for c in self.clients.values())
        while time.monotonic() - quiet < 0.75 and \
                time.monotonic() < t_end + 10.0:
            await asyncio.sleep(0.05)
            now_seen = sum(len(c.frames) for c in self.clients.values())
            if now_seen != seen:
                seen, quiet = now_seen, time.monotonic()
        # the last frame has no successor to show that it is complete: the
        # stream has run dry, so it is, and it counts where it arrived (in a
        # stream of a few frames a second that can be inside the window)
        for c in self.clients.values():
            await c.ack_open_frame()
            c.close_open_frame()
        await asyncio.sleep(0.2)

    def _shown_so_far(self) -> List[Optional[float]]:
        self.attribute()
        out: List[Optional[float]] = []
        for did in self.displays:
            ch, fr = self.changes_and_frames(did)
            out += metrics.shown_times(ch, fr)
        return out

    # -- joining the logs --------------------------------------------------
    def attribute(self) -> None:
        """Give every delivered frame the content index it shows.

        The recorder's way: wire frame id -> the recorder's trace of that
        frame (id set at emit) -> its ``capture`` mark -> the source call it
        brackets. It also says which source feeds which display. Where the
        mix's desktop says in its own pixels which step it shows
        (``read_index``), the picture is believed, and the recorder's answer
        is kept beside it (``traced``) for the count of disagreements."""
        rec = self.server.recorder
        by_key: Dict[Tuple[str, int], List[Any]] = {}
        for tr in rec._completed() + list(rec._open.values()):
            if tr.frame_id >= 0:
                by_key.setdefault((tr.display, tr.frame_id), []).append(tr)
        for did, c in self.clients.items():
            for f in c.frames[self._attributed.get(did, 0):]:
                cands = [t for t in by_key.get((did, f.frame_id), ())
                         if t.t0 <= f.t_first]
                if cands:
                    tr = max(cands, key=lambda t: t.t0)
                    cap = tr.spans.get("capture")
                    hit = self.log.find(cap[0], cap[1]) if cap else None
                    if hit is not None:
                        self.display_of_source.setdefault(hit[0], did)
                        f.traced = hit[1]
                src = self.source_of(did)
                if src is None:
                    break               # no frame of this display traced yet
                if hasattr(src, "read_index"):
                    rows = self._tops.setdefault(did, TopBand()).rows(f)
                    if rows is not None:
                        f.content = src.read_index(rows, f.t_last, f.traced)
                else:
                    f.content = f.traced
                self._attributed[did] = self._attributed.get(did, 0) + 1

    def tracing_disagrees(self) -> Dict[str, Tuple[int, int]]:
        """Per display: (frames whose traced content is not what the
        picture shows, frames where both are known)."""
        out = {}
        for did, c in self.clients.items():
            both = [(f.traced, f.content) for f in c.frames
                    if f.traced is not None and f.content is not None]
            out[did] = (sum(1 for a, b in both if a != b), len(both))
        return out

    def source_of(self, did: str):
        for number, d in self.display_of_source.items():
            if d == did:
                return self.sources[number]
        return None

    def changes_and_frames(self, did: str):
        src = self.source_of(did)
        if src is None:
            return [], []
        ch = src.due_times(*self.window)
        fr = [(f.content, f.t_last) for f in self.clients[did].frames
              if f.content is not None]
        return ch, fr

    # -- after the window --------------------------------------------------
    def health(self) -> List[str]:
        """What broke a guarantee of the configuration, if anything."""
        wrong = []
        payload = json.loads(self.server._health_payload())["displays"]
        for did in self.displays:
            d = payload.get(did)
            if d is None:
                wrong.append(f"{did}: no such display")
                continue
            if d["rung"] != "device":
                wrong.append(f"{did}: ladder at {d['rung']}")
            for k in ("restarts", "failures", "watchdog_restarts",
                      "encode_errors"):
                if d.get(k, 0):
                    wrong.append(f"{did}: {k}={d[k]}")
            if d.get("failed"):
                wrong.append(f"{did}: marked failed")
        return wrong

    def end_to_end(self) -> Dict[str, Any]:
        self.attribute()
        w0, w1 = self.window
        lat: List[float] = []
        never = frames = nbytes = 0
        self.latencies_by_second = {}
        for did in self.displays:
            ch, fr = self.changes_and_frames(did)
            l, n = metrics.latencies_ms(ch, fr, self.seconds)
            lat += l
            never += n
            for (_index, due), ms in zip(ch, l):
                self.latencies_by_second.setdefault(
                    int(due - w0), []).append(ms)
            k, b = metrics.delivered(
                [(f.t_last, f.nbytes) for f in self.clients[did].frames],
                w0, w1)
            frames += k
            nbytes += b
        self.latencies_ms = lat
        out = {"attempted": len(lat), "never_shown": never, "frames": frames}
        if lat and frames:
            out["metrics"] = self.metrics = {
                "delivered_fps": frames / self.seconds / len(self.displays),
                "latency_p50_ms": metrics.percentile(lat, 50),
                "wire_kB_per_frame": nbytes / 1000.0 / frames,
            }
        return out

    def unreadable(self) -> int:
        """Frames completed in the window of which the harness could not
        say which content step they show (top band undecodable, ruler
        unreadable, no trace of the frame): they are left out of the
        latencies and of the comparison's sample, so they are counted."""
        w0, w1 = self.window
        return sum(1 for c in self.clients.values() for f in c.frames
                   if w0 <= f.t_last < w1 and f.content is None)

    def regime(self) -> Dict[str, Any]:
        """Which of the encode pipeline's regimes the window ran in
        (PERF.md): how many delivered-frame periods the median change waited
        (Little's law: frames in flight between due and shown), the median
        wait for a fetch, and what the encoder says it holds in flight. The
        configuration states the band its bounds were measured in, on
        untraced windows; an untraced run outside it is flagged ``other``,
        not failed. Under the profiler the driver thread's ``pack`` doubles
        and the stream runs deeper (7.3 frames in flight traced against
        4.1-5.5 untraced, PERF.md, PR 39): a traced run is flagged
        ``traced`` and held to no band."""
        from .readers import in_flight as in_flight_reader, recorder_stage

        in_flight = in_flight_reader.read(self, {})
        regime = self.cell.config.get("regime", {})
        band = regime.get("frames_in_flight_by_traffic", {}).get(
            self.cell.traffic_name, regime.get("frames_in_flight"))
        out = {"latency_p95_ms": metrics.percentile(self.latencies_ms, 95),
               "frames_in_flight": in_flight,
               "fetch_wait_p50_ms": recorder_stage.read(
                   self, {"stages": ["fetch_wait"], "percentile": 50}),
               "inflight_batches": [st.get("inflight_batches")
                                    for st in self.encoder_stats.values()],
               "band": band,
               "session_fps": dict(self.session_fps),
               "latency_p50_by_second_ms": [
                   round(metrics.percentile(v, 50), 3) for _k, v in
                   sorted(self.latencies_by_second.items())],
               "stalled_s": {k: sum(b - a for a, b in v)
                             for k, v in self.stalls_by_kind().items()}}
        out["regime"] = "traced" if self.trace else (
            "not stated" if not band else (
                "expected" if band[0] <= in_flight <= band[1] else "other"))
        return out

    def collect_spans(self) -> None:
        w0, w1 = self.window
        rec = self.server.recorder
        self.spans = [t for t in rec._completed() if w0 <= t.t0 < w1]

    # -- correct -----------------------------------------------------------
    def compare(self) -> Tuple[Dict[str, float], int, float]:
        """Decode what the clients received and hold a sample of the
        window's frames, drawn from the seed with the last one in it,
        against the desktop each claims to show."""
        conf = self.cell.config
        ref = module("reference", conf["reference"], self.bench_dir)
        fid = check.Fidelity(ref, conf["quantiser"])
        per_display = max(1, int(self.cell.traffic.get("check_frames", 12))
                          // len(self.displays))
        w0, w1 = self.window
        for k, did in enumerate(self.displays):
            c, src = self.clients[did], self.source_of(did)
            if src is None:
                continue
            idx = [i for i, f in enumerate(c.frames)
                   if w0 <= f.t_last < w1 and f.content is not None]
            if not idx:
                continue
            rng = np.random.default_rng([self.seed, 0xC0, k])
            pick = set(rng.choice(idx[:-1], min(per_display - 1,
                                                len(idx) - 1),
                                  replace=False).tolist()) if len(idx) > 1 \
                else set()
            pick.add(idx[-1])
            canvas = Canvas(self.width, self.height)
            for i, f in enumerate(c.frames[:idx[-1] + 1]):
                fid.undecodable += canvas.show(f, decode_now=i in pick)
                if i in pick:
                    fid.add(src.frame(f.content), *canvas.planes())
            canvas.close()
        numbers = dict(fid.numbers(), unreadable=float(self.unreadable()))
        return numbers, fid.frames, fid.y_psnr_db()


class TopBand:
    """The luma of row band 0 of each frame, decoded in emission order: all
    that reading a desktop's ruler takes."""

    def __init__(self) -> None:
        self._h264 = None

    def rows(self, f: Frame) -> Optional[np.ndarray]:
        out = None
        for s in f.stripes:
            if s.y_start != 0:
                continue
            try:
                if f.kind == H264_STRIPE:
                    from .decoders import h264

                    if self._h264 is None:
                        self._h264 = h264.Decoder()
                    planes = self._h264.decode(s.payload)
                    out = planes[0] if planes is not None else out
                else:
                    from .decoders import jpeg

                    out = jpeg.decode(s.payload)[0]
            except Exception:
                return None
        return out


class Canvas:
    """What a client's screen holds: stripes decoded in emission order.
    H.264 stripes are streams (every unit is decoded, in order); a JPEG
    stripe stands alone, so only the newest one per row band is decoded,
    and only when the picture is looked at."""

    def __init__(self, width: int, height: int) -> None:
        self.w, self.h = width, height
        self.y = np.zeros((height, width), np.uint8)
        self.cb: Optional[np.ndarray] = None
        self.cr: Optional[np.ndarray] = None
        self._h264: Dict[int, Any] = {}
        self._jpeg_new: Dict[int, bytes] = {}

    def show(self, f: Frame, decode_now: bool) -> int:
        bad = 0
        for s in f.stripes:
            if f.kind == H264_STRIPE:
                bad += self._h264_stripe(s)
            else:
                self._jpeg_new[s.y_start] = s.payload
        if decode_now and self._jpeg_new:
            bad += self._jpeg_flush()
        return bad

    def _plane(self, name: str, shape) -> np.ndarray:
        p = getattr(self, name)
        if p is None:
            p = np.full(shape, 128, np.uint8)
            setattr(self, name, p)
        return p

    def _h264_stripe(self, s) -> int:
        from .decoders import h264

        dec = self._h264.get(s.y_start)
        if dec is None:
            dec = self._h264[s.y_start] = h264.Decoder(
                max_w=max(4096, self.w))
        try:
            out = dec.decode(s.payload)
        except ValueError:
            return 1
        if out is None:
            return 0
        y, cb, cr = out
        rows = min(y.shape[0], self.h - s.y_start)
        self.y[s.y_start:s.y_start + rows] = y[:rows, :self.w]
        ch, cw = (self.h + 1) // 2, (self.w + 1) // 2
        c0 = s.y_start // 2
        crow = min(cb.shape[0], ch - c0)
        self._plane("cb", (ch, cw))[c0:c0 + crow] = cb[:crow, :cw]
        self._plane("cr", (ch, cw))[c0:c0 + crow] = cr[:crow, :cw]
        return 0

    def _jpeg_flush(self) -> int:
        from .decoders import jpeg

        bad = 0
        for y0, payload in self._jpeg_new.items():
            try:
                y, cb, cr = jpeg.decode(payload)
            except Exception:
                bad += 1
                continue
            rows = min(y.shape[0], self.h - y0)
            self.y[y0:y0 + rows] = y[:rows, :self.w]
            self._plane("cb", (self.h, self.w))[y0:y0 + rows] = \
                cb[:rows, :self.w]
            self._plane("cr", (self.h, self.w))[y0:y0 + rows] = \
                cr[:rows, :self.w]
        self._jpeg_new.clear()
        return bad

    def planes(self):
        shape = (self.h, self.w)
        return self.y, self._plane("cb", shape), self._plane("cr", shape)

    def close(self) -> None:
        for d in self._h264.values():
            d.close()
