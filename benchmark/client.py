"""The benchmark's websocket client: a real ``websockets`` peer standing in
for the browser. SETTINGS handshake, receive stripes, ACK every complete
frame at once, keep what arrived and when. Decoding waits until the window
has closed, so that it takes no host time from the server inside it.

Copied from ``chip_smoke.py``'s ``Client`` (sound: PERF.md's verdict table)
with its own reading of the wire header, so that the program's protocol
module is not what says what arrived.
"""

from __future__ import annotations

import asyncio
import json
import struct
import time
from dataclasses import dataclass, field
from typing import List, Optional

JPEG_STRIPE, H264_STRIPE = 0x03, 0x04
_U16 = struct.Struct(">H")


@dataclass
class Stripe:
    y_start: int
    payload: bytes
    is_key: bool


@dataclass
class Frame:
    """One wire frame as the client saw it. Complete when its last stripe
    has arrived: known when the next frame's first stripe (or the end of
    receiving) shows that no more belongs to it."""
    frame_id: int
    kind: int
    t_first: float
    t_last: float
    nbytes: int = 0
    stripes: List[Stripe] = field(default_factory=list)
    # filled in by the harness: the content index the frame shows, and what
    # the server's tracing says it shows
    content: Optional[int] = None
    traced: Optional[int] = None


def parse_stripe(msg: bytes):
    """(kind, frame_id, Stripe) of a video stripe, or None for anything
    else (audio, full-frame H.264: no cell uses those)."""
    kind = msg[0]
    if kind == JPEG_STRIPE and len(msg) >= 6:
        return kind, _U16.unpack_from(msg, 2)[0], Stripe(
            _U16.unpack_from(msg, 4)[0], msg[6:], True)
    if kind == H264_STRIPE and len(msg) >= 10:
        return kind, _U16.unpack_from(msg, 2)[0], Stripe(
            _U16.unpack_from(msg, 4)[0], msg[10:], msg[1] == 0x01)
    return None


class Client:
    def __init__(self, port: int, display_id: str, width: int,
                 height: int, framerate: Optional[float] = None) -> None:
        self.port, self.display_id = port, display_id
        self.width, self.height = width, height
        #: the rate the client asks for in SETTINGS, as a browser that
        #: picked one does; None: none asked, the server's default
        self.framerate = framerate
        self.frames: List[Frame] = []       # in arrival order, all of them
        self.said: List[str] = []           # text the server sent
        self.killed: Optional[str] = None
        self.t_settings = 0.0               # when SETTINGS was sent
        self.ws = None
        self._task: Optional[asyncio.Task] = None
        self._open: Optional[Frame] = None

    async def connect(self) -> None:
        import websockets

        self.ws = await websockets.connect(
            f"ws://127.0.0.1:{self.port}", max_size=None, compression=None)
        if await self.ws.recv() != "MODE websockets":
            raise RuntimeError("no MODE line from the server")
        schema = json.loads(await self.ws.recv())
        if schema.get("type") != "server_settings":
            raise RuntimeError("no server_settings from the server")
        self.t_settings = time.monotonic()
        settings = {"displayId": self.display_id,
                    "initialClientWidth": self.width,
                    "initialClientHeight": self.height}
        if self.framerate is not None:
            settings["framerate"] = int(round(self.framerate))
        await self.ws.send("SETTINGS," + json.dumps(settings))
        self._task = asyncio.create_task(self._receive())

    async def _receive(self) -> None:
        import websockets

        try:
            async for m in self.ws:
                if not isinstance(m, bytes):
                    text = str(m)
                    self.said.append(text[:80])
                    if text.startswith("KILL"):
                        self.killed = text
                    continue
                done = self._take(m)
                if done is not None:
                    await self.ws.send(f"CLIENT_FRAME_ACK {done}")
        except (websockets.ConnectionClosed, asyncio.CancelledError):
            pass

    def _take(self, m: bytes) -> Optional[int]:
        """File one binary message; the id of the frame it completed, if it
        opened the next one."""
        now = time.monotonic()
        parsed = parse_stripe(m)
        if parsed is None:
            return None
        kind, fid, stripe = parsed
        cur, done = self._open, None
        if cur is None or cur.frame_id != fid:
            if cur is not None:
                self.frames.append(cur)
                done = cur.frame_id
            cur = self._open = Frame(fid, kind, now, now)
        cur.t_last = now
        cur.nbytes += len(stripe.payload)
        cur.stripes.append(stripe)
        return done

    async def ack_open_frame(self) -> None:
        """The stream has run dry: the frame still open is complete."""
        if self._open is not None and self.ws is not None:
            await self.ws.send(f"CLIENT_FRAME_ACK {self._open.frame_id}")

    def close_open_frame(self) -> None:
        """Receiving is over: the frame still open is as complete as it
        will get."""
        if self._open is not None:
            self.frames.append(self._open)
            self._open = None

    def frames_seen(self) -> int:
        return len(self.frames) + (1 if self._open is not None else 0)

    async def close(self) -> None:
        if self.ws is not None:
            await self.ws.close()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        self.close_open_frame()
