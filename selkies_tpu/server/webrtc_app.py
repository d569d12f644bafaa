"""WebRTC streaming session: tpuenc video + Opus audio + input data channel
over the in-repo WebRTC stack.

Role parity with the reference's legacy pipeline builder + orchestrator
(``legacy/gstwebrtc_app.py`` — webrtcbin, 14 encoder branches, data
channel; ``legacy/webrtc.py:330-980`` — signaling wiring, RTC config,
bitrate handlers), redesigned: the encoder is the TPU H.264 stripe encoder
in full-frame mode, the transport is :mod:`selkies_tpu.webrtc`, and the
signaling grammar is the same HELLO/SESSION + JSON sdp/ice the reference
speaks (``legacy/webrtc_signalling.py``), so either side can be swapped.

Flow (caller role, like the reference: the streaming server initiates):
  signaling HELLO → SESSION <peer> → SESSION_OK → create offer →
  {"sdp": offer} → {"sdp": answer} from browser → ICE → DTLS-SRTP →
  media tasks pump frames; "input" data channel feeds the input handler.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Callable, List, Optional

import numpy as np

from ..audio.capture import AudioCaptureSettings, open_source
from ..audio.codec import OpusEncoder, opus_available
from ..webrtc.peerconnection import PeerConnection
from ..rtc.signaling_client import SignalingClient

logger = logging.getLogger("selkies_tpu.server.webrtc_app")

VIDEO_CLOCK = 90000
OPUS_CLOCK = 48000
FRAME_MS = 20


def bitrate_to_qp(bps: int) -> int:
    """Map a congestion-control bitrate to an H.264 QP.

    Monotone heuristic calibrated around the reference's defaults: 8 Mbps
    (legacy default, webrtc.py:466) ≈ QP 26 (our encoder default); each
    halving of bitrate costs ~4 QP, clamped to [18, 46]."""
    if bps <= 0:
        return 46
    qp = 26 - 4.0 * np.log2(bps / 8_000_000)
    return int(np.clip(round(qp), 18, 46))


class WebRTCStreamingApp:
    def __init__(
        self,
        settings,
        encoder_factory: Optional[Callable] = None,
        source_factory: Optional[Callable] = None,
        audio_settings: Optional[AudioCaptureSettings] = None,
        input_handler=None,
        interfaces: Optional[List[str]] = None,
    ):
        self.settings = settings
        self.input_handler = input_handler
        self.interfaces = interfaces
        self.width = getattr(settings, "initial_width", 1280)
        self.height = getattr(settings, "initial_height", 720)
        # the real Settings exposes framerate as a RangeValue (allowed
        # range + default); plain numbers (tests, embedders) pass through
        fr = getattr(settings, "framerate", 60)
        self.framerate = float(getattr(fr, "default", fr))
        self.encoder_factory = encoder_factory or self._default_encoder
        self.source_factory = source_factory or self._default_source
        self.audio_settings = audio_settings or AudioCaptureSettings()

        self.pc: Optional[PeerConnection] = None
        self.signaling: Optional[SignalingClient] = None
        #: fired when the input data channel opens (webrtc_main re-sends
        #: the cached clipboard so pre-connect content isn't lost)
        self.on_input_channel_open: Optional[Callable[[], None]] = None
        self.encoder = None
        self.source = None
        self.input_channel = None
        self._tasks: List[asyncio.Task] = []
        self._running = False
        self.frames_sent = 0
        self.current_qp: Optional[int] = None

    # ------------------------------------------------------- factories

    def _default_encoder(self, width: int, height: int):
        from ..encoder.h264 import H264StripeEncoder

        pad16 = -(-height // 16) * 16
        return H264StripeEncoder(width, height, stripe_height=pad16)

    def _default_source(self, width: int, height: int, fps: float):
        from ..capture.x11 import X11Source
        from ..capture.synthetic import SyntheticSource

        if X11Source.available():
            return X11Source(width, height, fps)
        return SyntheticSource(width, height, fps, pattern="desktop")

    # ------------------------------------------------------- signaling

    async def run(self, signaling_uri: str, uid: str, peer_id: str) -> None:
        """Register with the signaling server and stream to ``peer_id``."""
        self.signaling = SignalingClient(signaling_uri, uid, peer_id)
        self.signaling.on_connect = self.signaling.setup_call
        self.signaling.on_session = lambda pid, meta: asyncio.ensure_future(
            self.start_pipeline())
        self.signaling.on_sdp = self._on_sdp
        self.signaling.on_ice = self._on_ice
        await self.signaling.connect()
        await self.signaling.start()

    async def _on_sdp(self, sdp_type: str, sdp: str) -> None:
        if sdp_type == "answer" and self.pc is not None:
            await self.pc.set_remote_description(sdp, "answer")

    async def _on_ice(self, mlineindex: int, candidate: str) -> None:
        if self.pc is not None and candidate:
            self.pc.add_ice_candidate(candidate)

    # -------------------------------------------------------- pipeline

    async def start_pipeline(self) -> None:
        """Build the session: encoder + pc + senders + offer (parity with
        GSTWebRTCApp.start_pipeline, gstwebrtc_app.py:1676)."""
        self.pc = PeerConnection(interfaces=self.interfaces)
        self.video_sender = self.pc.add_video_sender()
        fec_pct = int(getattr(self.settings, "video_packetloss_percent", 0))
        if fec_pct > 0:
            self.video_sender.enable_fec(fec_pct)
        self.audio_sender = self.pc.add_audio_sender()
        self.input_channel = self.pc.create_data_channel(
            "input", ordered=True, max_retransmits=0)
        self.input_channel.on_message = self._on_input_message
        self.input_channel.on_open = lambda: (
            self.on_input_channel_open and self.on_input_channel_open())
        self.pc.on_bitrate = self.set_video_bitrate
        self.pc.on_keyframe_request = self._on_keyframe_request

        self.encoder = self.encoder_factory(self.width, self.height)
        self.source = self.source_factory(
            self.width, self.height, self.framerate)

        offer = await self.pc.create_offer()
        if self.signaling is not None:
            await self.signaling.send_sdp("offer", offer)
        self._running = True
        self._tasks = [asyncio.create_task(self._video_loop())]
        if opus_available():
            self._tasks.append(asyncio.create_task(self._audio_loop()))

    async def stop_pipeline(self) -> None:
        self._running = False
        for t in self._tasks:
            t.cancel()
        self._tasks = []
        if self.pc is not None:
            await self.pc.close()
            self.pc = None

    # ----------------------------------------------------- media loops

    async def _video_loop(self) -> None:
        await self.pc.wait_connected()
        t0 = time.monotonic()
        # dispatch/harvest-capable encoders run pipelined so device
        # latency hides behind the frame interval; fakes/others stay
        # synchronous
        pipe = None
        if hasattr(self.encoder, "dispatch"):
            from ..encoder.pipeline import PipelinedH264Encoder

            pipe = PipelinedH264Encoder(self.encoder, depth=3)

        def _send(seq: int, stripes) -> None:
            if not stripes or not self._running:
                return
            au = b"".join(s.annexb for s in stripes)
            # timestamps advance per encoded frame, not per wall-clock
            # send instant: poll() can deliver several frames in one tick
            # and identical RTP timestamps would merge distinct AUs
            ts = int(seq * VIDEO_CLOCK / max(self.framerate, 1.0))
            self.video_sender.send_frame(au, ts)
            self.frames_sent += 1

        sync_seq = 0
        try:
            while self._running:
                start = time.monotonic()
                frame = self.source.next_frame()
                if pipe is None:
                    if frame is not None:
                        stripes = await asyncio.to_thread(
                            self.encoder.encode_frame, frame)
                        _send(sync_seq, stripes)
                        sync_seq += 1
                else:
                    # poll-then-submit every tick: completed frames ship
                    # even when capture hiccups, and draining first frees
                    # a pipeline slot the new frame would otherwise lose
                    def tick(f=frame):
                        done = pipe.poll()
                        if f is not None:
                            pipe.try_submit(f)
                        return done
                    for seq, stripes in await asyncio.to_thread(tick):
                        _send(seq, stripes)
                elapsed = time.monotonic() - start
                await asyncio.sleep(
                    max(0.0, 1.0 / max(self.framerate, 1.0) - elapsed))
        finally:
            if pipe is not None:
                # teardown arrives as a task cancellation: drain what the
                # device already produced (sends are gated on _running)
                for seq, stripes in await asyncio.shield(
                        asyncio.to_thread(pipe.flush)):
                    _send(seq, stripes)

    async def _audio_loop(self) -> None:
        await self.pc.wait_connected()
        settings = self.audio_settings
        src = open_source(settings)
        # in-band FEC on the lossy (SRTP) path, like the reference's
        # opusenc inband-fec=true (legacy/gstwebrtc_app.py:1048): the
        # receiver recovers a lost 20 ms frame from the next packet
        enc = OpusEncoder(settings.sample_rate, settings.channels,
                          settings.opus_bitrate, inband_fec=True)
        frames = settings.sample_rate * FRAME_MS // 1000
        ts = 0
        try:
            while self._running:
                pcm = await asyncio.to_thread(src.read_chunk, frames)
                if pcm is None:
                    await asyncio.sleep(FRAME_MS / 1000)
                    continue
                packet = enc.encode(pcm)
                if packet:
                    self.audio_sender.send_frame(packet, ts)
                ts += frames
        finally:
            src.close()
            enc.close()

    # ------------------------------------------------------- control

    def set_video_bitrate(self, bps: int) -> None:
        """Congestion-control feedback → encoder QP (parity with
        set_video_bitrate, gstwebrtc_app.py:1269, fed by rtpgccbwe)."""
        qp = bitrate_to_qp(bps)
        if qp != self.current_qp and self.encoder is not None:
            self.current_qp = qp
            if hasattr(self.encoder, "qp"):
                self.encoder.qp = qp

    def set_framerate(self, fps: float) -> None:
        self.framerate = float(np.clip(fps, 1, 120))

    def _on_keyframe_request(self) -> None:
        if self.encoder is not None and hasattr(self.encoder,
                                                "request_keyframe"):
            self.encoder.request_keyframe()

    def _on_input_message(self, data: bytes) -> None:
        """Input-plane messages from the browser data channel (parity with
        the legacy data channel → WebRTCInput.on_message path)."""
        if self.input_handler is None:
            return
        try:
            msg = data.decode()
        except UnicodeDecodeError:
            return
        result = self.input_handler.on_message(msg)
        if asyncio.iscoroutine(result):
            asyncio.ensure_future(result)

    def send_json(self, obj) -> None:
        """Server→client control message over the input channel (parity
        with the legacy send_clipboard/cursor data-channel helpers,
        gstwebrtc_app.py:1371-1471)."""
        import json

        if self.input_channel is not None and self.input_channel.open:
            self.input_channel.send(json.dumps(obj))
