"""WebRTC provider abstraction: aiortc when installed, native-rtp otherwise.

The reference's entire WebRTC stack (ICE/DTLS/SRTP/RTP/jitter/datachannel)
lives in its aiortc fork (SURVEY.md L3/L0); the first-party code only drives
a small API surface: RTCPeerConnection construction, addTransceiver +
setCodecPreferences, event decorators, setRemoteDescription/createAnswer/
setLocalDescription, and the private __gather() OBS workaround
(reference agent.py:123-395).

This module pins down exactly that surface as a provider interface:

* ``AiortcProvider`` — the real stack (stock upstream aiortc; its software
  codecs interoperate with our media plane via the VideoFrame duck type).
* ``LoopbackProvider`` — a hermetic in-process implementation: "SDP" is a
  JSON envelope, media flows through asyncio queues, datachannel messages
  are delivered directly.  It powers the end-to-end test tier (SURVEY.md
  section 4); selected by explicit WEBRTC_PROVIDER=loopback, or as the
  last-resort degrade when neither aiortc nor the native tier's runtime
  deps are available — the agent logic (tracks, events, config control
  plane, pipeline) is identical across tiers.

``get_provider()`` picks aiortc when importable; otherwise the native-rtp
tier (the in-repo secure WebRTC stack).  WEBRTC_PROVIDER=loopback/native-rtp
/aiortc overrides.
"""

from __future__ import annotations

import asyncio
import json
import logging
import uuid

from ..utils import env as env_util

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# loopback implementation
# ---------------------------------------------------------------------------

class SessionDescription:
    def __init__(self, sdp: str, type: str):
        self.sdp = sdp
        self.type = type


class LoopbackTrack:
    """Pull-model media track fed by an asyncio queue."""

    kind = "video"

    def __init__(self, name: str = "loopback"):
        self.name = name
        self._q: asyncio.Queue = asyncio.Queue(maxsize=16)
        self._ended = asyncio.Event()
        self._handlers: dict = {}

    def on(self, event: str, f=None):
        def register(fn):
            self._handlers[event] = fn
            return fn

        return register(f) if f else register

    async def push(self, frame):
        await self._q.put(frame)

    async def recv(self):
        if self._ended.is_set() and self._q.empty():
            raise ConnectionError("track ended")
        return await self._q.get()

    def recv_nowait(self):
        """Non-blocking pull, or None — lets the overload ingest hop
        (server/tracks.py) skip ahead to a fresher frame when this queue
        has backed up behind a slow pipeline."""
        try:
            return self._q.get_nowait()
        except asyncio.QueueEmpty:
            return None

    def stop(self):
        self._ended.set()
        from ..utils.dispatch import fire_handler

        fire_handler(self._handlers.get("ended"))


async def _maybe_await(x):
    if asyncio.iscoroutine(x):
        await x


class LoopbackDataChannel:
    def __init__(self, label="config"):
        self.label = label
        self._handlers: dict = {}

    def on(self, event: str, f=None):
        def register(fn):
            self._handlers[event] = fn
            return fn

        return register(f) if f else register

    async def deliver(self, message: str):
        h = self._handlers.get("message")
        if h:
            await _maybe_await(h(message))


class LoopbackPeerConnection:
    """Implements the RTCPeerConnection surface the agent drives."""

    def __init__(self, configuration=None):
        self.configuration = configuration
        self.connectionState = "new"
        self.iceConnectionState = "new"
        self.localDescription = None
        self.remoteDescription = None
        self._handlers: dict = {}
        self._transceivers: list = []
        self._senders: list = []
        self.out_tracks: list = []  # tracks the agent sends back to the peer
        self.in_track: LoopbackTrack | None = None
        self.datachannel = LoopbackDataChannel()
        self._gathered = False
        self.pc_id = str(uuid.uuid4())

    # -- event API ----------------------------------------------------------

    def on(self, event: str, f=None):
        def register(fn):
            self._handlers[event] = fn
            return fn

        return register(f) if f else register

    async def _emit(self, event: str, *args):
        h = self._handlers.get(event)
        if h:
            await _maybe_await(h(*args))

    # -- transceivers / tracks ---------------------------------------------

    def addTransceiver(self, kind: str, direction: str = "sendrecv"):
        tr = type("Transceiver", (), {"kind": kind, "sender": None, "_codecs": None})()

        def setCodecPreferences(codecs):
            tr._codecs = codecs

        tr.setCodecPreferences = setCodecPreferences
        self._transceivers.append(tr)
        return tr

    def getTransceivers(self):
        return list(self._transceivers)

    def addTrack(self, track):
        sender = type("Sender", (), {"track": track})()
        self._senders.append(sender)
        self.out_tracks.append(track)
        if self._transceivers:
            self._transceivers[0].sender = sender
        return sender

    # -- SDP ---------------------------------------------------------------

    async def setRemoteDescription(self, desc: SessionDescription):
        self.remoteDescription = desc
        # loopback "negotiation": the offer may carry an inbound track marker
        payload = _parse_loopback_sdp(desc.sdp)
        if payload.get("video"):
            self.in_track = LoopbackTrack()
            await self._emit("track", self.in_track)
        if payload.get("datachannel"):
            await self._emit("datachannel", self.datachannel)

    async def createAnswer(self):
        return SessionDescription(
            sdp=json.dumps({"loopback": True, "answer_for": self.pc_id}),
            type="answer",
        )

    async def setLocalDescription(self, desc: SessionDescription):
        self.localDescription = desc
        await self._connect()

    async def _connect(self):
        self.connectionState = "connected"
        self.iceConnectionState = "completed"
        await self._emit("connectionstatechange")

    async def close(self):
        if self.connectionState == "closed":
            return
        self.connectionState = "closed"
        if self.in_track:
            self.in_track.stop()
        await self._emit("connectionstatechange")

    # OBS workaround parity: the agent calls the name-mangled gather —
    # loopback has nothing to gather but records that it was requested
    # (reference agent.py:256-263, 369-376)
    async def _RTCPeerConnection__gather(self):
        self._gathered = True


def _parse_loopback_sdp(sdp: str) -> dict:
    try:
        d = json.loads(sdp)
        return d if isinstance(d, dict) else {}
    except (json.JSONDecodeError, ValueError):
        # real SDP text: detect a video m-line / datachannel m-line
        return {
            "video": "m=video" in sdp,
            "datachannel": "m=application" in sdp,
        }


def make_loopback_offer(video: bool = True, datachannel: bool = True) -> str:
    return json.dumps({"loopback": True, "video": video, "datachannel": datachannel})


# ---------------------------------------------------------------------------
# providers
# ---------------------------------------------------------------------------

class LoopbackProvider:
    name = "loopback"

    def session_description(self, sdp: str, type: str):
        return SessionDescription(sdp, type)

    def peer_connection(self, ice_servers: list[dict] | None = None):
        return LoopbackPeerConnection(configuration=ice_servers)

    def h264_codec_preferences(self, kind: str = "video"):
        return [{"mimeType": "video/H264", "name": "H264"}]

    def force_codec(self, pc, sender, forced_codec: str):
        kind = forced_codec.split("/")[0]
        prefs = [
            c
            for c in self.h264_codec_preferences(kind)
            if c["mimeType"] == forced_codec
        ]
        for t in pc.getTransceivers():
            if t.sender is sender:
                t.setCodecPreferences(prefs)


class AiortcProvider:
    name = "aiortc"

    def __init__(self):
        import aiortc
        from aiortc import (
            RTCConfiguration,
            RTCIceServer,
            RTCPeerConnection,
            RTCSessionDescription,
        )
        from aiortc.rtcrtpsender import RTCRtpSender

        self._aiortc = aiortc
        self._RTCConfiguration = RTCConfiguration
        self._RTCIceServer = RTCIceServer
        self._RTCPeerConnection = RTCPeerConnection
        self._RTCSessionDescription = RTCSessionDescription
        self._RTCRtpSender = RTCRtpSender

    def session_description(self, sdp: str, type: str):
        return self._RTCSessionDescription(sdp=sdp, type=type)

    def peer_connection(self, ice_servers: list[dict] | None = None):
        if ice_servers:
            cfg = self._RTCConfiguration(
                iceServers=[self._RTCIceServer(**s) for s in ice_servers]
            )
            return self._RTCPeerConnection(configuration=cfg)
        return self._RTCPeerConnection()

    def h264_codec_preferences(self, kind: str = "video"):
        caps = self._RTCRtpSender.getCapabilities(kind)
        return [c for c in caps.codecs if c.name == "H264"]

    def force_codec(self, pc, sender, forced_codec: str):
        # reference force_codec() agent.py:72-77
        kind = forced_codec.split("/")[0]
        caps = self._RTCRtpSender.getCapabilities(kind)
        transceiver = next(t for t in pc.getTransceivers() if t.sender == sender)
        prefs = [c for c in caps.codecs if c.mimeType == forced_codec]
        transceiver.setCodecPreferences(prefs)


def get_provider(name: str | None = None):
    name = name or env_util.get_str("WEBRTC_PROVIDER")

    def native():
        from .rtc_native import NativeRtpProvider

        return NativeRtpProvider()

    if name == "loopback":
        return LoopbackProvider()
    if name == "native-rtp":
        # asked for by name: a runtime that cannot be built is an error
        # here, with the compiler's output, not a session that dies later
        from ..media import native as native_rt

        native_rt.require()
        return native()
    if name and name != "aiortc":
        # three tiers with materially different security properties — a
        # typo must not silently select a different stack
        raise ValueError(
            f"unknown WEBRTC_PROVIDER {name!r} "
            "(expected aiortc | native-rtp | loopback)"
        )
    try:
        return AiortcProvider()
    except ImportError:
        if name == "aiortc":
            raise
        # r5: the native tier is the full browser-capable stack (real SDP,
        # ICE-lite + DTLS-SRTP, SCTP datachannels, RTCP) — a deployment
        # without aiortc should serve browsers, not the loopback test shim.
        # But only when its C++ runtime actually loads: a toolchain-less
        # box must keep degrading to a WORKING loopback, not boot an agent
        # whose every session dies at setup.
        from ..media import native as native_rt

        def secure_importable() -> bool:
            try:
                from .secure import SecureMediaSession  # noqa: F401

                return True
            except ImportError:
                return False

        if native_rt.load() is None or not secure_importable():
            # missing C++ runtime OR missing `cryptography` (the secure
            # tier's crypto backend): either way every browser session
            # would die at setup — degrade to a WORKING loopback instead
            logger.warning(
                "aiortc not installed and the native tier's runtime deps "
                "are unavailable — using the loopback provider"
            )
            return LoopbackProvider()
        logger.warning(
            "aiortc not installed — using the native-rtp provider "
            "(in-repo secure WebRTC tier)"
        )
        return native()
