"""Reusable native-RTP client: the peer-side loop of the media plane.

Shared by the live example (examples/native_rtp_client.py), the
glass-to-glass measurement (scripts/glass_check.py) and chip_smoke.py so the
offer envelope, socket plumbing and the feed/poll drain discipline exist
exactly once.  Nothing here imports JAX: the smoke's parent process drives
this client while a child process holds the chip.

The drain interleaves ``feed_packet`` with ``poll``: the receive ring is a
4-slot latest-wins buffer, so feeding a whole burst before popping would
evict all but the newest few frames and undercount a perfectly healthy
stream (code-review r3).
"""

from __future__ import annotations

import asyncio
import json

import numpy as np

from ..resilience import faults as _faults
from ..resilience.overload import DeadlineQueue
from ..utils import env
from .frames import VideoFrame
from .plane import H264RingSource, H264Sink
from .sockio import CoalescedFlush


class NativeRtpClient:
    """Encode/send + receive/decode endpoints against a native-rtp agent."""

    def __init__(self, width: int, height: int, fps: int = 30,
                 use_h264: bool | None = None):
        self.width, self.height, self.fps = width, height, fps
        self._use_h264 = use_h264
        # bounded downlink packet queue (resilience/overload.py): a slow
        # drain sheds the OLDEST packets instead of building unbounded
        # latency; sheds are counted on the queue (freshest-frame-wins at
        # packet granularity — no deadline here, since dropping individual
        # late fragments would corrupt the AUs their siblings complete)
        self._recv_q = DeadlineQueue(
            bound=env.get_int("OVERLOAD_RX_QUEUE_BOUND", 512)
        )
        self._recv_tr = None
        self._send_tr = None
        self.sink: H264Sink | None = None
        self.back: H264RingSource | None = None
        self._out = CoalescedFlush()  # per-frame coalesced uplink flush
        # chaos hooks (resilience/faults.py): impair this client's uplink
        # ("tx") and downlink ("rx") when a fault plan is active; both are
        # None — one is-None test per packet — otherwise
        self._tx_faults = _faults.scope("tx")
        self._rx_faults = _faults.scope("rx")

    async def open(self) -> "NativeRtpClient":
        loop = asyncio.get_event_loop()
        q = self._recv_q

        class _Recv(asyncio.DatagramProtocol):
            def datagram_received(self, data, addr):
                q.push(data)

        self._recv_tr, _ = await loop.create_datagram_endpoint(
            _Recv, local_addr=("0.0.0.0", 0)
        )
        self.back = H264RingSource(
            self.width, self.height, use_h264=self._use_h264
        )
        return self

    @property
    def port(self) -> int:
        return self._recv_tr.get_extra_info("sockname")[1]

    def offer_envelope(self) -> str:
        """The JSON-envelope offer body for this client's geometry/port."""
        return json.dumps(
            {
                "native_rtp": True, "video": True,
                "width": self.width, "height": self.height,
                "client_addr": ["127.0.0.1", self.port],
            }
        )

    async def connect(self, server_port: int, host: str = "127.0.0.1"):
        loop = asyncio.get_event_loop()
        self._send_tr, _ = await loop.create_datagram_endpoint(
            asyncio.DatagramProtocol, remote_addr=(host, server_port)
        )
        self._out.bind(self._send_tr)
        self.sink = H264Sink(
            self.width, self.height, fps=self.fps, use_h264=self._use_h264
        )

    def send(self, arr_u8: np.ndarray, index: int):
        frame = VideoFrame.from_ndarray(np.ascontiguousarray(arr_u8))
        frame.pts = index * (90_000 // self.fps)
        pkts = self.sink.consume(frame)
        if not pkts:
            return
        if self._tx_faults is None:
            self._flush(pkts)
            return
        # chaos path: apply per-packet faults, but pace at FRAME
        # granularity — delayed survivors ride ONE timer per frame (at
        # the latest injected delay) instead of one call_later per
        # fragment (ISSUE 2 satellite); copies stabilize pooled views
        # across the timer hop
        immediate, delayed, due = [], [], 0.0
        for pkt in pkts:
            # the injector can HOLD a packet across calls (reorder fault)
            # — pooled views must be stabilized before they reach it
            if not isinstance(pkt, (bytes, bytearray)):
                pkt = bytes(pkt)
            for d, delay in self._tx_faults.apply(pkt):
                if delay > 0:
                    delayed.append(bytes(d))
                    due = max(due, delay)
                else:
                    immediate.append(d)
        self._flush(immediate)
        if delayed:
            asyncio.get_event_loop().call_later(due, self._flush, delayed)

    def _flush(self, pkts):
        """One coalesced flush of a frame's packets on the connected send
        socket (sendmmsg when available, sendto loop otherwise)."""
        self._out.flush(pkts)

    def drain(self, on_frame=None) -> int:
        """Feed every queued packet, polling decoded frames AFTER EACH feed
        (latest-wins ring: batch-feeding would evict).  -> frames received.
        ``on_frame(rgb, pts)`` sees each decoded frame (a client that only
        counts passes nothing)."""
        got = 0

        def poll_all():
            nonlocal got
            while True:
                frame = self.back.poll()
                if frame is None:
                    return
                got += 1
                if on_frame is not None:
                    on_frame(*frame)

        while True:
            entry = self._recv_q.pop()
            if entry is None:
                break
            data, _stamp = entry
            if self._rx_faults is not None:
                # downlink impairment: delays collapse to reorder here (the
                # drain is synchronous — schedule-late == deliver-late)
                for d, _delay in self._rx_faults.apply(data):
                    self.back.feed_packet(d)
                    poll_all()
                continue
            self.back.feed_packet(data)
            poll_all()
        poll_all()
        return got

    def close(self):
        for c in (self.sink, self.back):
            if c is not None:
                c.close()
        self._out.close()
        for t in (self._send_tr, self._recv_tr):
            if t is not None:
                t.close()
