"""The load generator: one paced, latest-wins frame source per session.

A camera does not wait for its consumer.  Frame ``k`` of a session is due at
``t0 + k / fps`` by the schedule alone; a pacer task stamps each frame the
moment it comes due (how late it woke is the generator's own lateness), and
``recv()`` hands out the freshest due frame that has not been handed out
yet, counting the ones it skipped as superseded: the behaviour of the
agent's native frame ring in front of ``VideoStreamTrack``.  A consumer that
asks before the next frame is due waits for it.

Pixels are made only for frames that are handed out: a seeded texture per
session, larger than the frame, from which frame ``k`` is the crop at an
offset that moves with ``k`` (a pan), so consecutive frames differ and any
frame can be made again from ``(seed, k)`` alone by the output check.
"""

from __future__ import annotations

import asyncio
import time
from collections import deque

import numpy as np

_MARGIN = 128  # texture is frame size + margin; the pan wraps inside it


def session_texture(seed: int, height: int, width: int) -> np.ndarray:
    """Smooth blobs plus fine grain, uint8 [H+M, W+M, 3]."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x7E57])
    h, w = height + _MARGIN, width + _MARGIN
    coarse = rng.integers(0, 256, (h // 32 + 2, w // 32 + 2, 3)).astype(np.float32)
    smooth = np.repeat(np.repeat(coarse, 32, axis=0), 32, axis=1)[:h, :w]
    grain = rng.integers(-40, 41, (h, w, 3)).astype(np.float32)
    return np.clip(0.75 * smooth + 0.25 * 128 + grain, 0, 255).astype(np.uint8)


def frame_at(texture: np.ndarray, k: int, height: int, width: int) -> np.ndarray:
    """Frame ``k`` of the pan: a fresh contiguous uint8 [H, W, 3]."""
    oy, ox = (5 * k) % _MARGIN, (3 * k) % _MARGIN
    return np.ascontiguousarray(texture[oy:oy + height, ox:ox + width])


PHASES = ("aligned", "staggered")


def session_starts(t0: float, sessions: int, fps: float, phase: str = "aligned") -> list:
    """When frame 0 of each session is due (the traffic file's ``phase``).
    ``aligned``: every schedule starts at the one instant ``t0``, so frame k
    of every session comes due together.  ``staggered``: session i starts
    ``i / (sessions * fps)`` later, so the sessions' frames come due evenly
    spread over one source period.  With one session both are ``[t0]``."""
    if phase not in PHASES:
        raise ValueError(f"traffic phase {phase!r}: one of {PHASES}")
    step = 1.0 / (sessions * fps) if phase == "staggered" else 0.0
    return [t0 + i * step for i in range(sessions)]


class PacedSource:
    """``await recv()`` -> uint8 frame; duck-types the track a
    ``VideoStreamTrack`` pulls from.  All times are ``time.monotonic()``."""

    kind = "video"

    def __init__(self, seed: int, fps: float, height: int, width: int,
                 clock=time.monotonic):
        self.fps = float(fps)
        self.height, self.width = height, width
        self.texture = session_texture(seed, height, width)
        self._clock = clock
        self.t0: float | None = None
        self._due_idx = -1          # newest frame that has come due
        self._last_out = -1         # newest frame handed out
        self._fresh = asyncio.Event()
        self._pacer: asyncio.Task | None = None
        self.superseded = 0
        self.lateness_s: list = []  # pacer wake-up minus due time, per frame
        self.handed: list = []      # (k, due_time, handed_time) in order
        # the newest frames handed out, so that a consumer can tell its own
        # input coming back (a passthrough) from a result
        self.recent: deque = deque(maxlen=8)

    def due_time(self, k: int) -> float:
        return self.t0 + k / self.fps

    def start(self, at: float | None = None):
        """Frame 0 is due now, or at ``at`` on the source's clock: the
        offset that sets this session's phase against the others'."""
        self.t0 = self._clock() if at is None else at
        self._pacer = asyncio.get_running_loop().create_task(self._pace())

    async def stop(self):
        if self._pacer is not None:
            self._pacer.cancel()
            try:
                await self._pacer
            except asyncio.CancelledError:
                pass
            self._pacer = None

    async def _pace(self):
        k = 0
        while True:
            wait = self.due_time(k) - self._clock()
            if wait > 0:
                await asyncio.sleep(wait)
            now = self._clock()
            # a pacer that overslept several periods stamps each due frame
            while self.due_time(k) <= now:
                self.lateness_s.append(now - self.due_time(k))
                self._due_idx = k
                k += 1
            self._fresh.set()

    async def recv(self) -> np.ndarray:
        while self._due_idx <= self._last_out:
            self._fresh.clear()
            await self._fresh.wait()
        k = self._due_idx
        self.superseded += k - self._last_out - 1
        self._last_out = k
        self.handed.append((k, self.due_time(k), self._clock()))
        frame = frame_at(self.texture, k, self.height, self.width)
        self.recent.append((k, frame))
        return frame

    def superseded_between(self, lo: float, hi: float) -> int:
        """Due frames skipped by hand-outs made in [lo, hi)."""
        n, prev = 0, None
        for k, _, t in self.handed:
            if prev is not None and lo <= t < hi:
                n += k - prev - 1
            prev = k
        return n
