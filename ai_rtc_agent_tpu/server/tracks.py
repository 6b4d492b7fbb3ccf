"""Processed media track — parity with reference lib/tracks.py.

Wraps a source track; every ``recv()`` pulls a decoded frame and returns the
diffused frame.  Keeps the reference's warm-up semantics (drop WARMUP_FRAMES
frames through the pipeline to trigger compile/caches at connect time,
reference lib/tracks.py:21-25) and the DROP_FRAMES OBS-stutter workaround
(:27-31), with two deliberate fixes:

* WARMUP_FRAMES is parsed as int (the reference leaves it a str when set —
  latent TypeError, lib/tracks.py:17; flagged in SURVEY.md section 5).
* The diffusion step runs in a worker thread via ``asyncio.to_thread`` so a
  TPU step can NEVER stall the event loop (the reference blocks its loop on
  GPU inference inside recv(), lib/tracks.py:24,38 — SURVEY.md hazard list).
  Ordering stays strict because recv() calls are serialized per track.
* PIPELINE_DEPTH frames are kept in flight on the device (pipeline
  submit/fetch): recv() submits the new frame, then fetches the result of
  the frame submitted `depth` calls ago — dispatch, device compute and
  readback overlap across consecutive frames, which is where the TPU's
  throughput headroom lives.  depth=1 restores synchronous behavior.

Overload control (resilience/overload.py): the track is the INGEST hop of
the frame path.  When an ``overload`` control plane is attached, every
pulled frame is checked against its decode-stamp deadline
(``OVERLOAD_FRAME_DEADLINE_MS``): a stale frame with a fresher one already
queued behind it is shed (freshest-frame-wins, counted), and the
delivered-frame freshness lands in the /metrics reservoir.  Sources that
can skip ahead expose a non-blocking ``recv_nowait()`` (the loopback track
and the native ring source do); sources without one simply never shed here.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque

from ..resilience.overload import ShedFrame
from ..utils import env

logger = logging.getLogger(__name__)


class VideoStreamTrack:
    kind = "video"

    def __init__(self, track, pipeline, pipeline_depth: int | None = None,
                 overload=None, tracer=None):
        self.track = track
        self.pipeline = pipeline
        self.overload = overload  # OverloadControlPlane | None
        # obs/trace.py SessionTracer: the track is the INGEST hop, so it
        # is where a frame that arrived without a trace (loopback/aiortc
        # tiers — the native tier mints at decode) gets one, and where
        # freshest-wins sheds are terminal-marked.  None = tracing never
        # touches this track (zero overhead).
        self.tracer = tracer
        self.warmup_frame_idx = 0
        self.warmup_frames = env.warmup_frames()
        self.drop_frames = env.drop_frames()
        self.pipeline_depth = (
            env.pipeline_depth() if pipeline_depth is None else max(1, pipeline_depth)
        )
        if not hasattr(pipeline, "submit"):
            self.pipeline_depth = 1
        # the batch scheduler's session counts how long this track waited
        # for its source (hop ``pull_wait``); wrappers around the session
        # pass the attribute through, other pipelines have no such counter
        self._note_pull_wait = getattr(pipeline, "note_pull_wait", None)
        # in-flight bound: the submit loops below never hold more than
        # `pipeline_depth` entries (single-frame path) / batches (fbs path)
        self._pending: deque = deque(maxlen=self.pipeline_depth)
        self._handlers: dict = {}

    # minimal MediaStreamTrack event surface (works standalone and under
    # aiortc, which duck-types tracks through the same recv() pull model)
    def on(self, event: str, f=None):
        def register(fn):
            self._handlers[event] = fn
            return fn

        return register(f) if f else register

    def stop(self):
        from ..utils.dispatch import fire_handler

        fire_handler(self._handlers.get("ended"))

    @property
    def _fbs(self) -> int:
        return int(getattr(self.pipeline, "frame_buffer_size", 1) or 1)

    # -- observability --------------------------------------------------------

    @staticmethod
    def _stamp_ingest(trace, frame):
        """The ingest span: decode-complete (wall_ts stamp) -> admitted
        into the pipeline — exactly the queue-wait component the overload
        plane controls."""
        now = time.monotonic()
        wall = getattr(frame, "wall_ts", None)
        trace.add_span("ingest", wall if wall is not None else now, now)

    # -- overload hooks -------------------------------------------------------

    async def _pull_fresh(self):
        """One source frame, freshest-wins: while the frame at hand has
        aged past HALF the deadline AND the source has a backlog to skip
        into, shed it and take the next.  Stopping at the first barely-
        in-deadline frame would make delivered ages cluster just under the
        deadline (each engine step pushes the next pick right back to the
        edge) — the half-deadline target keeps freshness p99 comfortably
        inside it.  A stale frame with nothing behind it is still
        delivered — a late frame beats a frozen stream."""
        # a counter, not a span: an annotation belongs to its thread, and
        # tasks interleave on the event loop's thread across this await
        t_pull = time.monotonic()
        frame = await self.track.recv()
        if self._note_pull_wait is not None:
            self._note_pull_wait(time.monotonic() - t_pull)
        tracer = self.tracer
        trace = tracer.attach(frame) if tracer is not None else None
        ov = self.overload
        if ov is None:
            if trace is not None:
                self._stamp_ingest(trace, frame)
            return frame
        recv_nowait = getattr(self.track, "recv_nowait", None)
        if ov.frame_deadline_s and recv_nowait is not None:
            shed = 0
            while ov.frame_age(frame) > ov.frame_deadline_s / 2.0:
                nxt = recv_nowait()
                if nxt is None:
                    break
                if trace is not None:
                    # the shed frame's timeline ends HERE, visibly — PR 4's
                    # freshest-frame-wins eviction per frame, not just a
                    # counter bump
                    trace.mark("ingest_shed")
                    trace.finish("shed")
                frame = nxt
                trace = tracer.attach(frame) if tracer is not None else None
                shed += 1
            if shed:
                ov.note_shed_ingest(shed)
        if trace is not None:
            self._stamp_ingest(trace, frame)
        # freshness is measured HERE, at the pick: the queue-wait age of the
        # frame admitted into the pipeline is exactly the component the
        # overload plane controls (device time shows up in latency_p*_ms
        # and the glass gauge instead).  Unstamped frames (plain aiortc
        # remote tracks) carry no decode stamp — recording them would fill
        # the reservoir with fake perfect 0.0 samples, so they are skipped
        # and the freshness gauges reflect only frames that can be measured
        if getattr(frame, "wall_ts", None) is not None:
            ov.note_delivered(ov.frame_age(frame))
        return frame

    async def recv(self):
        fbs = self._fbs
        if fbs > 1 and hasattr(self.pipeline, "submit_batch"):
            return await self._recv_batched(fbs)

        while self.warmup_frame_idx < self.warmup_frames:
            logger.info("dropping warmup frames %d", self.warmup_frame_idx)
            frame = await self.track.recv()
            await asyncio.to_thread(self.pipeline, frame)
            self.warmup_frame_idx += 1

        # Drop frames to smooth certain encoders (OBS x264 stutter fix kept
        # from reference lib/tracks.py:27-31)
        for _ in range(self.drop_frames):
            await self.track.recv()

        if self.pipeline_depth == 1:
            frame = await self._pull_fresh()
            out = await asyncio.to_thread(self.pipeline, frame)
            if isinstance(out, ShedFrame):
                # unsupervised tier (SUPERVISOR=0): no resilience wrapper
                # to unwrap the bounded-queue shed marker — deliver pixels
                return out.frame
            return out

        # pipelined path: keep `depth` frames in flight, return the oldest
        while len(self._pending) < self.pipeline_depth:
            frame = await self._pull_fresh()
            handle = await asyncio.to_thread(self.pipeline.submit, frame)
            self._pending.append((frame, handle))
        src, handle = self._pending.popleft()
        out = await asyncio.to_thread(self.pipeline.fetch, handle, src)
        if isinstance(out, ShedFrame):
            # unsupervised tier (SUPERVISOR=0): no resilience wrapper to
            # unwrap the bounded-queue shed marker — deliver the pixels
            return out.frame
        return out

    async def _recv_batched(self, fbs: int):
        """frame_buffer_size>1 serving: fbs consecutive frames ride ONE
        device step (the reference's fbs amortization, lib/wrapper.py:159-163,
        brought to the live track); outputs drain one per recv()."""
        if not hasattr(self, "_outbuf"):
            # tpurtc: allow[bounded-queue] -- drained to empty before each refill; holds at most one fetch_batch's fbs outputs (fbs is not known at ctor time)
            self._outbuf = deque()

        async def pull_batch():
            return [await self._pull_fresh() for _ in range(fbs)]

        while self.warmup_frame_idx < self.warmup_frames:
            logger.info("dropping warmup frame batch @%d", self.warmup_frame_idx)
            srcs = await pull_batch()
            h = await asyncio.to_thread(self.pipeline.submit_batch, srcs)
            await asyncio.to_thread(self.pipeline.fetch_batch, h, srcs)
            self.warmup_frame_idx += fbs

        # keep `pipeline_depth` BATCHES in flight (dispatch/compute/readback
        # overlap across batches, same as the single-frame pipelined path)
        while not self._outbuf:
            for _ in range(self.drop_frames):
                await self.track.recv()
            srcs = await pull_batch()
            self._pending.append(
                (srcs, await asyncio.to_thread(self.pipeline.submit_batch, srcs))
            )
            if len(self._pending) >= max(1, self.pipeline_depth):
                srcs0, h0 = self._pending.popleft()
                outs = await asyncio.to_thread(self.pipeline.fetch_batch, h0, srcs0)
                # unsupervised tier (SUPERVISOR=0): unwrap bounded-queue
                # shed markers to their source pixels, the single-frame
                # recv rule — a raw ShedFrame must never reach the encoder
                self._outbuf.extend(
                    o.frame if isinstance(o, ShedFrame) else o for o in outs
                )
        return self._outbuf.popleft()
