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
* Up to PIPELINE_DEPTH frames are kept in flight on the device (pipeline
  submit/fetch): recv() submits the new frame, then fetches the result of
  the frame submitted `depth` calls ago — dispatch, device compute and
  readback overlap across consecutive frames, which is where the TPU's
  throughput headroom lives.  depth=1 restores synchronous behavior.
* While the device sets the pace, the pull that refills the pipeline is
  HELD until the running step is about to end (``_PullHold``): the frame
  it binds is the freshest the source has at the last moment that still
  keeps the device fed, instead of one that waits a whole step in the
  device's queue while fresher ones are thrown away.
* While the source sets the pace with time to spare, the frame in flight
  is fetched BEFORE the next pull, and returned as soon as its step is
  done rather than a source period later, when the next frame has been
  pulled and submitted.  The track reads it from its own clock and the
  ready stamp the session hands back on the handle (``_spare``): the
  newest pull still waited for its source by more than the lead after
  the newest frame's step came back.  The pulls land where they did, at
  the source's pace; where the pull stops waiting (the camera outpaces
  the step, the host stalled) the pipeline fills back to depth at the
  next call.

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

# The hold's constants are times of the host, the same for every model.
# From the end of a hold to the moment the fetch after it comes back (the
# lead): the pull, the submit, and how long that fetch then still blocks.
# The first two are the host's work; the third is the slack that keeps the
# device fed through the jitter of the other two and of the event loop's
# wake-up (the fetch returns well inside a millisecond of the step's end,
# so a fetch that does not block is a submit that landed on a drained device)
_HOLD_LEAD_S = 0.0065
# a host whose submit eats the lead keeps its device fed all the same: the
# fetch after a held submit is to block for at least this long
_HOLD_BLOCK_S = 0.001
# the share of an error the next hold corrects: one late wake-up moves the
# next hold by half of it.  (An error of more than the whole lead is no
# jitter: the hold then goes straight to where the lead would be twice its
# target, so a new step length is found in two frames and approached from
# the safe side.)
_HOLD_GAIN = 0.5
# a pull longer than this waited for its source: the device does not set
# the pace, so nothing is held
_PULL_WAITED_S = 0.002


class _PullHold:
    """How long the pipelined ``recv()`` pauses before the pull that
    refills the pipeline.

    Fetching frame n returns the moment step n is over, which is the
    moment step n+1 starts: a frame pulled and submitted right then waits
    a whole step behind it.  Pulled ``seconds`` later it is that much
    younger when its own step starts, as long as its submit still lands
    before the running step ends.  The track sees how close it came: the
    fetch after the submit returns when the running step is over, so the
    time from the hold's end to that return (the lead) is the pull, the
    submit and the slack that was left.  The lead is steered to
    ``_HOLD_LEAD_S``, every frame, from the track's own clock around the
    calls it makes anyway: a frame is then on the host one step and one
    lead after it was pulled, whatever the step's length and however fast
    this host's submit runs today.  A loop the source paces gets no hold
    and behaves as it did without: its fetch does not block, and its pull
    waits."""

    __slots__ = ("seconds", "_returned", "_cycle")

    def __init__(self):
        self.seconds = 0.0     # the next pull's hold
        self._returned = None  # when the last fetch came back
        self._cycle = None     # the last fetch-to-fetch interval

    def observe(self, lead_s: float, fetch_s: float, pull_wait_s: float,
                now: float):
        """One ``recv()``: its fetch blocked ``fetch_s`` and came back at
        ``now``, ``lead_s`` after the hold before its (last) pull ended;
        that pull took ``pull_wait_s``."""
        returned, self._returned = self._returned, now
        if returned is None:
            return  # the first call filled an empty pipeline: no cycle yet
        cycle, before = now - returned, self._cycle
        self._cycle = cycle
        if pull_wait_s > _PULL_WAITED_S or (
            before is not None and cycle - before > _HOLD_LEAD_S
        ):
            # the source sets the pace; or something stalled for longer
            # than the whole lead, and the return no longer says when the
            # running step began.  The next pull is the unheld one
            self.seconds = 0.0
            return
        # the tighter of the two: the lead over its target, the fetch's
        # block over its floor.  (A fetch that did not block is an
        # overshoot: the hold backs off at once, but not to nothing: one
        # late wake-up is no reason to queue the next frames a whole step.)
        over = min(lead_s - _HOLD_LEAD_S, fetch_s - _HOLD_BLOCK_S)
        step = max(_HOLD_GAIN * over, over - _HOLD_LEAD_S)
        # the hold and the lead fit into the step they wait out: a cycle
        # is one step while the device sets the pace
        cap = (cycle if before is None else min(cycle, before)) - _HOLD_LEAD_S
        self.seconds = max(0.0, min(self.seconds + step, cap))


def _session_frame(handle):
    """The session's own record of a frame inside the handle ``submit``
    returned, or None.  A wrapper around the session hands its handle on
    as it is (the benchmark's) or inside a tuple of its own (the
    supervisor's, the agent's timing one); another pipeline's handle has
    no such record."""
    stack = [handle]
    while stack:
        h = stack.pop()
        if isinstance(h, tuple):
            stack.extend(h)
        elif hasattr(h, "hold_s"):
            return h
    return None


def _stamp_handle(handle, **stamps):
    """The track's own stamps of a frame ride its session's record, to be
    folded into the scheduler's counters when it is fetched: ``t_pickup``
    (its pull ended), for a held pull ``t_hold`` (the hold began) and
    ``hold_s`` (hop ``hold``), for a frame fetched before the next pull
    ``early_s`` (hop ``early_return``)."""
    rec = _session_frame(handle)
    if rec is not None:
        for name, value in stamps.items():
            setattr(rec, name, value)


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
        self._pull_wait_s = 0.0  # the newest pull's wait, for the hold
        # the pipelined path's hold before a pull, on this clock and this
        # sleep (plain fields: a test drives them by hand)
        self._hold = _PullHold()
        self._clock = time.monotonic
        self._sleep = asyncio.sleep
        # whether the newest pull found no frame in flight (its wait then
        # came after the last fetch), and the newest fetched frame's
        # submit-to-ready (None: its handle carried no ready stamp)
        self._pulled_empty = False
        self._ready_s = None
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
        t_pull = self._clock()
        frame = await self.track.recv()
        self._pull_wait_s = self._clock() - t_pull
        if self._note_pull_wait is not None:
            self._note_pull_wait(self._pull_wait_s)
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

    def _spare(self) -> float | None:
        """The pipelined ``recv()``'s choice: the seconds the track expects
        to wait for its source after the frame in flight comes back, where
        that is more than the lead (the frame is then fetched before the
        next pull), else None (the pipeline is filled to depth).  The newest
        pull's wait, less the newest fetched frame's time from its submit's
        return to its step's ready stamp where that pull had a frame in
        flight; the wait alone where it had none, since the fetch then came
        before it.  The pulls land where they would have: the source paces
        them either way.  A handle with no ready stamp never starts it."""
        if self._pulled_empty:
            spare = self._pull_wait_s
        elif self._ready_s is None:
            return None
        else:
            spare = self._pull_wait_s - self._ready_s
        return spare if spare > _HOLD_LEAD_S else None

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

        # pipelined path: up to `depth` frames in flight, the oldest
        # returned; one, the frame just submitted, while the source paces
        # the track with time to spare
        spare = self._spare()
        depth = self.pipeline_depth if spare is None else 1
        hold, clock = self._hold, self._clock
        while len(self._pending) < depth:
            t_held, held = clock(), hold.seconds > 0.0
            if held:
                # on the event loop, yielding: a counter only (hop
                # ``hold``), as ``pull_wait``: no span across an await
                await self._sleep(hold.seconds)
            t_pull, self._pulled_empty = clock(), not self._pending
            frame = await self._pull_fresh()
            t_pickup = clock()
            handle = await asyncio.to_thread(self.pipeline.submit, frame)
            if held:
                _stamp_handle(
                    handle, t_pickup=t_pickup, t_hold=t_held, hold_s=t_pull - t_held
                )
            else:
                _stamp_handle(handle, t_pickup=t_pickup)
            self._pending.append((frame, handle, clock()))
        src, handle, t_submitted = self._pending.popleft()
        if spare is not None:
            _stamp_handle(handle, early_s=spare)
        t_fetch = clock()
        out = await asyncio.to_thread(self.pipeline.fetch, handle, src)
        now = clock()
        if spare is None:
            # the hold reads a fetch with a newer frame in flight behind it.
            # (None is held while the track returns early: its pulls wait.
            # The first fetch after that comes a long cycle after the last
            # one the hold read, so it holds nothing either)
            hold.observe(now - t_pull, now - t_fetch, self._pull_wait_s, now)
        # when the step it rode was first seen done, where the handle says
        t_ready = getattr(_session_frame(handle), "t_ready", None)
        self._ready_s = None if t_ready is None else t_ready - t_submitted
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
