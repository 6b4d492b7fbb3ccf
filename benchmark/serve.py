"""One run of one cell: set-up, the measured window, the readings.

The window drives the program's own ``VideoStreamTrack.recv()`` over
``BatchScheduler.claim()`` sessions.  The benchmark owns the two ends: a
paced latest-wins source per session (``source.py``) and a sink per session
that calls ``recv()`` in a loop, as the agent's sender task does, and takes
the result to a host uint8 array.  Around the two calls the track makes into
the session (``submit``, ``fetch``) it records host spans, also as
``jax.profiler.TraceAnnotation`` so that a traced run can put them beside the
device's gaps.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import gc
import logging
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import numpy as np

from .source import PacedSource, session_starts

logger = logging.getLogger("benchmark")

PRIME_RECVS = 4       # recv() calls per session before the window opens
TRACE_START_S = 2.0   # into the window
TRACE_SECONDS = 1.5   # ProfilerSession.stop() takes ~25 s per traced second of this program
HEAD_STATEFUL = 8     # first window frames kept per session for the check
TAIL_STATEFUL = 4     # and the window's last ones (a step that carries state)
HEAD_STATELESS = 2
RESERVOIR = 6         # seeded sample over the rest of the window (stateless)

_PROMPT_WORDS = [
    "watercolor", "neon", "charcoal", "mosaic", "origami", "stained glass",
    "oil painting", "pixel art", "ink wash", "pastel", "chrome", "woodcut",
]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_times: list = []
_listening = False


def _listen_for_compiles():
    """Count every XLA compile (or load from the persistent cache) by the
    time it ended, with JAX's own monitoring hook."""
    global _listening
    if not _listening:
        import jax.monitoring

        def _on(event, duration, **_kw):
            if event == _COMPILE_EVENT:
                _compile_times.append(time.monotonic())

        jax.monitoring.register_event_duration_secs_listener(_on)
        _listening = True


class SpanLog:
    """Host spans by name: (start, end) on ``time.monotonic()``."""

    def __init__(self):
        self.spans: dict = {}

    def add(self, name: str, start: float, end: float):
        self.spans.setdefault(name, []).append((start, end))

    def durations(self, name: str, lo: float, hi: float) -> list:
        return [e - s for s, e in self.spans.get(name, ()) if lo <= s and e <= hi]


class SpannedSession:
    """The benchmark's spans around the calls the track makes into a
    session.  Adds nothing else: every call goes to the session as is, and
    so does every attribute this wrapper does not define
    (``frame_buffer_size``, ``note_pull_wait``: the track looks them up on
    what it is given)."""

    def __init__(self, inner, log: SpanLog):
        self._inner, self._log = inner, log

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit(self, frame):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:submit"):
            handle = self._inner.submit(frame)
        self._log.add("submit", t0, time.monotonic())
        return handle

    def fetch(self, handle, src_frame=None):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:fetch"):
            out = self._inner.fetch(handle, src_frame)
        self._log.add("fetch", t0, time.monotonic())
        return out

    def __call__(self, frame):
        return self.fetch(self.submit(frame), frame)


@dataclass
class FrameRecord:
    k: int            # index in the session's schedule
    due: float
    handed: float
    done: float | None = None
    stylized: bool = False


@dataclass
class SessionLog:
    """What one session did: every frame it picked up, in order, and the
    outputs kept for the check."""

    index: int
    seed: int
    prompt: str
    warmup: int
    records: list = field(default_factory=list)   # returned or in flight
    consumed: list = field(default_factory=list)  # every k picked up, in order
    kept: dict = field(default_factory=dict)      # ordinal in records -> uint8


class Sink:
    """``recv()`` in a loop, as the sender task of a peer connection."""

    def __init__(self, log: SessionLog, track, source: PacedSource, rng, stateful):
        self.log, self.track, self.source = log, track, source
        self._rng, self._stateful = rng, stateful
        self._next = log.warmup   # handed[] index of the next frame to return
        self._in_window = 0
        self._reservoir: list = []
        self._last = None         # the window's newest kept frame (stateless)
        self._tail: deque = deque(maxlen=TAIL_STATEFUL)

    async def recv_one(self, keep_rule=None) -> FrameRecord:
        out = await self.track.recv()
        done = time.monotonic()
        k, due, handed = self.source.handed[self._next]
        self._next += 1
        arr = np.asarray(out)
        rec = FrameRecord(k, due, handed, done)
        rec.stylized = (
            arr.dtype == np.uint8
            and arr.shape == (self.source.height, self.source.width, 3)
            and not any(arr is f for _, f in self.source.recent)
        )
        self.log.records.append(rec)
        if keep_rule is not None and rec.stylized:
            keep_rule(len(self.log.records) - 1, arr)
        return rec

    def _keep_in_window(self, ordinal: int, arr):
        n = self._in_window
        self._in_window += 1
        head = HEAD_STATEFUL if self._stateful else HEAD_STATELESS
        if n < head:
            self.log.kept[ordinal] = arr
            return
        if self._stateful:
            self._tail.append((ordinal, arr))
            return
        # the window's newest frame is always kept (replacing the previous
        # newest), plus a seeded reservoir over everything after the head
        self._last = (ordinal, arr)
        m = n - head
        if m < RESERVOIR:
            self._reservoir.append((ordinal, arr))
        else:
            j = int(self._rng.integers(0, m + 1))
            if j < RESERVOIR:
                self._reservoir[j] = (ordinal, arr)

    async def run(self, t_close: float):
        while True:
            rec = await self.recv_one(self._keep_in_window)
            if rec.done >= t_close:
                return

    def finish(self):
        rest = self._reservoir + ([self._last] if self._last else []) + list(self._tail)
        for ordinal, arr in rest:
            self.log.kept[ordinal] = arr


@dataclass
class WindowResult:
    t_open: float
    t_close: float
    traced: tuple | None          # (start, end) host clock of the traced span
    xspace: bytes | None          # the traced span's serialized trace
    sessions: list                # SessionLog
    spans: SpanLog
    lateness: list                # (due, late_s) of every frame that came due
    superseded: int
    counters_open: dict
    counters_close: dict
    counters_trace: tuple | None  # (snapshot at trace start, at trace end)
    compiles_in_window: int
    setup_s: float
    memory_peak_bytes: int

    def steps_by_riders(self, traced: bool = False) -> dict:
        """{riders: steps dispatched with that many} over the window, or over
        the traced span: the program's ``batchsched_occupancy_hist``, later
        snapshot minus earlier."""
        if traced and self.counters_trace is None:
            return {}
        c0, c1 = self.counters_trace if traced else (self.counters_open, self.counters_close)
        before = c0.get("batchsched_occupancy_hist", {})
        after = c1.get("batchsched_occupancy_hist", {})
        return {int(k): v - before.get(k, 0) for k, v in after.items()}

    def counters_window(self) -> dict:
        """What the program counted over the window: steps by riders,
        dispatch causes, batches in flight at a dispatch, starved steps, the
        hops' milliseconds and counts (``hold``, ``launch`` ...)."""
        return counters_delta(self.counters_open, self.counters_close)


def counters_delta(c0: dict, c1: dict) -> dict:
    """Later snapshot minus earlier, for every cumulative counter of
    ``BatchScheduler.snapshot()`` (numbers and dicts of numbers whose name
    says total, count or hist); a maximum, or a percentile of the program's
    reservoir of waits, is the later snapshot's."""
    out = {}
    for key, after in c1.items():
        before = c0.get(key)
        if key.endswith("_max") or "_wait_ms_p" in key:
            out[key] = after
        elif not key.endswith(("_total", "_count", "_hist")):
            continue
        elif isinstance(after, dict):
            out[key] = {k: v - (before or {}).get(k, 0) for k, v in after.items()}
        else:
            out[key] = after - (before or 0)
    return out


def memory_peak() -> int:
    """The process's peak on its fullest chip so far: it never falls."""
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def session_plan(seed: int, n: int) -> list:
    """(session seed, prompt) for each session, from the run's seed: every
    session its own noise and its own prompt."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x5E55])
    words = rng.permutation(len(_PROMPT_WORDS))
    return [
        (int(rng.integers(1, 2**31 - 1)),
         f"a street at night, {_PROMPT_WORDS[words[i % len(words)]]} style, take {i}")
        for i in range(n)
    ]


def _start_profiler():
    """A profiler session whose trace comes back as bytes: nothing is
    written to disk (a 3 s trace of this program is 160 MB as a file, and
    JAX's ``stop_trace`` spends minutes exporting a viewer's copy of it).
    TraceMe spans and device events; no Python call tracing, which floods
    the trace and slows the host it is meant to observe."""
    from jax._src.lib import _profiler

    jax.devices()  # the backend before the session, or no device plane
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return _profiler.ProfilerSession(options)


async def _drive(sched, stream_cfg, traffic: dict, seed: int, seconds: float,
                 trace: bool, t_process_start: float) -> WindowResult:
    from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack

    n = traffic["sessions"]
    t_start = time.monotonic()
    stateful = len(stream_cfg.t_index_list) > 1 or stream_cfg.cfg_type != "none"
    spans = SpanLog()
    plan = session_plan(seed, n)
    sources, sinks, logs, claimed = [], [], [], []
    loop = asyncio.get_running_loop()
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=2 * n + 2, thread_name_prefix="bench-io"
    )
    loop.set_default_executor(pool)
    try:
        for i, (sess_seed, prompt) in enumerate(plan):
            sess = sched.claim(f"bench-{i}", prompt=prompt, seed=sess_seed)
            claimed.append(sess)
            src = PacedSource(
                sess_seed, traffic["source_fps"], stream_cfg.height, stream_cfg.width
            )
            track = VideoStreamTrack(
                src, SpannedSession(sess, spans),
                pipeline_depth=traffic["pipeline_depth"],
            )
            if track.warmup_frames != traffic["warmup_frames"]:
                raise RuntimeError(
                    f"the track warms up on {track.warmup_frames} frames, the "
                    f"traffic file states {traffic['warmup_frames']}"
                )
            log = SessionLog(i, sess_seed, prompt, track.warmup_frames)
            rng = np.random.default_rng([sess_seed, 0xC4EC])
            sources.append(src)
            logs.append(log)
            sinks.append(Sink(log, track, src, rng, stateful))
        t_claimed = time.monotonic()
        starts = session_starts(
            t_claimed, n, traffic["source_fps"], traffic.get("phase", "aligned")
        )
        for src, at in zip(sources, starts):
            src.start(at)

        async def prime(sink):
            for _ in range(PRIME_RECVS):
                await sink.recv_one()

        await asyncio.gather(*(prime(s) for s in sinks))

        # ---- the window ----
        t_open = time.monotonic()
        logger.info(
            "set-up: claiming %d session(s) took %.1f s, warm-up frames and "
            "filling the pipelines %.1f s", n, t_claimed - t_start, t_open - t_claimed,
        )
        t_close = t_open + seconds
        setup_s = t_open - t_process_start
        counters_open = sched.snapshot()
        compiles_before = len(_compile_times)
        tasks = [loop.create_task(s.run(t_close)) for s in sinks]
        traced, counters_trace, xspace = None, None, None
        if trace:
            start = min(TRACE_START_S, max(0.0, seconds - TRACE_SECONDS) / 2)
            await asyncio.sleep(start)
            session = await asyncio.to_thread(_start_profiler)
            c0 = sched.snapshot()
            a = time.monotonic()
            with jax.profiler.TraceAnnotation("bench:trace_window"):
                await asyncio.sleep(min(TRACE_SECONDS, seconds))
            b = time.monotonic()
            counters_trace = (c0, sched.snapshot())
            traced = (a, b)
            xspace = await asyncio.to_thread(session.stop)
            logger.info(
                "trace: %.1f s traced, collecting it took %.1f s (%d MB)",
                b - a, time.monotonic() - b, len(xspace) >> 20,
            )
        await asyncio.gather(*tasks)
        counters_close = sched.snapshot()
        compiles = sum(1 for t in _compile_times[compiles_before:] if t < t_close)
        peak = memory_peak()

        # frames in flight at the close: wait for each of them
        depth = traffic["pipeline_depth"]

        async def drain(sink):
            for _ in range(depth):
                await asyncio.wait_for(sink.recv_one(), timeout=60.0)

        await asyncio.gather(*(drain(s) for s in sinks))
        for s in sinks:
            s.finish()
            s.log.consumed = [k for k, _, _ in s.source.handed]
        lateness = [
            (src.due_time(k), late)
            for src in sources for k, late in enumerate(src.lateness_s)
        ]
        return WindowResult(
            t_open, t_close, traced, xspace, logs, spans, lateness,
            sum(src.superseded_between(t_open, t_close) for src in sources),
            counters_open, counters_close, counters_trace, compiles, setup_s, peak,
        )
    finally:
        for src in sources:
            await src.stop()
        for sess in claimed:
            sess.release()
        pool.shutdown(wait=True, cancel_futures=True)


def run_window(cfg: dict, weight_shapes, traffic: dict, seed: int, seconds: float,
               trace: bool, t_process_start: float,
               quant: str | None = None) -> WindowResult:
    """Build the program for this cell, run one window, free the program.
    ``weight_shapes``: of the configuration's reference module."""
    from .program import build_scheduler

    _listen_for_compiles()
    sched, stream_cfg = build_scheduler(
        cfg, weight_shapes(cfg), seed, traffic["slots"], quant
    )
    try:
        return asyncio.run(
            _drive(sched, stream_cfg, traffic, seed, seconds, trace, t_process_start)
        )
    finally:
        sched.close()
        del sched
        gc.collect()


@dataclass
class ReaderContext:
    """What a per-layer metric's ``read(ctx)`` is given."""

    cfg: dict
    traffic: dict
    result: WindowResult
    trace: dict | None   # trace_reduce.reduce_trace(...) of the traced span
    peaks: dict          # peaks.peaks_of(device_kind)
    flops: object        # the configuration's flops module (harness.Benchmark.flops)
