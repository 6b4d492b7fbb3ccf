"""Cross-session continuous batching: one jitted step, many sessions.

The stream-batch law (PAPER.md; reference lib/wrapper.py:159-163) buys
multi-step quality at one UNet pass per frame — but on the shared-engine
plane (``BATCHSCHED=0``) that batch axis carries *bubbles*: every session
shares one :class:`StreamEngine` and serializes through its submit lock, so
N sessions cost N sequential device steps.  This module fills the batch
axis with other users' frames instead:

* Per-session stream state lives in a **stacked pytree** ``[S, ...]``,
  one row a slot, claimed and released as sessions come and go: prompt
  embeddings, guidance/delta, stock noise, the latent ring — everything a
  session owns rides as a batched operand, so sessions keep fully
  independent control planes.
* ``submit()`` enqueues ``(session, frame)`` into a short bounded
  **coalescing window** (a :class:`DeadlineQueue` per slot — the
  bounded-queue invariant holds; a shed frame's waiter resolves as
  passthrough immediately).
* A dispatcher thread drains all waiting sessions into **ONE vmapped
  jitted step** at the nearest power-of-two bucket geometry
  (:func:`make_bucket_step` — gather active rows, step, scatter back).
  Padding repeats the last active row: identical compute, identical
  scatter writes.
* Dynamic join/leave never retraces: the bucket geometries are a small
  fixed set, AOT-compiled through ``aot/cache.py``
  (``stream_engine_key(..., sbucket=k, sessions=S)``) and warmed at build
  time (``BATCHSCHED_PREWARM`` / the build CLI's ``--sched-buckets``).
* Overload joins at **batch composition**: the per-session
  ``OverloadLadder`` sheds/skips BEFORE a frame enters the window (the
  resilient wrapper's ``admit_frame`` gate), never mid-batch; and the
  scheduler feeds the admission step-EWMA **per-batch-amortized** latency
  (``dt / occupancy``) via :attr:`on_step`, so advertised capacity
  reflects the batching gain.
* The frame path is **device-resident between the locks** (ISSUE 9): a
  session's submit stages its H2D copy (``stage_frame``) before any lock
  is taken, the bucket step consumes device-side rows (``jnp.stack`` of
  already-transferred frames), and at dispatch the output is sliced into
  per-slot rows ON DEVICE with ``copy_to_host_async`` kicked per row —
  each session's fetch resolves ONLY its own buffer (memoized on the
  batch row, so dup/skip fetches never re-resolve), so frame N's dispatch
  overlaps frame N−1's readback and one session's readback never bills
  the others.
* **Speed variants ride the same bucket steps**: ``QUANT_WEIGHTS=w8``
  params serve unchanged (the dequant lives in the layer primitives; the
  AOT keys gain ``quant-w8``), and the DeepCache cadence (``UNET_CACHE``)
  runs as a GLOBAL tick over (k, capture|cached)-keyed bucket executables
  — every slot captures on the same tick, and any install/prompt/t-index
  write resets the cadence so a zeroed or stale deep-feature cache is
  never consumed.
* **The session axis spans the mesh** (ISSUE 12, ROADMAP open item 4):
  with ``BATCHSCHED_DP=N`` (or a ``MESH_SHAPE`` dp axis) the stacked
  ``[S, ...]`` pytree shards its leading axis over a dp mesh
  (``parallel/sharding.py`` session-axis rules: params replicated, states
  /frames/outputs on ``P("dp")``), so one bucket step drives every chip —
  a v5e-8 serves ~8x the sessions of one chip at the same per-session
  latency.  The whole plane follows the sharding: submit stages each
  session's row onto ITS shard (``stage_frame(..., device=...)`` — H2D
  lands on the owning device, never device 0 then reshuffle), dispatch
  assembles the global frame batch from the per-shard rows zero-copy
  (``jax.make_array_from_single_device_arrays``), the per-slot readback
  slices each row FROM ITS SHARD (fetch isolation survives sharding: no
  cross-device gather resolves one session's frame), bucket sizes are
  dp multiples (padding rows land on otherwise-idle shards, so
  below-capacity occupancy is latency-neutral), and the AOT key plane
  carries the mesh shape (``dp-N`` via ``aot/cache.mesh_key_extra``)
  with prewarm covering every ``(k, variant, dp)`` geometry — join/leave
  /reshard never retraces mid-serve, watched by the devtel compile
  watchdog under ``sbucket-<k>:<variant>:dp<N>`` scopes.
* **``--fbs`` joins as a second batching dimension**: with
  ``frame_buffer_size > 1`` each session's window coalesces fbs
  CONSECUTIVE frames into one ``[fbs, H, W, 3]`` row and the bucket step
  batches ``[k, fbs, ...]`` — sessions x consecutive frames in ONE
  device step (the two batch axes the pre-ISSUE-12 scheduler declared
  mutually exclusive).  Each frame's handle resolves to its own slice of
  the session's row; the similarity filter stays fbs==1-only (a skip
  would desync the group boundaries).

Outputs match a dedicated engine per session to within one uint8
quantisation step (pinned by tests/test_batch_scheduler.py across
join/leave, prompt updates and similarity skips): the bucket step applies
the SAME pure step function to the session's state row that a dedicated
engine would apply to its state, and executables of different batch size
may fuse differently.

Single-session behavior is pass-through-cheap: with one live session the
dispatcher never waits out the window — the frame dispatches immediately
through the k=1 bucket.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from concurrent.futures import CancelledError, Future, InvalidStateError

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import devtel
from ..obs.trace import get_trace, hop, safe_list
from ..ops.pallas import (
    count_attention_paths,
    f32_relayout_copies,
    mosaic_kernel_counts,
)
from ..resilience import faults as _faults
from ..resilience.overload import DeadlineQueue, ShedFrame
from ..utils import env
from .engine import (
    SimilarityFilter,
    StreamEngine,
    make_bucket_step,
    make_step_fn,
    params_variant_extra,
    stage_frame,
    stream_engine_key,
)

logger = logging.getLogger(__name__)

__all__ = [
    "BatchScheduler", "ScheduledSession", "CapacityError",
    "SnapshotMismatch", "SESSION_SNAPSHOT_SCHEMA",
]

# session-snapshot schema version (live migration, ISSUE 15): the payload
# layout of snapshot_session()/restore_session().  Bump on ANY field or
# semantic change — restore REFUSES a mismatched version instead of
# guessing, because a misread row becomes silently wrong pixels on
# another agent (the blob itself carries a second, byte-layout version
# inside parallel/checkpoint.serialize_pytree).
# v2 (ISSUE 20): the state row may carry the per-session LoRA factor bank
# ("adapters" subtree — migration moves style bit-exact) and the payload
# gains the "adapter" name field; the fingerprint gains adapter_rank /
# adapter_targets when a bank is bound.
# Still v2 with a side network (ISSUE 34): only a scheduler that serves one
# adds the "controlnet_scale" field and the fingerprint's "cnet"; its rows
# carry cnet_cond / cnet_scale, which _check_row holds to the template.
# Every other scheduler's payload is byte for byte what it was.
SESSION_SNAPSHOT_SCHEMA = 2

# the hops the scheduler counts (``batchsched_hop_*`` in snapshot()): names
# from the obs/trace.py STAGES taxonomy, the closed key set of the counters
COUNTED_HOPS = (
    "hold", "pull_wait", "coerce", "stage_h2d", "enqueue_lock_wait", "dispatch",
    "launch", "await_row", "finish_output", "encode_prompt", "early_return",
)
# why a step was dispatched when it was (``batchsched_dispatch_cause_total``):
# solo = the one-live-session path in _enqueue; inline_full = this submit
# completed the batch; window = the dispatcher went with who showed up;
# backpressure = the dispatcher, every live session ready, held back only
# by the in-flight cap until a batch resolved; none_due = a live session
# was missing, but the device had drained and no missing session's frame
# was predicted inside the window, so the step went without waiting it
# out (in _enqueue, or by the dispatcher once the device drained)
DISPATCH_CAUSES = ("solo", "inline_full", "window", "backpressure", "none_due")
# enqueue stamps kept a slot to predict its next frame from (_due_before):
# four intervals, so a host stall keeps its slot "due" four frames at most
_ENQ_STAMPS = 5
# what the host was doing while the device sat idle between two steps
# (``batchsched_device_gap_*``): pull_wait = the launching frame's track
# waited for its camera; hold = that track held the pull; window_wait = the
# dispatcher waited for riders; host = anything else (submit's own hops,
# the event loop, a stall of the process)
GAP_CAUSES = ("pull_wait", "hold", "window_wait", "host")
# a fetched frame's time in the program (``batchsched_<piece>_ms_hist``):
# residence = pick-up to the fetch's return = to_launch (to the jitted
# call's return) + on_device (to the step's ready stamp) + after_ready;
# in bins of 0.1 ms
RESIDENCE_PIECES = ("residence", "to_launch", "on_device", "after_ready")
_RES_BINS_PER_S = 1e4


def _bump(counts: dict, key, by=1) -> dict:
    """``counts[key] += by``, copy-on-new-key: snapshot() iterates these
    dicts WITHOUT the stats lock (it must never block on a dispatch), so a
    dict gains a key only by being replaced wholesale, which keeps every
    published dict iteration-safe.  -> the dict to keep."""
    if key in counts:
        counts[key] += by
        return counts
    counts = dict(counts)
    counts[key] = by
    return counts


def _gap_cause(g0: float, g1: float, pull_wait, hold, window_wait) -> str:
    """The member of GAP_CAUSES that covers most of the device's idle gap
    ``[g0, g1]``: each of the first three is a (start, end) interval of the
    host's clock or None; ``host`` covers what none of them does.  Ties go
    to the earlier member."""
    cover, clipped = {}, []
    for name, span in zip(GAP_CAUSES, (pull_wait, hold, window_wait)):
        lo, hi = (g0, g0) if span is None else (max(g0, span[0]), min(g1, span[1]))
        cover[name] = max(0.0, hi - lo)
        if hi > lo:
            clipped.append((lo, hi))
    union, end = 0.0, g0
    for lo, hi in sorted(clipped):
        union += max(0.0, hi - max(lo, end))
        end = max(end, hi)
    cover["host"] = (g1 - g0) - union
    return max(GAP_CAUSES, key=cover.get)


def _due_before(stamps, until: float) -> bool:
    """Is a session whose newest enqueues were at ``stamps`` (oldest
    first) predicted to enqueue again before ``until``?  Its next frame is
    expected one shortest recent interval after its newest, less the
    intervals' spread (longest minus shortest: the jitter it has shown).
    With fewer than two intervals on record nothing is known: yes."""
    if len(stamps) < 3:
        return True
    t = list(stamps)
    ivs = [b - a for a, b in zip(t, t[1:])]
    lo = min(ivs)
    return t[-1] + lo - (max(ivs) - lo) < until


class CapacityError(RuntimeError):
    """Every session slot is claimed, or the engine is quarantined (maps
    to HTTP 503 in the agent)."""


class SnapshotMismatch(ValueError):
    """A session snapshot does not fit this scheduler — wrong schema
    version, wrong model/geometry/variant fingerprint, or a state row
    whose structure/shape/dtype differs from the compiled bucket steps'
    operand.  Restore refuses; the source keeps serving."""


class _DispatchedBatch:
    """One dispatched bucket step's per-slot readback plane.

    At dispatch the ``[k, ...]`` output is sliced into per-entry device
    rows and every row's D2H copy is started asynchronously — each
    rider's fetch resolves ONLY its own row (``BatchScheduler.
    _resolve_row``), so one session's readback never bills the others and
    the next dispatch overlaps this batch's readbacks.  Host copies are
    memoized per row (dup/skip fetches re-read the cached array, never
    the device).  ``feed``: False when this was a bucket's first
    (possibly lazily compiled) use — its duration must not reach the
    admission EWMA."""

    __slots__ = (
        "rows", "host", "rlocks", "entries", "t_dispatch", "occupancy",
        "resolved", "feed", "cause", "inflight", "starved", "dispatch_s",
        "launch", "out", "step", "k", "frames", "launcher", "window",
        "epoch", "ready_by", "ready",
    )

    def __init__(self, rows, entries, t_dispatch, occupancy, feed=True,
                 cause="solo", inflight=0, starved=False, dispatch_s=0.0,
                 launch=(0.0, 0.0), out=None, step=0, k=1, frames="",
                 launcher=None, window=None, epoch=0):
        self.rows = rows  # per-entry device buffers (async D2H in flight)
        self.host = [None] * len(rows)  # memoized per-row host copies
        self.rlocks = [threading.Lock() for _ in rows]
        self.entries = entries
        self.t_dispatch = t_dispatch
        self.occupancy = occupancy
        self.resolved = False  # first-row-resolved: accounting + in-flight
        self.feed = feed
        # the dispatch's own stamps, folded into the scheduler's counters
        # by the first resolver (_note_step): why it was dispatched (one of
        # DISPATCH_CAUSES), the batches then in flight, whether it found
        # the device drained, the host seconds of _step_batch_locked, and
        # the two clock reads around the jitted call inside it
        self.cause = cause
        self.inflight = inflight
        self.starved = starved
        self.dispatch_s = dispatch_s
        self.launch = launch
        # what the ready watcher reads (BatchScheduler._watch_ready): the
        # bucket output it blocks on (dropped once it is ready), the ids
        # ``rtc:launch`` and ``rtc:dispatch`` carry, the submitting frame
        # of an inline dispatch, the dispatcher's wait for riders before
        # it ((start, end) or None), and the counters' generation
        self.out = out
        self.step = step
        self.k = k
        self.frames = frames
        self.launcher = launcher
        self.window = window
        self.epoch = epoch
        # the earliest instant another thread saw this step done (a drain
        # check found its output ready, a fetch had its row on the host):
        # the watcher's stamp is the earlier of that and its own wake-up
        self.ready_by: float | None = None
        # the watcher's own sight of this step done (None until it woke, or
        # if the step raised)
        self.ready: float | None = None


class _PendingFrame:
    """One enqueued frame: the waiter future plus the stamps the
    observability spans need (enqueue -> dispatch = batch_join; dispatch
    -> resolve = engine_step) and the submit-side hop stamps that fetch
    folds into the scheduler's counters (None = the hop did not run)."""

    __slots__ = (
        "frame", "frame_dev", "future", "trace", "t_enq", "t_dispatch",
        "occupancy", "skipped", "readback", "seq", "pull_wait_s",
        "coerce_s", "stage_s", "lock_wait_s", "hold_s", "t_hold", "t_pickup",
        "early_s",
    )

    def __init__(self, frame, trace=None, seq=0, pull_wait_s=None,
                 coerce_s=None):
        self.frame = frame  # host pixels (shed-passthrough + similarity)
        self.frame_dev = None  # staged device copy (stage_frame at submit)
        self.future: Future = Future()
        self.trace = trace
        self.t_enq = time.monotonic()
        self.t_dispatch: float | None = None
        self.occupancy = 0
        self.skipped = False
        # the session's own count of submitted frames: with the slot, the
        # identifier every profiler span of this frame carries
        self.seq = seq
        self.pull_wait_s: float | None = pull_wait_s
        self.coerce_s: float | None = coerce_s
        self.stage_s: float | None = None
        self.lock_wait_s: float | None = None
        # how long the track held this frame's pull, when that hold began,
        # and when its pull ended (the pick-up) — server/tracks.py stamps
        # them on the handle ``submit`` returned, which reaches the track
        # through wrappers that pass no attribute of the session
        self.hold_s: float | None = None
        self.t_hold: float | None = None
        self.t_pickup: float | None = None
        # the track fetched this frame before its next pull: the seconds it
        # then expected to wait for its source (hop ``early_return``)
        self.early_s: float | None = None
        # (batch, row) of the _DispatchedBatch this frame rode — the
        # submitter resolves it directly at fetch, bypassing the future
        self.readback: tuple | None = None

    @property
    def t_ready(self) -> float | None:
        """The earliest sight of the step this frame rode done (the ready
        watcher's wake-up, a drain check, a fetch with its row on the
        host), for the track's choice of when to fetch; None before any,
        or for a frame that rode no step."""
        if self.readback is None:
            return None
        batch = self.readback[0]
        seen = [t for t in (batch.ready, batch.ready_by) if t is not None]
        return min(seen) if seen else None


class ScheduledSession:
    """Per-session view over the shared batch scheduler (one claimed slot).

    Duck-types the pipeline surface ``VideoStreamTrack`` / the resilience
    wrapper expect — ``__call__`` / ``submit`` / ``fetch`` /
    ``update_prompt`` / ``update_t_index_list`` / ``update_guidance`` /
    ``restart`` — so the track layer is identical to single-engine
    serving."""

    # the scheduler feeds the admission step-EWMA per-batch-amortized
    # latency itself; the resilient wrapper must not double-feed the raw
    # submit->fetch duration (resilience/supervisor.py reads this flag)
    owns_step_signal = True

    def __init__(self, owner: "BatchScheduler", slot: int, session_key: str,
                 prompt: str, seed: int):
        self._owner = owner
        self.slot = slot
        self.session_key = session_key
        # live control-plane snapshot — restart() restores THESE, never
        # module defaults (the restart-defaults invariant)
        self.prompt = prompt
        self.guidance_scale = owner.guidance_scale
        self.delta = owner.delta
        self.t_index_list = list(owner.t_index_list)
        self.controlnet_scale = owner.controlnet_scale
        self.adapter: str | None = None  # set by claim/restore/update paths
        self._seed = seed
        self._released = False
        cfg = owner.cfg
        # per-SESSION similarity filter: one session's static scene must
        # never skip (or perturb) another session's frames — the reason
        # the shared engine needed a thread-local flag is gone here
        self._sim = (
            SimilarityFilter(
                cfg.similar_image_threshold, cfg.similar_image_max_skip,
                seed=0,
            )
            if cfg.similar_image_filter
            else None
        )
        self._last_pending: _PendingFrame | None = None
        self._had_output = False
        self._pull_wait_s: float | None = None  # note_pull_wait -> next submit
        self.frames_submitted = 0
        self.frames_skipped_similar = 0

    # -- pipeline duck-type ---------------------------------------------------

    @property
    def frame_buffer_size(self) -> int:
        # fbs>1: the track layer batches fbs consecutive frames per step
        # (_recv_batched), exactly like the shared-pipeline path — here
        # they land as ONE [fbs, ...] row of the session's bucket slot
        return self._owner.fbs

    def submit_batch(self, frames):
        """fbs consecutive duck-typed frames -> one in-flight handle (the
        per-frame handles; the LAST submit completes the slot's group, so
        with every live session ready the dispatch runs inline here)."""
        return [self.submit(f) for f in frames]

    def fetch_batch(self, handles, src_frames=None):
        """Resolve a submit_batch handle -> list of fbs output frames
        (each resolves its own slice of the session's row — the memoized
        per-row host copy is read fbs times, transferred once)."""
        return [
            self.fetch(h, src_frames[i] if src_frames else None)
            for i, h in enumerate(handles)
        ]

    @property
    def window_queue(self) -> DeadlineQueue:
        """This session's coalescing-window queue (registered with the
        overload plane's /metrics queue registry by the agent)."""
        return self._owner._queues[self.slot]

    def note_pull_wait(self, seconds: float):
        """How long the track waited for its source before the frame it
        submits next (server/tracks.py ``_pull_fresh``; the supervisor's
        wrappers pass the attribute through).  A counter only
        (``pull_wait``): no span is held across an ``await``."""
        self._pull_wait_s = seconds

    def submit(self, frame):
        """Coerce + enqueue one frame into the coalescing window; returns
        a handle for :meth:`fetch`.  A similarity skip never enters the
        window — the handle duplicates the most recent submit's output
        (same dup discipline as StreamEngine.submit)."""
        from .pipeline import coerce_frame

        trace = get_trace(frame)
        self.frames_submitted += 1
        slot, seq = self.slot, self.frames_submitted
        with hop("submit", trace, slot=slot, seq=seq):
            with hop("coerce", slot=slot, seq=seq) as coerce:
                arr = coerce_frame(frame, self._owner.height, self._owner.width)
            handle = self._submit_arr(arr, trace, seq, coerce.seconds)
        if trace is not None and handle.skipped:
            trace.mark("similar_skip")
        return handle

    def _submit_arr(self, arr: np.ndarray, trace, seq: int,
                    coerce_s: float) -> _PendingFrame:
        pull_wait_s, self._pull_wait_s = self._pull_wait_s, None
        if (
            self._sim is not None
            and self._sim.should_skip(
                arr,
                have_output=self._had_output
                and self._last_pending is not None,
            )
        ):
            # skip the window entirely: the handle resolves with whatever
            # the most recent submit resolves with, so resolution order
            # stays correct even while that step is still in flight
            self.frames_skipped_similar += 1
            p = _PendingFrame(arr, trace, seq, pull_wait_s, coerce_s)
            p.skipped = True
            last = self._last_pending

            def _copy(f, p=p, last=last):
                if f.cancelled():
                    p.future.cancel()
                    return
                exc = f.exception()
                if exc is not None:
                    p.future.set_exception(exc)
                    return
                p.t_dispatch = last.t_dispatch
                p.occupancy = last.occupancy
                p.future.set_result(f.result())

            last.future.add_done_callback(_copy)
            return p
        p = _PendingFrame(arr, trace, seq, pull_wait_s, coerce_s)
        # stage the H2D copy NOW, on the caller's thread, before any
        # scheduler lock: concurrent sessions' transfers overlap each
        # other and in-flight compute instead of serializing behind the
        # dispatch (the engine-submit staging rule, shared helper).
        # Staged ROW-SHAPED ([1,H,W,3] — the [None] is a free host view):
        # a solo dispatch uses the buffer as-is and a batch is one
        # device-side concatenate, so the hot path never pays a per-frame
        # reshape op (per-op dispatch is real money at small step sizes).
        # On a dp mesh the copy lands on the SLOT'S OWN SHARD — never
        # device 0 followed by a cross-device reshuffle at dispatch
        with hop("stage_h2d", slot=self.slot, seq=seq) as stage:
            p.frame_dev = stage_frame(
                arr[None], device=self._owner._slot_device(self.slot)
            )
        p.stage_s = stage.seconds
        self._owner._enqueue(self.slot, p)
        if self._sim is not None:
            # dup-chain anchor — only the similarity filter ever reads it
            self._last_pending = p
        return p

    def fetch(self, handle: _PendingFrame, src_frame=None):
        """Resolve a submit handle to the session's output frame.
        ShedFrame markers (window shed under pressure) pass through raw so
        the resilience wrapper accounts them as passthrough."""
        with hop("fetch", slot=self.slot, seq=handle.seq):
            return self._fetch(handle, src_frame)

    def _await_row(self, handle: _PendingFrame, t0: float):
        """Block until this frame's row is on the host -> (out, t1, fi);
        a slot released with the frame still queued raises CancelledError."""
        if handle.readback is not None:
            # fast path: resolve THIS session's row right here (the
            # dedicated-engine flow — submit dispatched, fetch blocks on
            # its own per-slot readback, zero thread handoffs)
            batch, row, fi = handle.readback
            return (*self._owner._resolve_row(batch, row, t0), fi)
        out = handle.future.result(timeout=self._owner.fetch_timeout)
        if (
            isinstance(out, tuple)
            and len(out) == 3
            and isinstance(out[0], _DispatchedBatch)
        ):
            # this frame was waiting in the window when a dispatch
            # (inline or dispatcher) claimed it — the marker routes us
            # to our own per-slot row of that batch
            return (*self._owner._resolve_row(out[0], out[1], t0), out[2])
        return out, time.monotonic(), None

    def _fetch(self, handle: _PendingFrame, src_frame):
        trace = handle.trace
        if trace is None and src_frame is not None:
            trace = get_trace(src_frame)
        owner = self._owner
        with hop("await_row", slot=self.slot, seq=handle.seq) as wait:
            t0 = wait.t0
            try:
                out, t1, fi = self._await_row(handle, t0)
            except CancelledError:
                # teardown race: the slot was released with this frame
                # queued — deliver passthrough, never crash the (dying)
                # track
                return ShedFrame(handle.frame)
        if fi is not None and not isinstance(out, ShedFrame):
            # fbs>1: the memoized row is the session's [fbs, H, W, 3]
            # group — this handle owns exactly one consecutive frame of it
            out = out[fi]
        if isinstance(out, ShedFrame):
            owner._fold_frame(handle, wait.seconds, None)
            return out
        self._had_output = True
        if trace is not None:
            td = handle.t_dispatch
            if td is not None and not handle.skipped:
                # batch_join: the coalescing-window wait this frame paid to
                # ride a wider batch; engine_step: the batch's device
                # residency (dispatch -> resolve), stamped OUTSIDE jit.
                # A similarity-skipped dup rode NO batch — its inherited
                # t_dispatch predates its own enqueue, so stamping these
                # spans would render negative durations (similar_skip is
                # its marker instead).
                trace.add_span("batch_join", handle.t_enq, td)
                trace.add_span("engine_step", td, t1)
                if handle.occupancy:
                    trace.mark(f"batch_k{handle.occupancy}")
            trace.add_span("fetch", t0, t1)
        from .pipeline import finish_output

        with hop("finish_output", slot=self.slot, seq=handle.seq) as finish:
            result = finish_output(
                out, src_frame,
                safety_checker=owner.safety_checker, trace=trace,
            )
        owner._fold_frame(handle, wait.seconds, finish.seconds, finish.t1)
        return result

    def __call__(self, frame):
        return self.fetch(self.submit(frame), frame)

    # -- per-session control plane (no recompiles) ----------------------------

    def update_prompt(self, prompt: str):
        encoded = self._owner._encode(prompt)  # heavy — outside the step lock
        self._owner._apply_prompt(self.slot, encoded)
        self.prompt = prompt

    def update_t_index_list(self, t_index_list):
        self._owner._apply_t_index(self.slot, t_index_list)
        self.t_index_list = list(int(t) for t in t_index_list)

    def update_guidance(self, guidance_scale=None, delta=None):
        g = None if guidance_scale is None else float(guidance_scale)
        d = None if delta is None else float(delta)
        self._owner._apply_guidance(self.slot, g, d)
        if g is not None:
            self.guidance_scale = g
        if d is not None:
            self.delta = d

    @property
    def has_controlnet(self) -> bool:
        return self._owner.has_controlnet

    def update_controlnet_scale(self, scale: float):
        """THIS session's conditioning strength: one float32 written into
        its state row, read by the next step it rides (never a retrace)."""
        scale = float(scale)
        self._owner._apply_controlnet_scale(self.slot, scale)
        self.controlnet_scale = scale

    def update_adapter(self, name: str | None):
        """Hot-swap THIS slot's style-adapter factor rows (``None`` clears
        back to the zero bank).  A same-shaped ``.at[slot].set`` write on
        the stacked bank — validated against the registry BEFORE any
        state is touched, never a retrace."""
        self._owner._apply_adapter(self.slot, name)
        self.adapter = name

    def restart(self):
        """Supervisor recovery hook: a fresh stream state for THIS slot
        (clearing poisoned latents) on the same compiled bucket
        executables — the live prompt/guidance/t-indices are restored, not
        module defaults."""
        g = self._owner._guard
        if g is not None and g.quarantined:
            # engine-level fault, not a per-slot one: the guard's rebuild
            # restores this slot from its banked row (bit-exact — better
            # than the fresh state built here), and installing into the
            # poisoned stack would only crash the supervisor's recovery
            # thread.  Report success so the session keeps serving
            # passthrough instead of escalating to FAILED.
            return
        state = self._owner._build_state(
            self.prompt, self.guidance_scale, self.delta, self._seed,
            t_index_list=self.t_index_list, adapter=self.adapter,
            controlnet_scale=self.controlnet_scale,
        )
        self._owner._install(self.slot, state)

    def release(self):
        if not self._released:
            self._released = True
            self._owner.release(self.slot)

    def snapshot(self) -> dict:
        q = self.window_queue
        out = {
            "slot": self.slot,
            "frames_submitted": self.frames_submitted,
            "frames_skipped_similar": self.frames_skipped_similar,
            "window_depth": q.depth,
            "window_shed": q.shed_overflow + q.shed_stale,
        }
        owner = self._owner
        if owner.dp > 1:
            # which mesh shard this session's state row lives on (/health)
            out["shard"] = owner._slot_shard(self.slot)
        if owner._adapter_rank:
            # per-session style (/health): which adapter rides this slot's
            # factor rows and the bank's padded rank
            out["adapter"] = self.adapter
            out["adapter_rank"] = owner._adapter_rank
        if owner.has_controlnet:
            out["controlnet_scale"] = self.controlnet_scale
        return out


class BatchScheduler:
    """Owns the stacked per-session states, the bucket executables and the
    coalescing dispatcher; sessions are claimed per connection
    (:meth:`claim` -> :class:`ScheduledSession`)."""

    def __init__(
        self,
        models,
        params,
        cfg,
        encode_prompt,
        *,
        model_id: str = "",
        max_sessions: int | None = None,
        window_ms: float | None = None,
        queue_bound: int | None = None,
        fetch_timeout: float = 120.0,
        default_prompt: str = "",
        guidance_scale: float | None = None,
        delta: float | None = None,
        controlnet_scale: float | None = None,
        schedule=None,
        safety_checker=None,
        prewarm: bool | None = None,
        aot_build_on_miss: bool | None = None,
        cache_dir: str | None = None,
        mesh=None,
        dp: int | None = None,
        adapters=None,
    ):
        from .pipeline import (
            DEFAULT_CONTROLNET_SCALE,
            DEFAULT_DELTA,
            DEFAULT_GUIDANCE_SCALE,
            DEFAULT_PROMPT,
        )

        self.fbs = int(cfg.frame_buffer_size)
        if self.fbs > 1 and cfg.similar_image_filter:
            raise ValueError(
                "the scheduler's consecutive-frame batching (fbs>1) is "
                "incompatible with the similarity filter: a skipped frame "
                "would desync the fbs group boundaries"
            )
        self.cfg = cfg
        self.model_id = model_id
        self.height, self.width = cfg.height, cfg.width
        self.max_sessions = (
            env.get_int("BATCHSCHED_MAX_SESSIONS", 8)
            if max_sessions is None
            else int(max_sessions)
        )
        if self.max_sessions < 1:
            raise ValueError("max_sessions must be >= 1")
        self.window_s = (
            env.get_float("BATCHSCHED_WINDOW_MS", 3.0)
            if window_ms is None
            else float(window_ms)
        ) / 1e3
        self.queue_bound = (
            env.get_int("BATCHSCHED_QUEUE_BOUND", 2 * self.fbs)
            if queue_bound is None
            else int(queue_bound)
        )
        if self.queue_bound < self.fbs:
            raise ValueError(
                f"queue_bound ({self.queue_bound}) must hold at least one "
                f"fbs group ({self.fbs}) or no frame could ever dispatch"
            )
        # -- session-axis mesh (dp sharding) --------------------------------
        # the dp axis shards the stacked [S, ...] pytree's leading axis;
        # a trivial mesh (dp<=1) keeps the single-device scheduler exactly
        if mesh is None:
            dp = env.batchsched_dp() if dp is None else max(1, int(dp))
            if dp > 1:
                from ..parallel.mesh import make_mesh

                mesh = make_mesh(dp=dp)
        self.mesh = mesh
        self.dp = mesh.shape.get("dp", 1) if mesh is not None else 1
        if self.dp > 1:
            from ..parallel import sharding as SH

            if self.max_sessions % self.dp != 0:
                raise ValueError(
                    f"max_sessions ({self.max_sessions}) must be a "
                    f"multiple of the dp axis ({self.dp}) so the session "
                    "axis shards evenly"
                )
            # params replicated (single sharding broadcast over the pytree
            # — pjit prefix semantics), states/frames/outputs on P('dp')
            self._repl_sh, self._row_sh = SH.session_shardings(mesh)
            self._dp_devs = SH.dp_devices(mesh)
        else:
            self._repl_sh = self._row_sh = None
            self._dp_devs = None
        self.fetch_timeout = fetch_timeout
        self.safety_checker = safety_checker
        # scheduler-level defaults for new sessions; the global /config
        # surface (update_prompt & co below) moves these so operator
        # config keeps its pre-scheduler semantics of outliving sessions
        self.prompt = default_prompt or DEFAULT_PROMPT
        self.guidance_scale = (
            DEFAULT_GUIDANCE_SCALE if guidance_scale is None else guidance_scale
        )
        self.delta = DEFAULT_DELTA if delta is None else delta
        # the side network's strength (cfg.use_controlnet): a float32 in
        # every session's row (``cnet_scale``), beside the conditioning
        # ring (``cnet_cond``) that make_step_fn rotates with the latents
        self.controlnet_scale = (
            DEFAULT_CONTROLNET_SCALE if controlnet_scale is None
            else float(controlnet_scale)
        )
        self.t_index_list = list(cfg.t_index_list)
        # -- per-session style adapters (adapters/, ISSUE 20) ----------------
        # the registry's bank shape is BOUND here, once: rank = the largest
        # blessed bucket in use, targets = the union module set.  Every
        # later swap must fit this shape (same-shaped .at[slot].set — never
        # a retrace); an EMPTY/absent registry keeps the factors path off
        # and the stacked state / AOT keys identical to an adapterless
        # build.
        self.adapters = adapters
        self._adapter_rank = int(adapters.bank_rank) if adapters is not None else 0
        self._adapter_targets = dict(adapters.targets) if self._adapter_rank else {}
        self._adapter_dtype = cfg.jdtype
        if self._adapter_rank:
            from ..adapters import zero_factor_rows

            self._zero_rows = zero_factor_rows(
                self._adapter_targets, self._adapter_rank, self._adapter_dtype
            )
        else:
            self._zero_rows = None
        self.default_adapter: str | None = None  # global /config default
        self.adapter_swaps_total = 0
        # amortized admission feed: callable(dt_s, occupancy) — the agent
        # wires this to the overload plane's step EWMA as dt/occupancy
        self.on_step = None
        self.params = params
        self._encode_prompt = encode_prompt
        self._template = StreamEngine(
            models, params, cfg, self._encode_towers,
            schedule=schedule, jit_compile=False,
        )
        # DeepCache (UNET_CACHE) rides the scheduler as a GLOBAL cadence
        # over TWO vmapped graphs per bucket size: every slot captures on
        # the same tick, installs and control-plane writes reset the
        # cadence so a zeroed/stale deep cache is never consumed (sessions
        # follow a dedicated engine stepping the same cadence)
        self._cache_interval = (
            cfg.unet_cache_interval if cfg.unet_cache_interval >= 2 else 0
        )
        self._tick = 0
        # slots whose unet_cache row must NOT be consumed (zeroed by
        # install/recovery, or stale after a prompt/t-index write).  The
        # global tick reset alone is NOT enough: a bucket step only
        # touches its RIDERS' rows, so a freshly joined slot that sits
        # out the post-install capture batch would later ride a cached
        # batch with an all-zeros deep-feature row (code-review r1) —
        # any batch carrying an uncaptured rider is FORCED to capture
        self._uncaptured: set = set()
        self._variants = (
            ("capture", "cached") if self._cache_interval else ("full",)
        )
        self._vsteps = {
            v: jax.vmap(
                make_step_fn(models, cfg, unet_variant=v), in_axes=(None, 0, 0)
            )
            for v in self._variants
        }
        S = self.max_sessions
        # bucket geometries start at dp and grow by doubling: every bucket
        # is a dp multiple so the [k, ...] batch shards evenly — padding
        # rows of a below-minimum occupancy land on otherwise-IDLE shards,
        # so a solo session on a dp=8 mesh pays a k=8-shaped step whose
        # extra rows compute in parallel elsewhere (latency-neutral)
        sizes, b = [], self.dp
        while b < S:
            sizes.append(b)
            b *= 2
        sizes.append(S)
        self._bucket_sizes = sizes
        self._bucket_steps: dict = {}
        # bucket label -> {kernel name: Mosaic custom calls in its compiled
        # HLO}; filled by prewarm_buckets (an AOT-adopted or lazily
        # compiled bucket has no compiled object to read, and no entry)
        self.mosaic_kernels: dict = {}
        # bucket label -> {"packed" | "per_head": flash_attention calls
        # traced into it with that operand layout} (ops/pallas/attention.py)
        self.attention_paths: dict = {}
        # bucket label -> {"count", "bytes"} of the activation-sized float32
        # re-layouts left in it (ops/pallas f32_relayout_copies)
        self.relayout_copies: dict = {}
        # {"encode" | "decode": pixels a 128-lane row holds} in the VAE's
        # passes at the served frame (models/taesd.py), where the models say
        self.taesd_fold = (
            models.vae_fold(self.width) if getattr(models, "vae_fold", None) else {}
        )
        # {"kind", "latent_channels"[, "joint_tokens"]}: the denoiser the
        # steps run at the served frame, where the models say
        self.denoiser = (
            models.denoiser(self.height, self.width)
            if getattr(models, "denoiser", None) else {}
        )
        # bucket label -> the epilogue its compiled step holds: the fused
        # Pallas kernel where its HLO calls it, else XLA ops (also where the
        # configuration asks for the kernel and the kernel cannot compute it)
        self.epilogues: dict = {}
        self.active = [False] * S
        self._sessions: dict = {}  # slot -> ScheduledSession
        self._queues = [
            DeadlineQueue(self.queue_bound, on_evict=self._evict)
            for _ in range(S)
        ]
        # per slot, its session's newest enqueue stamps (the dispatch
        # lock; cleared at claim and release): _none_due_locked predicts a
        # missing session's next frame from them
        self._enq_stamps = [deque(maxlen=_ENQ_STAMPS) for _ in range(S)]
        # guards the template engine during heavy builds (text-encode +
        # prepare); deliberately separate from the step/states lock
        self._heavy_lock = threading.Lock()
        self._lock = threading.Lock()
        self._has_work = threading.Condition(self._lock)
        self._stop = False
        # in-flight throttle: bounded ring of _DispatchedBatch refs (every
        # dispatch path registers here); resolved flags flip at the first
        # per-row fetch, abandoned batches age out so a caller that stops
        # fetching degrades to the bounded queue path instead of wedging
        # dispatch forever.  _throttled: the dispatcher is parked on the
        # in-flight cap — the ONLY case a resolver must pay a lock to
        # notify (a plain-attribute read keeps the hot fetch path off the
        # dispatch lock)
        self._batches: deque = deque(maxlen=16)
        self._throttled = False
        # _windowing: the dispatcher is waiting out a coalescing window; the
        # ready watcher wakes it when a step leaves the device
        self._windowing = False
        # launched steps in dispatch order (put under the dispatch lock),
        # None to stop: what the ready watcher blocks on one at a time.  A
        # thread control queue, not the frame path: it holds the steps the
        # device has not finished, which the in-flight cap bounds
        self._ready_q: queue.SimpleQueue = queue.SimpleQueue()
        # the steps the watcher has stamped and the counters have not yet
        # booked (_book_ready_locked, at the next fetched frame or
        # snapshot): the watcher itself takes no lock.  It holds the steps
        # stamped since the last fetch, a few at most; past the bound (no
        # fetch and no snapshot for 256 steps) the oldest stamps go
        # unbooked, never a frame
        self._stamped: deque = deque(maxlen=256)
        self._stats_lock = threading.Lock()
        # (bucket size, variant) pairs that have completed at least one
        # dispatch (or were prewarmed/AOT-adopted): a bucket's FIRST use
        # may carry a lazy jit compile, and compile-sized latency must
        # never feed the admission EWMA (the ResilientPipeline warm-step
        # rule — every cold occupancy transition would otherwise 503
        # concurrent offers)
        self._warmed_buckets: set = set()
        # pad-tuple -> device index array: materializing a jnp.int32 array
        # from a python list costs ~0.4 ms per dispatch on CPU — a real
        # tax at small step sizes, and the pads repeat heavily (stable
        # active sets).  Bounded: cleared wholesale if it ever grows past
        # 512 entries (possible only under pathological churn).
        self._idx_cache: dict = {}
        # observability reservoir (bounded; appended by the dispatcher
        # only, percentiles computed per snapshot over <=512 floats)
        self._waits: deque = deque(maxlen=512)
        # the counters' generation: a step launched before a reset is
        # booked nothing, whenever its stamp lands
        self._epoch = 0
        self._reset_counters_locked()
        # ONE template prepare, tiled: inactive rows are placeholders —
        # claim() installs a freshly prepared state before any frame runs
        # (after the counters exist: the prepare encodes the default prompt)
        self._template.prepare(
            self.prompt, guidance_scale=self.guidance_scale,
            delta=self.delta, seed=0, controlnet_scale=self.controlnet_scale,
        )
        tmpl_state = self._template.state
        if self._adapter_rank:
            # the factor bank stacks WITH the latents: every slot is born
            # on the zero rows (a bitwise no-op through layers.linear), so
            # the bank changes shapes exactly once — at bind — and every
            # adapter install afterwards is a control-plane write
            tmpl_state = dict(tmpl_state)
            tmpl_state["adapters"] = self._zero_rows
        self.states = jax.tree.map(
            lambda x: jnp.stack([x] * S), tmpl_state
        )
        if self.dp > 1:
            # materialize the session-axis shards NOW: every later install
            # (.at[slot].set of an uncommitted fresh row) preserves the
            # sharding, so donation round-trips without resharding copies
            self.states = jax.device_put(self.states, self._row_sh)
        # bucket steps launched by this process, rehearsal included, never
        # reset: the n-th ``rtc:launch`` span is the n-th ``jit_bucket``
        # event of the chip's in-order program stream (dispatch lock held)
        self._dispatch_seq = 0
        # the newest step whose jitted call returned (dispatch lock), and
        # the number of the newest step the ready watcher saw leave the
        # device (_device_drained_locked)
        self._newest: _DispatchedBatch | None = None
        self._ready_seq = 0
        self._aot_adopted = False
        # -- engine fault domain (resilience/engine_guard.py) ---------------
        # duck-typed attach (attach_guard) — no construction-order coupling
        # with the agent.  The guard routes _step_batch_locked's one device
        # call through its deadline worker; while it is quarantined the
        # scheduler sheds instead of dispatching and claim() refuses.
        self._guard = None
        self._fault_scope = _faults.scope("engine")
        # snapshot bank: per-slot DEVICE-side state rows refreshed on a
        # cadence after successful dispatches.  The bucket steps DONATE the
        # stacked states (donate_argnums=(1,)), so at trip time
        # self.states is already unreadable — bit-exact restore is only
        # possible from rows banked BEFORE the fault (each x[slot] slice is
        # a fresh buffer the donation cannot invalidate, the
        # snapshot_session rule).  <=0 cadence banks after EVERY dispatch
        # (the chaos-test setting).
        self._snap_every_s = env.get_float("ENGINE_SNAPSHOT_EVERY_S", 5.0)
        self._snap_rows: dict = {}  # slot -> device-side state row pytree
        self._last_snap_t = 0.0
        # session_key -> full snapshot dict, frozen by the guard at
        # quarantine entry; snapshot_session serves these while the live
        # stack is poisoned (the /migrate/export evacuation path)
        self._quarantine_snaps: dict = {}
        # warm the bucket geometries so join/leave never retraces at serve
        # time: adopt serialized engines when the cache has them (build
        # them with AOT_ENGINES=1 / the build CLI), then optionally
        # eager-compile whatever is still cold
        if model_id and self.use_aot_cache(
            model_id,
            cache_dir=cache_dir,
            build_on_miss=(
                env.get_bool("AOT_ENGINES", False)
                if aot_build_on_miss is None
                else aot_build_on_miss
            ),
        ):
            logger.info(
                "batch scheduler serving from AOT engine cache "
                "(buckets %s)", self._bucket_sizes,
            )
        if prewarm is None:
            prewarm = env.get_bool("BATCHSCHED_PREWARM", True)
        # remembered so rebuild_engine() re-warms the way the boot did
        self._prewarm = bool(prewarm)
        if prewarm and not self._aot_adopted:
            self.prewarm_buckets()
        self._thread = threading.Thread(
            target=self._run, name="batchsched-dispatch", daemon=True
        )
        self._thread.start()
        self._ready_thread = threading.Thread(
            target=self._watch_ready, name="batchsched-ready", daemon=True
        )
        self._ready_thread.start()

    @classmethod
    def from_pipeline(cls, pipeline, **kw) -> "BatchScheduler":
        """Build a scheduler that serves the same model/config as an
        already-built :class:`StreamDiffusionPipeline` — the bundle
        (weights, encode_prompt) and the config are reused, so the
        scheduler compiles the graph variant the pipeline's warm-up step
        already ran."""
        eng = pipeline.engine
        if eng.mesh is not None and any(
            n > 1 for n in eng.mesh.shape.values()
        ):
            raise ValueError(
                "the batch scheduler owns its own session-axis (dp) mesh; "
                "an engine built on a tp/sp mesh keeps the shared-engine "
                "path (those axes shard the MODEL, not the sessions)"
            )
        return cls(
            eng.models,
            eng.params,
            pipeline.config,
            eng.encode_prompt,
            model_id=pipeline.model_id,
            default_prompt=pipeline.prompt,
            guidance_scale=pipeline.guidance_scale,
            delta=pipeline.delta,
            controlnet_scale=getattr(pipeline, "controlnet_scale", None),
            schedule=eng.schedule,
            safety_checker=pipeline.safety_checker,
            **kw,
        )

    # -- session lifecycle ----------------------------------------------------

    # lock-FREE gauge reads (GIL-atomic list scans, the DeadlineQueue
    # counter discipline): /capacity and /health read these on the event
    # loop, which must never queue behind a dispatch — or, with
    # BATCHSCHED_PREWARM=0, behind a lazy bucket compile — holding _lock
    @property
    def free_slots(self) -> int:
        return self.active.count(False)

    @property
    def live_sessions(self) -> int:
        return self.active.count(True)

    def claim(
        self,
        session_key: str | None = None,
        prompt: str | None = None,
        seed: int | None = None,
        adapter: str | None = None,
    ) -> ScheduledSession:
        """Claim a slot for a new connection; raises CapacityError when
        full (the agent maps it to 503 + Retry-After).  The heavy state
        build (text-encode + prepare) runs OUTSIDE the step lock so live
        sessions keep batching while someone joins.  ``adapter`` picks the
        session's style-adapter factor rows (default: the scheduler-level
        default the global update_adapter sets; validated against the
        registry before any state is touched)."""
        g = self._guard
        if g is not None and g.quarantined:
            # no dispatch plane to serve the new session — same 503 +
            # Retry-After surface as a full pool (docs/resilience.md)
            raise CapacityError("engine quarantined — rebuild in progress")
        adapter = self.default_adapter if adapter is None else adapter
        # validate BEFORE claiming a slot (an unknown name must not churn
        # the slot pool or pay the heavy prepare)
        self._adapter_rows(adapter)
        with self._lock:
            slot = self._pick_slot_locked()
            self.active[slot] = True
        prompt = self.prompt if prompt is None else prompt
        seed = slot if seed is None else seed
        try:
            state = self._build_state(
                prompt, self.guidance_scale, self.delta, seed,
                t_index_list=self.t_index_list, adapter=adapter,
                controlnet_scale=self.controlnet_scale,
            )
        except Exception:
            with self._lock:
                self.active[slot] = False
            raise
        sess = ScheduledSession(
            self, slot, session_key or f"slot-{slot}", prompt, seed
        )
        sess.adapter = adapter
        try:
            with self._has_work:
                self._install_locked(slot, state)
                self._sessions[slot] = sess
                self._enq_stamps[slot].clear()
        except Exception:
            # a failed install (e.g. states poisoned by a concurrent step
            # failure) must not leak the slot into permanent 503s
            with self._lock:
                self.active[slot] = False
                self._sessions.pop(slot, None)
            raise
        logger.info("batchsched session claimed -> slot %d", slot)
        return sess

    def _pick_slot_locked(self) -> int:
        """The next slot a new session lands on (caller holds the lock;
        raises CapacityError when full)."""
        try:
            if self.dp > 1:
                # shard-balanced placement: claim a free slot on the
                # LEAST-LOADED shard (ties -> lowest slot), so partial
                # occupancy spreads rows across chips — each session's
                # bucket row then computes on its OWN shard (no
                # per-dispatch cross-device hops) and the idle-shard
                # parallelism the dp-multiple buckets promise is real
                loads = [0] * self.dp
                for s, live in enumerate(self.active):
                    if live:
                        loads[self._slot_shard(s)] += 1
                return min(
                    (s for s, live in enumerate(self.active) if not live),
                    key=lambda s: (loads[self._slot_shard(s)], s),
                )
            return self.active.index(False)
        except ValueError:
            raise CapacityError(
                f"all {self.max_sessions} scheduler session slots in use"
            ) from None

    def release(self, slot: int):
        if not (0 <= slot < self.max_sessions):
            raise ValueError(
                f"slot {slot} out of range [0, {self.max_sessions})"
            )
        with self._lock:
            self.active[slot] = False
            self._sessions.pop(slot, None)
            self._enq_stamps[slot].clear()
        # drain this slot's window outside the step lock; waiters (there
        # should be none on an orderly teardown) unblock as cancelled
        q = self._queues[slot]
        while True:
            got = q.pop()
            if got is None:
                break
            got[0].future.cancel()
        logger.info("batchsched session released <- slot %d", slot)

    # -- live session migration (snapshot/restore — ISSUE 15) ------------------

    def session(self, session_key: str) -> "ScheduledSession | None":
        """The live session claimed under ``session_key`` (lock-free
        scan, the /health read discipline), or None."""
        for sess in safe_list(self._sessions.values()):
            if sess.session_key == session_key:
                return sess
        return None

    def snapshot_fingerprint(self) -> dict:
        """What must MATCH for a snapshot to restore here: the model, the
        frame geometry, the batching shape and the params variant — the
        things the compiled bucket steps bake in.  A mismatch is a
        refused restore, never a reshape."""
        qextra = params_variant_extra(self.params)
        fp = {
            "model_id": self.model_id,
            "height": self.height,
            "width": self.width,
            "fbs": self.fbs,
            "n_stages": int(self.cfg.n_stages),
            "dtype": np.dtype(self.cfg.jdtype).name,
            "unet_cache": int(self._cache_interval),
            "similar_filter": bool(self.cfg.similar_image_filter),
            "quant": str(qextra.get("quant", "")),
        }
        if self._adapter_rank:
            # the factor bank is part of the compiled row shape: rows only
            # land on a scheduler whose bank has the same padded rank and
            # target-module set (names stay out — the factors travel in
            # the row itself).  Adapterless schedulers omit the keys, so
            # their snapshots keep restoring against each other.
            from ..adapters.registry import targets_digest

            fp["adapter_rank"] = self._adapter_rank
            fp["adapter_targets"] = targets_digest(self._adapter_targets)
        if self.has_controlnet:
            # the row carries a conditioning ring of this annotator's maps
            # (the side network's identity rides model_id); a scheduler
            # without one omits the key, as the adapter keys are omitted
            fp["cnet"] = self.cfg.annotator
        return fp

    def snapshot_session(self, session_key: str) -> dict:
        """Serialize one live session for migration: its state row of the
        stacked pytree (bit-exact, parallel/checkpoint.serialize_pytree)
        plus the full control plane restart() already reconstructs —
        prompt, guidance/delta, t-index list, similarity-filter state,
        DeepCache tick alignment — under the versioned schema
        restore_session() enforces.  The row is read under the step lock
        (never mid-dispatch); in-flight window frames stay behind and are
        delivered by THIS agent, which keeps serving until the client
        actually moves."""
        g = self._guard
        if g is not None and g.quarantined:
            # the live stack is poisoned (donated buffers / lost device):
            # serve the snapshot the guard froze at quarantine entry — the
            # bank the evacuation's /migrate/export reads
            snap = self._quarantine_snaps.get(session_key)
            if snap is not None:
                return dict(snap)
            raise KeyError(
                f"no banked snapshot for quarantined session {session_key!r}"
            )
        sess = self.session(session_key)
        if sess is None:
            raise KeyError(f"no live scheduler session {session_key!r}")
        with self._lock:
            if self._sessions.get(sess.slot) is not sess:
                # the session released (and its slot may already be
                # REUSED) between the lock-free lookup and this lock:
                # exporting would pair THIS session's control plane with
                # another session's state row — a cross-session leak
                raise KeyError(
                    f"session {session_key!r} released mid-export"
                )
            # DEVICE-side row slices under the lock (cheap ops — each
            # x[slot] is a fresh buffer, so the later donation of the
            # stacked states cannot invalidate them); the blocking D2H
            # pull happens OUTSIDE the lock so one export never stalls
            # the other live sessions' dispatches
            row_dev = jax.tree.map(
                lambda x, slot=sess.slot: x[slot], self.states
            )
            cache_tick = self._tick
            cache_uncaptured = sess.slot in self._uncaptured
        row = jax.tree.map(np.asarray, row_dev)
        return self._row_snapshot(sess, row, cache_tick, cache_uncaptured)

    def _row_snapshot(self, sess, row, cache_tick, cache_uncaptured) -> dict:
        """One session's full snapshot dict from an already-host state row
        (shared by the live export path above and the guard's quarantine
        bank capture)."""
        import base64

        from ..parallel.checkpoint import serialize_pytree

        snap = {
            "schema": SESSION_SNAPSHOT_SCHEMA,
            "kind": "scheduler",
            "fingerprint": self.snapshot_fingerprint(),
            "session": sess.session_key,
            "prompt": sess.prompt,
            "guidance_scale": float(sess.guidance_scale),
            "delta": float(sess.delta),
            "t_index_list": [int(t) for t in sess.t_index_list],
            "seed": int(sess._seed),
            "had_output": bool(sess._had_output),
            "frames_submitted": int(sess.frames_submitted),
            "frames_skipped_similar": int(sess.frames_skipped_similar),
            # DeepCache alignment: the restore marks the slot uncaptured
            # (forced capture on its first ride — the install discipline),
            # so these ride along for observability, not for replay
            "cache_tick": int(cache_tick),
            "cache_uncaptured": bool(cache_uncaptured),
            # which adapter rides this row's factor bank (observability +
            # post-restore hot-swap bookkeeping; the factors themselves
            # travel bit-exact inside state_b64)
            "adapter": sess.adapter,
            "state_b64": base64.b64encode(serialize_pytree(row)).decode(
                "ascii"
            ),
        }
        if sess._sim is not None:
            snap["similarity"] = sess._sim.export_state()
        if self.has_controlnet:
            # the conditioning scale and ring travel bit-exact in the row;
            # this is the session object's copy, which restart() restores
            snap["controlnet_scale"] = float(sess.controlnet_scale)
        return snap

    def _check_row(self, row):
        """Refuse a restored row whose structure/shape/dtype differs from
        the stacked template — the compiled bucket steps would
        misinterpret it (or XLA would crash mid-serve, which is worse)."""
        flat_row, td_row = jax.tree.flatten(row)
        flat_tmpl, td_tmpl = jax.tree.flatten(self.states)
        if td_row != td_tmpl:
            raise SnapshotMismatch(
                "state-row structure differs from this scheduler's "
                f"stacked pytree ({td_row} vs {td_tmpl})"
            )
        for got, want in zip(flat_row, flat_tmpl):
            wshape, wdtype = tuple(want.shape[1:]), np.dtype(want.dtype)
            if tuple(np.shape(got)) != wshape or np.dtype(
                np.asarray(got).dtype
            ) != wdtype:
                raise SnapshotMismatch(
                    f"state-row leaf {np.shape(got)}/{np.asarray(got).dtype}"
                    f" does not match the compiled {wshape}/{wdtype}"
                )

    def restore_session(
        self, snapshot: dict, session_key: str | None = None
    ) -> ScheduledSession:
        """Install a migrated session: claim a slot and set its state row
        to the snapshot's BYTES (no prepare, no re-prime — the stream
        resumes exactly where the source froze it).  REFUSES mismatched
        schema/fingerprint/row shapes (SnapshotMismatch) and full slot
        pools (CapacityError) BEFORE touching any state, so a refused
        restore leaves this scheduler — and the source, which still holds
        the live session — completely untouched."""
        import base64
        import binascii

        from ..parallel.checkpoint import deserialize_pytree

        g = self._guard
        if g is not None and g.quarantined:
            raise CapacityError("engine quarantined — rebuild in progress")
        if not isinstance(snapshot, dict):
            raise SnapshotMismatch("session snapshot must be an object")
        schema = snapshot.get("schema")
        if schema != SESSION_SNAPSHOT_SCHEMA:
            raise SnapshotMismatch(
                f"session-snapshot schema {schema!r} unsupported (this "
                f"build speaks {SESSION_SNAPSHOT_SCHEMA})"
            )
        fp, want = snapshot.get("fingerprint"), self.snapshot_fingerprint()
        if fp != want:
            diffs = sorted(
                k for k in set(want) | set(fp or {})
                if (fp or {}).get(k) != want.get(k)
            )
            raise SnapshotMismatch(
                f"snapshot fingerprint mismatch on {diffs} "
                f"(snapshot {fp!r}, this scheduler {want!r})"
            )
        from .engine import _coeff_state

        try:
            row = deserialize_pytree(
                base64.b64decode(snapshot["state_b64"], validate=True)
            )
            prompt = str(snapshot["prompt"])
            guidance = float(snapshot["guidance_scale"])
            delta = float(snapshot["delta"])
            t_index_list = [int(t) for t in snapshot["t_index_list"]]
            seed = int(snapshot.get("seed", 0))
            cnet_scale = float(
                snapshot.get("controlnet_scale", self.controlnet_scale)
            )
            if len(t_index_list) != self.cfg.n_stages:
                raise ValueError(
                    f"t_index_list length {len(t_index_list)} != compiled "
                    f"n_stages {self.cfg.n_stages}"
                )
            # value validation NOW (the update_t_index_list contract): a
            # bad list must refuse the restore, not detonate the first
            # supervisor restart()'s _build_state
            _coeff_state(self.cfg, self._template.schedule,
                         tuple(t_index_list))
        except (KeyError, IndexError, TypeError, ValueError,
                binascii.Error) as e:
            raise SnapshotMismatch(f"session snapshot unusable: {e}") from e
        self._check_row(row)
        with self._lock:
            slot = self._pick_slot_locked()
            self.active[slot] = True
        sess = ScheduledSession(
            self, slot, session_key or snapshot.get("session")
            or f"slot-{slot}", prompt, seed,
        )
        sess.guidance_scale = guidance
        sess.delta = delta
        sess.t_index_list = t_index_list
        sess.controlnet_scale = cnet_scale
        adapter = snapshot.get("adapter")
        sess.adapter = str(adapter) if adapter is not None else None
        sess._had_output = bool(snapshot.get("had_output", False))
        sess.frames_submitted = int(snapshot.get("frames_submitted", 0))
        sess.frames_skipped_similar = int(
            snapshot.get("frames_skipped_similar", 0)
        )
        sim_state = snapshot.get("similarity")
        if sess._sim is not None and sim_state is not None:
            try:
                sess._sim.restore_state(sim_state)
            except ValueError as e:
                with self._lock:
                    self.active[slot] = False
                raise SnapshotMismatch(str(e)) from e
        try:
            with self._has_work:
                # _install_locked keeps the whole install discipline: the
                # sharded placement rides .at[slot].set on the stacked
                # states, and a DeepCache slot is marked uncaptured so its
                # first ride FORCES a capture batch (the migrated deep-
                # feature row is stale by definition — the snapshot's
                # cadence phase cannot graft onto this scheduler's global
                # tick without perturbing its existing riders)
                self._install_locked(slot, row)
                self._sessions[slot] = sess
                self._enq_stamps[slot].clear()
        except Exception:
            with self._lock:
                self.active[slot] = False
                self._sessions.pop(slot, None)
            raise
        logger.info(
            "batchsched session restored from snapshot -> slot %d (%s)",
            slot, sess.session_key,
        )
        return sess

    # -- heavy/cheap state plumbing -------------------------------------------

    def _adapter_rows(self, name: str | None):
        """One session row of the factor bank for adapter ``name`` at the
        BOUND shape (the zero rows for None), or None when no bank is
        bound.  Raises before any state is touched: a requested adapter
        with no registry, an unknown name, or an adapter that outgrew the
        bound rank must refuse the claim/swap cleanly."""
        if not self._adapter_rank:
            if name is not None:
                raise ValueError(
                    f"adapter {name!r} requested but this scheduler has no "
                    "adapter registry bound (set ADAPTER_DIR and restart)"
                )
            return None
        if name is None:
            return self._zero_rows
        return self.adapters.factor_rows(
            name, rank=self._adapter_rank, targets=self._adapter_targets,
            dtype=self._adapter_dtype,
        )

    def _build_state(self, prompt, guidance, delta, seed, t_index_list=None,
                     adapter: str | None = None,
                     controlnet_scale: float = 1.0):
        from .engine import _coeff_state

        rows = self._adapter_rows(adapter)  # validate before the heavy build
        # devtel: a session claim at serve time runs host-side eager ops
        # whose tiny per-op compiles are expected costs, not retrace
        # breaches (the watchdog still records + attributes them)
        with self._heavy_lock, devtel.expected_scope("sched-state-build"):
            self._template.prepare(
                prompt, guidance_scale=guidance, delta=delta, seed=seed,
                controlnet_scale=controlnet_scale,
            )
            state = self._template.state
            if t_index_list is not None and tuple(t_index_list) != tuple(
                self.cfg.t_index_list
            ):
                state = dict(state)
                state["coeffs"] = _coeff_state(
                    self.cfg, self._template.schedule, tuple(t_index_list)
                )
            if rows is not None:
                # the row must mirror the stacked pytree's structure —
                # _install_locked's .at[slot].set pairs leaf-for-leaf
                state = dict(state)
                state["adapters"] = rows
            return state

    def _install(self, slot: int, state):
        with self._lock:
            self._install_locked(slot, state)

    def _install_locked(self, slot: int, state):
        # devtel: the slot-install .at[].set programs eager-compile on
        # first use — expected control-plane cost, same as _build_state
        with devtel.expected_scope("sched-slot-install"):
            self.states = jax.tree.map(
                lambda stacked, fresh: stacked.at[slot].set(fresh),
                self.states, state,
            )
        if self._cache_interval:
            # the fresh slot's unet_cache row is zeros — make the NEXT
            # global step a capture AND track the slot: if it sits out
            # that batch, its first ride still forces a capture
            self._tick = 0
            self._uncaptured.add(slot)

    def _encode_towers(self, prompt: str):
        """The bundle's ``encode_prompt`` as the template engine calls it,
        from ``prepare`` (a claim) and from ``_encode`` (a prompt write):
        the text towers, spanned and counted, without the heavy lock's
        wait."""
        with hop("encode_prompt") as towers:
            res = self._encode_prompt(prompt)
        with self._stats_lock:
            self._count_hop_locked("encode_prompt", towers.seconds)
        return res

    def _encode(self, prompt: str):
        with self._heavy_lock, devtel.expected_scope("sched-prompt-encode"):
            res = self._encode_towers(prompt)
            return res if len(res) == 3 else (*res, {})

    def _apply_prompt(self, slot: int, encoded):
        cond, uncond, extras = encoded
        dt = self.cfg.jdtype
        with self._lock, devtel.expected_scope("sched-control-write"):
            self.states["cond"] = (
                self.states["cond"].at[slot].set(jnp.asarray(cond, dt))
            )
            self.states["uncond"] = (
                self.states["uncond"].at[slot].set(jnp.asarray(uncond, dt))
            )
            if self.cfg.use_added_cond and "pooled" in extras:
                self.states["added_text"] = (
                    self.states["added_text"]
                    .at[slot]
                    .set(jnp.asarray(extras["pooled"], dt))
                )
            if self._cache_interval:
                # DeepCache: stale deep cross-attention features must not
                # serve under the NEW prompt — recapture globally (same
                # contract as StreamEngine.update_prompt) and pin THIS
                # slot until a capture batch actually carries it
                self._tick = 0
                self._uncaptured.add(slot)

    def _apply_t_index(self, slot: int, t_index_list):
        from .engine import _coeff_state

        t_index_list = tuple(int(t) for t in t_index_list)
        if len(t_index_list) != self.cfg.n_stages:
            raise ValueError(
                f"t_index_list length must stay {self.cfg.n_stages} "
                "(compiled batch size)"
            )
        coeffs = _coeff_state(self.cfg, self._template.schedule, t_index_list)
        with self._lock, devtel.expected_scope("sched-control-write"):
            for k, v in coeffs.items():
                self.states["coeffs"][k] = (
                    self.states["coeffs"][k].at[slot].set(v)
                )
            if self._cache_interval:
                self._tick = 0  # new timesteps -> global recapture
                self._uncaptured.add(slot)

    def _apply_guidance(self, slot: int, guidance, delta):
        with self._lock, devtel.expected_scope("sched-control-write"):
            if guidance is not None:
                self.states["guidance"] = (
                    self.states["guidance"]
                    .at[slot]
                    .set(jnp.asarray(guidance, jnp.float32))
                )
            if delta is not None:
                self.states["delta"] = (
                    self.states["delta"]
                    .at[slot]
                    .set(jnp.asarray(delta, jnp.float32))
                )

    @property
    def has_controlnet(self) -> bool:
        return bool(self.cfg.use_controlnet)

    def _require_controlnet(self):
        if not self.has_controlnet:
            raise ValueError(
                "controlnet_scale: this scheduler serves no side network "
                "(name one in the model id: <base>+<controlnet id>)"
            )

    def _apply_controlnet_scale(self, slot: int, scale: float):
        self._require_controlnet()
        with hop("scale_write", slot=slot), self._lock, \
                devtel.expected_scope("sched-control-write"):
            self.states["cnet_scale"] = (
                self.states["cnet_scale"]
                .at[slot]
                .set(jnp.asarray(scale, jnp.float32))
            )
            self._cnet_scale_writes += 1

    def _apply_adapter(self, slot: int, name: str | None):
        """Swap one slot's factor rows in the stacked bank — the hot-swap
        core: same-shaped ``.at[slot].set`` writes per target (the closed
        rank-bucket contract makes every adapter the SAME shape), so the
        compiled bucket steps never retrace.  ``None`` writes the zero
        rows back (exact no-style)."""
        rows = self._adapter_rows(name)  # raises BEFORE any write
        if rows is None:
            raise ValueError(
                "adapter hot-swap unavailable: no adapter registry bound "
                "(set ADAPTER_DIR and restart)"
            )
        with self._lock, devtel.expected_scope("sched-control-write"):
            bank = self.states["adapters"]
            for path, f in rows.items():
                bank[path]["down"] = bank[path]["down"].at[slot].set(f["down"])
                bank[path]["up"] = bank[path]["up"].at[slot].set(f["up"])
            self.adapter_swaps_total += 1
            if self._cache_interval:
                # DeepCache: deep features captured under the OLD style
                # must not serve under the new one — same recapture
                # contract as a prompt write
                self._tick = 0
                self._uncaptured.add(slot)

    # -- global control plane (POST /config parity: applies to every live
    # session AND becomes the default for future ones) ------------------------

    def update_prompt(self, prompt: str):
        encoded = self._encode(prompt)  # heavy — outside the step lock
        with self._lock:
            slots = [s for s, sess in self._sessions.items()]
        for s in slots:
            self._apply_prompt(s, encoded)
            sess = self._sessions.get(s)
            if sess is not None:
                sess.prompt = prompt
        self.prompt = prompt

    def update_t_index_list(self, t_index_list):
        from .engine import _coeff_state

        t_index_list = [int(t) for t in t_index_list]
        if len(t_index_list) != self.cfg.n_stages:
            raise ValueError(
                f"t_index_list length must stay {self.cfg.n_stages} "
                "(compiled batch size)"
            )
        # validate the values NOW even with zero live sessions — a bad
        # default must fail this call, not the next claim()
        _coeff_state(self.cfg, self._template.schedule, tuple(t_index_list))
        with self._lock:
            slots = list(self._sessions)
        for s in slots:
            self._apply_t_index(s, t_index_list)
            sess = self._sessions.get(s)
            if sess is not None:
                sess.t_index_list = list(int(t) for t in t_index_list)
        # the operator default outlives sessions (shared-pipeline
        # semantics): future claims prepare with THESE indices, exactly
        # like the prompt/guidance defaults above
        self.t_index_list = list(int(t) for t in t_index_list)

    def update_guidance(self, guidance_scale=None, delta=None):
        g = None if guidance_scale is None else float(guidance_scale)
        d = None if delta is None else float(delta)
        with self._lock:
            slots = list(self._sessions)
        for s in slots:
            self._apply_guidance(s, g, d)
            sess = self._sessions.get(s)
            if sess is not None:
                if g is not None:
                    sess.guidance_scale = g
                if d is not None:
                    sess.delta = d
        if g is not None:
            self.guidance_scale = g
        if d is not None:
            self.delta = d

    def update_controlnet_scale(self, scale: float):
        """Global conditioning strength (POST /config ``controlnet_scale``):
        every live session's row AND the default future claims start with."""
        scale = float(scale)
        self._require_controlnet()  # also with no live session to write
        with self._lock:
            slots = list(self._sessions)
        for s in slots:
            self._apply_controlnet_scale(s, scale)
            sess = self._sessions.get(s)
            if sess is not None:
                sess.controlnet_scale = scale
        self.controlnet_scale = scale

    def update_adapter(self, name: str | None):
        """Global adapter swap (POST /config parity with the other
        update_* surfaces): applies to every live session AND becomes the
        default future claims are born with; ``None`` clears to the zero
        bank.  Validated once up front, so a bad name fails THIS call
        even with zero live sessions."""
        self._adapter_rows(name)
        if not self._adapter_rank:
            # name=None with no bank: nothing to clear, but the operator
            # surface must still say why a swap can never work here
            raise ValueError(
                "adapter hot-swap unavailable: no adapter registry bound "
                "(set ADAPTER_DIR and restart)"
            )
        with self._lock:
            slots = list(self._sessions)
        for s in slots:
            self._apply_adapter(s, name)
            sess = self._sessions.get(s)
            if sess is not None:
                sess.adapter = name
        self.default_adapter = name

    # -- bucket executables ---------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self._bucket_sizes:
            if b >= n:
                return b
        return self._bucket_sizes[-1]

    def _idx_for(self, pad):
        key = tuple(pad)
        idx = self._idx_cache.get(key)
        if idx is None:
            if len(self._idx_cache) > 512:
                self._idx_cache.clear()
            idx = jnp.asarray(pad, jnp.int32)
            self._idx_cache[key] = idx
        return idx

    def _slot_shard(self, slot: int) -> int:
        """slot -> shard index (slot-major: contiguous S/dp slot blocks
        per shard) — THE single definition of row residence, shared by
        the staging target, the bucket layout, /health and /metrics."""
        return slot * self.dp // self.max_sessions

    def _slot_device(self, slot: int):
        """The shard device that owns this slot's state row, or None
        off-mesh — the staging target for the session's H2D copies."""
        if self._dp_devs is None:
            return None
        return self._dp_devs[self._slot_shard(slot)]

    def _bucket_label(self, k: int, variant: str) -> str:
        """Devtel compile-attribution scope for one bucket geometry — the
        mesh shape rides the label (``sbucket-<k>:<variant>:dp<N>``) so a
        serve-time reshard retrace alerts with the right key; a bound
        factor bank adds its padded rank (``:r<R>``) the same way.  An
        adapterless dp=1 scheduler keeps the original spelling."""
        label = f"sbucket-{k}:{variant}"
        if self._adapter_rank:
            label = f"{label}:r{self._adapter_rank}"
        return f"{label}:dp{self.dp}" if self.dp > 1 else label

    def _bucket_step(self, k: int, variant: str = "full"):
        step = self._bucket_steps.get((k, variant))
        if step is None:
            fn = make_bucket_step(
                self._vsteps[variant], self.max_sessions,
                scatter_output=False,
            )
            if self.dp > 1:
                # session-axis sharding (parallel/sharding.py rules):
                # params replicated, stacked states + the [k, ...] frame
                # batch and output on P('dp') — one dispatch drives every
                # chip, and the donated states round-trip shard-in-place
                step = jax.jit(
                    fn,
                    in_shardings=(
                        self._repl_sh, self._row_sh, self._row_sh,
                        self._repl_sh,
                    ),
                    out_shardings=(self._row_sh, self._row_sh),
                    donate_argnums=(1,),
                )
            else:
                step = jax.jit(fn, donate_argnums=(1,))
            self._bucket_steps[(k, variant)] = step
            logger.info(
                "batchsched bucket step %d/%d (%s, dp=%d) registered "
                "(compiles on first use unless prewarmed)", k,
                self.max_sessions, variant, self.dp,
            )
        return step

    def _bucket_specs(self, k: int):
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        frame_shape = (
            (k, self.height, self.width, 3)
            if self.fbs == 1
            else (k, self.fbs, self.height, self.width, 3)
        )
        return (
            jax.tree.map(spec, self.params),
            jax.tree.map(spec, self.states),
            jax.ShapeDtypeStruct(frame_shape, jnp.uint8),
            jax.ShapeDtypeStruct((k,), jnp.int32),
        )

    def bucket_keys(self, model_id: str | None = None) -> dict:
        """{(bucket size k, unet variant) -> engine-cache key} — the
        single key recipe shared by serving adoption and the build CLI
        (``sbucket``/``sessions`` extend the stream key; a DeepCache
        config keys a capture+cached PAIR per bucket, w8 params add
        ``quant-w8`` the way ``attn``/``fused`` already ride the key, and
        a dp mesh adds ``dp-N`` via ``aot/cache.mesh_key_extra`` so a
        sharded executable never collides with the single-device slot,
        and a bound factor bank adds ``lrank-R`` via
        ``aot/cache.adapter_key_extra`` — the AOT key space is
        ``(k, variant, rank, dp)``)."""
        from ..aot.cache import adapter_key_extra, mesh_key_extra

        model_id = model_id or self.model_id
        qextra = params_variant_extra(self.params)
        mextra = mesh_key_extra(self.mesh)
        aextra = adapter_key_extra(self._adapter_rank)
        return {
            (k, v): stream_engine_key(
                model_id, self.cfg, sbucket=k, sessions=self.max_sessions,
                **({"variant": v} if v != "full" else {}),
                **qextra,
                **mextra,
                **aextra,
            )
            for k in self._bucket_sizes
            for v in self._variants
        }

    def aot_status(self, model_id: str | None = None,
                   cache_dir: str | None = None) -> dict:
        """{(bucket size, variant) -> already serialized?} via
        EngineCache.has() — lets the build CLI pre-warm only the missing
        geometries."""
        from ..aot.cache import EngineCache

        cache = EngineCache(cache_dir)
        return {
            kv: cache.has(key, self._bucket_specs(kv[0]))
            for kv, key in self.bucket_keys(model_id).items()
        }

    def use_aot_cache(
        self, model_id: str | None = None, cache_dir: str | None = None,
        build_on_miss: bool = True,
    ) -> bool:
        """Swap every bucket step for a serialized AOT executable (the
        StreamEngine.use_aot_cache discipline, one key per bucket
        geometry).  All-or-nothing: a partial adoption would stall the
        missing occupancy on a lazy compile mid-serve.  dp-sharded
        schedulers are not exported (a serialized program is
        per-topology — the StreamEngine mesh policy);
        prewarm_buckets is their no-retrace guarantee instead."""
        if self.dp > 1:
            return False
        from ..aot.cache import EngineCache

        cache = EngineCache(cache_dir)
        keys = self.bucket_keys(model_id)
        if not build_on_miss and not all(
            cache.has(key, self._bucket_specs(k))
            for (k, _v), key in keys.items()
        ):
            return False
        calls = {}
        for (k, v), key in keys.items():
            call = cache.load_or_build(
                key,
                make_bucket_step(
                    self._vsteps[v], self.max_sessions, scatter_output=False
                ),
                self._bucket_specs(k),
                donate_argnums=(1,),
                build=build_on_miss,
            )
            if call is None:
                return False
            calls[(k, v)] = call
        self._bucket_steps.update(calls)
        self._warmed_buckets.update(calls)
        # tpurtc: allow[lock-discipline] -- build-time single-thread phase (no dispatcher/guard yet; rebuild_engine locks because it runs live)
        self._aot_adopted = True
        return True

    def prewarm_buckets(self):
        """Eagerly compile every (bucket geometry, unet variant) NOW (jit
        alone is lazy): occupancy transitions at serve time must dispatch,
        not compile — a join stalling every live session on a retrace is
        exactly what this subsystem exists to remove.  On a dp mesh this
        covers every (k, variant, dp) geometry, so join/leave/reshard
        within the prewarmed set never retraces mid-serve."""
        for k in self._bucket_sizes:
            for v in self._variants:
                if self._aot_adopted and (k, v) in self._bucket_steps:
                    continue
                params_s, states_s, frames_s, idx_s = self._bucket_specs(k)
                # devtel: attribute the eager compile to its bucket (the
                # sharded label carries :dp<N>).  It is EXPECTED: a
                # legitimate operator-triggered prewarm (e.g. after a
                # mesh reshape) must never false-alarm the watchdog even
                # in the serving phase, while a LAZY dispatch compile
                # (_step_batch_locked) keeps breach semantics
                label = self._bucket_label(k, v)
                with devtel.compile_scope(label, expected=True):
                    with count_attention_paths() as paths:
                        lowered = self._bucket_step(k, v).lower(
                            params_s, states_s, frames_s, idx_s
                        )
                    compiled = lowered.compile()
                # what the executable that will serve really contains:
                # /health reports it, chip_smoke.py asserts on it
                text = compiled.as_text()
                self.mosaic_kernels[label] = mosaic_kernel_counts(text)
                self.attention_paths[label] = dict(paths)
                self.relayout_copies[label] = f32_relayout_copies(text)
                self.epilogues[label] = (
                    "fused_stream_epilogue"
                    if self.mosaic_kernels[label].get("fused_stream_epilogue")
                    else "xla"
                )
                self._bucket_steps[(k, v)] = compiled
                self._warmed_buckets.add((k, v))
                logger.info(
                    "prewarmed batchsched bucket %d/%d (%s, dp=%d): "
                    "kernels %s, attention paths %s, float32 re-layouts %s, "
                    "TAESD fold %s, epilogue %s, denoiser %s",
                    k, self.max_sessions, v, self.dp,
                    self.mosaic_kernels[label], self.attention_paths[label],
                    self.relayout_copies[label], self.taesd_fold,
                    self.epilogues[label], self.denoiser,
                )

    def compiled_steps(self) -> dict:
        """{bucket label: compiled executable}, for the buckets
        prewarm_buckets compiled (an AOT-adopted or lazily jitted bucket has
        no compiled object to read)."""
        return {
            self._bucket_label(k, v): step
            for (k, v), step in self._bucket_steps.items()
            if hasattr(step, "as_text")
        }

    def compiled_text(self) -> dict:
        """{bucket label: HLO text of its compiled executable}.  A profiler
        trace names a device op by its HLO instruction only; the
        instruction's ``metadata={op_name=...}`` in this text is where its
        ``jax.named_scope`` path is (benchmark/scope_reduce.py)."""
        return {
            label: step.as_text() for label, step in self.compiled_steps().items()
        }

    def rehearse(self):
        """Walk throw-away sessions through everything a real one will do —
        claim, one frame through every bucket size, a prompt / t-index /
        guidance write, release — while the process is still warming up.

        ``prewarm_buckets`` compiles the bucket STEPS; what is left are the
        small eager per-slot programs around them (row-install scatters,
        the frame-batch stack, per-row readback slices, snapshot-bank row
        slices), which JAX compiles on first use.  On a v5e each takes
        70-300 ms (PERF.md "Bring-up on the chip"): unrehearsed, the first
        sessions of a process pay seconds of them and the compile watchdog
        rightly calls the slow ones serve-time breaches.  After this,
        serving compiles nothing (``devtel_serving_compiles_total == 0``).

        The agent calls it once at the end of startup, after the engine
        guard is attached (so the snapshot bank's slices are rehearsed too)
        and before the first real claim.  The gauges it moved are reset."""
        rng = np.random.default_rng(0)
        shape = (self.height, self.width, 3)
        # a window nobody outwaits: only FULL batches dispatch — the last
        # live session's submit completes the batch inline at occupancy k
        window, self.window_s = self.window_s, 3600.0
        on_step, self.on_step = self.on_step, None  # not a capacity signal
        sessions: list = []
        try:
            for k in self._bucket_sizes:
                while len(sessions) < k:
                    sessions.append(self.claim(f"rehearsal-{len(sessions)}"))
                # distinct noise per round: a similarity filter must not
                # skip a rider and leave the batch forever incomplete
                handles = [
                    s.submit_batch([
                        rng.integers(0, 256, shape, dtype=np.uint8)
                        for _ in range(self.fbs)
                    ])
                    for s in sessions
                ]
                for s, hs in zip(sessions, handles):
                    s.fetch_batch(hs)
            self.update_prompt(self.prompt)
            self.update_t_index_list(self.t_index_list)
            self.update_guidance(self.guidance_scale, self.delta)
        finally:
            for s in sessions:
                s.release()
            self.window_s, self.on_step = window, on_step
        with self._lock:
            self._snap_rows, self._last_snap_t = {}, 0.0
            self._tick = 0
        with self._stats_lock:
            self._reset_counters_locked()
        logger.info(
            "batchsched rehearsed %d session(s) through buckets %s",
            len(sessions), self._bucket_sizes,
        )

    # -- engine fault domain (resilience/engine_guard.py) ----------------------

    def attach_guard(self, guard):
        """Wire an EngineGuard into the dispatch path: every bucket step
        now runs under its deadline, and while it is quarantined the
        scheduler sheds (passthrough) instead of dispatching, refuses
        claims/restores, and serves banked snapshots to /migrate/export."""
        self._guard = guard

    def _maybe_bank_rows_locked(self):
        """Refresh the snapshot bank (per-slot DEVICE-side state rows) on
        the ENGINE_SNAPSHOT_EVERY_S cadence, after a successful dispatch.
        Each ``x[slot]`` slice is a fresh buffer the bucket step's later
        donation cannot invalidate (the snapshot_session rule) — these
        rows are the ONLY readable copy of session state once a trip
        poisons the stack.  Cheap device ops under the lock; nothing is
        pulled to the host here."""
        if self._guard is None or self._snap_every_s <= 0:
            return  # <=0 disables banking (rebuilds re-derive from control)
        now = time.monotonic()
        if now - self._last_snap_t < self._snap_every_s:
            return
        self._last_snap_t = now
        rows = {}
        for slot, sess in self._sessions.items():
            if not self.active[slot]:
                continue
            rows[slot] = jax.tree.map(
                lambda x, slot=slot: x[slot], self.states
            )
        self._snap_rows = rows

    def capture_quarantine_snapshots(self) -> dict:
        """Freeze ``session_key -> full snapshot dict`` from the banked
        device rows + the live sessions' control plane — the guard calls
        this ONCE at quarantine entry, before any rebuild attempt, so an
        eventual evacuation exports exactly what the bank held.  Slots
        without a banked row (claimed after the last cadence refresh) are
        skipped here and rebuilt from their control plane by
        :meth:`rebuild_engine`.  Best-effort per slot: one unreadable row
        must not void the other sessions' evacuation."""
        with self._lock:
            rows = dict(self._snap_rows)
            sessions = {
                slot: sess for slot, sess in self._sessions.items()
                if self.active[slot]
            }
            cache_tick = self._tick
            uncaptured = set(self._uncaptured)
        snaps = {}
        for slot, sess in sessions.items():
            row_dev = rows.get(slot)
            if row_dev is None:
                logger.warning(
                    "quarantine capture: slot %d has no banked row "
                    "(claimed after the last bank refresh) — control-plane "
                    "rebuild only", slot,
                )
                continue
            try:
                row = jax.tree.map(np.asarray, row_dev)
                snaps[sess.session_key] = self._row_snapshot(
                    sess, row, cache_tick, slot in uncaptured
                )
            except Exception:
                logger.exception(
                    "quarantine capture failed for slot %d (%s)",
                    slot, sess.session_key,
                )
        self._quarantine_snaps = snaps
        return snaps

    def rebuild_engine(self, snapshots: dict | None = None) -> int:
        """Quarantine recovery: re-derive the compiled step plane (every
        executable may have baked in the dead device) and restore every
        live slot — from its banked snapshot row BIT-EXACT when one
        exists, from its session's control plane otherwise; never module
        defaults.  Returns the number of slots restored bit-exact.
        Raises on failure (the guard backs off and retries)."""
        import base64

        from ..parallel.checkpoint import deserialize_pytree

        snapshots = snapshots if snapshots is not None else (
            self._quarantine_snaps
        )
        # _has_work is Condition(self._lock) — acquiring the Lock directly
        # is the same mutual exclusion (no wait/notify on this path)
        with self._lock:
            self._bucket_steps = {}
            self._warmed_buckets = set()
            self._idx_cache = {}
            self._aot_adopted = False
            self._vsteps = {
                v: jax.vmap(
                    make_step_fn(
                        self._template.models, self.cfg, unet_variant=v
                    ),
                    in_axes=(None, 0, 0),
                )
                for v in self._variants
            }
            placeholder = None
            per = []
            exact = 0
            for slot in range(self.max_sessions):
                sess = (
                    self._sessions.get(slot) if self.active[slot] else None
                )
                row = None
                if sess is not None:
                    snap = snapshots.get(sess.session_key)
                    if snap is not None:
                        try:
                            row = deserialize_pytree(
                                base64.b64decode(snap["state_b64"])
                            )
                            self._check_row(row)
                        except Exception:
                            logger.exception(
                                "banked row unusable for slot %d — "
                                "control-plane rebuild", slot,
                            )
                            row = None
                    if row is not None:
                        exact += 1
                    else:
                        row = self._build_state(
                            sess.prompt, sess.guidance_scale, sess.delta,
                            sess._seed, t_index_list=sess.t_index_list,
                            adapter=sess.adapter,
                        )
                else:
                    if placeholder is None:
                        placeholder = self._build_state(
                            self.prompt, self.guidance_scale, self.delta,
                            slot, t_index_list=self.t_index_list,
                        )
                    row = placeholder
                per.append(row)
            self.states = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
            if self.dp > 1:
                self.states = jax.device_put(self.states, self._row_sh)
            if self._cache_interval:
                self._tick = 0  # fresh deep caches -> forced recapture
                self._uncaptured.update(range(self.max_sessions))
            # old in-flight batch refs pin poisoned buffers — drop them
            self._batches = deque(maxlen=self._batches.maxlen)
            self._snap_rows = {}
            self._last_snap_t = 0.0
        # re-warm the way the boot did (outside the step lock; the guard
        # only re-arms dispatch after this returns)
        if self._prewarm:
            self.prewarm_buckets()
        self._quarantine_snaps = {}
        logger.warning(
            "batchsched engine rebuilt: %d/%d live slot(s) restored "
            "bit-exact from the snapshot bank",
            exact, len([a for a in self.active if a]),
        )
        return exact

    # -- coalescing window + dispatcher ---------------------------------------

    def _evict(self, pending: _PendingFrame, reason: str):
        """A bounded window queue shed this frame: unblock its waiter with
        passthrough pixels immediately (recv never hangs), marked so the
        resilience wrapper never accounts it as an engine step."""
        fut = pending.future
        try:
            if not fut.cancelled() and not fut.done():
                fut.set_result(ShedFrame(pending.frame))
        except InvalidStateError:
            pass  # lost a teardown race — the waiter is unblocked either way

    def _batches_in_flight(self, now: float) -> int:
        return sum(
            1
            for b in self._batches
            if not b.resolved and now - b.t_dispatch < 60.0
        )

    def _enqueue(self, slot: int, pending: _PendingFrame):
        g = self._guard
        if g is not None and g.quarantined:
            # no dispatch plane: resolve the waiter as passthrough NOW
            # (the _evict discipline) instead of queueing work that could
            # only shed at its deadline — recv never hangs on a quarantine
            self._evict(pending, "engine-quarantined")
            return
        with hop("enqueue", slot=slot, seq=pending.seq) as enqueue, self._has_work:
            pending.lock_wait_s = time.monotonic() - enqueue.t0
            self._enq_stamps[slot].append(pending.t_enq)
            room = (
                self._batches_in_flight(pending.t_enq) < self.PIPELINE_DEPTH
            )
            if (
                self.fbs == 1
                and room
                and self.active.count(True) == 1
                and self._queues[slot].depth == 0
            ):
                # solo ultra path: one live session, nothing queued ahead
                # — dispatch THIS frame without touching the window queue
                # at all (the pass-through-cheap promise: a lock and a
                # gather/scatter, not a queue round-trip + thread handoff)
                self._dispatch_entries_locked(
                    [(slot, [pending])], pending, "solo"
                )
                return
            self._queues[slot].push(pending, stamp=pending.t_enq)
            waiting = self._waiting_slots()
            if room and len(waiting) >= self.active.count(True):
                # fast path: THIS frame completed the batch (every live
                # session has a full fbs group waiting) — dispatch NOW on
                # the caller thread: no window, no dispatcher handoff;
                # each rider's fetch resolves its own per-slot row
                self._dispatch_inline_locked(pending, "inline_full")
                return
            if room and self._none_due_locked(waiting):
                # the window would buy nothing: the same inline dispatch
                # with who is waiting
                self._dispatch_inline_locked(pending, "none_due")
                return
            self._has_work.notify()

    def _pop_group(self, slot: int):
        """Pop one dispatch group for a slot: the single oldest frame
        (fbs==1) or the slot's fbs OLDEST consecutive frames — the
        second batching dimension the bucket step consumes as one
        [fbs, H, W, 3] row.  Caller holds the lock."""
        if self.fbs == 1:
            got = self._queues[slot].pop()
            return None if got is None else [got[0]]
        plist = []
        for _ in range(self.fbs):
            got = self._queues[slot].pop()
            if got is None:
                break
            plist.append(got[0])
        return plist or None

    def _dispatch_inline_locked(self, submitter: _PendingFrame, cause: str):
        entries = []
        for s in self._waiting_slots():
            plist = self._pop_group(s)
            if plist is not None:
                entries.append((s, plist))
        if not entries:
            return
        self._dispatch_entries_locked(entries, submitter, cause)

    def _none_due_locked(self, waiting) -> bool:
        """May the partial batch of ``waiting`` go now rather than wait
        out the window (from its oldest enqueue)?  Only where waiting
        would buy nothing: no live session missing from it is predicted
        to enqueue before the window ends (``_due_before``), and the
        device has drained, so waiting buys no device time, only latency.
        ``fbs > 1`` keeps its window."""
        oldest = self._oldest_enqueue(waiting)
        if self.fbs != 1 or self.window_s <= 0.0 or oldest is None:
            return False
        until = oldest + self.window_s
        if any(
            _due_before(self._enq_stamps[s], until)
            for s in range(self.max_sessions)
            if self.active[s] and s not in waiting
        ):
            return False
        return self._device_drained_locked()

    def _device_drained_locked(self) -> bool:
        """Has every step launched before now left the device?  The ready
        watcher's newest stamp answers it; where the watcher has not woken
        yet, one non-blocking ``is_ready()`` of the newest step's output
        does (steps leave the device in launch order), and a yes is kept
        as that step's ``ready_by``.  The step about to be dispatched then
        finds the chip idle — a bubble the host let open, counted with no
        profiler attached (``batchsched_dispatch_starved_total``)."""
        b = self._newest
        if b is None or self._ready_seq >= b.step:
            return True
        out = b.out
        try:
            # the watcher drops the output only once the step is done
            done = out is None or out.is_ready()
        except (AttributeError, RuntimeError):
            return True  # not a device buffer: nothing in flight
        if done and b.ready_by is None:
            b.ready_by = time.monotonic()
        return done

    def _step_batch_locked(self, entries):
        """The ONE dispatch sequence both paths share (dispatcher loop and
        inline fast path): bucket-select, pad with the last ready row,
        assemble the PRE-STAGED device frames (zero-copy per-shard on a
        dp mesh), stamp, step, slice per-slot rows on device — each FROM
        ITS OWN SHARD when sharded — and kick each row's async readback.
        Caller holds the lock; a raising step is the caller's to deliver
        to the waiters.  Runs inside the caller's ``rtc:dispatch`` span
        and opens its children ``assemble``, ``launch`` and
        ``readback_start``.  -> (rows, t_disp, feed, launch, out, k):
        ``feed`` False on a bucket variant's first use (a lazy compile may
        ride it — not a capacity signal); ``launch`` the finished hop
        around the jitted call (its ``t0``, ``t1``), ``out`` the bucket's
        output, which the ready watcher blocks on."""
        idx = [s for s, _ in entries]
        k = self._bucket_for(len(idx))
        with hop("assemble", k=k):
            pad, positions = self._layout_pad(idx, k)
            # frames were staged to device ROW-SHAPED at submit time
            # (stage_frame, outside any lock, onto the slot's own shard): a
            # solo bucket consumes the staged buffer with ZERO extra device
            # ops, a wider bucket pays one concatenate/stack per shard —
            # never an H2D copy under the dispatch lock
            by_slot = {}
            for s, plist in entries:
                bufs = [
                    stage_frame(p.frame[None], device=self._slot_device(s))
                    if p.frame_dev is None
                    else p.frame_dev
                    for p in plist
                ]
                if self.fbs == 1:
                    by_slot[s] = bufs[0]
                else:
                    # a (defensive) short group pads by repeating its last
                    # frame — identical compute, the absent handles were shed
                    bufs = (bufs + [bufs[-1]] * self.fbs)[: self.fbs]
                    by_slot[s] = jnp.concatenate(bufs, axis=0)
            frames_k = self._assemble_frames(pad, by_slot, k)
        t_disp = time.monotonic()
        occ = len(entries)
        for _, plist in entries:
            for p in plist:
                p.t_dispatch = t_disp
                p.occupancy = occ
        variant = "full"
        if self._cache_interval:
            # global DeepCache cadence: full capture every Nth batch step,
            # the cheap cached graph between (both compiled; the host just
            # picks one — no data-dependent control flow on device).  A
            # batch carrying any UNCAPTURED rider (joined/prompt-updated
            # slot that sat out the post-reset capture) is FORCED to
            # capture: an off-cadence extra capture is merely slower, a
            # cached step over a zeroed/stale deep-feature row is wrong
            variant = (
                "capture"
                if (
                    self._tick % self._cache_interval == 0
                    or any(s in self._uncaptured for s in idx)
                )
                else "cached"
            )
            self._tick += 1
            if variant == "capture":
                self._uncaptured.difference_update(idx)
        feed = (k, variant) in self._warmed_buckets
        step = self._bucket_step(k, variant)
        step_args = (self.params, self.states, frames_k, self._idx_for(pad))

        def _device_step():
            # compile-watchdog attribution: a bucket step that compiles
            # HERE (prewarm disabled, or an evicted/missed geometry) is
            # recorded against its (k, variant[, dp]) — in the serving
            # phase that is the serve-time retrace breach this plane
            # exists to catch.  Fault injection (slow_step / wedge /
            # device_lost) fires on the SAME thread the step runs on, so
            # a wedge holds the guard's worker, not the dispatch lock's
            # owner.
            if self._fault_scope is not None:
                self._fault_scope.step()
            with devtel.compile_scope(self._bucket_label(k, variant)):
                return step(*step_args)

        self._dispatch_seq += 1
        with hop("launch", step=self._dispatch_seq, k=k) as launch:
            guard = self._guard
            if guard is None:
                self.states, out = _device_step()
            else:
                # deadline-bounded dispatch (resilience/engine_guard.py): a
                # wedged or lost device trips the guard and raises — states
                # are assigned only on success, so an abandoned worker's late
                # result can never race the rebuild's fresh stack.  Cold
                # bucket variants get the long compile deadline (the
                # warm-step rule's analog).
                self.states, out = guard.dispatch(_device_step, cold=not feed)
        self._warmed_buckets.add((k, variant))
        # per-slot readback plane: slice each rider's row ON DEVICE and
        # start its D2H copy now — a fetch resolves only its own buffer,
        # so one session's readback never bills the others and the next
        # dispatch overlaps these copies.  Sharded, each row slices FROM
        # ITS OWN SHARD (no cross-device gather resolves one session's
        # frame).  A single-device solo batch skips the slice (its whole
        # output IS the row — _resolve_row squeezes leading singleton
        # axes on the host for free)
        with hop("readback_start", k=k):
            if self.dp > 1:
                rows = self._rows_from_sharded(out, positions, k)
            else:
                rows = (
                    [out]
                    if len(entries) == 1
                    else [out[i] for i in positions]
                )
            for r in rows:
                try:
                    r.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    pass
        return rows, t_disp, feed, launch, out, k

    def _layout_pad(self, idx, k: int):
        """Bucket layout: which slot fills each of the k rows, and which
        row each ENTRY resolves from.  Single-device: entries are a
        prefix, padding repeats the last (the PR 7 layout).  On a dp
        mesh rows are placed SHARD-AWARE: row i computes on shard
        i//(k/dp), so each entry goes to a row on its state row's OWN
        shard while that shard has space (claim() balances the live set,
        so in steady state every row is home — zero cross-device hops);
        only overload of one shard spills, and padding repeats a row
        already resident on the padded shard.  -> (pad, positions) with
        ``positions[j]`` the row entry j resolves from (its home-shard
        occurrence when one exists)."""
        if self.dp <= 1:
            return (idx + [idx[-1]] * k)[:k], list(range(len(idx)))
        rps = k // self.dp
        shard_rows = [[] for _ in range(self.dp)]
        spill = []
        for s in idx:
            d = self._slot_shard(s)
            if len(shard_rows[d]) < rps:
                shard_rows[d].append(s)
            else:
                spill.append(s)
        for s in spill:  # one shard overloaded: first shard with space
            for d in range(self.dp):
                if len(shard_rows[d]) < rps:
                    shard_rows[d].append(s)
                    break
        for d in range(self.dp):
            # padding repeats a row already ON this shard when it has
            # one (zero-copy duplicate); an entirely idle shard repeats
            # the last entry (the one unavoidable hop — idle-shard
            # padding is what makes below-minimum occupancy legal)
            filler = shard_rows[d][-1] if shard_rows[d] else idx[-1]
            while len(shard_rows[d]) < rps:
                shard_rows[d].append(filler)
        pad = [s for rows in shard_rows for s in rows]
        positions = []
        for s in idx:
            home = self._slot_shard(s)
            cand = [i for i, x in enumerate(pad) if x == s]
            positions.append(
                next((i for i in cand if i // rps == home), cand[0])
            )
        return pad, positions

    def _assemble_frames(self, pad, by_slot, k: int):
        """The global frame batch for one dispatch.  Single-device: one
        concatenate/stack of the staged rows.  On a dp mesh: group the
        bucket's rows by owning shard (row i of k -> shard i//(k/dp)),
        build each shard's block ON ITS DEVICE (a straggler staged
        elsewhere pays one explicit D2D hop) and assemble the global
        [k, ...] array ZERO-COPY via make_array_from_single_device_arrays
        — the batch is born sharded; nothing funnels through device 0."""
        if self.dp <= 1:
            if self.fbs == 1:
                return (
                    by_slot[pad[0]]
                    if k == 1
                    else jnp.concatenate([by_slot[s] for s in pad], axis=0)
                )
            return jnp.stack([by_slot[s] for s in pad])
        rps = k // self.dp  # rows per shard (bucket sizes are dp multiples)
        shards = []
        for d in range(self.dp):
            dev = self._dp_devs[d]
            rows = []
            for i in range(d * rps, (d + 1) * rps):
                r = by_slot[pad[i]]
                if dev not in r.devices():
                    r = jax.device_put(r, dev)
                rows.append(r)
            if self.fbs == 1:
                # rows are [1,H,W,3] staged buffers -> [rps,H,W,3]
                shards.append(
                    rows[0] if rps == 1 else jnp.concatenate(rows, axis=0)
                )
            else:
                # rows are [fbs,H,W,3] groups -> [rps,fbs,H,W,3]
                shards.append(jnp.stack(rows))
        shape = (
            (k, self.height, self.width, 3)
            if self.fbs == 1
            else (k, self.fbs, self.height, self.width, 3)
        )
        return jax.make_array_from_single_device_arrays(
            shape, self._row_sh, shards
        )

    def _rows_from_sharded(self, out, positions, k: int):
        """Per-entry device rows of a SHARDED bucket output: entry j's
        row (``positions[j]``) is sliced from the addressable shard
        that owns it (its ``copy_to_host_async`` + host resolve then
        move only that session's bytes off that device) — fetch
        isolation survives sharding by construction."""
        rps = k // self.dp
        shards = sorted(
            out.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        return [shards[i // rps].data[i % rps] for i in positions]

    @staticmethod
    def _fail_entries(entries, exc):
        for _, plist in entries:
            for p in plist:
                if not p.future.cancelled():
                    try:
                        p.future.set_exception(exc)
                    except InvalidStateError:
                        pass

    def _recover_states_locked(self, cause):
        """A failed step invalidated the DONATED stacked state — left
        alone, every later dispatch and control-plane write would raise
        'Array has been deleted' forever (the dedicated-engine path
        recovers via restart()->prepare(); the scheduler must too).
        Rebuild every live session's row from its tracked control plane
        (a fresh stream state — the engine-restart recovery semantics);
        inactive rows share one placeholder.  Best-effort: if the model
        itself is broken this raises nothing and leaves the next dispatch
        to surface it."""
        try:
            placeholder = None
            per = []
            for slot in range(self.max_sessions):
                sess = self._sessions.get(slot) if self.active[slot] else None
                if sess is not None:
                    per.append(
                        self._build_state(
                            sess.prompt, sess.guidance_scale, sess.delta,
                            sess._seed, t_index_list=sess.t_index_list,
                            adapter=sess.adapter,
                        )
                    )
                else:
                    if placeholder is None:
                        placeholder = self._build_state(
                            self.prompt, self.guidance_scale, self.delta,
                            slot, t_index_list=self.t_index_list,
                        )
                    per.append(placeholder)
            self.states = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
            if self.dp > 1:
                # re-materialize the session-axis shards (the rebuilt
                # stack is single-device) so the next donated dispatch
                # doesn't pay a silent resharding copy
                self.states = jax.device_put(self.states, self._row_sh)
            if self._cache_interval:
                self._tick = 0  # fresh (zeroed) deep caches -> recapture
                self._uncaptured.update(range(self.max_sessions))
            logger.warning(
                "batchsched: rebuilt %d session state rows after a failed "
                "step (%r)", self.max_sessions, cause,
            )
        except Exception:
            logger.exception(
                "batchsched state recovery failed — sessions will "
                "passthrough until restart/reclaim"
            )

    def _dispatch_entries_locked(
        self, entries, submitter: "_PendingFrame | None", cause: str,
        window=None,
    ):
        """Dispatch + hand every rider its per-slot readback, and hand the
        launched step to the ready watcher.
        ``cause``: why now, one of DISPATCH_CAUSES (the dispatch span and
        ``batchsched_dispatch_cause_total`` carry it).  ``window``: the
        dispatcher's wait for riders before this dispatch, (start, end).
        ``submitter``: the EXACT pending whose submit is running this
        dispatch inline (None = dispatcher thread — every future gets the
        marker; there is no caller to re-raise into).  Identity matters:
        the inline path pops each slot's OLDEST queued frame, which for
        the submitter's own slot may be an EARLIER frame than the one
        just submitted — that frame's waiter may already be blocked on
        its future, so only the submitted pending itself may skip the
        future machinery (code-review r1)."""
        occ = len(entries)
        frames = ",".join(f"{s}:{p.seq}" for s, plist in entries for p in plist)
        try:
            with hop(
                "dispatch", k=self._bucket_for(occ), riders=occ, cause=cause,
                frames=frames,
            ) as dispatch:
                inflight = self._batches_in_flight(dispatch.t0)
                starved = self._device_drained_locked()
                rows, t_disp, feed, launch, out, k = self._step_batch_locked(
                    entries
                )
        except Exception as e:
            # a dispatch failing must unblock EVERY rider's future (the
            # other sessions' fetches would otherwise hang out the full
            # fetch timeout) and surface in the submitter's track
            self._fail_entries(entries, e)
            g = self._guard
            if g is not None and g.quarantined:
                # engine-level trip: the guard owns recovery (quarantine →
                # rebuild from the snapshot bank).  The per-step rebuild
                # below would both write into a poisoned stack and clobber
                # the banked bit-exact rows with fresh prepares.
                if submitter is None:
                    return
                raise
            self._recover_states_locked(e)
            if submitter is None:
                return
            raise
        batch = _DispatchedBatch(
            rows, entries, t_disp, occ, feed, cause, inflight, starved,
            dispatch.seconds, (launch.t0, launch.t1), out, self._dispatch_seq,
            k, frames, submitter, window, self._epoch,
        )
        self._newest = batch
        self._ready_q.put(batch)
        self._maybe_bank_rows_locked()
        if any(b.resolved for b in self._batches):
            # drop resolved batches WHEREVER they sit — the ring exists
            # only for the in-flight count, and a resolved batch kept
            # behind an unresolved head would pin its unread row buffers
            # (MBs each at real geometry) until it aged out; riders still
            # mid-resolve hold their own refs via the handle
            self._batches = deque(
                (b for b in self._batches if not b.resolved),
                maxlen=self._batches.maxlen,
            )
        self._batches.append(batch)
        for i, (s, plist) in enumerate(entries):
            sess = self._sessions.get(s)
            for fi, p in enumerate(plist):
                sub = fi if self.fbs > 1 else None
                p.readback = (batch, i, sub)
                # other riders may ALREADY be blocked on their future
                # (their frame sat in the window when this dispatch
                # claimed it) — a marker result wakes them into their own
                # per-row resolve.  Only the EXACT pending whose submit is
                # running this dispatch skips the Future machinery (its
                # fetch hasn't started yet), and even it keeps the future
                # when a similarity-skip dup may chain off it.
                if p is not submitter or (
                    sess is not None and sess._sim is not None
                ):
                    try:
                        if not p.future.cancelled():
                            p.future.set_result((batch, i, sub))
                    except InvalidStateError:
                        pass

    def _resolve_row(self, batch: _DispatchedBatch, row: int, t0: float):
        """Resolve ONE rider's per-slot row of a dispatched batch.  The
        host copy is memoized on the batch row (dup/skip fetches re-read
        it, never the device) and each row resolves under its OWN lock —
        one session's readback never serializes another's.  The first
        resolver (any row) does the per-batch accounting."""
        out = batch.host[row]
        if out is None:
            with batch.rlocks[row]:
                out = batch.host[row]
                if out is None:
                    try:
                        arr = np.asarray(batch.rows[row])  # this row ONLY
                    except Exception:
                        # a failed readback must FREE the in-flight slot
                        # right away (the old dispatcher drain did): left
                        # unresolved, this batch would throttle dispatch
                        # for the full 60s age-out while every session's
                        # window sheds.  No EWMA feed — a failure is not
                        # a capacity sample.  The error surfaces to THIS
                        # caller; other riders hit their own rows' errors.
                        with self._stats_lock:
                            batch.resolved = True
                        if self._throttled:
                            with self._has_work:
                                self._has_work.notify()
                        raise
                    # host-side squeeze (free): a sliced row is
                    # [fbs=1,H,W,3], a solo batch's unsliced output is
                    # [k=1,fbs=1,H,W,3]; with fbs>1 the row stays the
                    # session's [fbs,H,W,3] group — each handle slices
                    # its own frame at fetch
                    while arr.ndim > 3 and arr.shape[0] == 1:
                        arr = arr[0]
                    # D2H accounting (obs/devtel.py): exactly one note
                    # per row — the memoized host copy means dup/skip
                    # fetches never re-transfer, so this meter is the
                    # fetch-isolation story as a live counter
                    devtel.note_d2h(arr.nbytes)
                    batch.host[row] = arr
                    batch.rows[row] = None  # release the device buffer
                    out = arr
        t1 = time.monotonic()
        first = False
        with self._stats_lock:
            if batch.ready_by is None or t1 < batch.ready_by:
                batch.ready_by = t1  # the row is on the host: the step is done
            if not batch.resolved:
                batch.resolved = True
                first = True
        if first:
            # step-cost estimate for the admission EWMA: dispatch->resolve
            # OVERSTATES when the caller pipelines (frame N's fetch runs an
            # inter-frame interval after its dispatch — an idle 10 fps solo
            # box would read as a 100 ms "step" and 503 new offers), while
            # the observed BLOCKING time (t1 - t0) understates by the
            # pre-fetch head start.  The min of the two is exact whenever
            # the device is the bottleneck (fetch arrives before compute
            # finishes) and near-zero when the box is idle — both correct
            # directions for a capacity signal.
            self._note_step(min(t1 - batch.t_dispatch, t1 - t0), batch)
            if self._throttled:
                # an in-flight slot just freed and the dispatcher is
                # parked on the backpressure cap — wake it (a racing
                # park falls back on its wait timeout)
                with self._has_work:
                    self._has_work.notify()
        return out, t1

    def _waiting_slots(self):
        # a slot is dispatch-ready with a FULL group queued: one frame,
        # or fbs consecutive frames when the scheduler batches the frame
        # axis too (a partial group keeps waiting for its tail)
        return [
            s
            for s in range(self.max_sessions)
            if self.active[s] and self._queues[s].depth >= self.fbs
        ]

    def _oldest_enqueue(self, waiting):
        stamps = [
            t
            for t in (self._queues[s].oldest_stamp() for s in waiting)
            if t is not None
        ]
        return min(stamps) if stamps else None

    # keep up to this many batch steps in flight: step N's readback
    # overlaps step N+1's dispatch (same rationale as the single-engine
    # submit/fetch pipeline)
    PIPELINE_DEPTH = 2

    def _run(self):
        """Window-expiry dispatcher.  Dispatch is all it does now: every
        rider's future gets its per-slot readback marker at dispatch time
        and the riders resolve their OWN rows on their fetch threads — the
        dispatcher never blocks on a device->host copy, so batch N+1
        dispatches while batch N's readbacks drain on the fetchers."""
        while True:
            with self._has_work:
                # someone was missing when the dispatcher last looked, so
                # the dispatch that follows is the window's ("window", or
                # "none_due" where the window would have bought nothing);
                # otherwise every live session was ready and only the
                # in-flight cap held the step back ("backpressure").
                # ``waited``: from the first to the end of the last wait
                # for riders since then, for the ready watcher's gap booking
                cause, waited = "backpressure", None
                while not self._stop:
                    waiting = self._waiting_slots()
                    g = self._guard
                    if g is not None and g.quarantined:
                        # no dispatch plane: shed whatever queued (their
                        # waiters resolve passthrough immediately) and
                        # idle until the guard's rebuild re-arms
                        for s in waiting:
                            while True:
                                plist = self._pop_group(s)
                                if plist is None:
                                    break
                                for p in plist:
                                    self._evict(p, "engine-quarantined")
                        self._has_work.wait(timeout=0.1)
                        continue
                    if not waiting:
                        cause, waited = "backpressure", None
                        self._has_work.wait(timeout=0.5)
                        continue
                    if (
                        self._batches_in_flight(time.monotonic())
                        >= self.PIPELINE_DEPTH
                    ):
                        # backpressure: a rider's first row-resolve frees a
                        # slot and notifies (it checks _throttled); the
                        # timeout is a safety net for abandoned batches
                        # (they age out at 60s) and the set/check race
                        self._throttled = True
                        with hop("window_wait", on="inflight_cap"):
                            self._has_work.wait(timeout=0.05)
                        self._throttled = False
                        continue
                    live = self.active.count(True)
                    if len(waiting) >= live or live <= 1:
                        # every live session has work (or there's nobody
                        # to wait for): dispatch NOW — the single-session
                        # fast path never pays the window
                        break
                    cause = "window"
                    if self.window_s <= 0.0:
                        break
                    oldest = self._oldest_enqueue(waiting)
                    remain = (
                        0.0
                        if oldest is None
                        else oldest + self.window_s - time.monotonic()
                    )
                    if remain <= 0.0:
                        break  # window expired: go with who showed up
                    if self._none_due_locked(waiting):
                        cause = "none_due"
                        break
                    # the ready watcher wakes this wait when the device
                    # drains (_note_ready): the window may then buy nothing
                    self._windowing = True
                    with hop("window_wait", on="window") as w:
                        self._has_work.wait(timeout=remain)
                    self._windowing = False
                    waited = (w.t0 if waited is None else waited[0], w.t1)
                if self._stop:
                    break
                entries = []
                for s in self._waiting_slots():
                    plist = self._pop_group(s)
                    if plist is not None:
                        entries.append((s, plist))
                if entries:
                    self._dispatch_entries_locked(entries, None, cause, waited)
        # drain on stop
        for q in self._queues:
            while True:
                got = q.pop()
                if got is None:
                    break
                got[0].future.cancel()

    def _reset_counters_locked(self):
        """Zero everything snapshot() counts (construction, and the end of
        rehearse(): set-up's steps are not serving's).  Fixed key sets,
        so snapshot() copies them without the lock."""
        self.steps_total = 0
        self._waits.clear()
        self._occ_hist: dict = {}
        self._hop_ms = dict.fromkeys(COUNTED_HOPS, 0.0)
        self._hop_max_ms = dict.fromkeys(COUNTED_HOPS, 0.0)
        self._hop_n = dict.fromkeys(COUNTED_HOPS, 0)
        self._cause_n = dict.fromkeys(DISPATCH_CAUSES, 0)
        # index = batches dispatched and not yet resolved at a dispatch
        self._inflight_n = [0] * (self._batches.maxlen + 1)
        self._starved_n = 0
        # session rows that rode a step with the side network in it, and
        # /config or datachannel writes of a row's conditioning scale
        self._cnet_rows = 0
        self._cnet_scale_writes = 0
        # from the ready watcher's stamps: by bucket k, the ready-to-ready
        # milliseconds and count of the steps queued behind the one before
        # (the device time of each); by GAP_CAUSES, the device's idle gaps
        # between two steps; by RESIDENCE_PIECES, a fetched frame's time in
        # the program in 0.1 ms bins (bin -> frames)
        self._ready_ms: dict = {}
        self._ready_n: dict = {}
        self._gap_ms = dict.fromkeys(GAP_CAUSES, 0.0)
        self._gap_max_ms = dict.fromkeys(GAP_CAUSES, 0.0)
        self._gap_n = dict.fromkeys(GAP_CAUSES, 0)
        self._res_hist = {piece: {} for piece in RESIDENCE_PIECES}
        self._last_ready: float | None = None  # this generation's newest
        self._epoch += 1

    def _count_hop_locked(self, name: str, seconds: float):
        ms = 1e3 * seconds
        self._hop_ms[name] += ms
        self._hop_n[name] += 1
        if ms > self._hop_max_ms[name]:
            self._hop_max_ms[name] = ms

    def _fold_frame(self, p: _PendingFrame, await_s: float, finish_s,
                    t_end: float | None = None):
        """Fold one fetched frame's hop stamps into the counters: the one
        short critical section a frame that the counters add (the batch's
        own stamps ride _note_step, which the first resolver already
        runs), and where the ready watcher's stamps wait to be booked.
        ``t_end``: when its fetch returned the output; with the track's
        pick-up stamp and the step it rode, the frame's residence is split
        at the launch's return and the earliest sight of the step done (the
        watcher's, or this fetch's own: the row is on the host).  A frame
        the track did not stamp is left out."""
        with self._stats_lock:
            self._book_ready_locked()
            for name, seconds in (
                ("hold", p.hold_s), ("early_return", p.early_s),
                ("pull_wait", p.pull_wait_s),
                ("coerce", p.coerce_s),
                ("stage_h2d", p.stage_s),
                ("enqueue_lock_wait", p.lock_wait_s),
                ("await_row", await_s), ("finish_output", finish_s),
            ):
                if seconds is not None:
                    self._count_hop_locked(name, seconds)
            if t_end is None or p.t_pickup is None or p.readback is None:
                return
            ready = p.t_ready
            if ready is not None:
                self._fold_residence_locked(
                    p.t_pickup, p.readback[0].launch[1], ready, t_end
                )

    def _fold_residence_locked(self, t_pick, t_launch, ready, t_end):
        """One frame's residence, pick-up to fetch return, and its three
        pieces: to the launch's return, on the device (to the step's ready
        stamp, or the fetch's return where the stamp woke later), after it.
        Each stamp is binned from the pick-up, so the pieces' bins sum to
        the residence's bin exactly."""
        ready = min(ready, t_end)
        r, lo, d = (
            int((t - t_pick) * _RES_BINS_PER_S) for t in (t_end, t_launch, ready)
        )
        for piece, b in zip(RESIDENCE_PIECES, (r, lo, d - lo, r - d)):
            self._res_hist[piece] = _bump(self._res_hist[piece], b)

    def _note_step(self, dt_s: float, batch: _DispatchedBatch):
        occupancy, entries, feed = batch.occupancy, batch.entries, batch.feed
        with self._stats_lock:  # dispatcher + inline-fetch callers
            self.steps_total += 1
            self._count_hop_locked("dispatch", batch.dispatch_s)
            self._count_hop_locked("launch", batch.launch[1] - batch.launch[0])
            self._cause_n[batch.cause] += 1
            self._inflight_n[min(batch.inflight, len(self._inflight_n) - 1)] += 1
            if batch.starved:
                self._starved_n += 1
            if self.has_controlnet:
                self._cnet_rows += occupancy
            self._occ_hist = _bump(self._occ_hist, occupancy)
            for _, plist in entries:
                for p in plist:
                    if p.t_dispatch is not None:
                        self._waits.append(p.t_dispatch - p.t_enq)
        cb = self.on_step
        if cb is not None and feed:
            # feed=False on a bucket's first use: a lazy compile may ride
            # that step, and compile time is not capacity (the warm-step
            # rule ResilientPipeline applies to its own EWMA feed)
            try:
                # per-batch-amortized: N sessions riding one step cost
                # dt/N each — THE number advertised capacity must reflect
                cb(dt_s / max(1, occupancy), occupancy)
            except Exception:
                logger.exception("batchsched on_step hook failed")

    def _watch_ready(self):
        """The ready watcher: takes the launched steps in dispatch order
        and blocks until each has left the device, inside
        ``rtc:step_ready`` (``step``, ``k``, ``frames`` as the step's
        ``rtc:launch`` and ``rtc:dispatch`` carry them: in a profiler trace
        the span ends after that step's ``jit_bucket`` event, by the wait
        for the GIL).  Its end is one sighting of the step done
        (_book_ready_locked books the earliest).  A step that raises is
        booked nothing; the watcher lives on."""
        while True:
            batch = self._ready_q.get()
            if batch is None:
                return
            ready = None
            try:
                with hop(
                    "step_ready", step=batch.step, k=batch.k, frames=batch.frames
                ) as wait:
                    jax.block_until_ready(batch.out)
                ready = wait.t1
            except Exception:
                pass  # the riders' fetches raise it; nothing to book
            self._note_ready(batch, ready)

    def _note_ready(self, batch: _DispatchedBatch, ready: float | None):
        """The watcher's record of one step (``ready`` None: it raised):
        drop the output, keep the stamp, hand the step to the counters.
        No lock and no booking here: the watcher wakes when a step ends,
        which is often while the next launch holds the GIL, so it holds
        the GIL for as few bytecodes as it can."""
        batch.out = None
        batch.ready = ready
        self._stamped.append(batch)
        self._ready_seq = batch.step
        if self._windowing and self._lock.acquire(blocking=False):
            # the dispatcher waits out a window with the device running:
            # wake it to look again (never blocks the watcher; a stamp
            # that races the wait's start falls back on the window's end)
            try:
                self._has_work.notify()
            finally:
                self._lock.release()

    def _book_ready_locked(self):
        """Book the steps the watcher stamped since the last call, in
        dispatch order (stats lock held).  A step's stamp is the earlier of
        the watcher's wake-up and ``ready_by`` (a thread that held the GIL
        while the step ended delays the watcher, not the step).  Against
        the previous step's stamp: a step whose launch returned before it
        was queued behind that step, so the device ran it from that stamp
        to its own (``step_ready``); any other launch ended a gap of the
        device, from that stamp to the launch's return (the call enqueues
        the program before it returns, so its host time is the device's to
        wait out), booked to the GAP_CAUSES member that covers most of
        it."""
        while self._stamped:
            batch = self._stamped.popleft()
            if batch.epoch != self._epoch:
                continue  # launched before the counters' last reset
            ready = batch.ready
            if ready is not None and batch.ready_by is not None:
                ready = batch.ready = min(ready, batch.ready_by)
            prev, self._last_ready = self._last_ready, ready
            if ready is None or prev is None:
                continue
            t1 = batch.launch[1]
            if t1 < prev:
                self._ready_ms = _bump(self._ready_ms, batch.k, 1e3 * (ready - prev))
                self._ready_n = _bump(self._ready_n, batch.k)
                continue
            cause = _gap_cause(prev, t1, *self._gap_spans(batch))
            ms = 1e3 * (t1 - prev)
            self._gap_ms[cause] += ms
            self._gap_n[cause] += 1
            if ms > self._gap_max_ms[cause]:
                self._gap_max_ms[cause] = ms

    @staticmethod
    def _gap_spans(batch: _DispatchedBatch):
        """(pull_wait, hold, window_wait) intervals before a launch, each
        (start, end) or None: the launching frame's track's wait for its
        camera and its hold (the submitting frame of an inline dispatch,
        else the rider picked up last), and the dispatcher's wait for
        riders."""
        p = batch.launcher
        if p is None or p.t_pickup is None:
            picked = [q for _, pl in batch.entries for q in pl if q.t_pickup is not None]
            p = max(picked, key=lambda q: q.t_pickup, default=None)
        pull = hold = None
        if p is not None:
            if p.pull_wait_s is not None:
                pull = (p.t_pickup - p.pull_wait_s, p.t_pickup)
            if p.t_hold is not None and p.hold_s is not None:
                hold = (p.t_hold, p.t_hold + p.hold_s)
        return pull, hold, batch.window

    def close(self):
        with self._has_work:
            self._stop = True
            self._has_work.notify()
        self._thread.join(timeout=10)
        self._ready_q.put(None)
        self._ready_thread.join(timeout=10)

    # -- observability --------------------------------------------------------

    @staticmethod
    def _percentile(sorted_vals, frac):
        n = len(sorted_vals)
        return sorted_vals[min(n - 1, int(n * frac))]

    def snapshot(self) -> dict:
        """/metrics gauges — O(1) int reads, one <=512-float reservoir
        (safe_list: the obs retry-copy idiom for lock-free appenders) and
        the copy-on-new-key dicts, never a frame-queue traversal.  Books
        the ready stamps not yet booked first (the stats lock, which no
        dispatch holds), so the stamps' counters are current."""
        with self._stats_lock:
            self._book_ready_locked()
        waits = sorted(safe_list(self._waits))
        out = {
            "batchsched_sessions": self.active.count(True),
            "batchsched_max_sessions": self.max_sessions,
            "batchsched_steps_total": self.steps_total,
            "batchsched_window_ms": round(1e3 * self.window_s, 3),
            "batchsched_dp": self.dp,
            "batchsched_fbs": self.fbs,
            "batchsched_occupancy_hist": {
                str(k): v for k, v in sorted(self._occ_hist.items())
            },
            # cumulative since the rehearsal, never reset while serving: a
            # later snapshot minus an earlier one is that window's.  Host
            # milliseconds and counts by hop (COUNTED_HOPS), the longest
            # single one beside them
            "batchsched_hop_ms_total": {
                h: round(v, 4) for h, v in self._hop_ms.items()
            },
            "batchsched_hop_count": dict(self._hop_n),
            "batchsched_hop_ms_max": {
                h: round(v, 4) for h, v in self._hop_max_ms.items()
            },
            "batchsched_dispatch_cause_total": dict(self._cause_n),
            "batchsched_dispatch_inflight_hist": {
                str(i): n for i, n in enumerate(list(self._inflight_n)) if n
            },
            "batchsched_dispatch_starved_total": self._starved_n,
            # from the ready watcher's stamps (_book_ready_locked): by bucket k,
            # the queued steps' ready-to-ready milliseconds and count; by
            # GAP_CAUSES, the device's idle gaps between steps; a fetched
            # frame's time in the program by piece (RESIDENCE_PIECES), bins
            # of 0.1 ms keyed by their lower edge in ms
            "batchsched_step_ready_ms_total": {
                str(k): round(v, 4) for k, v in sorted(self._ready_ms.items())
            },
            "batchsched_step_ready_count": {
                str(k): n for k, n in sorted(self._ready_n.items())
            },
            "batchsched_device_gap_ms_total": {
                c: round(v, 4) for c, v in self._gap_ms.items()
            },
            "batchsched_device_gap_count": dict(self._gap_n),
            "batchsched_device_gap_ms_max": {
                c: round(v, 4) for c, v in self._gap_max_ms.items()
            },
            **{
                f"batchsched_{piece}_ms_hist": {
                    f"{b / 10:.1f}": n for b, n in sorted(hist.items())
                }
                for piece, hist in self._res_hist.items()
            },
            "batchsched_attention_paths": dict(self.attention_paths),
            "batchsched_f32_relayout_copies": dict(self.relayout_copies),
            "batchsched_taesd_fold": dict(self.taesd_fold),
            "batchsched_denoiser": dict(self.denoiser),
            "batchsched_epilogue": dict(self.epilogues),
        }
        if self.has_controlnet:
            out["batchsched_controlnet_rows_total"] = self._cnet_rows
            out["batchsched_controlnet_scale_writes_total"] = (
                self._cnet_scale_writes
            )
        if self._adapter_rank:
            # style-adapter plane (adapters/): live sessions riding a
            # non-zero factor bank + total hot-swap control writes.
            # Lock-free like every gauge here (safe_list dict scan).
            out["adapter_sessions"] = sum(
                1 for s in safe_list(self._sessions.values())
                if s.adapter is not None
            )
            out["adapter_swaps_total"] = self.adapter_swaps_total
            out["adapter_rank"] = self._adapter_rank
        if self.dp > 1:
            # per-shard live-session occupancy (_slot_shard residence —
            # claim() balances it): the operator's view of how evenly the
            # session axis fills the mesh; bounded keys (dp values),
            # GIL-atomic list scan
            hist = {str(d): 0 for d in range(self.dp)}
            for s, live in enumerate(self.active):
                if live:
                    hist[str(self._slot_shard(s))] += 1
            out["batchsched_shard_sessions"] = hist
        if waits:
            out["batchsched_window_wait_ms_p50"] = round(
                1e3 * self._percentile(waits, 0.5), 3
            )
            out["batchsched_window_wait_ms_p99"] = round(
                1e3 * self._percentile(waits, 0.99), 3
            )
        return out

    def session_snapshots(self) -> dict:
        """{session_key -> per-session scheduler view} for /health —
        lock-free like the gauges above (safe_list retries the racy dict
        copy instead of queueing the event loop behind a dispatch)."""
        sessions = safe_list(self._sessions.values())
        return {sess.session_key: sess.snapshot() for sess in sessions}
