"""The scheduler stamps when each bucket step leaves the device: one ready
watcher blocks on every launched step in dispatch order, and from its
stamps come the queued steps' device time, the device's idle gaps booked to
what the host was doing, and a fetched frame's time in the program split at
the launch and the ready stamp (``batchsched_step_ready_*``,
``batchsched_device_gap_*``, ``batchsched_*_ms_hist``)."""

import asyncio
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.server import tracks
from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack
from ai_rtc_agent_tpu.stream.scheduler import (
    GAP_CAUSES, RESIDENCE_PIECES, BatchScheduler, _DispatchedBatch,
    _gap_cause, _PendingFrame,
)

H = W = 32
HISTS = [f"batchsched_{piece}_ms_hist" for piece in RESIDENCE_PIECES]


@pytest.fixture(scope="module")
def bundle():
    return registry.load_model_bundle("tiny-test")


@pytest.fixture(scope="module")
def cfg():
    return registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=H, width=W,
    )


def _sched(bundle, cfg, **kw):
    kw.setdefault("max_sessions", 1)
    kw.setdefault("prewarm", False)
    return BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt, **kw
    )


def _frame(rng):
    return rng.integers(0, 256, (H, W, 3), np.uint8)


def _settle(s, timeout=30.0):
    """Wait until the ready watcher has stamped every launched step."""
    deadline = time.monotonic() + timeout
    while s._newest is not None and s._ready_seq < s._newest.step:
        assert time.monotonic() < deadline, "the ready watcher fell behind"
        time.sleep(0.001)


def _record_stamps(s):
    """Every (batch, ready) the watcher books, in the order it books them."""
    seen = []
    note = s._note_ready

    def recording(batch, ready):
        seen.append((batch.step, batch.launch, ready))
        note(batch, ready)

    s._note_ready = recording
    return seen


def _hist_frames(snap, key):
    return sum(snap[key].values())


def _hist_ms(snap, key):
    return sum(float(b) * n for b, n in snap[key].items())


# -- the watcher on a live scheduler -------------------------------------------

def test_every_step_is_stamped_once_in_dispatch_order(bundle, cfg, rng):
    s = _sched(bundle, cfg)
    try:
        seen = _record_stamps(s)
        a = s.claim("a", prompt="p", seed=1)
        first = s._dispatch_seq + 1
        n = 7
        handles = []
        for _ in range(n):
            handles.append(a.submit(_frame(rng)))
            if len(handles) == 2:  # depth 2, as the track keeps it
                a.fetch(handles.pop(0))
        for h in handles:
            a.fetch(h)
        _settle(s)
        assert [step for step, _, _ in seen] == list(range(first, first + n))
        for _, (t0, t1), ready in seen:
            assert ready is not None and t0 <= t1 <= ready
        assert s._device_drained_locked()
        assert s._thread.is_alive() and s._ready_thread.is_alive()
    finally:
        s.close()
    assert not s._ready_thread.is_alive()  # close() joins the watcher


def test_serial_steps_are_starved_and_each_gap_is_booked(bundle, cfg, rng):
    """One frame at a time, each fetched and stamped before the next
    submit: every step finds the device drained, none is queued behind
    another, and the idle time between two steps is a gap of the device."""
    s = _sched(bundle, cfg)
    try:
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))
        _settle(s)
        c0 = s.snapshot()
        n = 4
        for _ in range(n):
            time.sleep(0.002)
            a(_frame(rng))
            _settle(s)
        snap = s.snapshot()
        starved = snap["batchsched_dispatch_starved_total"] - c0["batchsched_dispatch_starved_total"]
        assert starved == n
        assert snap["batchsched_step_ready_count"] == c0["batchsched_step_ready_count"]
        gaps = snap["batchsched_device_gap_count"]
        assert set(gaps) == set(GAP_CAUSES)
        assert sum(gaps.values()) - sum(c0["batchsched_device_gap_count"].values()) == n
        # no track, no dispatcher wait: the host was doing something else
        assert gaps["host"] - c0["batchsched_device_gap_count"]["host"] == n
        assert snap["batchsched_device_gap_ms_total"]["host"] >= n * 2.0
        assert snap["batchsched_device_gap_ms_max"]["host"] >= 2.0
    finally:
        s.close()


def test_a_step_raising_at_launch_is_booked_nothing(bundle, cfg, rng, monkeypatch):
    s = _sched(bundle, cfg)
    try:
        seen = _record_stamps(s)
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))
        _settle(s)
        launched = s._newest.step

        def broken(*args, **kw):
            raise RuntimeError("device says no")

        monkeypatch.setattr(s, "_bucket_step", lambda k, v: broken)
        with pytest.raises(RuntimeError):
            a.submit(_frame(rng))
        monkeypatch.undo()
        assert s._newest.step == launched and s._device_drained_locked()
        a(_frame(rng))
        _settle(s)
        assert [step for step, _, _ in seen] == [launched, s._newest.step]
        assert s._newest.step == launched + 2  # the broken launch took a number
        assert s._ready_thread.is_alive()
    finally:
        s.close()


def test_a_step_raising_on_the_device_leaves_the_watcher_alive(bundle, cfg, rng):
    class Lost:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    s = _sched(bundle, cfg)
    try:
        seen = _record_stamps(s)
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))
        _settle(s)
        c0 = s.snapshot()
        bad = _DispatchedBatch(
            [], [], 0.0, 1, launch=(0.0, 0.0), out=Lost(), step=-1,
            epoch=s._epoch,
        )
        s._ready_q.put(bad)
        a(_frame(rng))
        _settle(s)
        assert seen[-2] == (-1, (0.0, 0.0), None)  # booked nothing ...
        assert seen[-1][2] is not None               # ... and lived on
        assert bad.out is None and bad.ready is None
        snap = s.snapshot()
        # the step after the raising one has no stamp to measure from
        assert snap["batchsched_device_gap_count"] == c0["batchsched_device_gap_count"]
        assert snap["batchsched_step_ready_count"] == c0["batchsched_step_ready_count"]
        assert s._ready_thread.is_alive()
    finally:
        s.close()
    assert not s._ready_thread.is_alive()


def test_the_rehearsal_leaves_every_new_counter_at_zero(bundle, cfg, rng):
    s = _sched(bundle, cfg, max_sessions=2)
    try:
        s.rehearse()
        _settle(s)
        snap = s.snapshot()
        assert snap["batchsched_step_ready_ms_total"] == {}
        assert snap["batchsched_step_ready_count"] == {}
        for key in ("batchsched_device_gap_ms_total", "batchsched_device_gap_count",
                    "batchsched_device_gap_ms_max"):
            assert set(snap[key]) == set(GAP_CAUSES)
            assert set(snap[key].values()) == {0}, key
        for key in HISTS:
            assert snap[key] == {}, key
    finally:
        s.close()


# -- the track's stamps and a frame's time in the program ----------------------

class _Source:
    """A camera of ``period_s``: each ``recv`` waits for the next frame."""

    kind = "video"

    def __init__(self, rng, period_s):
        self._rng, self._period = rng, period_s

    async def recv(self):
        await asyncio.sleep(self._period)
        return _frame(self._rng)


class _Wrapper:
    """As the supervisor's wrappers: the handle comes back in a tuple."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def submit(self, frame):
        return ("wrapped", self._inner.submit(frame))

    def fetch(self, handle, src_frame=None):
        return self._inner.fetch(handle[1], src_frame)

    def __call__(self, frame):
        return self._inner(frame)


def test_each_fetched_frame_lands_once_in_each_residence_histogram(bundle, cfg, rng):
    s = _sched(bundle, cfg)
    try:
        sess = s.claim("a", prompt="p", seed=1)
        track = VideoStreamTrack(_Source(rng, 0.004), _Wrapper(sess), pipeline_depth=2)
        track.warmup_frames = 2  # through __call__: no pick-up stamp

        async def drive():
            return [await track.recv() for _ in range(6)]

        outs = asyncio.run(drive())
        assert all(o.shape == (H, W, 3) for o in outs)
        _settle(s)
        snap = s.snapshot()
        fetched = snap["batchsched_hop_count"]["await_row"]
        assert fetched == 2 + 6  # the warm-up frames are fetched, not split
        for key in HISTS:
            assert _hist_frames(snap, key) == 6, key
        # the pieces' bins sum to the residence's, frame by frame, so the
        # sums over the frames agree to the bin
        pieces = sum(_hist_ms(snap, k) for k in HISTS[1:])
        assert pieces == pytest.approx(_hist_ms(snap, HISTS[0]), abs=1e-6)
        # the camera's wait is before the pick-up: not in the residence
        assert _hist_ms(snap, HISTS[0]) > 0
        assert all(float(b) >= 0 for key in HISTS for b in snap[key])
    finally:
        s.close()


def test_the_pieces_of_one_frame_each_sit_within_one_bin(quiet):
    s = quiet
    _reset(s)
    t_pick, t_launch, ready, t_end = 10.0, 10.00734, 10.02219, 10.02577
    s._fold_residence_locked(t_pick, t_launch, ready, t_end)
    got = {p: next(iter(h)) / 10 for p, h in s._res_hist.items()}
    want = {
        "residence": t_end - t_pick, "to_launch": t_launch - t_pick,
        "on_device": ready - t_launch, "after_ready": t_end - ready,
    }
    for piece in RESIDENCE_PIECES:
        assert abs(got[piece] - 1e3 * want[piece]) < 0.1, piece
    assert got["to_launch"] + got["on_device"] + got["after_ready"] == pytest.approx(
        got["residence"]
    )
    # a stamp that woke after the fetch returned: the fetch's return bounds it
    _reset(s)
    s._fold_residence_locked(t_pick, t_launch, t_end + 0.003, t_end)
    assert s._res_hist["after_ready"] == {0: 1}


def test_the_track_stamps_the_pick_up_and_the_hold_through_wrappers():
    p = _PendingFrame(np.zeros((2, 2, 3), np.uint8))
    tracks._stamp_handle(("timing", ("supervisor", p)), t_pickup=4.0)
    assert (p.t_pickup, p.t_hold, p.hold_s) == (4.0, None, None)
    tracks._stamp_handle(p, t_pickup=5.0, t_hold=4.98, hold_s=0.015)
    assert (p.t_pickup, p.t_hold, p.hold_s) == (5.0, 4.98, 0.015)
    tracks._stamp_handle(("another pipeline's handle",), t_pickup=6.0)  # no place


# -- gaps booked by interval, on a fake clock ----------------------------------

@pytest.mark.parametrize("spans,want", [
    # the camera: the track waited from before the gap to most of it
    (((0.5, 8.0), None, None), "pull_wait"),
    # the hold covers most; the pull after it was quick
    (((9.0, 9.1), (1.0, 9.0), None), "hold"),
    # the dispatcher waited for riders over the gap's last 4.5 of 10
    (((3.0, 6.0), None, (5.5, 10.0)), "window_wait"),
    # nothing the host waited on covers most of it: the host's own work
    (((0.0, 2.0), (2.0, 4.0), None), "host"),
    ((None, None, None), "host"),
    # a tie goes to the earlier member
    (((0.0, 5.0), (5.0, 10.0), None), "pull_wait"),
])
def test_a_gap_is_booked_to_what_covers_most_of_it(spans, want):
    assert _gap_cause(0.0, 10.0, *spans) == want


def _fake_batch(s, step, t0, t1, launcher=None, window=None, k=1, riders=()):
    riders = list(riders) or ([launcher] if launcher is not None else [])
    return _DispatchedBatch(
        [], [(0, riders)] if riders else [], t0, 1, launch=(t0, t1), step=step,
        k=k, launcher=launcher, window=window, epoch=s._epoch,
    )


def _pending(pull_wait_s, t_pickup, t_hold=None, hold_s=None):
    p = _PendingFrame(np.zeros((2, 2, 3), np.uint8), pull_wait_s=pull_wait_s)
    p.t_pickup, p.t_hold, p.hold_s = t_pickup, t_hold, hold_s
    return p


@pytest.fixture(scope="module")
def quiet(bundle, cfg):
    """A scheduler whose watcher sees only the batches a test books by
    hand (nothing is ever dispatched on it)."""
    s = _sched(bundle, cfg)
    yield s
    s.close()


def _reset(s):
    with s._stats_lock:
        s._reset_counters_locked()


def test_queued_steps_and_gaps_are_told_apart(quiet):
    s = quiet
    _reset(s)
    s._note_ready(_fake_batch(s, 1, 0.000, 0.001), 0.020)
    # launched (returned) while step 1 ran: queued, its device time is
    # ready to ready
    s._note_ready(_fake_batch(s, 2, 0.010, 0.012), 0.034)
    # launched after step 2 had left: a gap of 7 ms to the launch's
    # return (the device waits out the launch's host time too)
    s._note_ready(_fake_batch(s, 3, 0.040, 0.041), 0.056)
    # its launch began before step 3's end and returned after it: a gap of 1
    s._note_ready(_fake_batch(s, 4, 0.055, 0.057), 0.071)
    # queued, in the k=2 bucket
    s._note_ready(_fake_batch(s, 5, 0.060, 0.062, k=2), 0.099)
    snap = s.snapshot()
    assert snap["batchsched_step_ready_count"] == {"1": 1, "2": 1}
    assert snap["batchsched_step_ready_ms_total"]["1"] == pytest.approx(14.0)
    assert snap["batchsched_step_ready_ms_total"]["2"] == pytest.approx(28.0)
    assert sum(snap["batchsched_device_gap_count"].values()) == 2
    assert snap["batchsched_device_gap_ms_total"]["host"] == pytest.approx(8.0)
    assert snap["batchsched_device_gap_ms_max"]["host"] == pytest.approx(7.0)


def test_the_gap_is_booked_from_the_launching_frames_stamps(quiet):
    s = quiet
    _reset(s)
    s._note_ready(_fake_batch(s, 1, 0.990, 0.991), 1.000)
    # the track waited for its camera over 1.001-1.010, then submitted
    cam = _pending(0.009, 1.010)
    s._note_ready(_fake_batch(s, 2, 1.0125, 1.013, launcher=cam), 1.025)
    # the track held its pull over 1.020-1.032
    held = _pending(0.0001, 1.0321, t_hold=1.020, hold_s=0.012)
    s._note_ready(_fake_batch(s, 3, 1.0345, 1.035, launcher=held), 1.050)
    # the dispatcher waited 4 ms for riders
    s._note_ready(_fake_batch(s, 4, 1.0552, 1.056, window=(1.051, 1.055)), 1.070)
    # the dispatcher's batch: the rider picked up last is the launching one
    early, last = _pending(0.001, 1.071), _pending(0.008, 1.0785)
    s._note_ready(_fake_batch(s, 5, 1.0800, 1.081, riders=(last, early)), 1.095)
    # no stamp covers it: the host's own work
    s._note_ready(_fake_batch(s, 6, 1.1000, 1.101, riders=(_pending(None, None),)), 1.110)
    snap = s.snapshot()
    count = snap["batchsched_device_gap_count"]
    assert count == {"pull_wait": 2, "hold": 1, "window_wait": 1, "host": 1}
    ms = snap["batchsched_device_gap_ms_total"]
    assert ms["pull_wait"] == pytest.approx(13.0 + 11.0)
    assert ms["hold"] == pytest.approx(10.0)
    assert ms["window_wait"] == pytest.approx(6.0)
    assert ms["host"] == pytest.approx(6.0)
    assert snap["batchsched_device_gap_ms_max"]["pull_wait"] == pytest.approx(13.0)


def test_another_threads_sight_of_the_step_done_tightens_the_stamp(quiet):
    """A drain check that finds the newest step's output ready, or a fetch
    with the row on the host, saw the step done before the watcher woke:
    the stamp is the earliest of them."""

    class Out:
        ready = False

        def is_ready(self):
            return self.ready

    s = quiet
    _reset(s)
    s._note_ready(_fake_batch(s, 1, 0.000, 0.001), 0.010)
    b = _fake_batch(s, 2, 0.012, 0.013)
    b.out = Out()
    s._newest = b
    try:
        assert not s._device_drained_locked() and b.ready_by is None
        b.out.ready = True
        t = time.monotonic()
        assert s._device_drained_locked()
        assert t <= b.ready_by <= time.monotonic()
        b.ready_by = 0.030
        s._note_ready(b, 0.0325)  # the watcher woke 2.5 ms later
        assert s._device_drained_locked()  # stamped: no poll
        snap = s.snapshot()
        assert b.ready == 0.030
        assert snap["batchsched_device_gap_ms_total"]["host"] == pytest.approx(3.0)
        assert snap["batchsched_step_ready_count"] == {}
    finally:
        s._newest = None


def test_a_batch_launched_before_a_reset_is_booked_nothing(quiet):
    s = quiet
    _reset(s)
    old = _fake_batch(s, 1, 0.0, 0.001)
    _reset(s)
    s._note_ready(old, 0.020)
    s._note_ready(_fake_batch(s, 2, 0.010, 0.011), 0.040)  # no stamp before it
    snap = s.snapshot()
    assert snap["batchsched_step_ready_count"] == {}
    assert sum(snap["batchsched_device_gap_count"].values()) == 0
    assert s._ready_seq == 2


def test_a_frame_fetched_before_the_watchers_stamp_is_split_at_the_fetch(quiet):
    """The fetch had the row on the host (``ready_by``) before the watcher
    woke: the frame is split then, and the watcher's later stamp moves
    nothing of it."""
    s = quiet
    _reset(s)
    p = _pending(None, 2.000)
    batch = _fake_batch(s, 1, 2.003, 2.00405)
    batch.ready_by = 2.02405
    p.readback = (batch, 0, None)
    s._fold_frame(p, 0.001, 0.0001, t_end=2.02515)
    want = {
        "batchsched_residence_ms_hist": {"25.1": 1},
        "batchsched_to_launch_ms_hist": {"4.0": 1},
        "batchsched_on_device_ms_hist": {"20.0": 1},
        "batchsched_after_ready_ms_hist": {"1.1": 1},
    }
    assert {key: s.snapshot()[key] for key in HISTS} == want
    s._note_ready(batch, 2.0246)
    assert {key: s.snapshot()[key] for key in HISTS} == want
    # a frame the track did not stamp is left out
    q = _pending(None, None)
    q.readback = (batch, 0, None)
    s._fold_frame(q, 0.001, 0.0001, t_end=2.030)
    assert sum(s.snapshot()["batchsched_residence_ms_hist"].values()) == 1


def test_the_watcher_books_nothing_itself(quiet):
    """The watcher only records its stamp: the counters book it at the next
    fetched frame or snapshot, under the lock they hold anyway."""
    s = quiet
    _reset(s)
    s._note_ready(_fake_batch(s, 1, 0.000, 0.001), 0.010)
    s._note_ready(_fake_batch(s, 2, 0.012, 0.013), 0.020)
    assert len(s._stamped) == 2 and s._ready_seq == 2
    assert s._gap_n["host"] == 0
    s._fold_frame(_pending(None, None), 0.001, None)
    assert not s._stamped and s._gap_n["host"] == 1
    assert s._gap_ms["host"] == pytest.approx(3.0)


def test_stamps_booked_by_many_fetching_threads_are_each_booked_once(quiet):
    """The watcher appends its stamps with no lock while the fetching
    threads book them under the stats lock: with more fetchers than cores
    and a short switch interval, every step but the first is booked once,
    as queued or as a gap, and none is lost."""
    s = quiet
    _reset(s)
    n = 3000
    stop = threading.Event()

    def fetcher():
        while not stop.is_set():
            s._fold_frame(_pending(None, None), 0.0, None)

    threads = [
        threading.Thread(target=fetcher) for _ in range(len(os.sched_getaffinity(0)) + 2)
    ]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for i in range(1, n + 1):
            while len(s._stamped) > 64:  # the device paces the watcher
                time.sleep(0)
            # odd steps launched after the previous stamp (a gap), even
            # ones before it (queued)
            t = 0.010 * i
            launch = (t + 0.001, t + 0.002) if i % 2 else (t - 0.004, t - 0.003)
            s._note_ready(_fake_batch(s, i, *launch), t + 0.008)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = s.snapshot()
    assert not s._stamped
    assert snap["batchsched_step_ready_count"] == {"1": n // 2}
    assert sum(snap["batchsched_device_gap_count"].values()) == n // 2 - 1


# -- the profiler's trace -------------------------------------------------------

def test_step_ready_spans_carry_the_launch_spans_step(bundle, cfg, rng):
    from jax._src.lib import _profiler

    s = _sched(bundle, cfg)
    try:
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))  # compile outside the trace
        _settle(s)
        jax.devices()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        session = _profiler.ProfilerSession(options)
        for _ in range(3):
            a(_frame(rng))
        _settle(s)
        xspace = session.stop()
    finally:
        s.close()
    from jax.profiler import ProfileData

    spans: dict = {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("rtc:launch", "rtc:dispatch", "rtc:step_ready"):
                    spans.setdefault(ev.name, []).append(
                        (dict(ev.stats), ev.start_ns + ev.duration_ns)
                    )
    launch = sorted(spans["rtc:launch"], key=lambda x: x[0]["step"])
    ready = sorted(spans["rtc:step_ready"], key=lambda x: x[0]["step"])
    dispatch = sorted(spans["rtc:dispatch"], key=lambda x: x[1])
    assert [ids["step"] for ids, _ in ready] == [ids["step"] for ids, _ in launch]
    assert len(ready) == 3
    for (lids, lend), (rids, rend), (dids, _) in zip(launch, ready, dispatch):
        assert rids["k"] == lids["k"] == 1
        assert rids["frames"] == dids["frames"]
        assert rend >= lend
