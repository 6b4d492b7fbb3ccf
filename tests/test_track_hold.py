"""The pipelined ``VideoStreamTrack.recv()`` holds the pull that refills the
pipeline until the running step is about to end (ISSUE 30).  Everything here
runs on a fake clock: a fake device that takes ``step_s`` a frame, a fake
latest-wins source, a fake sleep that only moves the clock.  No test sleeps
on the wall clock."""

import asyncio
import types

import numpy as np
import pytest

from ai_rtc_agent_tpu.server import tracks
from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack

MS = 1e-3
LEAD, BLOCK = tracks._HOLD_LEAD_S, tracks._HOLD_BLOCK_S


class World:
    """One clock, one device queue, one camera.  ``submit`` costs the host
    ``submit_s`` and queues a step behind whatever the device still runs;
    ``fetch`` comes back ``tail_s`` after the frame's step ended."""

    def __init__(self, step_s, period_s, submit_s=3 * MS, tail_s=0.5 * MS,
                 short_steps=None):
        self.now = 1.0          # mid-stream: the camera has been running
        self.step_s, self.period_s = step_s, period_s
        self.submit_s, self.tail_s = submit_s, tail_s
        self.short_steps = short_steps or {}   # frame ordinal -> its step_s
        self.free_at = 0.0      # when the device has run all it was given
        self.steps = []         # one record per submitted frame
        self.pulled = []        # (k, when) of every source frame handed out
        self.sleeps = []        # every hold, as asked for
        self._last_k = -1

    # -- the source: frame k is due at k * period, latest wins ---------------
    async def source_recv(self):
        k = int(self.now / self.period_s)
        if k <= self._last_k:   # nothing new is due yet: wait for it
            k = self._last_k + 1
            self.now = k * self.period_s
        self._last_k = k
        self.pulled.append((k, self.now))
        return k

    # -- the session ---------------------------------------------------------
    def submit(self, frame):
        self.now += self.submit_s
        n = len(self.steps)
        start = max(self.now, self.free_at)
        rec = types.SimpleNamespace(
            frame=frame, landed=self.now, start=start,
            idle=start - self.free_at if n else 0.0,
            end=start + self.short_steps.get(n, self.step_s),
            slack=self.free_at - self.now,   # landed this long before the
            hold_s=None,                     # running step ended
        )
        self.free_at = rec.end
        self.steps.append(rec)
        return rec

    def fetch(self, rec, src_frame=None):
        self.now = max(self.now, rec.end + self.tail_s)
        return ("stylized", rec.frame)

    def __call__(self, frame):
        return self.fetch(self.submit(frame), frame)

    async def sleep(self, seconds):
        self.sleeps.append(seconds)
        self.now += seconds
        await asyncio.sleep(0)

    @property
    def slack_wanted(self):
        """Where the lead's target puts the submit: this long before the
        running step ends."""
        return LEAD - self.submit_s - self.tail_s


class Source:
    kind = "video"

    def __init__(self, world):
        self.recv = world.source_recv


def _track(world, pipeline=None, depth=2):
    track = VideoStreamTrack(
        Source(world), world if pipeline is None else pipeline,
        pipeline_depth=depth,
    )
    track.warmup_frames = 0
    track._clock = lambda: world.now
    track._sleep = world.sleep
    return track


def _drive(track, n):
    async def go():
        return [await track.recv() for _ in range(n)]

    return asyncio.run(go())


async def _parent_recv(world, pending, depth=2):
    """The pipelined branch as it was before the hold: pull the instant
    the previous fetch returned."""
    while len(pending) < depth:
        frame = await world.source_recv()
        pending.append((frame, world.submit(frame)))
    src, handle = pending.pop(0)
    return world.fetch(handle, src)


# (a) the device sets the pace ------------------------------------------------

@pytest.mark.parametrize("step_ms", [18, 26, 52, 100])
def test_device_bound_loop_lands_the_submit_just_before_the_step_ends(step_ms):
    """From a cold start, for any step: within 8 frames every submit lands
    a short lead before the running step ends, the device never runs dry,
    and a frame is on the host one step and one lead after it was pulled,
    not two steps."""
    w = World(step_ms * MS, period_s=1 / 120)
    _drive(_track(w), 40)
    assert all(rec.idle == 0.0 for rec in w.steps)
    for rec in w.steps[8:]:
        assert -0.05 * MS < rec.slack - w.slack_wanted <= 0.5 * MS, (step_ms, rec.slack)
    when_pulled = dict(w.pulled)
    last = w.steps[-2]
    assert last.slack == pytest.approx(w.slack_wanted, abs=0.05 * MS)
    assert last.end + w.tail_s - when_pulled[last.frame] == pytest.approx(
        w.step_s + LEAD, abs=0.05 * MS
    )
    # where the parent pulled it two steps before
    first = w.steps[2]
    assert first.end + w.tail_s - when_pulled[first.frame] == pytest.approx(2 * w.step_s)
    # and the rate is the step's
    assert w.steps[-1].end - w.steps[0].end == pytest.approx(40 * w.step_s)


@pytest.mark.parametrize("step_ms", [18, 26, 52, 100])
def test_a_camera_just_faster_than_the_step_is_held_once_the_device_lags(step_ms):
    """A source 8 % faster than the step: the loop starts at the source's
    pace (pulls wait, nothing is held), falls behind a little every frame,
    and is held from the frame on that finds the device busy."""
    w = World(step_ms * MS, period_s=step_ms * MS / 1.08)
    _drive(_track(w), 60)
    held = [rec.hold_s is not None for rec in w.steps]
    first = held.index(True)
    assert 3 <= first < 20 and all(held[first:])
    assert all(rec.idle == 0.0 for rec in w.steps)
    assert w.steps[-1].slack == pytest.approx(w.slack_wanted, abs=0.05 * MS)


@pytest.mark.parametrize("step_ms", [18, 26, 52, 100])
def test_hold_is_never_longer_than_the_step(step_ms):
    w = World(step_ms * MS, period_s=1 / 120)
    _drive(_track(w), 40)
    assert len(w.sleeps) == 38 and max(w.sleeps) < w.step_s
    # every held pull is stamped on its handle with the seconds it took
    assert [r.hold_s for r in w.steps if r.hold_s] == pytest.approx(w.sleeps)


@pytest.mark.parametrize("submit_ms,tail_ms", [(3, 0.5), (1, 0.2), (5, 0.5), (2, 3)])
def test_the_lead_is_the_same_whatever_the_hosts_submit_costs(submit_ms, tail_ms):
    """Pull-to-pixels is one step and one lead for a fast host and a slow
    one: what a slower submit eats is the slack, not the viewer's time."""
    w = World(26 * MS, 1 / 120, submit_s=submit_ms * MS, tail_s=tail_ms * MS)
    _drive(_track(w), 40)
    rec = w.steps[-2]
    assert all(r.idle == 0.0 for r in w.steps)
    assert rec.slack == pytest.approx(w.slack_wanted, abs=0.05 * MS)
    assert rec.end + w.tail_s - dict(w.pulled)[rec.frame] == pytest.approx(
        w.step_s + LEAD, abs=0.05 * MS
    )


@pytest.mark.parametrize("submit_ms", [6.2, 9, 15])
def test_a_submit_that_eats_the_whole_lead_still_keeps_the_device_fed(submit_ms):
    """The floor on the fetch's block takes over: the lead grows to the
    submit plus that floor, and the device does not run dry."""
    w = World(52 * MS, 1 / 120, submit_s=submit_ms * MS)
    _drive(_track(w), 60)
    assert all(r.idle == 0.0 for r in w.steps)
    rec = w.steps[-2]
    assert rec.slack + w.tail_s == pytest.approx(BLOCK, abs=0.05 * MS)
    assert rec.end + w.tail_s - dict(w.pulled)[rec.frame] == pytest.approx(
        w.step_s + w.submit_s + BLOCK, abs=0.05 * MS
    )


def test_depth_three_is_held_by_the_same_law():
    w = World(26 * MS, 1 / 120)
    _drive(_track(w, depth=3), 40)
    assert all(r.idle == 0.0 for r in w.steps)
    # one more step is queued when it lands: the oldest is about to end
    assert w.steps[-1].slack - w.step_s == pytest.approx(w.slack_wanted, abs=0.05 * MS)


# (b) the source sets the pace ------------------------------------------------

@pytest.mark.parametrize("step_ms,fps", [(18, 30), (26, 30), (5, 60), (52, 15)])
def test_source_bound_loop_holds_nothing_and_pulls_as_the_parent(step_ms, fps):
    w = World(step_ms * MS, period_s=1 / fps)
    _drive(_track(w), 30)
    assert w.sleeps == []
    assert all(r.hold_s is None for r in w.steps)

    ref = World(step_ms * MS, period_s=1 / fps)

    async def parent():
        pending = []
        for _ in range(30):
            await _parent_recv(ref, pending)

    asyncio.run(parent())
    assert w.pulled == ref.pulled
    assert [r.end for r in w.steps] == [r.end for r in ref.steps]


def test_a_pull_that_waited_for_its_source_ends_the_hold():
    """The camera slows down under a held loop: the first pull that has to
    wait for a frame zeroes the hold, though the fetch still blocked."""
    w = World(18 * MS, period_s=1 / 120)
    track = _track(w)
    _drive(track, 20)
    assert track._hold.seconds > 5 * MS
    w._last_k = int(w.now / w.period_s) + 3   # the next frame is late
    _drive(track, 1)
    assert w.pulled[-1][1] == pytest.approx(w._last_k * w.period_s)
    assert track._hold.seconds == 0.0


# (c) overshoot ---------------------------------------------------------------

def test_an_overshoot_is_followed_by_a_shorter_hold():
    """One step ends early: the held submit lands on a drained device, the
    fetch after it does not block, and the next hold is shorter at once."""
    w = World(52 * MS, period_s=1 / 120, short_steps={20: 47 * MS})
    track = _track(w)
    _drive(track, 40)
    settled = 52 * MS - LEAD
    assert w.steps[20].hold_s == pytest.approx(settled, abs=0.1 * MS)
    assert w.steps[21].hold_s == pytest.approx(settled, abs=0.1 * MS)
    assert w.steps[21].idle > 0.0          # the device did run dry, once
    assert w.steps[22].hold_s < settled - 0.4 * MS   # backed off at once
    assert all(r.idle == 0.0 for r in w.steps[22:])
    assert w.steps[-1].hold_s == pytest.approx(settled, abs=0.1 * MS)  # and back
    assert max(w.sleeps) < w.step_s


def test_a_late_wake_up_is_corrected_by_half_not_echoed():
    w = World(26 * MS, period_s=1 / 120)
    track = _track(w)
    _drive(track, 20)
    settled = track._hold.seconds
    sleep = w.sleep

    async def late(seconds):       # the event loop comes back 2 ms late, once
        await sleep(seconds)
        w.now += 2 * MS
        track._sleep = sleep

    track._sleep = late
    _drive(track, 1)
    assert w.steps[-1].idle == 0.0   # inside the slack: the device stayed fed
    assert w.steps[-1].hold_s == pytest.approx(settled + 2 * MS)
    assert track._hold.seconds == pytest.approx(settled - 1 * MS, abs=0.05 * MS)
    _drive(track, 8)
    assert track._hold.seconds == pytest.approx(settled, abs=0.05 * MS)


def test_one_stalled_fetch_unholds_the_next_pull():
    """The host stalls for 300 ms inside a fetch while the device runs on:
    when the fetch comes back the running step is long over, so holding
    the next pull would leave the device dry for the whole hold."""
    w = World(18 * MS, period_s=1 / 120)
    track = _track(w)
    _drive(track, 20)
    n = len(w.sleeps)
    fetch = w.fetch

    def stalled(rec, src=None):
        w.fetch = fetch
        out = fetch(rec, src)
        w.now += 0.3
        return out

    w.fetch = stalled
    _drive(track, 2)
    assert len(w.sleeps) == n + 1      # the pull after the stall was not held
    _drive(track, 8)
    assert max(w.sleeps) < w.step_s
    assert track._hold.seconds == pytest.approx(18 * MS - LEAD, abs=0.3 * MS)


# (d) no observation, no hold -------------------------------------------------

def test_the_first_pipelined_calls_hold_nothing():
    w = World(26 * MS, period_s=1 / 120)
    track = _track(w)
    _drive(track, 2)
    # the first call fills the empty pipeline (two pulls) and the second
    # is the first whose cycle is known: three pulls, none held
    assert len(w.pulled) == 3 and w.sleeps == []
    assert track._hold.seconds > 0.0
    _drive(track, 1)
    assert len(w.sleeps) == 1


def test_a_fresh_track_starts_from_nothing():
    hold = tracks._PullHold()
    assert hold.seconds == 0.0
    hold.observe(lead_s=52 * MS, fetch_s=48 * MS, pull_wait_s=0.0, now=1.0)
    assert hold.seconds == 0.0           # one return is no cycle
    hold.observe(lead_s=52 * MS, fetch_s=48 * MS, pull_wait_s=0.0, now=1.052)
    assert hold.seconds == pytest.approx(52 * MS - 2 * LEAD)  # found at once
    hold.observe(lead_s=2 * LEAD, fetch_s=9 * MS, pull_wait_s=0.0, now=1.104)
    assert hold.seconds == pytest.approx(52 * MS - 1.5 * LEAD)  # then by halves
    hold.observe(lead_s=LEAD, fetch_s=0.2 * MS, pull_wait_s=0.0, now=1.156)
    assert hold.seconds == pytest.approx(   # the fetch did not block
        52 * MS - 1.5 * LEAD - tracks._HOLD_GAIN * (BLOCK - 0.2 * MS)
    )
    hold.observe(lead_s=LEAD, fetch_s=3 * MS, pull_wait_s=5 * MS, now=1.208)
    assert hold.seconds == 0.0           # the pull waited


# (e) the other branches are as they were --------------------------------------

def test_depth_one_never_holds():
    w = World(18 * MS, period_s=1 / 60)
    track = _track(w, depth=1)
    outs = _drive(track, 12)
    assert w.sleeps == [] and len(outs) == 12
    assert track._hold.seconds == 0.0
    assert [k for k, _ in w.pulled] == [r.frame for r in w.steps]


def test_frame_buffer_batches_never_hold():
    w = World(18 * MS, period_s=1 / 60)

    class Batched:
        frame_buffer_size = 2

        def submit_batch(self, frames):
            return [w.submit(f) for f in frames]

        def fetch_batch(self, handles, srcs=None):
            return [w.fetch(h) for h in handles]

        submit, fetch, __call__ = w.submit, w.fetch, w.__call__

    track = _track(w, pipeline=Batched())
    outs = _drive(track, 12)
    assert len(outs) == 12 and w.sleeps == []
    assert track._hold.seconds == 0.0


def test_a_pipeline_without_submit_never_holds():
    w = World(18 * MS, period_s=1 / 60)
    track = _track(w, pipeline=w.__call__)
    assert track.pipeline_depth == 1
    _drive(track, 6)
    assert w.sleeps == []


def test_a_handle_with_no_place_for_the_seconds_is_left_alone():
    """Other pipelines hand out tuples of arrays and flags: the hold
    engages all the same, and nothing is stamped."""
    w = World(18 * MS, period_s=1 / 120)
    recs = {}

    class Tupled:
        def submit(self, frame):
            recs[frame] = w.submit(frame)
            return ("dev", np.zeros(3), frame, True)

        def fetch(self, handle, src=None):
            return w.fetch(recs.pop(handle[2]), src)

    _drive(_track(w, pipeline=Tupled()), 20)
    assert len(w.sleeps) == 18
    assert all(r.hold_s is None for r in w.steps)


def test_the_seconds_reach_a_handle_that_the_agents_wrappers_nest():
    """``_TimedPipeline`` hands out ``(handle, t_submit)`` around the
    supervisor's ``("live", handle, frame)``."""
    w = World(18 * MS, period_s=1 / 120)

    class Nested:
        def submit(self, frame):
            return ("live", w.submit(frame), frame), 12.5

        def fetch(self, handle, src=None):
            return w.fetch(handle[0][1], src)

    _drive(_track(w, pipeline=Nested()), 20)
    assert len(w.sleeps) == 18
    assert [r.hold_s for r in w.steps if r.hold_s] == pytest.approx(w.sleeps)


# (f) under the benchmark's wrapper, into the scheduler's counters -------------

class NarrowWrapper:
    """``benchmark/serve.py SpannedSession``'s surface and nothing else:
    ``submit``, ``fetch``, ``__call__``, ``frame_buffer_size``.  The fake
    device's timing rides on it; the session inside is the real one, and
    its handles pass through as they are."""

    def __init__(self, inner, world):
        self._inner, self._world, self._recs = inner, world, {}

    @property
    def frame_buffer_size(self):
        return self._inner.frame_buffer_size

    def submit(self, frame):
        handle = self._inner.submit(frame)
        self._recs[id(handle)] = self._world.submit(frame)
        return handle

    def fetch(self, handle, src_frame=None):
        out = self._inner.fetch(handle, src_frame)
        self._world.fetch(self._recs.pop(id(handle)))
        return out

    def __call__(self, frame):
        return self.fetch(self.submit(frame), frame)


@pytest.fixture(scope="module")
def sched():
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=32, width=32,
    )
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=1, prewarm=False,
    )
    yield s
    s.close()


def _real_frames(world, rng):
    async def recv():
        await world.source_recv()
        return rng.integers(0, 256, (32, 32, 3), np.uint8)

    return types.SimpleNamespace(kind="video", recv=recv)


def test_hold_engages_under_a_narrow_wrapper_and_the_scheduler_counts_it(sched, rng):
    w = World(26 * MS, period_s=1 / 120)
    sess = sched.claim("held", prompt="p", seed=1)
    try:
        wrapped = NarrowWrapper(sess, w)
        assert not hasattr(wrapped, "note_pull_wait")
        track = _track(w, pipeline=wrapped)
        track.track = _real_frames(w, rng)
        before = sched.snapshot()
        outs = _drive(track, 16)
        assert all(o.shape == (32, 32, 3) and o.dtype == np.uint8 for o in outs)
        snap = sched.snapshot()
        count, ms = snap["batchsched_hop_count"], snap["batchsched_hop_ms_total"]
        fetched = count["await_row"] - before["batchsched_hop_count"]["await_row"]
        assert fetched == 16
        # 17 frames pulled, the first three unheld, the newest still in flight
        assert len(w.sleeps) == 14
        assert count["hold"] - before["batchsched_hop_count"]["hold"] == 13
        assert ms["hold"] - before["batchsched_hop_ms_total"]["hold"] == pytest.approx(
            1e3 * sum(w.sleeps[:13]), rel=1e-6
        )
        assert snap["batchsched_hop_ms_max"]["hold"] == pytest.approx(
            1e3 * max(w.sleeps[:13]), abs=1e-3
        )
        assert count["pull_wait"] == before["batchsched_hop_count"]["pull_wait"]
    finally:
        sess.release()


def test_source_bound_session_counts_no_hold(sched, rng):
    w = World(10 * MS, period_s=1 / 30)
    sess = sched.claim("unheld", prompt="p", seed=2)
    try:
        track = _track(w, pipeline=NarrowWrapper(sess, w))
        track.track = _real_frames(w, rng)
        before = sched.snapshot()["batchsched_hop_count"]
        _drive(track, 10)
        after = sched.snapshot()["batchsched_hop_count"]
        assert after["await_row"] - before["await_row"] == 10
        assert after["hold"] == before["hold"] and w.sleeps == []
    finally:
        sess.release()
