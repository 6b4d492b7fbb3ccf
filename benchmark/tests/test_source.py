"""The paced latest-wins source: due times from the schedule, not from the
consumer; superseded frames dropped and counted; lateness reported."""

import asyncio

import numpy as np
import pytest

from benchmark.source import PacedSource, frame_at, session_starts, session_texture


def test_frames_are_a_function_of_seed_and_index():
    a, b = session_texture(5, 64, 64), session_texture(5, 64, 64)
    assert a.dtype == np.uint8 and a.shape == (192, 192, 3)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, session_texture(6, 64, 64))
    f3 = frame_at(a, 3, 64, 64)
    assert f3.shape == (64, 64, 3) and f3.flags["C_CONTIGUOUS"]
    assert not np.array_equal(f3, frame_at(a, 4, 64, 64))
    assert np.array_equal(f3, frame_at(b, 3, 64, 64))
    # seeds the size of the driver's (over 2**31) are taken
    assert session_texture(2**31 + 17, 8, 8).shape == (136, 136, 3)


def test_due_times_come_from_the_schedule_and_a_slow_consumer_drops_frames():
    async def go():
        src = PacedSource(1, fps=100.0, height=8, width=8)
        src.start()
        picked = []
        for _ in range(6):
            frame = await src.recv()
            picked.append(src.handed[-1])
            assert frame.shape == (8, 8, 3)
            await asyncio.sleep(0.035)  # a consumer 3.5 periods slow
        await src.stop()
        return src, picked

    src, picked = asyncio.run(go())
    ks = [k for k, _, _ in picked]
    assert ks == sorted(set(ks)), "each hand-out is a newer frame"
    assert ks[-1] >= 12, "the schedule ran on while the consumer slept"
    for k, due, handed in picked:
        assert abs(due - (src.t0 + k / 100.0)) < 1e-9   # schedule, not consumer
        assert handed >= due
    # every due frame between two hand-outs was dropped, and counted
    assert src.superseded == ks[-1] - ks[0] - (len(ks) - 1) + ks[0]
    assert src.superseded >= 8
    assert src.superseded_between(picked[1][2], picked[-1][2] + 1) == sum(
        b - a - 1 for a, b in zip(ks, ks[1:])
    )
    # lateness: one reading per frame that came due, small and not negative
    assert len(src.lateness_s) >= ks[-1] + 1
    assert all(0.0 <= l < 0.05 for l in src.lateness_s)


def test_a_fast_consumer_waits_for_the_next_due_frame():
    async def go():
        src = PacedSource(2, fps=50.0, height=8, width=8)
        src.start()
        for _ in range(5):
            await src.recv()
        await src.stop()
        return src

    src = asyncio.run(go())
    assert [k for k, _, _ in src.handed] == [0, 1, 2, 3, 4]
    assert src.superseded == 0
    # the fifth frame cannot be handed out before it is due
    assert src.handed[-1][2] >= src.t0 + 4 / 50.0


class FakeClock:
    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now


@pytest.mark.parametrize("sessions,fps", [(4, 30.0), (3, 15.0)])
def test_the_two_phases_on_a_fake_clock(sessions, fps):
    """``aligned``: frame k of every session is due at one instant.
    ``staggered``: session i runs ``i / (sessions * fps)`` behind session 0,
    so a period's frames come due evenly spread over it."""
    clock = FakeClock()

    def sources(phase):
        out = []
        for i, at in enumerate(session_starts(clock(), sessions, fps, phase)):
            src = PacedSource(i, fps, 8, 8, clock=clock)
            src.t0 = at  # start() without the pacer task: no loop here
            out.append(src)
        return out

    for k in (0, 1, 7):
        due = [s.due_time(k) for s in sources("aligned")]
        assert due == [100.0 + k / fps] * sessions
        due = [s.due_time(k) for s in sources("staggered")]
        assert due == pytest.approx(
            [100.0 + k / fps + i / (sessions * fps) for i in range(sessions)])
        assert max(due) - min(due) < 1 / fps  # all inside one period
    with pytest.raises(ValueError, match="phase"):
        session_starts(0.0, 2, 30.0, "random")


def test_one_session_starts_as_it_did_before_there_was_a_phase():
    """Both phases give one session the instant itself, and a source started
    at no stated time takes its clock's now: what ``solo30`` / ``solo60``
    ran before the traffic file could state a phase."""
    assert session_starts(5.0, 1, 60.0, "aligned") == [5.0]
    assert session_starts(5.0, 1, 60.0, "staggered") == [5.0]
    assert session_starts(5.0, 1, 60.0) == [5.0]

    async def go():
        clock = FakeClock(7.0)
        a = PacedSource(1, 1000.0, 8, 8, clock=clock)
        b = PacedSource(1, 1000.0, 8, 8, clock=clock)
        a.start()
        b.start(at=7.25)
        await a.stop()
        await b.stop()
        return a, b

    a, b = asyncio.run(go())
    assert a.t0 == 7.0 and a.due_time(3) == 7.003
    assert b.t0 == 7.25 and b.due_time(0) == 7.25
