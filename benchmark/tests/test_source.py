"""The paced latest-wins source: due times from the schedule, not from the
consumer; superseded frames dropped and counted; lateness reported."""

import asyncio

import numpy as np

from benchmark.source import PacedSource, frame_at, session_texture


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
