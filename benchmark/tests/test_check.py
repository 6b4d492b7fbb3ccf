"""The output check has to fail what it is there to catch.

The control: the program's own lower-precision path (int8 weights) in the
program's place.  The faults: the timed path broken underneath a whole run
(past the look for a chip), once per fault a serving cell can have: an
answer altered where it is produced (a block of pixels; a stale frame; two
sessions' rows swapped in the batch), early in the window or only late in
it.  A second control is of the comparison itself: the reference fed the
source frame after the one the session consumed.  Each has to come out
``correct`` false; the unbroken run comes out true (test_harness.py).
"""

import json
import os

import numpy as np
import pytest


@pytest.mark.parametrize("workload", ["tiny64.duo20", "tinyturbo64.duo20", "tinyxl64.duo20"])
def test_the_control_in_lower_precision_comes_out_not_correct(run_cell, workload):
    code, line, err = run_cell(workload, seed=41, control="w8")
    assert code == 0, err
    c = line["compared"]["session_bias_rel_max"]
    assert line["correct"] is False, c
    assert c["value"] > 3 * c["limit"]


@pytest.mark.parametrize("workload", ["tiny64.duo20", "tinyturbo64.duo20"])
def test_the_reference_fed_the_next_source_frame_comes_out_not_correct(run_cell, workload):
    """The input path (which frame, uint8 preprocess, TAESD encode) is in
    what is compared: one frame of the pan off is seen."""
    code, line, err = run_cell(workload, seed=46, control="wrong_frame")
    assert code == 0, err
    assert line["correct"] is False, line["compared"]


def _break_fetch(monkeypatch, alter):
    from ai_rtc_agent_tpu.stream import scheduler

    real = scheduler.ScheduledSession.fetch

    def broken(self, handle, src_frame=None):
        return alter(self, real(self, handle, src_frame))

    monkeypatch.setattr(scheduler.ScheduledSession, "fetch", broken)


def test_a_block_of_pixels_altered_comes_out_not_correct(run_cell, monkeypatch):
    def alter(sess, out):
        out = np.array(out)
        h, w = out.shape[0] // 2, out.shape[1] // 2
        out[:h, :w] = np.clip(out[:h, :w].astype(np.int16) + 12, 0, 255).astype(np.uint8)
        return out

    _break_fetch(monkeypatch, alter)
    code, line, err = run_cell("tinyturbo64.duo20", seed=42)
    assert code == 0, err
    assert line["correct"] is False and line["failed"] == 0


def test_a_fault_that_appears_late_in_the_window_comes_out_not_correct(run_cell, monkeypatch):
    """A step that carries state is followed from the claim only through
    the window's first frames; the window's last frames are compared by a
    reference session warmed in over the frames before them."""
    fetched = {}

    def alter(sess, out):
        n = fetched[sess.slot] = fetched.get(sess.slot, 0) + 1
        if n <= 40:  # 10 warm-up, 4 priming, the 8 head frames and then some
            return out
        out = np.array(out)
        h = out.shape[0] // 2
        out[:h] = np.clip(out[:h].astype(np.int16) + 12, 0, 255).astype(np.uint8)
        return out

    _break_fetch(monkeypatch, alter)
    code, line, err = run_cell("tiny64.duo20", seed=47)
    assert code == 0, err
    assert line["attempted"] > 100  # some 60 frames a session: the fault is in the last third
    assert line["correct"] is False and line["failed"] == 0


def test_a_session_started_afresh_has_the_followed_state_after_its_warm_in():
    """What the late-window comparison rests on: the reference's own state
    forgets where it started (the ring in as many steps as stages, the stock
    noise by 0.71 a step)."""
    from benchmark import check
    from benchmark.reference import sd_stream
    from benchmark.source import frame_at, session_texture

    from .conftest import HERE

    with open(os.path.join(HERE, "data", "configs", "tiny64.json")) as f:
        cfg = dict(json.load(f), name="tiny64")
    ref = check.reference_for(cfg, 5, sd_stream)
    assert ref.stateful and ref.warm_in_steps == 20
    tex = session_texture(9, 64, 64)
    followed, fresh = ref.session("neon", 9), ref.session("neon", 9)
    start = 7
    for k in range(start + ref.warm_in_steps + 1):
        a = followed.step(frame_at(tex, k, 64, 64))
        if k >= start:
            b = fresh.step(frame_at(tex, k, 64, 64))
    # a thousandth of the image's contrast (51 levels at this seed)
    assert np.abs(a - b).mean() < 1e-3 * a.std()


def test_rows_swapped_between_sessions_come_out_not_correct(run_cell, monkeypatch):
    """The stacked-state gather/scatter gone wrong: each session is handed
    the other's newest frame."""
    newest = {}

    def alter(sess, out):
        newest[sess.slot] = out
        other = newest.get(1 - sess.slot)
        return out if other is None else other

    _break_fetch(monkeypatch, alter)
    code, line, err = run_cell("tinyturbo64.duo20", seed=43)
    assert code == 0, err
    assert line["correct"] is False


def test_a_stale_frame_comes_out_not_correct(run_cell, monkeypatch):
    """A session whose state carries over (the latent ring) is handed its
    previous result again: every answer one step behind."""
    previous = {}

    def alter(sess, out):
        old = previous.get(sess.slot)
        previous[sess.slot] = out
        return out if old is None else old

    _break_fetch(monkeypatch, alter)
    code, line, err = run_cell("tiny64.duo20", seed=44)
    assert code == 0, err
    assert line["correct"] is False


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(run_cell, monkeypatch):
    """The bucket step computes its output but hands the stacked state back
    as it got it: the latent ring and the R-CFG stock noise never advance."""
    from ai_rtc_agent_tpu.stream import scheduler

    real = scheduler.make_bucket_step

    def broken(vstep, capacity, scatter_output=True):
        bucket = real(vstep, capacity, scatter_output)

        def stuck(params, states, frames_k, idx):
            _, out = bucket(params, states, frames_k, idx)
            return states, out

        return stuck

    monkeypatch.setattr(scheduler, "make_bucket_step", broken)
    code, line, err = run_cell("tiny64.duo20", seed=45)
    assert code == 0, err
    assert line["correct"] is False
