"""The program names its own host work (ISSUE 25, host side): one span
helper (``obs/trace.py hop``) writes every hop of the frame path into JAX's
profiler trace as ``rtc:<hop>``, its clock reads feed cumulative counters in
``BatchScheduler.snapshot()``, and a ``FrameTrace`` riding the frame is
stamped exactly as before."""

import asyncio
import time

import jax
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.obs import trace as T
from ai_rtc_agent_tpu.obs.trace import FrameTrace, STAGES, hop
from ai_rtc_agent_tpu.stream.scheduler import (
    COUNTED_HOPS, DISPATCH_CAUSES, BatchScheduler,
)

H = W = 32


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
    kw.setdefault("max_sessions", 2)
    kw.setdefault("prewarm", False)
    return BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt, **kw
    )


def _frame(rng):
    return rng.integers(0, 256, (H, W, 3), np.uint8)


class _TracedFrame:
    """A frame that can carry a FrameTrace (the software tiers' kind)."""

    def __init__(self, arr, trace):
        self._arr, self.trace = arr, trace

    def to_ndarray(self, format="rgb24"):
        return self._arr


# -- the helper ---------------------------------------------------------------

def test_hop_stamps_both_sides_and_allocates_no_frame_trace(monkeypatch):
    made = []
    init = FrameTrace.__init__
    monkeypatch.setattr(
        FrameTrace, "__init__",
        lambda self, *a, **kw: (made.append(1), init(self, *a, **kw))[1],
    )
    before = time.monotonic()
    with hop("coerce", slot=0, seq=1) as h:
        pass
    assert before <= h.t0 <= h.t1 <= time.monotonic()
    assert h.seconds == h.t1 - h.t0
    assert made == []  # no profiler session, no FrameTrace: nothing minted


def test_hop_stamps_a_riding_frame_trace_under_its_own_name():
    ft = FrameTrace(1, "s")
    with hop("submit", ft, slot=0, seq=1) as h:
        pass
    assert ft.spans == [("submit", h.t0, h.t1)]


def test_hop_closes_on_a_raise():
    ft = FrameTrace(1, "s")
    with pytest.raises(RuntimeError):
        with hop("fetch", ft):
            raise RuntimeError("x")
    assert [n for n, _, _ in ft.spans] == ["fetch"]


def test_hop_taxonomy_is_the_stages_tuple():
    """The counted hops, and every hop name the package's sites use, are
    members of the one taxonomy (STAGES, extended), each once."""
    import pathlib
    import re

    assert len(set(STAGES)) == len(STAGES)
    assert set(COUNTED_HOPS) <= set(STAGES)
    pkg = pathlib.Path(T.__file__).resolve().parents[1]
    used = set()
    for path in pkg.rglob("*.py"):
        used |= set(re.findall(r'\bhop\(\s*"([a-z_0-9]+)"', path.read_text()))
    assert {"submit", "dispatch", "launch", "fetch", "await_row"} <= used
    assert used <= set(STAGES), used - set(STAGES)


def test_one_helper_builds_every_profiler_annotation():
    import pathlib

    pkg = pathlib.Path(T.__file__).resolve().parents[1]
    sites = [
        str(p.relative_to(pkg)) for p in pkg.rglob("*.py")
        if "TraceAnnotation(" in p.read_text()
        or "_annotation(" in p.read_text()
    ]
    assert sites == ["obs/trace.py"]


# -- the counters -------------------------------------------------------------

def test_bare_ndarray_session_counts_every_hop(bundle, cfg, rng):
    """No FrameTrace rides a bare ndarray, and every hop is still counted:
    each per-frame hop once a frame, each per-step hop once a step."""
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        n = 6
        handles = []
        for i in range(n):
            a.note_pull_wait(0.001 * (i + 1))
            handles.append(a.submit(_frame(rng)))
            if i >= 2:  # as the track: the first pulls are not held
                handles[-1].hold_s = 0.002 * (i + 1)
            if len(handles) == 2:  # depth 2, as the track keeps it
                a.fetch(handles.pop(0))
        for h in handles:
            a.fetch(h)
        snap = s.snapshot()
        count, ms = snap["batchsched_hop_count"], snap["batchsched_hop_ms_total"]
        assert set(count) == set(ms) == set(COUNTED_HOPS)
        assert set(snap["batchsched_hop_ms_max"]) == set(COUNTED_HOPS)
        assert a.frames_submitted == n
        for per_frame in ("pull_wait", "coerce", "stage_h2d", "enqueue_lock_wait",
                          "await_row", "finish_output"):
            assert count[per_frame] == n, per_frame
        assert count["hold"] == n - 2  # the frames whose pull was held
        assert ms["hold"] == pytest.approx(2.0 * (3 + 4 + 5 + 6), rel=1e-6)
        assert snap["batchsched_hop_ms_max"]["hold"] == pytest.approx(12.0)
        assert count["dispatch"] == count["launch"] == snap["batchsched_steps_total"] == n
        assert ms["pull_wait"] == pytest.approx(1.0 + 2 + 3 + 4 + 5 + 6, rel=1e-6)
        assert snap["batchsched_hop_ms_max"]["pull_wait"] == pytest.approx(6.0)
        assert all(ms[h] > 0 for h in COUNTED_HOPS)
        assert ms["launch"] <= ms["dispatch"]
        # one session: every step is the solo path's
        assert snap["batchsched_dispatch_cause_total"] == {
            "solo": n, "inline_full": 0, "window": 0, "backpressure": 0,
        }
        assert sum(snap["batchsched_dispatch_inflight_hist"].values()) == n
        assert set(snap["batchsched_dispatch_inflight_hist"]) <= {"0", "1"}
        # the first step finds the device drained, by definition
        assert 1 <= snap["batchsched_dispatch_starved_total"] <= n
        assert snap["batchsched_h2d_bytes_total"] == n * H * W * 3
        assert snap["batchsched_d2h_bytes_total"] == n * H * W * 3
    finally:
        s.close()


def test_counters_are_cumulative_and_window_by_subtraction(bundle, cfg, rng):
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))
        c0 = s.snapshot()
        for _ in range(3):
            a(_frame(rng))
        c1 = s.snapshot()
        assert c1["batchsched_hop_count"]["coerce"] - c0["batchsched_hop_count"]["coerce"] == 3
        assert c1["batchsched_hop_ms_total"]["dispatch"] > c0["batchsched_hop_ms_total"]["dispatch"]
        assert c1["batchsched_hop_count"]["pull_wait"] == 0  # no track told it
        assert c1["batchsched_hop_count"]["hold"] == 0
        assert c1["batchsched_hop_ms_total"]["hold"] == 0.0
    finally:
        s.close()


def test_two_sessions_give_inline_full_and_window_causes(bundle, cfg, rng):
    s = _sched(bundle, cfg, window_ms=30.0)
    try:
        a = s.claim("a", prompt="pa", seed=1)
        b = s.claim("b", prompt="pb", seed=2)
        # both submit: the second completes the batch on its own thread
        ha, hb = a.submit(_frame(rng)), b.submit(_frame(rng))
        a.fetch(ha), b.fetch(hb)
        # only a submits: the dispatcher goes with who showed up
        a.fetch(a.submit(_frame(rng)))
        snap = s.snapshot()
        cause = snap["batchsched_dispatch_cause_total"]
        assert set(cause) == set(DISPATCH_CAUSES)
        assert cause["inline_full"] == 1 and cause["window"] == 1
        assert sum(cause.values()) == snap["batchsched_steps_total"] == 2
        assert snap["batchsched_occupancy_hist"] == {"1": 1, "2": 1}
        assert snap["batchsched_hop_count"]["dispatch"] == 2
        assert snap["batchsched_hop_count"]["coerce"] == 3
    finally:
        s.close()


def test_backpressure_cause_when_only_the_inflight_cap_held_the_step(bundle, cfg, rng):
    """Two batches in flight and unfetched: the third frame queues, and the
    dispatcher takes it once a batch resolves."""
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        hs = [a.submit(_frame(rng)) for _ in range(3)]
        for h in hs:
            a.fetch(h)
        cause = s.snapshot()["batchsched_dispatch_cause_total"]
        assert cause == {"solo": 2, "inline_full": 0, "window": 0, "backpressure": 1}
    finally:
        s.close()


def test_rehearsal_leaves_the_counters_at_zero(bundle, cfg):
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        s.rehearse()
        snap = s.snapshot()
        assert snap["batchsched_steps_total"] == 0
        assert set(snap["batchsched_hop_count"].values()) == {0}
        assert set(snap["batchsched_dispatch_cause_total"].values()) == {0}
        assert snap["batchsched_dispatch_starved_total"] == 0
        assert snap["batchsched_dispatch_inflight_hist"] == {}
        assert s._dispatch_seq >= 1  # the chip's program count is not reset
    finally:
        s.close()


def test_rehearsal_clears_the_hold_counter(bundle, cfg, rng):
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        handle = a.submit(_frame(rng))
        handle.hold_s = 0.0125
        a.fetch(handle)
        a.release()
        snap = s.snapshot()
        assert snap["batchsched_hop_count"]["hold"] == 1
        assert snap["batchsched_hop_ms_total"]["hold"] == pytest.approx(12.5)
        s.rehearse()
        snap = s.snapshot()
        assert snap["batchsched_hop_count"]["hold"] == 0
        assert snap["batchsched_hop_ms_total"]["hold"] == 0.0
        assert snap["batchsched_hop_ms_max"]["hold"] == 0.0
    finally:
        s.close()


def test_track_counts_its_wait_for_the_source(bundle, cfg, rng):
    """VideoStreamTrack._pull_fresh: a counter, never a span across the
    await; reaches the session through any attribute-passing wrapper."""
    from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack

    class Source:
        kind = "video"

        async def recv(self):
            await asyncio.sleep(0.01)
            return _frame(rng)

    class Wrapper:  # as the supervisor's wrappers: passes attributes through
        def __init__(self, inner):
            self._inner = inner
            self.submit, self.fetch = inner.submit, inner.fetch

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __call__(self, frame):
            return self._inner(frame)

    s = _sched(bundle, cfg, max_sessions=1)
    try:
        sess = s.claim("a", prompt="p", seed=1)
        track = VideoStreamTrack(Source(), Wrapper(sess), pipeline_depth=2)
        track.warmup_frames = 0

        async def drive():
            return [await track.recv() for _ in range(3)]

        outs = asyncio.run(drive())
        assert all(o.shape == (H, W, 3) for o in outs)
        snap = s.snapshot()
        n = snap["batchsched_hop_count"]["pull_wait"]
        assert n == 3  # frames fetched so far (a fourth is in flight)
        assert snap["batchsched_hop_ms_total"]["pull_wait"] >= n * 9.0
        # a track that waits for its source holds nothing
        assert snap["batchsched_hop_count"]["hold"] == 0
        assert snap["batchsched_hop_ms_total"]["hold"] == 0.0
    finally:
        s.close()


# -- the profiler's trace -----------------------------------------------------

def _host_spans(xspace: bytes) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xspace)
    return [
        (ev.name, dict(ev.stats), ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("rtc:")
    ]


def test_every_prompt_encode_is_spanned_and_counted(bundle, cfg):
    """The text towers run on a claim, on a session's prompt write and on
    the global one (``POST /config``): each is one ``rtc:encode_prompt``
    span in the profiler's trace and one count of the ``encode_prompt``
    hop, whose first count is the default prompt the scheduler encodes
    while it is built."""
    from jax._src.lib import _profiler

    s = _sched(bundle, cfg, max_sessions=1)
    try:
        built = s.snapshot()["batchsched_hop_count"]["encode_prompt"]
        jax.devices()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        session = _profiler.ProfilerSession(options)
        a = s.claim("a", prompt="p", seed=1)
        a.update_prompt("q")
        s.update_prompt("r")
        spans = [sp for sp in _host_spans(session.stop()) if sp[0] == "rtc:encode_prompt"]
        snap = s.snapshot()
    finally:
        s.close()
    assert built == 1 and len(spans) == 3
    assert snap["batchsched_hop_count"]["encode_prompt"] == 4
    traced_ms = sum(t1 - t0 for _, _, t0, t1 in spans) / 1e6
    assert 0 < traced_ms <= snap["batchsched_hop_ms_total"]["encode_prompt"]
    assert snap["batchsched_hop_ms_max"]["encode_prompt"] * 4 >= (
        snap["batchsched_hop_ms_total"]["encode_prompt"]
    )


def test_profiler_session_holds_the_hops_with_matching_ids(bundle, cfg, rng):
    from jax._src.lib import _profiler

    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        a(_frame(rng))  # compile outside the trace
        jax.devices()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        session = _profiler.ProfilerSession(options)
        for _ in range(3):
            a(_frame(rng))
        spans = _host_spans(session.stop())
    finally:
        s.close()
    by_name: dict = {}
    for name, ids, t0, t1 in spans:
        by_name.setdefault(name, []).append((ids, t0, t1))
    for name in ("rtc:submit", "rtc:coerce", "rtc:stage_h2d", "rtc:enqueue",
                 "rtc:dispatch", "rtc:assemble", "rtc:launch",
                 "rtc:readback_start", "rtc:fetch", "rtc:await_row",
                 "rtc:finish_output"):
        assert len(by_name[name]) == 3, name
    # frames 2, 3, 4 of slot 0: a frame's submit, the step it rode and its
    # fetch are joined by slot and seq
    for i, seq in enumerate((2, 3, 4)):
        sub, t0, t1 = by_name["rtc:submit"][i]
        assert (sub["slot"], sub["seq"]) == (0, seq)
        disp, d0, d1 = by_name["rtc:dispatch"][i]
        assert disp["frames"] == f"0:{seq}" and disp["cause"] == "solo"
        assert (disp["k"], disp["riders"]) == (1, 1)
        assert t0 <= d0 <= d1 <= t1  # the inline dispatch is submit's child
        launch, l0, l1 = by_name["rtc:launch"][i]
        assert d0 <= l0 <= l1 <= d1
        fetch, f0, f1 = by_name["rtc:fetch"][i]
        assert (fetch["slot"], fetch["seq"]) == (0, seq)
        wait, w0, w1 = by_name["rtc:await_row"][i]
        assert f0 <= w0 <= w1 <= f1 and wait["seq"] == seq
    steps = [ids["step"] for ids, _, _ in by_name["rtc:launch"]]
    assert steps == [steps[0], steps[0] + 1, steps[0] + 2]


# -- the frame timeline, as before --------------------------------------------

def test_frame_trace_gets_the_same_spans_as_before(bundle, cfg, rng):
    s = _sched(bundle, cfg, max_sessions=1)
    try:
        a = s.claim("a", prompt="p", seed=1)
        ft = FrameTrace(7, "a")
        frame = _TracedFrame(_frame(rng), ft)
        out = a.fetch(a.submit(frame), frame)
        assert out.shape == (H, W, 3)
        assert [n for n, _, _ in ft.spans] == [
            "submit", "batch_join", "engine_step", "fetch",
        ]
        span = {n: (t0, t1) for n, t0, t1 in ft.spans}
        assert span["submit"][0] <= span["batch_join"][0] <= span["batch_join"][1]
        assert span["batch_join"][1] == span["engine_step"][0]  # the dispatch stamp
        assert span["engine_step"][1] == span["fetch"][1]       # the resolve stamp
        assert span["fetch"][0] >= span["submit"][1]
        assert [m for m, _ in ft.marks] == ["batch_k1"]
    finally:
        s.close()
