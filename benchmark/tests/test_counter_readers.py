"""The readers of the program's counters, on recorded snapshots
(``data/counters``: the window's open and close), and the wrapper the
benchmark puts around a session."""

import json
import os

import pytest

from benchmark.harness import Benchmark

from .conftest import HERE, REPO


def _ctx(name):
    with open(os.path.join(HERE, "data", "counters", name + ".json")) as f:
        data = json.load(f)

    class Result:
        counters_open, counters_close = data["open"], data["close"]

    class Ctx:
        result = Result

    return Ctx


def _read(metric, ctx):
    return Benchmark(REPO).reader({"name": metric})(ctx)


@pytest.mark.parametrize("metric,want", [
    # 60 of the window's 310 steps went off when the window ran out
    ("dispatch_window_share", 100 * 60 / 310),
    # 600 of the 1040 frames fetched in the window had their pull held
    ("hold_share", 100 * 600 / 1040),
    ("dispatch_starved_share", 100 * 10 / 310),
    ("dispatch_host_mean_ms", 930.0 / 310),
    ("window_wait_p50_ms", 1.25),
])
def test_a_window_with_mixed_causes(metric, want):
    assert _read(metric, _ctx("mixed_causes")) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    ("hold_share", 0.0),             # no held frame reads 0, not an error
    ("dispatch_window_share", 0.0),  # every step went off solo
    ("window_wait_p50_ms", None),    # the program kept no wait: nothing to read
])
def test_a_window_the_sources_pace(metric, want):
    assert _read(metric, _ctx("source_paced")) == want


@pytest.mark.parametrize("metric", ["hold_share", "dispatch_window_share"])
def test_a_program_without_the_counter_reads_nothing(metric):
    ctx = _ctx("source_paced")
    ctx.result.counters_open = ctx.result.counters_close = {}
    assert _read(metric, ctx) is None


def test_counters_delta_is_the_later_snapshot_minus_the_earlier():
    from benchmark.serve import counters_delta

    with open(os.path.join(HERE, "data", "counters", "mixed_causes.json")) as f:
        data = json.load(f)
    d = counters_delta(data["open"], data["close"])
    assert d["batchsched_occupancy_hist"] == {"1": 20, "2": 50, "3": 40, "4": 200}
    assert d["batchsched_dispatch_cause_total"]["window"] == 60
    assert d["batchsched_hop_count"]["hold"] == 600
    assert d["batchsched_dispatch_starved_total"] == 10
    assert d["batchsched_window_wait_ms_p50"] == 1.25  # a percentile: the later one's
    assert "batchsched_hop_ms_total" in d


def test_the_spanned_session_passes_on_what_it_does_not_define():
    """The track looks ``note_pull_wait`` (and ``frame_buffer_size``) up on
    the pipeline it is given: the benchmark's wrapper hands them through to
    the session, so hop ``pull_wait`` is counted under the benchmark too."""
    from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack
    from benchmark.serve import SpanLog, SpannedSession

    class Session:
        frame_buffer_size = 1

        def __init__(self):
            self.waits, self.submitted = [], []

        def note_pull_wait(self, seconds):
            self.waits.append(seconds)

        def submit(self, frame):
            self.submitted.append(frame)
            return ("handle", frame)

        def fetch(self, handle, src_frame=None):
            return handle[1]

    inner, log = Session(), SpanLog()
    wrapped = SpannedSession(inner, log)
    assert wrapped.frame_buffer_size == 1
    wrapped.note_pull_wait(0.25)
    assert inner.waits == [0.25]
    assert wrapped("f") == "f" and inner.submitted == ["f"]
    assert len(log.spans["submit"]) == 1 and len(log.spans["fetch"]) == 1
    with pytest.raises(AttributeError):
        wrapped.no_such_attribute
    # the track finds the counter's hook through the wrapper
    track = VideoStreamTrack(object(), wrapped, pipeline_depth=2)
    track._note_pull_wait(0.5)
    assert inner.waits == [0.25, 0.5]


def test_the_generators_lateness_median_and_tail_read_one_sample():
    from types import SimpleNamespace as NS

    lateness = [(9.9, 0.5)] + [(10.0 + k / 15, 0.0002 * (1 + k % 10)) for k in range(50)]
    ctx = NS(result=NS(lateness=lateness, t_open=10.0, t_close=20.0))
    assert _read("source_late_p50_ms", ctx) == pytest.approx(1.1)
    assert 1.8 <= _read("source_late_p95_ms", ctx) <= 2.0
    ctx.result.lateness = lateness[:1]  # nothing came due inside the window
    assert _read("source_late_p50_ms", ctx) is None
