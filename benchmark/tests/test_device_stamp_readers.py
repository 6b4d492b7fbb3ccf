"""The readers of a frame's time in the program, on a recorded window
(``data/counters/device_stamps.json``: the snapshots at the window's open,
at the traced span's two ends and at the close): each reads the window less
the traced span, and nothing from a program without the counters; and
``benchmark.tools.stamp_join``, which joins the ready stamps to a trace."""

import json
import os

import pytest

from benchmark.harness import Benchmark

from .conftest import HERE, REPO

METRICS = ("residence_p50_ms", "residence_p95_ms")


def _ctx(with_trace=True):
    with open(os.path.join(HERE, "data", "counters", "device_stamps.json")) as f:
        data = json.load(f)

    class Result:
        counters_open, counters_close = data["open"], data["close"]
        counters_trace = tuple(data["trace"]) if with_trace else None
        t_open, t_close = data["t_open"], data["t_close"]
        traced = tuple(data["traced"]) if with_trace else None

    class Ctx:
        result = Result

    return Ctx


def _read(metric, ctx):
    return Benchmark(REPO).reader({"name": metric})(ctx)


@pytest.mark.parametrize("metric,want", [
    # 400 frames: 100 at 66, 200 at 67, 80 at 68, 20 at 70 (0.1 ms bins)
    ("residence_p50_ms", 67.05),
    ("residence_p95_ms", 68.05),
])
def test_each_reader_reads_the_window_less_the_traced_span(metric, want):
    assert _read(metric, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric,want", [
    # with nothing traced, the span's slow frames count too
    ("residence_p50_ms", 67.05),
    ("residence_p95_ms", 96.05),
])
def test_a_window_with_no_traced_span_reads_all_of_it(metric, want):
    assert _read(metric, _ctx(with_trace=False)) == pytest.approx(want)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_counters_reads_nothing(metric):
    ctx = _ctx()
    strip = lambda c: {k: v for k, v in c.items() if "step_ready" not in k  # noqa: E731
                       and "device_gap" not in k and "_ms_hist" not in k}
    ctx.result.counters_open = strip(ctx.result.counters_open)
    ctx.result.counters_close = strip(ctx.result.counters_close)
    ctx.result.counters_trace = tuple(strip(c) for c in ctx.result.counters_trace)
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric", METRICS)
def test_a_window_with_nothing_to_read_reads_nothing(metric):
    """No frame split: nothing, not a zero."""
    ctx = _ctx()
    ctx.result.counters_close = ctx.result.counters_open
    ctx.result.counters_trace = (ctx.result.counters_open,) * 2
    assert _read(metric, ctx) is None


def test_the_reading_functions_read_an_untraced_runs_line():
    """``counters_window`` of a result line is close minus open: the same
    functions read it."""
    from benchmark.layer_metrics import residence
    from benchmark.serve import counters_delta

    with open(os.path.join(HERE, "data", "counters", "device_stamps.json")) as f:
        data = json.load(f)
    window = counters_delta(data["open"], data["close"])
    assert residence.percentile_ms(window[residence.KEY], 0.95) == pytest.approx(96.05)


def test_the_new_metrics_are_read_where_they_move_what_the_cell_reports():
    bench = Benchmark(REPO)
    for cell in bench.spec["workloads"]:
        names = [m["name"] for m in bench.per_layer(cell)]
        assert "residence_p50_ms" in names
        assert ("residence_p95_ms" in names) == (cell["name"] != "turbo512.trio15")


def _stamp_trace() -> str:
    """A hand-built trace (microseconds): three programs; step 2 was queued
    behind step 1 and the device started it 40 us after step 1 ended
    (unbooked: the host had it there); step 3's launch returned 69.8 us
    after step 2's ready stamp, in an idle time of 80."""
    from .test_scope_reduce import _event, _plane

    mods = " ".join(_event(1, t, 100) for t in (100, 240, 420))
    device = _plane(
        "/device:TPU:0", f'lines {{ id: 2 name: "XLA Modules" {mods} }}',
        {1: "jit_bucket(99)"},
    )
    host_names = {1: "bench:trace_window", 2: "rtc:launch", 3: "rtc:step_ready"}
    launches = " ".join([
        _event(1, 0, 1000),
        _event(2, 50, 10, [(1, 7)]), _event(2, 150, 10, [(1, 8)]), _event(2, 400, 10, [(1, 9)]),
    ])
    watcher = " ".join([
        _event(3, 60, 140.3, [(1, 7)]), _event(3, 200.3, 140.0, [(1, 8)]),
        _event(3, 410, 110.5, [(1, 9)]),
    ])
    host = _plane(
        "/host:CPU",
        f'lines {{ id: 7 name: "submit" {launches} }} '
        f'lines {{ id: 8 name: "ready" {watcher} }}',
        host_names, ("step",),
    )
    return device + " " + host


def _check_join(j):
    assert j["ready"]["steps"] == 3
    assert j["ready"]["offset_ms_median"] == pytest.approx(0.0003)
    assert j["ready"]["offset_ms_max"] == pytest.approx(0.0005)
    gaps = j["gaps"]
    assert gaps["pairs"] == 2
    assert gaps["idle_ms_total"] == pytest.approx(0.12, abs=1e-3)
    assert gaps["host_ms_total"] == pytest.approx(0.0698, abs=1e-3)
    assert gaps["unbooked_ms_total"] == pytest.approx(0.0502, abs=1e-3)
    assert gaps["longest_unbooked"][0]["step"] == 8


def test_stamp_join_reads_the_ready_stamps_against_the_trace():
    from jax.profiler import ProfileData

    from benchmark.tools.stamp_join import join_stamps

    _check_join(join_stamps(ProfileData.from_text_proto(_stamp_trace())))


def test_stamp_join_reads_the_trace_that_trace_report_saved(tmp_path, capsys):
    import gzip

    from jax.profiler import ProfileData

    from benchmark.tools import stamp_join

    path = tmp_path / "trace.gz"
    with gzip.open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_stamp_trace()))
    assert stamp_join.main([str(path)]) == 0
    _check_join(json.loads(capsys.readouterr().out))
