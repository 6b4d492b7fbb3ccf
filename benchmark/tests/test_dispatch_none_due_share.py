"""``dispatch_none_due_share`` on recorded snapshots (``data/counters``: the
window's open and close), read as the harness reads it."""

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
    # 850 of the window's 890 steps went off at once, none due in the window
    ("dispatch_none_due_share", 100 * 850 / 890),
    # the older reader counts the new cause in its denominator unedited
    ("dispatch_window_share", 100 * 30 / 890),
])
def test_a_window_the_staggered_sources_pace(metric, want):
    assert _read(metric, _ctx("none_due")) == pytest.approx(want)


def test_a_window_of_solo_steps_reads_0():
    ctx = _ctx("source_paced")
    for snap in (ctx.result.counters_open, ctx.result.counters_close):
        snap["batchsched_dispatch_cause_total"]["none_due"] = 0
    assert _read("dispatch_none_due_share", ctx) == 0.0


@pytest.mark.parametrize("counters", [
    {},
    # the parent's four causes, with no none_due among them
    {"batchsched_dispatch_cause_total": {
        "solo": 0, "inline_full": 3, "window": 500, "backpressure": 0}},
])
def test_a_program_without_the_cause_reads_nothing(counters):
    ctx = _ctx("none_due")
    ctx.result.counters_open = ctx.result.counters_close = counters
    assert _read("dispatch_none_due_share", ctx) is None


def test_it_is_read_in_the_staggered_cell_alone():
    bench = Benchmark(REPO)
    for cell in bench.spec["workloads"]:
        names = [m["name"] for m in bench.per_layer(cell)]
        assert ("dispatch_none_due_share" in names) == (
            cell["name"] == "turbo512.trio15"
        ), cell["name"]
