"""``early_return_share`` on recorded snapshots (``data/counters``: the
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


def _read(ctx):
    return Benchmark(REPO).reader({"name": "early_return_share"})(ctx)


def test_a_window_the_sources_pace_below_the_knee():
    # 880 of the window's 900 fetched frames came back right after their submit
    assert _read(_ctx("early_return")) == pytest.approx(100 * 880 / 900)


def test_a_window_with_no_early_frame_reads_0():
    ctx = _ctx("early_return")
    for snap in (ctx.result.counters_open, ctx.result.counters_close):
        snap["batchsched_hop_count"]["early_return"] = 0
    assert _read(ctx) == 0.0


@pytest.mark.parametrize("counters", [{}, {"batchsched_hop_count": {"await_row": 14}}])
def test_a_program_without_the_counter_reads_nothing(counters):
    """The parent of the program that counts it: no hop ``early_return``
    (or no counters at all), and the metric is left out of the line."""
    ctx = _ctx("early_return")
    ctx.result.counters_open = ctx.result.counters_close = counters
    assert _read(ctx) is None


def test_every_cell_reads_it():
    bench = Benchmark(REPO)
    for cell in bench.spec["workloads"]:
        names = [m["name"] for m in bench.per_layer(cell)]
        assert "early_return_share" in names, cell["name"]
