"""The trace reduction on a hand-built trace with known busy, idle and
kernel times (times in microseconds below; the proto wants picoseconds)."""

import pytest

from benchmark import trace_reduce


def _event(meta, start_us, dur_us, stat=None):
    s = f"events {{ metadata_id: {meta} offset_ps: {int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)}"
    if stat:
        s += f' stats {{ metadata_id: 1 str_value: "{stat}" }}'
    return s + " }"


def _plane(name, lines, names):
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for i, n in names.items()
    )
    return (
        f'planes {{ name: "{name}" {lines} {meta} '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }'
    )


def build():
    names = {1: "fusion.1", 2: "custom-call.7", 3: "fusion.2", 4: "jit_bucket(123)"}
    ops = " ".join([
        _event(1, 100, 300),                                     # 100-400
        _event(2, 400, 200, "jit(bucket)/unet/flash_attention"),  # 400-600
        _event(3, 550, 150),                                     # overlaps: 550-700
        _event(2, 1000, 250, "jit(bucket)/unet/flash_attention"), # 1000-1250
        _event(1, 1900, 200),                                    # cut by the window at 2000
    ])
    mods = " ".join([_event(4, 100, 600), _event(4, 1000, 250), _event(4, 1900, 200)])
    device = _plane(
        "/device:TPU:0",
        f'lines {{ id: 1 name: "XLA Ops" {ops} }} lines {{ id: 2 name: "XLA Modules" {mods} }}',
        names,
    )
    host_names = {1: "bench:trace_window", 2: "bench:submit", 3: "bench:fetch", 4: "other"}
    host = _plane(
        "/host:CPU",
        'lines { id: 7 name: "main" ' + " ".join([
            _event(1, 0, 2000), _event(2, 690, 200), _event(3, 1250, 600), _event(4, 0, 90),
        ]) + " }",
        host_names,
    )
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(device + " " + host)


def test_busy_idle_kernels_modules_and_gap_blame():
    r = trace_reduce.reduce_trace(build(), kernel_names=("flash_attention",))
    us = 1e-6
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(2000 * us)
    # union: 100-700 (600) + 1000-1250 (250) + 1900-2000 (100, clipped)
    assert r["busy_s"] == pytest.approx(950 * us)
    assert r["kernels"]["flash_attention"] == pytest.approx([200 * us, 250 * us])
    # whole programs inside the window only: the third is cut by its edge
    assert r["modules"] == {"jit_bucket(123)": pytest.approx([600 * us, 250 * us])}
    ops = dict(r["device_ops"])
    assert ops["custom-call.7"] == pytest.approx(450 * us)
    assert ops["fusion.1"] == pytest.approx(400 * us)
    # gaps, longest first: 1250-1900 under fetch, 700-1000 under submit,
    # 0-100 under nothing of the benchmark's
    assert r["idle_gaps"][0] == ["fetch", pytest.approx(650 * us)]
    assert r["idle_gaps"][1] == ["submit", pytest.approx(300 * us)]
    assert r["idle_gaps"][2] == ["none", pytest.approx(100 * us)]
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])


def test_step_mfu_is_read_from_the_devices_clock_alone():
    """Operations of the frames the whole steps of the span carried, over
    those steps' device time: the span's length, the gaps between steps and
    the frames the host counted do not enter."""
    from benchmark.harness import Benchmark

    from .conftest import REPO

    bench = Benchmark(REPO)
    read = bench.reader({"name": "step_mfu"})
    reduced = trace_reduce.reduce_trace(build())

    class Result:
        traced = (0.0, 123.0)  # host clock: not read
        sessions = ()

        @staticmethod
        def steps_by_riders(traced=False):
            return {1: 3, 4: 6} if traced else {}

    class Flops:
        @staticmethod
        def frame_flops(cfg):
            return 1e9

    class Ctx:
        cfg, traffic, result, trace = None, None, Result, reduced
        peaks = {"bf16_flops": 1e14}
        flops = Flops

    # 3 riders a step on average, the two whole steps took 600 + 250 us
    assert read(Ctx) == pytest.approx(100 * 1e9 * 3 * 2 / (850e-6 * 1e14))
    Ctx.trace = dict(reduced, modules={})
    assert read(Ctx) is None


def test_union_and_gaps_arithmetic():
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.union_seconds([]) == 0
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace_reduce.gaps([(0, 6)], 0, 6) == []


def test_a_trace_without_a_device_plane_is_an_error():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto('planes { name: "/host:CPU" }')
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace_reduce.reduce_trace(pd)
