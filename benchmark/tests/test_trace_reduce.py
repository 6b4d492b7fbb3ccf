"""The trace reduction on a hand-built trace with known busy, idle and
kernel times (times in microseconds below; the proto wants picoseconds)."""

import pytest

from benchmark import trace_reduce


def _event(meta, start_us, dur_us, stat=None, riders=None):
    s = f"events {{ metadata_id: {meta} offset_ps: {int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)}"
    if stat:
        s += f' stats {{ metadata_id: 1 str_value: "{stat}" }}'
    if riders is not None:
        s += f" stats {{ metadata_id: 2 int64_value: {riders} }}"
    return s + " }"


def _plane(name, lines, names):
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for i, n in names.items()
    )
    return (
        f'planes {{ name: "{name}" {lines} {meta} '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
        'stat_metadata { key: 2 value { id: 2 name: "riders" } } }'
    )


def build(riders=3):
    """Three step programs, the third cut by the window's edge; the two
    whole ones each launched by an ``rtc:dispatch`` span stating ``riders``."""
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
    host_names = {1: "bench:trace_window", 2: "bench:submit", 3: "bench:fetch", 4: "other",
                  5: "rtc:dispatch"}
    host = _plane(
        "/host:CPU",
        'lines { id: 7 name: "main" ' + " ".join([
            _event(1, 0, 2000), _event(2, 690, 200), _event(3, 1250, 600), _event(4, 0, 90),
            _event(5, 50, 5, riders=riders), _event(5, 950, 5, riders=riders),
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

    class Flops:
        @staticmethod
        def frame_flops(cfg):
            return 1e9

    class Ctx:
        cfg, traffic, result, trace = None, None, Result, reduced
        peaks = {"bf16_flops": 1e14}
        flops = Flops

    # 3 riders in each dispatch, the two whole steps took 600 + 250 us
    assert read(Ctx) == pytest.approx(100 * 1e9 * 3 * 2 / (850e-6 * 1e14))
    Ctx.trace = dict(reduced, modules={}, steps=[])
    assert read(Ctx) is None
    # no step joined to a dispatch (a trace with no rtc:dispatch span):
    # nothing to read, not a number from another source
    Ctx.trace = dict(reduced, steps=[[n, t, None] for n, t, _ in reduced["steps"]])
    assert read(Ctx) is None
    assert bench.reader({"name": "step_device_ms"})(Ctx) is None


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


def test_an_op_that_consumes_a_kernels_result_is_not_the_kernel():
    """A TPU trace names an event by its whole HLO instruction, operands
    included: the kernel is the instruction named after it, not the fusion
    that reads ``%flash_attention.7``."""
    names = {
        1: "%flash_attention.7 = bf16[1,256,1280] custom-call(%a, %b, %c)",
        2: "%fusion.3 = bf16[1,256,1280] fusion(%flash_attention.7, %w), kind=kOutput",
        3: "%custom-call.9 = bf16[1,64,64] custom-call(%x)",
        4: "jit_bucket(1)",
    }
    ops = " ".join([
        _event(1, 100, 40),
        _event(2, 140, 70),   # the consumer: its operands name the kernel
        # a kernel the instruction does not name: the JAX op name stat does
        _event(3, 300, 20, "jit(bucket)/epilogue/fused_stream_epilogue"),
    ])
    device = _plane(
        "/device:TPU:0",
        f'lines {{ id: 1 name: "XLA Ops" {ops} }} '
        f'lines {{ id: 2 name: "XLA Modules" {_event(4, 100, 300)} }}',
        names,
    )
    host = _plane(
        "/host:CPU", 'lines { id: 7 name: "main" ' + _event(1, 0, 1000) + " }",
        {1: "bench:trace_window"},
    )
    from jax.profiler import ProfileData

    r = trace_reduce.reduce_trace(
        ProfileData.from_text_proto(device + " " + host),
        kernel_names=("flash_attention", "fused_stream_epilogue"),
    )
    assert r["kernels"]["flash_attention"] == pytest.approx([40e-6])
    assert r["kernels"]["fused_stream_epilogue"] == pytest.approx([20e-6])
    assert dict(r["device_ops"])["%fusion.3"] == pytest.approx(70e-6)


def _two_sizes():
    """Three k=1 steps and two k=4 steps; the first k=1 step was dispatched
    before the trace began (no span), the last k=4 step is cut by the edge."""
    names = {1: "fusion.1", 4: "jit_bucket(11)", 5: "jit_bucket(44)"}
    mods = " ".join([
        _event(4, 10, 100),    # no dispatch span: launched before the trace
        _event(4, 200, 100),   # dispatch at 150, 1 rider
        _event(5, 400, 400),   # dispatch at 320, 3 riders (padded to k=4)
        _event(4, 900, 100),   # dispatch at 450 (queued behind), 1 rider
        _event(5, 1800, 400),  # dispatch at 1700, 4 riders; cut at 2000
    ])
    device = _plane(
        "/device:TPU:0",
        f'lines {{ id: 1 name: "XLA Ops" {_event(1, 10, 1900)} }} '
        f'lines {{ id: 2 name: "XLA Modules" {mods} }}',
        names,
    )
    host = _plane(
        "/host:CPU",
        'lines { id: 7 name: "dispatcher" ' + " ".join([
            _event(1, 0, 2000), _event(2, 150, 5, riders=1), _event(2, 320, 5, riders=3),
            _event(2, 1700, 5, riders=4),
        ]) + ' } lines { id: 8 name: "bench-io_0" ' + _event(2, 450, 5, riders=1) + " }",
        {1: "bench:trace_window", 2: "rtc:dispatch"},
    )
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(device + " " + host)


def test_each_step_program_takes_the_riders_of_the_dispatch_that_launched_it():
    assert trace_reduce.join_riders([(150, 1), (320, 3)], [10, 200, 400, 900]) == [
        None, 1, 3, None]
    assert trace_reduce.join_riders([], [10, 200]) == [None, None]
    r = trace_reduce.reduce_trace(_two_sizes())
    us = 1e-6
    assert [(n, round(t / us), k) for n, t, k in r["steps"]] == [
        ("jit_bucket(11)", 100, None), ("jit_bucket(11)", 100, 1),
        ("jit_bucket(44)", 400, 3), ("jit_bucket(11)", 100, 1),
    ]


def test_step_readers_weigh_each_step_by_the_frames_it_carried():
    from benchmark.harness import Benchmark

    from .conftest import REPO

    bench = Benchmark(REPO)

    class Flops:
        @staticmethod
        def frame_flops(cfg):
            return 1e9

    class Ctx:
        cfg, traffic, result = None, None, None
        trace = trace_reduce.reduce_trace(_two_sizes())
        peaks = {"bf16_flops": 1e14}
        flops = Flops

    # the leading k=1 step, dispatched before the trace began, is left out
    frames, seconds = 1 + 3 + 1, 600e-6
    assert bench.reader({"name": "step_mfu"})(Ctx) == pytest.approx(
        100 * 1e9 * frames / (seconds * 1e14))
    # two frames rode a 100 us step, three a 400 us step
    assert bench.reader({"name": "step_device_ms"})(Ctx) == pytest.approx(
        1e3 * (2 * 100e-6 + 3 * 400e-6) / 5)
    # one session: every step one rider, the plain mean as before
    Ctx.trace = trace_reduce.reduce_trace(build(riders=1))
    assert bench.reader({"name": "step_device_ms"})(Ctx) == pytest.approx(1e3 * 425e-6)
