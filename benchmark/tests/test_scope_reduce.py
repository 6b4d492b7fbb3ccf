"""The scope reduction on a hand-built trace: device ops named by HLO
instruction as a TPU trace names them, a compiled module's text that says
which scope each instruction belongs to, and the program's ``rtc:`` spans on
two host threads (times in microseconds below; the proto wants
picoseconds)."""

import pytest

from benchmark import scope_reduce, serve
from benchmark.tools import trace_report

# what compiled.as_text() looks like, cut to what the parser reads: a
# fusion with its own op name, a Mosaic custom call, one fusion with no
# metadata whose computation's body says where it belongs, a layout copy
# with none at all
HLO = """
HloModule jit_bucket, entry_computation_layout={()->()}

%fused_computation.7 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %m.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(bucket)/vmap(unet)/mid/resnet_1/mul"}
  ROOT %a.1 = bf16[8]{0} add(%m.1, %p), metadata={op_name="jit(bucket)/vmap(unet)/mid/resnet_1/add"}
}

ENTRY %main.1 (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0), metadata={op_name="frames_k"}
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(bucket)/vmap(unet)/down_0/resnet_0/jit(silu)/mul" stack_frame_id=3}
  %flash_attention.2 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(bucket)/vmap(unet)/down_0/transformer_0/self_attn/flash_attention/pallas_call"}
  %copy.5 = bf16[8]{0} copy(%flash_attention.2), metadata={op_name="jit(bucket)/vmap(unet)/down_0/transformer_0/self_attn/transpose"}
  %fusion.9 = bf16[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.7
  %copy.6 = bf16[8]{0} copy(%fusion.9)
  %fusion.3 = bf16[8]{0} fusion(%copy.6), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(bucket)/vmap(vae_decode)/conv_general_dilated"}
  ROOT %fusion.4 = bf16[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(bucket)/scatter/scatter"}
}
"""


def _event(meta, start_us, dur_us, stats=()):
    s = (
        f"events {{ metadata_id: {meta} offset_ps: {int(start_us * 1e6)} "
        f"duration_ps: {int(dur_us * 1e6)}"
    )
    for sid, value in stats:
        kind = "str_value" if isinstance(value, str) else "int64_value"
        value = f'"{value}"' if isinstance(value, str) else value
        s += f" stats {{ metadata_id: {sid} {kind}: {value} }}"
    return s + " }"


def _plane(name, lines, names, stat_names=()):
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for i, n in names.items()
    )
    stats = "".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for i, n in enumerate(stat_names, start=1)
    )
    return f'planes {{ name: "{name}" {lines} {meta} {stats} }}'


def build():
    ops = {
        1: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop",
        2: "%flash_attention.2 = bf16[8]{0} custom-call(bf16[8]{0} %fusion.1)",
        3: "%copy.5 = bf16[8]{0} copy(bf16[8]{0} %flash_attention.2)",
        4: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %copy.5)",
        5: "%copy.6 = bf16[8]{0} copy(bf16[8]{0} %fusion.9)",
        6: "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %copy.6)",
        7: "%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %fusion.3)",
        8: "jit_bucket(99)", 9: "jit__threefry(7)", 10: "%fusion.77 = f32[] fusion()",
    }

    def step(t0):  # one step: 100 us of ops back to back
        return [
            _event(1, t0, 30), _event(2, t0 + 30, 40), _event(3, t0 + 70, 5),
            _event(4, t0 + 75, 10), _event(5, t0 + 85, 5), _event(6, t0 + 90, 8),
            _event(7, t0 + 98, 2),
        ]

    op_line = " ".join(
        step(100) + step(400) + [_event(10, 700, 20)] + step(1950)  # last: cut by the window
    )
    mods = " ".join([
        _event(8, 100, 100), _event(8, 400, 100), _event(9, 700, 20), _event(8, 1950, 100),
    ])
    device = _plane(
        "/device:TPU:0",
        f'lines {{ id: 1 name: "XLA Ops" {op_line} }} '
        f'lines {{ id: 2 name: "XLA Modules" {mods} }}',
        ops,
    )
    host_names = {
        1: "bench:trace_window", 2: "rtc:submit", 3: "rtc:dispatch", 4: "rtc:launch",
        5: "rtc:await_row", 6: "rtc:fetch", 7: "rtc:finish_output", 8: "bench:fetch",
    }
    stat_names = ("slot", "seq", "step", "cause")
    submit_thread = " ".join([
        _event(1, 0, 2000),
        # the gap 200-400 has a submit over it whose dispatch and launch
        # children cover 260-400: launch, the innermost, is the blame
        _event(2, 210, 200, [(1, 0), (2, 7)]),
        _event(3, 250, 155, [(4, "solo")]),
        _event(4, 260, 140, [(3, 41)]),
        _event(4, 80, 15, [(3, 40)]),
        _event(4, 1940, 5, [(3, 42)]),
    ])
    fetch_thread = " ".join([
        # a fetch waits over every gap; 720-1950 is covered by waits only,
        # 500-700 by finish_output
        _event(6, 150, 1900, [(1, 0), (2, 6)]),
        _event(5, 150, 330, [(1, 0), (2, 6)]),
        _event(7, 490, 205, [(1, 0), (2, 6)]),
        _event(5, 700, 1300, [(1, 0), (2, 7)]),
        _event(8, 0, 2000),
    ])
    host = _plane(
        "/host:CPU",
        f'lines {{ id: 7 name: "bench-io_0" {submit_thread} }} '
        f'lines {{ id: 8 name: "bench-io_1" {fetch_thread} }}',
        host_names, stat_names,
    )
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(device + " " + host)


def test_op_names_from_compiled_text():
    table = scope_reduce.op_names_from_hlo(HLO)
    assert table["%fusion.1"].endswith("down_0/resnet_0/jit(silu)/mul")
    assert "flash_attention/pallas_call" in table["%flash_attention.2"]
    # no metadata of its own: the op name most of its computation's body carries
    assert table["%fusion.9"].startswith("jit(bucket)/vmap(unet)/mid/resnet_1/")
    assert "%copy.6" not in table
    assert table["%fusion.4"] == "jit(bucket)/scatter/scatter"  # the ROOT too


@pytest.mark.parametrize("op_name,path,part", [
    ("jit(bucket)/vmap(unet)/down_1/resnet_0/jit(silu)/mul",
     ("unet", "down_1", "resnet_0", "silu"), "resnet"),
    ("jit(bucket)/vmap(unet)/up_2/transformer_1/self_attn/flash_attention/pallas_call",
     ("unet", "up_2", "transformer_1", "self_attn", "flash_attention"), "flash_attention"),
    ("jit(bucket)/vmap(unet)/up_2/transformer_1/self_attn/transpose",
     ("unet", "up_2", "transformer_1", "self_attn"), "self_attn"),
    ("jit(bucket)/vmap(unet)/mid/transformer_0/cross_attn/dot_general",
     ("unet", "mid", "transformer_0", "cross_attn"), "cross_attn"),
    ("jit(bucket)/vmap(unet)/mid/transformer_0/ff/mul", ("unet", "mid", "transformer_0", "ff"), "ff"),
    ("jit(bucket)/vmap(unet)/mid/transformer_0/proj/dot_general",
     ("unet", "mid", "transformer_0", "proj"), "proj"),
    ("jit(bucket)/vmap(unet)/down_0/downsample/conv_general_dilated",
     ("unet", "down_0", "downsample"), "downsample"),
    ("jit(bucket)/vmap(unet)/time_embed/dot_general", ("unet", "time_embed"), "time_embed"),
    ("jit(bucket)/vmap(epilogue)/fused_stream_epilogue/pallas_call",
     ("epilogue", "fused_stream_epilogue"), "fused_stream_epilogue"),
    ("jit(bucket)/vmap(epilogue)/slice", ("epilogue",), "epilogue"),
    ("jit(bucket)/vmap(vae_encode)/jit(relu)/max", ("vae_encode", "relu"), "vae_encode"),
    ("jit(bucket)/gather/jit(_take)/gather", ("gather", "_take"), "gather/scatter"),
    ("jit(bucket)/scatter/scatter", ("scatter",), "gather/scatter"),
    ("jit(bucket)/vmap()/concatenate", (), "unscoped"),
    ("states['noise']", (), "unscoped"),
    ("", (), "unscoped"),
])
def test_scope_path_and_model_part(op_name, path, part):
    kernels = ("flash_attention", "fused_stream_epilogue")
    assert scope_reduce.scope_path(op_name) == path
    assert scope_reduce.model_part(path, kernels) == part


def test_step_by_model_part_over_whole_programs_in_the_window():
    us = 1e-6
    tables = {
        "sbucket-2:full": {"%fusion.1": "jit(bucket)/other/x"},  # another executable's names
        "sbucket-1:full": scope_reduce.op_names_from_hlo(HLO),
    }
    r = scope_reduce.by_scope(build(), tables, ("flash_attention",))
    # two whole bucket programs; the third is cut by the window's edge, the
    # threefry program between them is another program's time
    assert r["steps"] == 2
    assert r["module_s"] == pytest.approx(200 * us)
    assert r["ops_s"] == pytest.approx(200 * us)
    assert r["other_programs_s"] == pytest.approx(20 * us)
    parts = {name: (s, n, share) for name, s, n, share in r["parts"]}
    assert parts["flash_attention"] == (pytest.approx(80 * us), 2, pytest.approx(0.40))
    # fusion.1 (30) + fusion.9 (10, placed by its computation's body)
    assert parts["resnet"] == (pytest.approx(80 * us), 4, pytest.approx(0.40))
    assert parts["self_attn"] == (pytest.approx(10 * us), 2, pytest.approx(0.05))
    assert parts["vae_decode"][0] == pytest.approx(16 * us)
    assert parts["gather/scatter"][0] == pytest.approx(4 * us)
    # the copy with no metadata is printed, never dropped
    assert parts["unscoped"] == (pytest.approx(10 * us), 2, pytest.approx(0.05))
    assert r["unscoped_share"] == pytest.approx(0.05)
    assert sum(p[1] for p in r["parts"]) == pytest.approx(r["ops_s"])
    # at a stated depth instead of the roll-up
    deep = dict((n, s) for n, s, _, _ in scope_reduce.by_scope(build(), tables, depth=2)["parts"])
    assert deep["unet/down_0"] == pytest.approx(150 * us)
    assert deep["unet/mid"] == pytest.approx(20 * us)


def test_idle_gaps_are_put_down_to_the_programs_own_spans():
    us = 1e-6
    g = scope_reduce.blame_gaps(build())
    # 720-1950: only waits cover it -> no span, and who waited
    assert g[0]["gap_s"] == pytest.approx(1230 * us)
    assert g[0]["blame"] == "no span" and g[0]["waiting"] == ["await_row", "fetch"]
    # 200-400: submit covers 95 %, dispatch 75 %, launch 70 %: most cover wins
    assert g[1]["gap_s"] == pytest.approx(200 * us)
    assert (g[1]["blame"], g[1]["thread"]) == ("submit", "bench-io_0")
    assert g[1]["ids"] == {"slot": 0, "seq": 7}
    # 500-700: finish_output on the fetch thread, though a fetch span covers it too
    assert g[2]["gap_s"] == pytest.approx(200 * us)
    assert (g[2]["blame"], g[2]["thread"]) == ("finish_output", "bench-io_1")
    assert g[2]["waiting"] == ["fetch"]
    # 0-100 before anything: no rtc: span at all (bench: spans are not the program's)
    assert g[3]["gap_s"] == pytest.approx(100 * us)
    assert g[3]["blame"] == "no span" and g[3]["waiting"] == []
    assert len(g) == 4


def test_nth_launch_span_is_the_nth_bucket_program():
    j = trace_report.launch_join(build())
    assert j["leading_programs_skipped"] == 0 and j["pairs"] == 3
    assert j["launches_unpaired"] == 0 and j["steps_consecutive"] is True
    assert j["delay_ms_median"] == pytest.approx(0.020)   # 80 -> 100
    assert j["delay_ms_max"] == pytest.approx(0.140)      # 260 -> 400


def test_counters_delta_keeps_cumulative_counters_only():
    c0 = {"batchsched_hop_count": {"dispatch": 2}, "batchsched_steps_total": 2,
          "batchsched_hop_ms_max": {"dispatch": 9.0}, "batchsched_sessions": 1}
    c1 = {"batchsched_hop_count": {"dispatch": 12}, "batchsched_steps_total": 12,
          "batchsched_hop_ms_max": {"dispatch": 9.5}, "batchsched_sessions": 1,
          "batchsched_dispatch_inflight_hist": {"1": 10}}
    assert serve.counters_delta(c0, c1) == {
        "batchsched_hop_count": {"dispatch": 10}, "batchsched_steps_total": 10,
        "batchsched_hop_ms_max": {"dispatch": 9.5},
        "batchsched_dispatch_inflight_hist": {"1": 10},
    }
