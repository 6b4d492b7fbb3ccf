"""The harness end to end on the CPU at the tiny size, and its refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from .conftest import HERE, REPO, make_root, tiny_spec

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_without_a_chip_it_refuses_to_report():
    """The real command on this CPU-only machine: non-zero, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "turbo512.solo60",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("workload", ["tiny64.duo20", "tinyturbo64.duo20", "tinyxl64.duo20"])
def test_with_a_device_stub_one_well_formed_last_line(run_cell, workload):
    code, line, err = run_cell(workload, seed=2**31 + 5)
    assert code == 0, err
    assert list(line)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 20 and line["failed"] == 0
    spec = tiny_spec()
    assert list(line["metrics"]) == [m["name"] for m in spec["end_to_end"]]
    for m in spec["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes", "check_memory_peak_bytes"}
    # each number compared stands beside its limit, in the line and as the
    # last lines of standard error
    assert set(line["compared"]) == {"session_bias_rel_max", "frame_pooled_rms_max"}
    last = [l for l in err.splitlines() if l.strip()][-2:]
    assert last[0].startswith("compared session_bias_rel_max") and "limit" in last[0]
    assert last[1].startswith("compared frame_pooled_rms_max") and "limit" in last[1]
    # tiny64 carries state: the window's first 8 frames and its last 4, per session
    assert line["readings"]["frames_compared"] >= 18


def test_a_named_piece_the_disk_lacks_is_an_error_that_names_it(tmp_path, run_cell):
    root = make_root(tmp_path)
    os.remove(os.path.join(root, "benchmark", "traffic", "duo20.json"))
    code, line, err = run_cell("tiny64.duo20", root=root)
    assert code != 0 and line is None
    assert "traffic mix 'duo20'" in err and "traffic/duo20.json" in err

    root = make_root(tmp_path / "b")
    os.remove(os.path.join(root, "benchmark", "configs", "tiny64.json"))
    code, line, err = run_cell("tiny64.duo20", root=root)
    assert code != 0 and line is None
    assert "configuration 'tiny64'" in err and "configs/tiny64.json" in err

    root = make_root(tmp_path / "c")
    os.remove(os.path.join(root, "benchmark", "layer_metrics", "step_mfu.py"))
    code, line, err = run_cell("tiny64.duo20", root=root, trace=1)
    assert code != 0 and line is None
    assert "per-layer metric 'step_mfu'" in err and "layer_metrics/step_mfu.py" in err

    code, line, err = run_cell("tiny64.nosuch", root=root)
    assert code != 0 and "tiny64.nosuch" in err

    root = make_root(tmp_path / "d")
    os.remove(os.path.join(root, "benchmark", "reference", "sd_stream.py"))
    code, line, err = run_cell("tiny64.duo20", root=root)
    assert code != 0 and line is None
    assert "reference 'sd_stream' of configuration 'tiny64'" in err
    assert "reference/sd_stream.py" in err

    root = make_root(tmp_path / "e")
    os.remove(os.path.join(root, "benchmark", "flops", "sd_stream.py"))
    code, line, err = run_cell("tiny64.duo20", root=root)
    assert code != 0 and line is None
    assert "flops module 'sd_stream' of configuration 'tiny64'" in err


def test_new_pieces_are_added_by_files_and_entries_alone(tmp_path):
    """A later PR's model family, traffic mix and per-layer metric: new files
    and new BENCHMARK.json entries, no edit to a file that is there.  The
    family is the second one the repo has: its configuration file, its
    reference module and its operations module, copied into a root that
    holds the first family alone."""
    from benchmark.harness import Benchmark, MissingPiece

    family = [("configs", "tinyxl64.json"), ("reference", "sdxl_stream.py"),
              ("flops", "sdxl_stream.py")]
    spec = tiny_spec()
    spec["configs"] = [c for c in spec["configs"] if c["name"] != "tinyxl64"]
    spec["workloads"] = [w for w in spec["workloads"] if w["config"] != "tinyxl64"]
    root = make_root(tmp_path, spec)
    home = os.path.join(root, "benchmark")
    for kind, f in family:
        os.remove(os.path.join(home, kind, f))
    with pytest.raises(MissingPiece, match="tinyxl64.trickle"):
        Benchmark(root).cell("tinyxl64.trickle")

    # what the later PR brings: entries ...
    spec["configs"].append(
        {"name": "tinyxl64", "source": "tests", "file": "benchmark/configs/tinyxl64.json",
         "reduced": [], "why": "two text towers, text_time addition embedding"}
    )
    spec["workloads"].append(
        {"name": "tinyxl64.trickle", "config": "tinyxl64", "traffic": "trickle",
         "chips": 1, "why": "added"}
    )
    spec["per_layer"].append(
        {"name": "dummy_count", "unit": "frames", "better": "higher",
         "source": "program_counter", "layer": "load generator (benchmark)",
         "moves": "stylized_fps", "workloads": ["tinyxl64.trickle"]}
    )
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    # ... and files: the family's three, a traffic mix, a metric's reader
    src = {"configs": os.path.join(HERE, "data", "configs"),
           "reference": os.path.join(REPO, "benchmark", "reference"),
           "flops": os.path.join(REPO, "benchmark", "flops")}
    for kind, f in family:
        shutil.copy(os.path.join(src[kind], f), os.path.join(home, kind, f))
    with open(os.path.join(home, "traffic", "trickle.json"), "w") as f:
        json.dump({"sessions": 1, "slots": 1, "source_fps": 5,
                   "pipeline_depth": 2, "warmup_frames": 10}, f)
    with open(os.path.join(home, "layer_metrics", "dummy_count.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.traffic['source_fps'])\n")

    bench = Benchmark(root)
    cell = bench.cell("tinyxl64.trickle")
    cfg = bench.config(cell)
    assert cfg["name"] == "tinyxl64" and cfg["program_text_subtrees"] == ["clip", "clip2"]
    ref_module, flops = bench.reference(cfg), bench.flops(cfg)
    assert ref_module.__file__ == os.path.join(home, "reference", "sdxl_stream.py")
    assert set(ref_module.weight_shapes(cfg)) == {"unet", "clip", "clip2", "taesd"}
    assert "add_embedding" in ref_module.weight_shapes(cfg)["unet"]
    assert flops.frame_flops(cfg) > flops.sd_stream.frame_flops(cfg)
    assert len(flops.attention_calls(cfg)) == 2 * (2 + 2 + 4)  # depth 2: down, mid, up x 2
    assert bench.traffic(cell)["source_fps"] == 5
    names = [m["name"] for m in bench.per_layer(cell)]
    # every metric that lists no cells of its own, and the one that lists this cell
    assert names[-1] == "dummy_count" and names[:-1] == [
        m["name"] for m in spec["per_layer"] if "workloads" not in m]
    # the old cells do not see the new metric, which lists its own cells
    assert "dummy_count" not in [m["name"] for m in bench.per_layer(bench.cell("tiny64.duo20"))]

    class Ctx:
        traffic = bench.traffic(cell)

    assert bench.reader(bench.per_layer(cell)[-1])(Ctx) == 5.0


def test_the_real_benchmark_json_finds_all_its_pieces():
    from benchmark.harness import Benchmark

    bench = Benchmark(REPO)
    assert bench.spec["command"] == ["python3", "-m", "benchmark.run"]
    for cell in bench.spec["workloads"]:
        assert cell["chips"] == 1
        cfg, traffic = bench.config(cell), bench.traffic(cell)
        assert traffic["slots"] == traffic["sessions"]
        assert set(cfg["check"]["limits"]) == {"session_bias_rel_max"}
        # a source-paced cell is not judged on the tail (PERF.md section 2)
        tail = set() if cell["name"] == "turbo512.trio15" else {"frame_latency_p95_ms"}
        assert {m["name"] for m in bench.end_to_end(cell)} == {
            "stylized_fps", "frame_latency_p50_ms", "setup_s"} | tail
        assert bench.flops(cfg).frame_flops(cfg) > 1e12
        assert callable(bench.reference(cfg).weight_shapes)
        for m in bench.per_layer(cell):
            assert callable(bench.reader(m))
            assert m["moves"] in {e["name"] for e in bench.end_to_end(cell)}


def test_the_traffic_of_three_sessions_is_on_disk_as_the_issue_states_it():
    from benchmark.harness import Benchmark

    bench = Benchmark(REPO)
    t = bench.traffic(bench.cell("turbo512.trio15"))
    assert (t["sessions"], t["slots"], t["source_fps"]) == (3, 3, 15)
    assert t["phase"] == "staggered" and t["source"] == "paced_latest_wins"
    assert t["pipeline_depth"] == 2 and t["warmup_frames"] == 10


def test_the_cell_that_works_the_scheduler_and_its_readers_are_found():
    from benchmark.harness import Benchmark

    bench = Benchmark(REPO)
    cell = bench.cell("turbo512.trio15")
    assert cell["config"] == "turbo512" and cell["chips"] == 1 and cell["traffic"] == "trio15"
    names = [m["name"] for m in bench.per_layer(cell)]
    for metric in ("dispatch_window_share", "hold_share", "batch_occupancy_mean",
                   "window_wait_p50_ms", "step_device_ms", "step_mfu",
                   "source_late_p50_ms"):
        assert metric in names
        assert callable(bench.reader({"name": metric}))
    # a one-session cell dispatches solo alone: it does not list the share
    solo = [m["name"] for m in bench.per_layer(bench.cell("turbo512.solo60"))]
    assert "dispatch_window_share" not in solo and "hold_share" in solo
    assert "source_late_p95_ms" in solo and "source_late_p50_ms" not in solo
    # a metric that moves the tail is not read where the tail is not judged
    assert "source_late_p95_ms" not in names
    # the accepted cells' traffic files state no phase: one session has none
    assert "phase" not in bench.traffic(bench.cell("turbo512.solo60"))
