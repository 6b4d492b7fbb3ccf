"""bench.py and scripts/*_bench.py contract guarantees.

bench.py is one process that touches the chip once: it prints one JSON line
that names the device it measured on, or it fails with a non-zero exit code
and no line.  What can be pinned without a chip is the second half: no
silent CPU fallback, no peak assumed for a device it does not know.  The
script benches print one contract line each and bank it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(env_changes: dict, args=(), config="tiny64", timeout=180):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # keep the subprocess hermetic
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, "bench.py", "--config", config, *args],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def _json_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_bench_requires_a_tpu_when_platform_is_unset():
    """JAX_PLATFORMS unset and no TPU: JAX falls back to the CPU without a
    word, and bench.py must exit non-zero WITHOUT a result line (BENCH_r05
    filed 0.04 fps from exactly this fallback under a device metric's
    name).  Fast: the guard runs before any model builds."""
    r = _run_bench({"JAX_PLATFORMS": None})
    assert r.returncode != 0, r.stdout
    assert not _json_lines(r.stdout), r.stdout
    assert "no TPU found" in r.stderr


def test_bench_errors_on_a_device_without_a_published_peak():
    """An MFU needs the device's published peak; a device_kind that is not
    in bench.PEAK_BF16_FLOPS is an error, not a default — which also means
    a CPU asked for by name gets no result line either."""
    r = _run_bench({"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0, r.stdout
    assert not _json_lines(r.stdout), r.stdout
    assert "no published peak for device_kind 'cpu'" in r.stderr

    import bench

    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit, match="TPU v7"):
        bench.peak_flops("TPU v7")


def test_host_plane_bench_contract_and_speedup(tmp_path):
    """Host-plane microbench smoke (ISSUE 2): runs in seconds on CPU,
    emits exactly one contract line, BANKS it into PERF_LOG_PATH, and the
    batched path must not be slower than per-packet.  The ratio fence is
    deliberately loose (the ≥3x acceptance number is measured by a full
    run on an uncontended box); a regression that makes batching SLOWER
    than the per-packet loop still fails here."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "HOST_PLANE_BENCH_FRAMES": "60",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/host_plane_bench.py"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "host_plane_batched_speedup"
    assert d["pkts_per_frame"] >= 15  # 512²-rate FU-A shape at 1200 MTU
    # honest-bench fingerprint (ISSUE 8): shared utils/hwfp.py dict
    assert d["fingerprint"]["host_cpus"] >= 1
    assert d["fingerprint"]["jax_backend"] == "unprobed"  # pure-host bench
    # not-slower fence with headroom for a contended 1-core CI box
    assert d["value"] >= 0.9, d
    # banked: the same entry landed in the log
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "host_plane_batched_speedup"


def test_broadcast_bench_contract(tmp_path):
    """Broadcast fan-out bench smoke (ISSUE 17): runs in seconds on CPU,
    emits exactly TWO contract lines (amortization + single-viewer
    overhead), BANKS both, and the bench contract pin holds: the PLI
    storm fired inside the fan-out leg produced exactly one GOP replay
    and zero encoder IDRs.  No ratio fence here beyond sanity — the
    amortization claim is measured by a full run (perf_compare fences
    the banked numbers); what this catches is the harness rotting."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "BROADCAST_BENCH_FRAMES": "4",
            "BROADCAST_BENCH_VIEWERS": "4",
            "BROADCAST_BENCH_DIM": "64",
            "BROADCAST_BENCH_PAIRS": "2",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/broadcast_bench.py"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 2, r.stdout
    by_metric = {json.loads(ln)["metric"]: json.loads(ln) for ln in lines}
    assert set(by_metric) == {
        "broadcast_viewers_per_core_30fps",
        "broadcast_single_viewer_overhead_ratio",
    }
    for d in by_metric.values():
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in d, d
        assert "error" not in d, d
        assert d["value"] > 0, d
        assert d["fingerprint"]["jax_backend"] == "unprobed"  # host bench
    assert by_metric["broadcast_viewers_per_core_30fps"]["unit"] == "viewers"
    # the bench-contract half of the acceptance pin: the in-harness PLI
    # storm coalesced to ONE gop replay, ZERO encoder/engine IDRs
    d = by_metric["broadcast_viewers_per_core_30fps"]
    assert d["pli_storm"] == {"replays": 1, "encoder_idrs": 0}
    banked = {json.loads(x)["metric"] for x in log.read_text().splitlines()}
    assert banked == set(by_metric)


def test_unet_cache_prefix_validated():
    """advisor r3: 'foo:3' must not parse as a valid UNET_CACHE spelling."""
    import pytest

    from ai_rtc_agent_tpu.models import registry

    import os
    os.environ["UNET_CACHE"] = "foo:3"
    try:
        with pytest.raises(ValueError, match="deepcache"):
            registry.default_stream_config("tiny-test")
    finally:
        del os.environ["UNET_CACHE"]
    os.environ["UNET_CACHE"] = "deepcache:3"
    try:
        assert registry.default_stream_config("tiny-test").unet_cache_interval == 3
    finally:
        del os.environ["UNET_CACHE"]


@pytest.mark.slow
def test_batch_scheduler_bench_contract(tmp_path):
    """Batch-scheduler amortization microbench smoke (ISSUE 7): emits
    exactly one contract line, BANKS it, and batching must not be SLOWER
    than serializing sessions through the shared engine.  Runs at 2
    sessions (half the bucket compiles); `slow` tier — ISSUE 7's budget
    satellite trades this ~30s of compiles for tier-1 headroom (the
    scheduler itself is tier-1-covered by tests/test_batch_scheduler.py,
    and the committed 4-session PERF_LOG line carries the ≥1.5x / ≤5%
    acceptance numbers).  What this fence catches is a scheduler
    regression that makes coalescing a pessimization."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "BATCHSCHED_BENCH_FRAMES": "6",
            "BATCHSCHED_BENCH_PAIRS": "4",
            "BATCHSCHED_BENCH_SESSIONS": "2",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/batch_scheduler_bench.py"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "batchsched_amortization_2s"
    assert d["sessions"] == 2
    # pessimization fences with headroom for a contended 1-core CI box
    # (at 2 sessions with tiny reps the median ratio wobbles around ~1.2;
    # a real regression that makes coalescing slower reads ~0.5): the
    # committed PERF_LOG line carries the real 4-session ≥1.5x / ≤5%
    assert d["value"] >= 0.8, d
    assert d["single_session_overhead_pct"] <= 40.0, d
    # full fingerprint: this bench initializes jax for the measurement
    assert d["fingerprint"]["jax_backend"] == "cpu"
    assert d["fingerprint"]["device_count"] >= 1
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "batchsched_amortization_2s"


@pytest.mark.slow
def test_adapter_bench_contract(tmp_path):
    """Per-session style adapter bench smoke (ISSUE 20): emits exactly
    one contract line with the NxN metric + bank-rank/swap labels and
    BANKS it, and the factor-bank path must not be grossly slower than
    the fused dedicated engines it replaces.  Runs at 2x2 (half the
    compiles — two fused engines + one 2-slot prewarm); `slow` tier like
    its batchsched sibling; the committed 4x4 PERF_LOG line carries the
    acceptance trajectory."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "ADAPTER_BENCH_FRAMES": "6",
            "ADAPTER_BENCH_PAIRS": "4",
            "ADAPTER_BENCH_SESSIONS": "2",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/adapter_bench.py"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "adapter_amortization_2x2"
    assert d["sessions"] == 2 and d["adapters"] == 2
    assert d["bank_rank"] == 4
    # pessimization fence with contended-box headroom: the factors path
    # collapsing (per-frame graft retraces, bank copies) reads ~0.3
    assert d["value"] >= 0.7, d
    # a hot-swap is one same-shaped bank write — never an engine build
    assert d["adapter_swap_ms"] < 500.0, d
    assert d["fingerprint"]["jax_backend"] == "cpu"
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "adapter_amortization_2x2"


@pytest.mark.slow
def test_mesh_sched_bench_contract(tmp_path):
    """Mesh-sharded scheduler amortization smoke (ISSUE 12): emits
    exactly one contract line with the dp/session labels + fingerprint
    and BANKS it.  Runs at dp=2 (two virtual devices — two bucket
    prewarms per scheduler instead of eight); `slow` tier like its
    batchsched sibling.  No ratio floor on this 2-core box: virtual
    devices oversubscribe the host so the honest CPU value is <1 (the
    committed dp8 PERF_LOG row + perf_compare fence carry the
    trajectory; the TPU watcher row is the accelerator truth) — what
    this smoke pins is the contract shape and that the sharded path
    serves at all under the bench harness."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)  # the bench forces its own device flag
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "MESHSCHED_BENCH_FRAMES": "4",
            "MESHSCHED_BENCH_PAIRS": "3",
            "MESHSCHED_BENCH_SESSIONS": "2",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/mesh_sched_bench.py"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "meshsched_amortization_dp2"
    assert d["sessions"] == 2 and d["dp"] == 2
    assert d["value"] > 0, d
    assert d["fingerprint"]["jax_backend"] == "cpu"
    assert d["fingerprint"]["device_count"] == 2
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "meshsched_amortization_dp2"


# -- perf_compare.py: the trajectory fence (ISSUE 8) -------------------------

def _perf_compare(args, timeout=60):
    return subprocess.run(
        [sys.executable, "scripts/perf_compare.py", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def _write_jsonl(path, entries):
    path.write_text("".join(json.dumps(e) + "\n" for e in entries))


def test_perf_compare_passes_within_fence_and_fails_regression(tmp_path):
    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "e2e_fps_turbo512_singlechip", "value": 30.0,
         "unit": "fps", "backend": "tpu", "live": True,
         "recorded_at": "2026-08-01T00:00:00+00:00"},
    ])
    # within tolerance (and improvements always pass)
    _write_jsonl(fresh, [
        {"metric": "e2e_fps_turbo512_singlechip", "value": 28.0,
         "unit": "fps", "backend": "tpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    # a real regression (beyond the default 35% fence) fails the run
    _write_jsonl(fresh, [
        {"metric": "e2e_fps_turbo512_singlechip", "value": 10.0,
         "unit": "fps", "backend": "tpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout


def test_perf_compare_direction_and_per_metric_tolerance(tmp_path):
    """Overhead ratios are lower-is-better: a RISE past the fence fails;
    per-metric tolerance overrides tighten the default."""
    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "trace_off_overhead_ratio", "value": 1.06, "unit": "x",
         "backend": "cpu", "live": True},
    ])
    _write_jsonl(fresh, [
        {"metric": "trace_off_overhead_ratio", "value": 1.30, "unit": "x",
         "backend": "cpu"},
    ])
    # 1.30 vs banked 1.06: inside the loose default fence...
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout
    # ...but outside a tightened 10% per-metric fence
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--tolerance-metric",
                       "trace_off_overhead_ratio=0.1"])
    assert r.returncode == 1, r.stdout
    # and a LOWER ratio (improvement) always passes
    _write_jsonl(fresh, [
        {"metric": "trace_off_overhead_ratio", "value": 0.95, "unit": "x",
         "backend": "cpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--tolerance-metric",
                       "trace_off_overhead_ratio=0.1"])
    assert r.returncode == 0, r.stdout


def test_perf_compare_share_metrics_are_lower_better(tmp_path):
    """secure_core_share_at_rate's acceptance bound is '< 0.05 core' —
    a cost metric: a 10x core-share blowup must FAIL and a halving must
    pass (the heuristic must not silently invert the fence; explicit
    --higher-better can still force the other reading)."""
    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "secure_core_share_at_rate", "value": 0.0118,
         "unit": "core_frac", "backend": "cpu", "live": True},
    ])
    _write_jsonl(fresh, [
        {"metric": "secure_core_share_at_rate", "value": 0.118,
         "unit": "core_frac", "backend": "cpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
    _write_jsonl(fresh, [
        {"metric": "secure_core_share_at_rate", "value": 0.006,
         "unit": "core_frac", "backend": "cpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout
    # explicit overrides beat the heuristic for future metric names
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--higher-better", "secure_core_share_at_rate"])
    assert r.returncode == 1, r.stdout


def test_perf_compare_hardware_tier_isolation(tmp_path):
    """A CPU fresh run must NOT be fenced against a TPU banked number
    (no-trajectory; --strict makes that a failure), and fingerprinted
    entries must also match on device kind."""
    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "e2e_fps_turbo512_singlechip", "value": 30.0,
         "unit": "fps", "backend": "tpu", "live": True},
    ])
    _write_jsonl(fresh, [
        {"metric": "e2e_fps_turbo512_singlechip", "value": 0.04,
         "unit": "fps", "backend": "cpu"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0 and "NO-TRAJECTORY" in r.stdout
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--strict"])
    assert r.returncode == 1
    # same backend, different silicon: fingerprints keep them apart
    _write_jsonl(banked, [
        {"metric": "m", "value": 30.0, "backend": "tpu", "live": True,
         "fingerprint": {"device_kind": "TPU v5e"}},
    ])
    _write_jsonl(fresh, [
        {"metric": "m", "value": 1.0, "backend": "tpu",
         "fingerprint": {"device_kind": "TPU v2"}},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0 and "NO-TRAJECTORY" in r.stdout


def test_perf_compare_skips_replays_and_failed_runs(tmp_path):
    """live:false replay lines must never become their own baseline, and
    a failed fresh run (value 0 + error) always fails the fence."""
    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "m", "value": 50.0, "backend": "tpu", "live": False},
        {"metric": "m", "value": 30.0, "backend": "tpu", "live": True},
        {"metric": "m", "value": 0.0, "backend": "tpu",
         "error": "it died"},
    ])
    _write_jsonl(fresh, [{"metric": "m", "value": 29.0, "backend": "tpu"}])
    # fenced against the live 30.0, not the replayed 50.0 or the failure
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--tolerance", "0.1"])
    assert r.returncode == 0, r.stdout
    _write_jsonl(fresh, [
        {"metric": "m", "value": 0.0, "backend": "tpu",
         "error": "unreachable"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "FRESH-RUN-FAILED" in r.stdout


@pytest.mark.slow
def test_device_path_bench_contract(tmp_path):
    """Device-path microbench smoke (ISSUE 9): emits exactly one contract
    line per leg (overlap + readback isolation), BANKS both, and holds the
    loose fences — a regression that makes per-slot fetch resolve time
    scale with batch occupancy again (the whole-batch host copy) reads as
    a ~4x isolation ratio; what the fence tolerates is CI-box noise.
    `slow` tier like the batch-scheduler smoke (two tiny-model compiles +
    the bucket prewarm)."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(
        {
            "PERF_LOG_PATH": str(log),
            "DEVPATH_BENCH_FRAMES": "8",
            "DEVPATH_BENCH_PAIRS": "4",
            "JAX_PLATFORMS": "cpu",
        }
    )
    r = subprocess.run(
        [sys.executable, "scripts/device_path_bench.py"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 2, r.stdout
    by_metric = {}
    for ln in lines:
        d = json.loads(ln)
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in d, d
        assert "error" not in d, d
        by_metric[d["metric"]] = d
    assert set(by_metric) == {
        "pipelined_overlap_speedup_d4", "batchsched_fetch_isolation_ratio_4s",
    }
    iso = by_metric["batchsched_fetch_isolation_ratio_4s"]
    # isolation: the mean per-slot fetch must NOT scale ~4x with occupancy
    # (whole-batch readback); headroom for a contended 1-core CI box
    assert 0 < iso["value"] <= 2.0, iso
    assert iso["sessions"] == 4
    assert iso["fetch_mean_ms_1s"] > 0 and iso["fetch_mean_ms_4s"] > 0
    ov = by_metric["pipelined_overlap_speedup_d4"]
    # overlap: pure-CPU has no RTT to hide — the fence catches the path
    # actively SERIALIZING (thread-pool fetches blocked behind a lock)
    assert ov["value"] >= 0.4, ov
    assert ov["fingerprint"]["jax_backend"] == "cpu"
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert {b["metric"] for b in banked} == set(by_metric)


def _perf_compare_main():
    """scripts/perf_compare.py as an importable module (one load): the
    new-leg tests below call its main() in-process — same code path as
    the CLI, minus ~1s of interpreter+import per invocation (tier-1
    budget; the subprocess surface itself is pinned by the older
    perf_compare tests above)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_compare_inproc", os.path.join(REPO, "scripts", "perf_compare.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_perf_compare_knows_device_path_legs(tmp_path, capsys):
    """ISSUE 9 satellite: the new leg names ship with built-in
    direction-aware tolerances — the isolation ratio is lower-is-better
    with a 0.5 fence, the overlap speedup higher-is-better with 0.25 —
    without any --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "batchsched_fetch_isolation_ratio_4s", "value": 1.0,
         "unit": "x", "backend": "cpu", "live": True, "sessions": 4},
        {"metric": "pipelined_overlap_speedup_d4", "value": 1.0,
         "unit": "x", "backend": "cpu", "live": True, "pipeline_depth": 4},
    ])
    # within the built-in fences: ratio may rise to 1.5, speedup may drop
    # to 0.75
    _write_jsonl(fresh, [
        {"metric": "batchsched_fetch_isolation_ratio_4s", "value": 1.45,
         "unit": "x", "backend": "cpu", "sessions": 4},
        {"metric": "pipelined_overlap_speedup_d4", "value": 0.8,
         "unit": "x", "backend": "cpu", "pipeline_depth": 4},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    # beyond them: the ratio RISING past 1.5 fails (direction-aware —
    # lower is better), and the speedup cratering fails
    _write_jsonl(fresh, [
        {"metric": "batchsched_fetch_isolation_ratio_4s", "value": 1.8,
         "unit": "x", "backend": "cpu", "sessions": 4},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
    _write_jsonl(fresh, [
        {"metric": "pipelined_overlap_speedup_d4", "value": 0.6,
         "unit": "x", "backend": "cpu", "pipeline_depth": 4},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
    # an explicit --tolerance-metric still overrides the built-in default
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked),
                       "--tolerance-metric",
                       "pipelined_overlap_speedup_d4=0.5"])
    assert r.returncode == 0, r.stdout


def test_perf_compare_knows_devtel_leg(tmp_path, capsys):
    """ISSUE 10 satellite: the devtel off-mode ratio ships with a
    built-in lower-is-better fence (0.35) — a fresh run past it fails
    with no --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "devtel_off_overhead_ratio", "value": 1.0, "unit": "x",
         "backend": "cpu", "live": True, "label": "trace_overhead_2000f"},
    ])
    _write_jsonl(fresh, [
        {"metric": "devtel_off_overhead_ratio", "value": 1.3, "unit": "x",
         "backend": "cpu", "label": "trace_overhead_2000f"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    _write_jsonl(fresh, [
        {"metric": "devtel_off_overhead_ratio", "value": 1.4, "unit": "x",
         "backend": "cpu", "label": "trace_overhead_2000f"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout


def test_perf_compare_knows_journey_leg(tmp_path, capsys):
    """ISSUE 13 satellite: the journey-ring off-mode ratio ships with a
    built-in lower-is-better fence (0.35) — a fresh run past it fails
    with no --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "journey_off_overhead_ratio", "value": 1.0, "unit": "x",
         "backend": "cpu", "live": True, "label": "trace_overhead_2000f"},
    ])
    _write_jsonl(fresh, [
        {"metric": "journey_off_overhead_ratio", "value": 1.3, "unit": "x",
         "backend": "cpu", "label": "trace_overhead_2000f"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    _write_jsonl(fresh, [
        {"metric": "journey_off_overhead_ratio", "value": 1.4, "unit": "x",
         "backend": "cpu", "label": "trace_overhead_2000f"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout


def test_variant_fields_fence_separately(tmp_path, capsys):
    """ISSUE 9 satellite: a quantized / cached-cadence contract line must
    never fence against (or replay as) the dense baseline — the
    quant/unet_cache fields are part of the same-config predicate."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "batchsched_amortization_4s", "value": 1.7, "unit": "x",
         "backend": "cpu", "live": True, "sessions": 4},
    ])
    # a w8-quantized fresh line: NO trajectory against the dense entry
    _write_jsonl(fresh, [
        {"metric": "batchsched_amortization_4s", "value": 0.2, "unit": "x",
         "backend": "cpu", "sessions": 4, "quant": "w8"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0 and "NO-TRAJECTORY" in r.stdout, r.stdout
    # same for a DeepCache cadence line
    _write_jsonl(fresh, [
        {"metric": "batchsched_amortization_4s", "value": 0.2, "unit": "x",
         "backend": "cpu", "sessions": 4, "unet_cache": 3},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0 and "NO-TRAJECTORY" in r.stdout, r.stdout
    # dense-vs-dense still fences
    _write_jsonl(fresh, [
        {"metric": "batchsched_amortization_4s", "value": 0.2, "unit": "x",
         "backend": "cpu", "sessions": 4},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout


# -- scripts/fleet_bench.py: the fleet router hop (ISSUE 11) -----------------

def test_fleet_bench_contract(tmp_path):
    """Fleet-router placement-overhead microbench smoke (ISSUE 11): pure
    host (never imports jax), emits exactly one contract line, BANKS it,
    and the added /offer p50 stays in single-digit-milliseconds territory
    even on a contended CI box.  The committed PERF_LOG line carries the
    real number (~1.3ms on this box); what this fence catches is the hop
    going pathological (tens of ms = per-request scans or body churn)."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update({
        "PERF_LOG_PATH": str(log),
        "FLEET_BENCH_OFFERS": "20",
    })
    r = subprocess.run(
        [sys.executable, "scripts/fleet_bench.py"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "fleet_router_offer_overhead_ms"
    assert d["offers"] == 20
    # pure-host bench: the fingerprint must say jax never entered
    assert d["fingerprint"]["jax_backend"] == "unprobed"
    assert 0 < d["value"] < 50.0, d
    assert d["routed_p50_ms"] > 0 and d["direct_p50_ms"] > 0
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "fleet_router_offer_overhead_ms"


def test_perf_compare_knows_fleet_leg(tmp_path, capsys):
    """ISSUE 11 satellite: the fleet router hop ships with a built-in
    lower-is-better fence (1.0 = up to 2x the banked ms) — a fresh run
    past it fails with no --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "fleet_router_offer_overhead_ms", "value": 1.3,
         "unit": "ms", "backend": "host", "live": True,
         "label": "fleet_router_60o"},
    ])
    _write_jsonl(fresh, [
        {"metric": "fleet_router_offer_overhead_ms", "value": 2.5,
         "unit": "ms", "backend": "host", "label": "fleet_router_60o"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    _write_jsonl(fresh, [
        {"metric": "fleet_router_offer_overhead_ms", "value": 2.7,
         "unit": "ms", "backend": "host", "label": "fleet_router_60o"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout


# -- scripts/upgrade_bench.py: the rolling-upgrade move window (ISSUE 16) ----

def test_upgrade_bench_contract(tmp_path):
    """Upgrade session-move microbench smoke (ISSUE 16): pure host (never
    imports jax), a REAL upgrade sweep moves every session between two
    loopback agents, emits exactly one contract line, BANKS it, and the
    per-session export-to-re-point p50 stays in single-digit-to-tens-of-
    milliseconds territory even on a contended CI box.  The committed
    PERF_LOG line carries the real number (~2.6ms on this box); what this
    fence catches is the move window going pathological (snapshot
    re-copies, serialized sweeps = hundreds of ms)."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update({
        "PERF_LOG_PATH": str(log),
        "UPGRADE_BENCH_SESSIONS": "4",
    })
    r = subprocess.run(
        [sys.executable, "scripts/upgrade_bench.py"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "upgrade_session_move_ms"
    assert d["sessions"] == 4
    # pure-host bench: the fingerprint must say jax never entered
    assert d["fingerprint"]["jax_backend"] == "unprobed"
    assert 0 < d["value"] < 100.0, d
    assert d["move_p99_ms"] >= d["value"]
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "upgrade_session_move_ms"


def test_perf_compare_knows_upgrade_leg(tmp_path, capsys):
    """ISSUE 16 satellite: the upgrade move window ships with a built-in
    lower-is-better fence (1.0 = up to 2x the banked ms) — a fresh run
    past it fails with no --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "upgrade_session_move_ms", "value": 2.6,
         "unit": "ms", "backend": "host", "live": True,
         "label": "upgrade_move_8s"},
    ])
    _write_jsonl(fresh, [
        {"metric": "upgrade_session_move_ms", "value": 5.0,
         "unit": "ms", "backend": "host", "label": "upgrade_move_8s"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    _write_jsonl(fresh, [
        {"metric": "upgrade_session_move_ms", "value": 5.5,
         "unit": "ms", "backend": "host", "label": "upgrade_move_8s"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout


# -- scripts/engine_recovery_bench.py: the fault-domain windows (ISSUE 19) ---

def test_engine_recovery_bench_evacuate_contract(tmp_path):
    """Evacuation-move microbench smoke (ISSUE 19): pure host (never
    imports jax), a REAL /fleet/evacuate sweep moves every session
    between two loopback agents, emits exactly one contract line, BANKS
    it, and the per-session export-to-re-point p50 stays in
    single-digit-to-tens-of-milliseconds territory on a contended CI
    box.  The rebuild leg (real scheduler + recompile) rides the slow
    tier below."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update({
        "PERF_LOG_PATH": str(log),
        "ENGINE_BENCH_SESSIONS": "4",
    })
    r = subprocess.run(
        [sys.executable, "scripts/engine_recovery_bench.py",
         "--leg", "evacuate"],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, d
    assert "error" not in d, d
    assert d["metric"] == "evacuation_session_move_ms"
    assert d["sessions"] == 4
    # host leg: the fingerprint must say jax never entered
    assert d["fingerprint"]["jax_backend"] == "unprobed"
    assert 0 < d["value"] < 100.0, d
    assert d["move_p99_ms"] >= d["value"]
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "evacuation_session_move_ms"


@pytest.mark.slow
def test_engine_recovery_bench_rebuild_contract(tmp_path):
    """Rebuild-leg smoke (ISSUE 19): a REAL trip/quarantine/rebuild cycle
    on the tiny scheduler — the contract line carries the jax backend
    (the TPU watcher row replays this leg on hardware) and the sample
    includes the re-prewarm compile (`slow` tier: two of them)."""
    log = tmp_path / "PERF_LOG.jsonl"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update({
        "PERF_LOG_PATH": str(log),
        "ENGINE_BENCH_REBUILDS": "1",
        "JAX_PLATFORMS": "cpu",
    })
    r = subprocess.run(
        [sys.executable, "scripts/engine_recovery_bench.py",
         "--leg", "rebuild"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    d = json.loads(lines[0])
    assert "error" not in d, d
    assert d["metric"] == "engine_rebuild_ms"
    assert d["trips"] == 1 and d["rebuilds"] == 1
    assert d["backend"] == "cpu"
    assert d["fingerprint"]["jax_backend"] == "cpu"
    assert d["value"] > 0, d
    assert d["rebuild_p99_ms"] >= d["value"]
    banked = [json.loads(x) for x in log.read_text().splitlines()]
    assert banked and banked[-1]["metric"] == "engine_rebuild_ms"


def test_perf_compare_knows_engine_recovery_legs(tmp_path, capsys):
    """ISSUE 19 satellite: both fault-domain windows ship with built-in
    lower-is-better fences (1.0 = up to 2x the banked ms) — a fresh run
    past either fails with no --tolerance-metric flags."""
    main = _perf_compare_main()

    def _perf_compare(args):
        class R:
            pass

        r = R()
        r.returncode = main(args)
        r.stdout = capsys.readouterr().out
        r.stderr = ""
        return r

    banked = tmp_path / "banked.jsonl"
    fresh = tmp_path / "fresh.jsonl"
    _write_jsonl(banked, [
        {"metric": "engine_rebuild_ms", "value": 16000.0,
         "unit": "ms", "backend": "cpu", "live": True,
         "label": "engine_rebuild_3x"},
        {"metric": "evacuation_session_move_ms", "value": 7.0,
         "unit": "ms", "backend": "host", "live": True,
         "label": "evacuation_move_8s"},
    ])
    _write_jsonl(fresh, [
        {"metric": "engine_rebuild_ms", "value": 30000.0,
         "unit": "ms", "backend": "cpu", "label": "engine_rebuild_3x"},
        {"metric": "evacuation_session_move_ms", "value": 13.0,
         "unit": "ms", "backend": "host", "label": "evacuation_move_8s"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 0, r.stdout + r.stderr
    _write_jsonl(fresh, [
        {"metric": "engine_rebuild_ms", "value": 33000.0,
         "unit": "ms", "backend": "cpu", "label": "engine_rebuild_3x"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
    _write_jsonl(fresh, [
        {"metric": "evacuation_session_move_ms", "value": 14.5,
         "unit": "ms", "backend": "host", "label": "evacuation_move_8s"},
    ])
    r = _perf_compare(["--fresh", str(fresh), "--log", str(banked)])
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
