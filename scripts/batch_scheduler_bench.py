"""Continuous batch scheduler amortization: batched vs serialized sessions.

Measures the cost-per-user lever ROADMAP open item 1 names: today N
concurrent sessions share one ``StreamEngine`` and serialize through its
submit lock (N sequential device steps per wall-clock frame tick); the
batch scheduler (stream/scheduler.py) coalesces them into ONE vmapped
step.  Two legs on the hermetic tiny model (single-stage turbo config —
the per-step dispatch overhead the scheduler amortizes is the same host
machinery at every model scale; on real accelerators the batch
additionally rides idle matrix-unit capacity):

  serialized: 4 sessions' frames through the shared engine, back to back
              (the pre-scheduler serving path, measured end to end).
  batched:    the same 4 frames through a real BatchScheduler — 4
              submits coalesce into one k=4 bucket step.

Plus the single-session guard: ONE session through the scheduler
(dispatcher thread, window bypass, future resolution) vs the engine
called directly — the pass-through-cheap promise as a measured overhead
percentage.

Prints ONE JSON line (bank-and-commit contract) and appends it to
PERF_LOG.jsonl (PERF_LOG_PATH overrides; empty value disables).

Env knobs: BATCHSCHED_BENCH_FRAMES (default 16 per rep), BATCHSCHED_BENCH_PAIRS (default 24), BATCHSCHED_BENCH_SESSIONS (default 4; the tier-1 smoke uses 2 to halve compile cost).
"""

import json
import os
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from ai_rtc_agent_tpu.utils.hwfp import fingerprint  # noqa: E402
from ai_rtc_agent_tpu.utils.perfbank import paired as _paired  # noqa: E402

FRAMES = int(os.getenv("BATCHSCHED_BENCH_FRAMES") or 16)
PAIRS = int(os.getenv("BATCHSCHED_BENCH_PAIRS") or 24)
# the acceptance number is measured at 4 sessions; the tier-1 smoke runs
# 2 (half the bucket compiles) — the metric name carries the count
SESSIONS = int(os.getenv("BATCHSCHED_BENCH_SESSIONS") or 4)


def run() -> dict:
    import numpy as np

    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler
    from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception  # noqa: F401

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=24, width=24,
    )
    # variant labels from what ACTUALLY runs (ISSUE 9 satellite): a
    # QUANT_WEIGHTS=w8 env quantizes via cast_params below, and UNET_CACHE
    # reaches the config through default_stream_config — either must stamp
    # the contract line so the number never replays as (or fences against)
    # the dense baseline, exactly like bench.py's quant/unet_cache fields.
    # The quant label comes from the CAST RESULT, not the env: with the
    # default QUANT_MIN_SIZE (16384) the tiny model's kernels all stay
    # dense, and an env-only label would bank dense numbers as the w8
    # trajectory (set QUANT_MIN_SIZE=256 to actually quantize tiny-test —
    # the watcher items do)
    variant_fields = {}
    if (os.getenv("QUANT_WEIGHTS") or "").lower() in ("w8", "int8"):
        from ai_rtc_agent_tpu.models.quant import quantized_bytes_saved

        bundle.params = registry.cast_params(bundle.params, cfg.dtype)
        if quantized_bytes_saved(bundle.params) > 0:
            variant_fields["quant"] = "w8"
    if cfg.unet_cache_interval >= 2:
        variant_fields["unet_cache"] = cfg.unet_cache_interval

    # --- today's path: ONE shared engine, sessions serialize through it
    engine = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt
    )
    engine.prepare("bench prompt", seed=0)

    # --- the scheduler path: 4 claimed sessions, one vmapped bucket step
    # dp=1 explicitly: this bench IS the single-device trajectory — a
    # BATCHSCHED_DP env leaking in must not reshard the measured path
    # (scripts/mesh_sched_bench.py owns the sharded numbers)
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=SESSIONS, prewarm=True, dp=1,
    )
    sessions = [
        sched.claim(f"bench-{i}", prompt="bench prompt", seed=i)
        for i in range(SESSIONS)
    ]

    rng = np.random.default_rng(7)
    frames = rng.integers(
        0, 256, (SESSIONS, cfg.height, cfg.width, 3), dtype=np.uint8
    )

    # Per-TICK latency amortization: at every wall-clock frame tick all 4
    # sessions need a result before their next frame.  Today that costs 4
    # sequential engine steps through the shared submit lock; batched, one
    # vmapped step.  Each leg runs its tick to completion (submit all,
    # resolve all) — the latency shape a 30 fps deadline actually imposes.
    def serialized_rep() -> float:
        t0 = time.perf_counter()
        for _ in range(FRAMES):
            for j in range(SESSIONS):
                engine(frames[j])
        return (time.perf_counter() - t0) / FRAMES

    def batched_rep() -> float:
        t0 = time.perf_counter()
        for _ in range(FRAMES):
            handles = [s.submit(frames[j]) for j, s in enumerate(sessions)]
            for s, h in zip(sessions, handles):
                s.fetch(h)
        return (time.perf_counter() - t0) / FRAMES

    # Warmup (compiles + pool growth), then MANY SHORT paired reps via
    # perfbank.paired (median-of-adjacent-ratios throttle discipline).
    # Per-leg mins are reported for the absolute ms fields.
    serialized_rep()
    batched_rep()
    serialized_s, batched_s, amortization = _paired(
        serialized_rep, batched_rep, PAIRS
    )

    # --- single-session overhead: scheduler machinery vs direct engine
    for s in sessions[1:]:
        s.release()
    solo = sessions[0]
    f0 = frames[0]
    solo(f0)
    engine(f0)
    def direct_rep() -> float:
        t0 = time.perf_counter()
        for _ in range(FRAMES):
            engine(f0)
        return (time.perf_counter() - t0) / FRAMES

    def solo_rep() -> float:
        t0 = time.perf_counter()
        for _ in range(FRAMES):
            solo(f0)
        return (time.perf_counter() - t0) / FRAMES

    # the two legs differ by well under the box's throttle jitter — the
    # paired-ratio median (solo/direct measured adjacently) is the only
    # stable estimator here; extra pairs because the difference itself
    # is small
    solo_s, direct_s, inv_ratio = _paired(solo_rep, direct_rep, 3 * PAIRS)
    overhead_pct = 100.0 * (inv_ratio - 1.0)
    sched.close()

    import jax

    return {
        "check": "batch_scheduler_bench",
        "sessions": SESSIONS,
        "frames": FRAMES,
        "config": "tiny24-turbo1",
        "serialized_ms_per_frame": round(1e3 * serialized_s, 2),
        "batched_ms_per_frame": round(1e3 * batched_s, 2),
        "serialized_ms_per_session_frame": round(
            1e3 * serialized_s / SESSIONS, 2
        ),
        "batched_ms_per_session_frame": round(1e3 * batched_s / SESSIONS, 2),
        "single_direct_ms": round(1e3 * direct_s, 2),
        "single_scheduler_ms": round(1e3 * solo_s, 2),
        "single_session_overhead_pct": round(overhead_pct, 1),
        # the contract quartet
        "metric": f"batchsched_amortization_{SESSIONS}s",
        "value": round(amortization, 2),
        "unit": "x",
        "vs_baseline": round(amortization, 2),
        # the REAL backend: the cpu env default is a setdefault, so a
        # JAX_PLATFORMS=tpu run must not be mislabelled
        "backend": jax.default_backend(),
        "live": True,
        "label": f"batchsched_{SESSIONS}s_{FRAMES}f",
        "recorded_at": datetime.now(timezone.utc).isoformat(),
        # shared hardware identity (utils/hwfp.py) — full probe: jax is
        # already initialized by the measurement itself
        "fingerprint": fingerprint(),
        **variant_fields,
    }


from ai_rtc_agent_tpu.utils.perfbank import bank as _bank  # noqa: E402


def main():
    from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception

    sigterm_to_exception("batch_scheduler_bench timeout")
    entry = {
        "check": "batch_scheduler_bench",
        "metric": f"batchsched_amortization_{SESSIONS}s",
        "value": 0.0,
        "unit": "x",
        "vs_baseline": 0.0,
    }
    try:
        entry = run()
        _bank(entry)
    except BaseException as e:  # the contract line must survive any exit
        entry["error"] = f"{type(e).__name__}: {e}"
    finally:
        print(json.dumps(entry))
    sys.exit(0)


if __name__ == "__main__":
    main()
