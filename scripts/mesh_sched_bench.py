"""Mesh-sharded scheduler amortization: the session axis across chips.

Measures ISSUE 12's tentpole as a banked trajectory number: N concurrent
sessions served by ONE dp=N mesh-sharded bucket step (each session's
state row, H2D staging and per-slot readback on its OWN shard) vs the
same N sessions on the single-device scheduler (PR 7's vmapped step on
one chip).

  single:  scheduler S=N, dp=1 — N sessions, one device computes all N
           rows per tick (the pre-ISSUE-12 default path).
  sharded: scheduler S=N, dp=N — the same N sessions, one sharded
           dispatch computes 1 row per device.

Metric ``meshsched_amortization_dp<N>`` = single/sharded per-tick median
paired ratio (higher is better).  On real TPUs the N rows compute on N
real chips and the ratio approaches N; on this CPU tier the "devices"
are XLA's 8-virtual-device simulation sharing the host's cores, so the
honest CPU number mostly prices the sharded dispatch/assembly machinery
(partitioned executable, per-shard staging, global-array assembly) —
the fence catches that machinery regressing; the accelerator truth is a
run on the four-chip host, which has not been made.  Never
bank the CPU number on the accelerator trajectory: the ``backend``
field + perf_compare's hardware-tier predicate keep the two apart.

Prints ONE JSON line (bank-and-commit contract) and appends it to
PERF_LOG.jsonl (PERF_LOG_PATH overrides; empty value disables).

Env knobs: MESHSCHED_BENCH_FRAMES (default 12 per rep),
MESHSCHED_BENCH_PAIRS (default 12), MESHSCHED_BENCH_SESSIONS (default
8; = the dp axis size — the metric name carries it).
"""

import json
import os
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

FRAMES = int(os.getenv("MESHSCHED_BENCH_FRAMES") or 12)
PAIRS = int(os.getenv("MESHSCHED_BENCH_PAIRS") or 12)
SESSIONS = int(os.getenv("MESHSCHED_BENCH_SESSIONS") or 8)

if os.environ.get("JAX_PLATFORMS") != "tpu":
    # the CPU tier simulates the mesh with virtual devices (the tier-1
    # flag); a real accelerator run uses its actual chip complement
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={SESSIONS}"
        )

from ai_rtc_agent_tpu.utils.hwfp import fingerprint  # noqa: E402
from ai_rtc_agent_tpu.utils.perfbank import paired as _paired  # noqa: E402


def run() -> dict:
    import jax
    import numpy as np

    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

    if len(jax.devices()) < SESSIONS:
        raise RuntimeError(
            f"need {SESSIONS} devices for the dp axis, have "
            f"{len(jax.devices())}"
        )
    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=24, width=24,
    )
    variant_fields = {}
    if (os.getenv("QUANT_WEIGHTS") or "").lower() in ("w8", "int8"):
        from ai_rtc_agent_tpu.models.quant import quantized_bytes_saved

        bundle.params = registry.cast_params(bundle.params, cfg.dtype)
        if quantized_bytes_saved(bundle.params) > 0:
            variant_fields["quant"] = "w8"
    if cfg.unet_cache_interval >= 2:
        variant_fields["unet_cache"] = cfg.unet_cache_interval

    def build(dp: int):
        sched = BatchScheduler(
            bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
            max_sessions=SESSIONS, prewarm=True, dp=dp,
        )
        sessions = [
            sched.claim(f"mesh-{dp}-{i}", prompt="bench prompt", seed=i)
            for i in range(SESSIONS)
        ]
        return sched, sessions

    sched_1, sess_1 = build(1)
    sched_n, sess_n = build(SESSIONS)

    rng = np.random.default_rng(7)
    frames = rng.integers(
        0, 256, (SESSIONS, cfg.height, cfg.width, 3), dtype=np.uint8
    )

    # per-TICK latency: every wall-clock frame tick all N sessions need a
    # result before their next frame — submit all, resolve all, per leg
    def make_rep(sessions):
        def rep() -> float:
            t0 = time.perf_counter()
            for _ in range(FRAMES):
                handles = [
                    s.submit(frames[j]) for j, s in enumerate(sessions)
                ]
                for s, h in zip(sessions, handles):
                    s.fetch(h)
            return (time.perf_counter() - t0) / FRAMES
        return rep

    single_rep = make_rep(sess_1)
    sharded_rep = make_rep(sess_n)

    # warmup, then MANY SHORT paired reps via perfbank.paired (the
    # median-of-adjacent-ratios throttle-jitter discipline)
    single_rep()
    sharded_rep()
    single_s, sharded_s, amortization = _paired(single_rep, sharded_rep, PAIRS)

    sched_1.close()
    sched_n.close()

    return {
        "check": "mesh_sched_bench",
        "sessions": SESSIONS,
        "dp": SESSIONS,
        "frames": FRAMES,
        "config": "tiny24-turbo1",
        "single_device_ms_per_tick": round(1e3 * single_s, 2),
        "sharded_ms_per_tick": round(1e3 * sharded_s, 2),
        "single_device_ms_per_session_frame": round(
            1e3 * single_s / SESSIONS, 2
        ),
        "sharded_ms_per_session_frame": round(1e3 * sharded_s / SESSIONS, 2),
        # the contract quartet
        "metric": f"meshsched_amortization_dp{SESSIONS}",
        "value": round(amortization, 2),
        "unit": "x",
        "vs_baseline": round(amortization, 2),
        "backend": jax.default_backend(),
        "live": True,
        "label": f"meshsched_dp{SESSIONS}_{FRAMES}f",
        "recorded_at": datetime.now(timezone.utc).isoformat(),
        "fingerprint": fingerprint(),
        **variant_fields,
    }


from ai_rtc_agent_tpu.utils.perfbank import bank as _bank  # noqa: E402


def main():
    from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception

    sigterm_to_exception("mesh_sched_bench timeout")
    entry = {
        "check": "mesh_sched_bench",
        "metric": f"meshsched_amortization_dp{SESSIONS}",
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
