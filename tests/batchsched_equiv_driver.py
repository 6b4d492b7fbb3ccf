"""Subprocess driver for the batch-scheduler equivalence test.

Every frame a scheduler session returns is compared with the frame a
dedicated ``StreamEngine`` returns for the same input, through
:func:`assert_one_step` — the one tolerance of this file: a bucket
executable (batch k, gather/scatter fused in) and a dedicated engine
(batch 1) are different XLA programs, the CPU backend fuses them
differently, and a float that lands on a uint8 rounding boundary can fall
either way.  So every element must be within 1 and at most 0.1 % of a
frame's elements may differ at all.  Runs OUTSIDE the test harness's
``--xla_force_host_platform_device_count=8`` simulation (that flag changes
XLA's CPU thread partitioning per batch shape on top).  Prints
``EQUIV_OK <n> ties=<t>`` (n = frame comparisons, t = elements off by
one over all of them) or raises on the first comparison out of tolerance.

ISSUE 9 variant legs: the SAME scheduler-vs-dedicated comparison under
``QUANT_WEIGHTS=w8`` (int8 kernels + fused dequant) and the DeepCache
cadence (``unet_cache_interval``), each across bucket sizes k=4/2/1,
same variant on both sides, same tolerance; the per-leg counts print as
``EQUIV_W8_OK <n>`` / ``EQUIV_DC_OK <n>``.

ISSUE 12 legs:

* ``--leg sharded`` runs a SEPARATE process UNDER the 8-virtual-device
  flag (the dp mesh needs devices): a dp=2-sharded scheduler vs
  dedicated engines across join/leave spanning the shard boundary,
  prompt/guidance/t-index updates, restart and rejoin.  Tolerance: the
  virtual-device simulation changes XLA's CPU thread partitioning
  between the sharded batch-k graph and the batch-1 engine graph, so a
  float rounding tie can flip one uint8 by 1 (exactly PR 7's documented
  tie class) — the leg asserts ``|diff| <= 1`` and prints the tie count
  (``EQUIV_SHARD_OK <n> ties=<t>``; 5 over 25 comparisons at PR 31).
* The fbs leg (in the default run): scheduler ``frame_buffer_size=2`` —
  sessions x consecutive frames as TWO batch dimensions of one bucket
  step — vs dedicated fbs=2 engines (``EQUIV_FBS_OK <n>``).

ISSUE 17 budget shave: ``--leg dense`` runs ONLY the dense drive (no
variant legs) — the lighter tier-1 sibling; the full composition (w8 +
DeepCache + fbs, each re-tracing k=4/2/1) runs in the slow tier.

ISSUE 20 adapter leg (in the full run): per-session LoRA factor banks
THROUGH the scheduler — each slot's style applied inside the shared
bucket step — vs dedicated engines with the SAME style offline-fused
(``models/lora.py``).  The factors path computes ``y + (x@down.T)@up.T``
where the fuse bakes ``kernel + down.T@up.T``: identical math up to
float association order, so the documented tolerance is PR 7's rounding
tie class (``|uint8 diff| <= 1``; ties reported — a couple observed per
run on this box).  A slot with NO adapter carries zero factors through the same
graph and is held to :func:`assert_one_step` against a plain engine,
like every adapterless comparison here.  Prints
``EQUIV_ADAPTER_OK <n> ties=<t>``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
if "--leg" in sys.argv and "sharded" in sys.argv:
    # the dp mesh needs devices: force the SAME 8-virtual-device flag the
    # tier-1 harness runs under (this is the sharded serving simulation,
    # not the single-device environment of the default run)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
else:
    os.environ.pop("XLA_FLAGS", None)

import numpy as np  # noqa: E402

from ai_rtc_agent_tpu.models import registry  # noqa: E402
from ai_rtc_agent_tpu.stream.engine import (  # noqa: E402
    SimilarityFilter,
    StreamEngine,
)
from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler  # noqa: E402

# elements off by one over every assert_one_step comparison of this run
TIES = 0


def assert_one_step(out, ref):
    """A scheduler frame against the dedicated engine's: every element
    within 1 (compared as int16) and at most 0.1 % of the frame's elements
    off at all.  Not exact equality, because the two sides are executables
    of different batch size, which the CPU backend fuses differently: a
    value on a uint8 rounding boundary may quantise one step apart.  More
    than that is a real divergence and raises."""
    global TIES
    d = np.abs(np.asarray(out).astype(np.int16) - np.asarray(ref).astype(np.int16))
    off = int(np.count_nonzero(d))
    assert d.max() <= 1 and off <= d.size // 1000, (
        f"scheduler output diverged from the dedicated engine: max diff "
        f"{d.max()}, {off} of {d.size} elements differ"
    )
    TIES += off


def dedicated_engines(n, bundle, cfg, params=None):
    """n dedicated engines SHARING one set of jitted step callables.

    Every StreamEngine jits its own make_step_fn closure, so n identical
    engines pay n identical tiny-model compiles — the single biggest
    wall-time cost of this driver (tier-1 budget, ROADMAP standing
    constraint).  The step fn is pure in (params, state, frame), so
    engines over the same models/config are interchangeable at the
    executable level; sharing keeps the COMPARISON exact while paying
    each graph's compile once."""
    params = bundle.params if params is None else params
    engines = [
        StreamEngine(
            bundle.stream_models, params, cfg, bundle.encode_prompt
        )
        for _ in range(n)
    ]
    for eng in engines[1:]:
        eng._step = engines[0]._step
        if engines[0]._step_cached is not None:
            eng._step_cached = engines[0]._step_cached
    return engines


def drive_variant(label: str, bundle, cfg, params) -> int:
    """k=4 -> k=2 -> k=1 scheduler-vs-dedicated drive under one serving
    variant.  Three sessions claim up-front (every install resets the
    global DeepCache cadence, so the LAST claim leaves the tick at 0 —
    exactly the dedicated engines' fresh-prepare state), then release one
    by one: releases never touch the cadence, so both sides stay
    tick-aligned through every bucket transition."""
    rng = np.random.default_rng(hash(label) % (2**32))

    def frames(n):
        return [rng.integers(0, 256, (64, 64, 3), np.uint8) for _ in range(n)]

    # HUGE window: dispatch must happen ONLY when every live session has
    # a frame waiting (the inline full-batch path) — with a small window
    # a throttle hiccup between two submits lets the dispatcher fire a
    # PARTIAL batch, which advances the global DeepCache tick twice in
    # one comparison round and desyncs the cadence from the dedicated
    # engines (dense/w8 are cadence-free, so only the DC leg could flake)
    sched = BatchScheduler(
        bundle.stream_models, params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=1,
    )
    prompts = ["a red cat", "a blue dog", "green hills"]
    sessions = [
        sched.claim(f"{label}-{i}", prompt=p, seed=40 + i)
        for i, p in enumerate(prompts)
    ]
    engines = dedicated_engines(3, bundle, cfg, params)
    for eng, (i, p) in zip(engines, enumerate(prompts)):
        eng.prepare(p, seed=40 + i)
    compared = 0

    def rounds(n, sess, engs):
        nonlocal compared
        for _ in range(n):
            fs = frames(len(sess))
            handles = [s.submit(f) for s, f in zip(sess, fs)]
            outs = [s.fetch(h) for s, h in zip(sess, handles)]
            for out, eng, f in zip(outs, engs, fs):
                assert_one_step(out, eng(f))
                compared += 1

    # 3 rounds per occupancy: with interval-3 DeepCache that is one full
    # capture + two cached steps at every bucket size — both graphs of
    # the pair execute and stay pinned at each k
    rounds(3, sessions, engines)            # k=4 (3 live rows, padded)
    sessions[2].release()
    rounds(3, sessions[:2], engines[:2])    # k=2
    sessions[1].release()
    rounds(3, sessions[:1], engines[:1])    # k=1 (solo-ultra inline path)
    sessions[0].release()
    sched.close()
    return compared


def drive_sharded():
    """ISSUE 12 parity leg: a dp=2 mesh-sharded scheduler vs dedicated
    engines, join/leave ACROSS the shard boundary (slots 0-1 live on
    shard 0, slots 2-3 on shard 1), per-session control-plane updates,
    restart and rejoin.  Runs under the 8-virtual-device flag (set at
    module import for ``--leg sharded``); the documented tolerance is a
    single uint8 rounding tie (see module docstring)."""
    import jax

    assert len(jax.devices()) >= 2, "sharded leg needs the device flag"
    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
    )
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=2,
    )
    assert sched.dp == 2 and sched._bucket_sizes == [2, 4]
    engines = dedicated_engines(3, bundle, cfg)
    rng = np.random.default_rng(12)
    compared = 0
    ties = 0

    def frames(n):
        return [rng.integers(0, 256, (64, 64, 3), np.uint8) for _ in range(n)]

    def step_pairs(sessions, dedicated, fs):
        nonlocal compared, ties
        handles = [s.submit(f) for s, f in zip(sessions, fs)]
        outs = [s.fetch(h) for s, h in zip(sessions, handles)]
        for out, eng, f in zip(outs, dedicated, fs):
            d = np.abs(out.astype(np.int16) - eng(f).astype(np.int16))
            assert d.max() <= 1, (
                f"sharded output diverged beyond a rounding tie "
                f"(max diff {d.max()})"
            )
            ties += int((d == 1).sum())
            compared += 1

    e1, e2, e3 = engines
    s1 = sched.claim("sh-a", prompt="a red cat", seed=11)     # slot 0, shard 0
    e1.prepare("a red cat", seed=11)
    # balanced claim() crosses the shard boundary HERE: the least-loaded
    # shard is 1, so the second session lands on slot 2 / shard 1
    s2 = sched.claim("sh-b", prompt="a blue dog", seed=22)
    e2.prepare("a blue dog", seed=22)
    assert s2.snapshot()["shard"] == 1, s2.snapshot()
    for _ in range(2):
        step_pairs([s1, s2], [e1, e2], frames(2))   # k=2, one row per shard

    # JOIN: balanced claim fills shard 0's second slot -> k=4
    s3 = sched.claim("sh-c", prompt="green hills", seed=33)
    e3.prepare("green hills", seed=33)
    assert s3.snapshot()["shard"] == 0, s3.snapshot()
    for _ in range(2):
        step_pairs([s1, s2, s3], [e1, e2, e3], frames(3))

    # per-session control plane across shards: only the target changes
    s2.update_prompt("a completely different prompt")
    e2.update_prompt("a completely different prompt")
    s3.update_guidance(guidance_scale=1.7, delta=0.8)
    e3.update_guidance(1.7, 0.8)
    s1.update_t_index_list([5])
    e1.update_t_index_list([5])
    for _ in range(2):
        step_pairs([s1, s2, s3], [e1, e2, e3], frames(3))

    # LEAVE empties shard 1 entirely; both survivors live on shard 0, so
    # the k=2 bucket spills one row onto the idle shard (the explicit
    # D2D straggler hop in _assemble_frames) — parity must hold through it
    s2.release()
    for _ in range(2):
        step_pairs([s1, s3], [e1, e3], frames(2))

    # restart() restores the live control plane on a fresh sharded row
    s1.restart()
    e1.prepare("a red cat", seed=11)
    e1.update_t_index_list([5])
    step_pairs([s1, s3], [e1, e3], frames(2))

    # rejoin: balanced claim re-fills the emptied shard 1 (freed slot 2)
    s2b = sched.claim("sh-d", prompt="a blue dog", seed=22)
    e2.prepare("a blue dog", seed=22)
    assert s2b.snapshot()["shard"] == 1, s2b.snapshot()
    step_pairs([s1, s2b, s3], [e1, e2, e3], frames(3))

    snap = sched.snapshot()
    assert snap["batchsched_dp"] == 2
    assert snap["batchsched_shard_sessions"] == {"0": 2, "1": 1}, snap
    sched.close()
    print(f"EQUIV_SHARD_OK {compared} ties={ties}")


def drive_fbs(bundle) -> int:
    """ISSUE 12 fbs leg: frame_buffer_size=2 THROUGH the scheduler —
    sessions x consecutive frames as two batch dimensions of one bucket
    step — vs dedicated fbs=2 engines, held to assert_one_step."""
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        frame_buffer_size=2,
    )
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=False, dp=1,
    )
    engines = dedicated_engines(2, bundle, cfg)
    e1, e2 = engines
    s1 = sched.claim("fbs-a", prompt="a red cat", seed=11)
    e1.prepare("a red cat", seed=11)
    s2 = sched.claim("fbs-b", prompt="a blue dog", seed=22)
    e2.prepare("a blue dog", seed=22)
    rng = np.random.default_rng(21)
    compared = 0

    def group(n):
        return rng.integers(0, 256, (n, 64, 64, 3), np.uint8)

    def step_groups(sessions, dedicated):
        nonlocal compared
        gs = [group(2) for _ in sessions]
        handles = [s.submit_batch(list(g)) for s, g in zip(sessions, gs)]
        for s, h, eng, g in zip(sessions, handles, dedicated, gs):
            out = np.stack(s.fetch_batch(h))
            assert_one_step(out, eng(g))
            compared += 2

    for _ in range(3):
        step_groups([s1, s2], [e1, e2])   # k=2 x fbs=2 in one step
    s2.release()
    for _ in range(2):
        step_groups([s1], [e1])           # solo keeps the group batching
    s1.release()
    sched.close()
    return compared


def drive_adapter(bundle) -> int:
    """ISSUE 20 parity leg: per-session style adapters through the
    scheduler's stacked factor bank vs dedicated engines with the same
    LoRA offline-fused, across join/leave/bucket transitions, hot-swaps
    (mirrored as a params reassignment on the dedicated side — the step
    fn is pure in params) and restart.  See module docstring for the
    documented tolerance."""
    from ai_rtc_agent_tpu.adapters import AdapterRegistry
    from ai_rtc_agent_tpu.models import loader as LD
    from ai_rtc_agent_tpu.models import lora as LR

    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
    )
    km = LD.unet_key_map(bundle.unet_cfg)
    MQ = "down_blocks.0.attentions.0.transformer_blocks.0.attn1.to_q"
    MV = "down_blocks.0.attentions.0.transformer_blocks.0.attn1.to_v"
    grng = np.random.default_rng(77)

    def mk_groups(mods, r=2, din=8, dout=8):
        return {
            m: {
                "down": (grng.normal(size=(r, din)) * 0.2).astype(np.float32),
                "up": (grng.normal(size=(dout, r)) * 0.2).astype(np.float32),
                "alpha": float(r),
            }
            for m in mods
        }

    # styleA touches ONE module, styleB two: the bank's target set is the
    # union, so styleA's row carries explicit zeros at MV (zero-extension)
    gA = mk_groups([MQ])
    gB = mk_groups([MQ, MV])
    reg = AdapterRegistry(bundle.params["unet"], km)
    reg.add("styleA", gA)
    reg.add("styleB", gB)
    assert reg.bank_rank == 4, reg.bank_rank  # rank 2 pads to bucket 4

    def fused(groups):
        unet, applied, unmatched = LR.fuse_lora_into_unet(
            bundle.params["unet"], groups, km
        )
        assert applied == len(groups) and not unmatched
        p = dict(bundle.params)
        p["unet"] = unet
        return p

    pA, pB = fused(gA), fused(gB)

    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=1,
        adapters=reg,
    )
    # dedicated engines share ONE jitted step (pure in params); the plain
    # one doubles as the hot-swap mirror by reassigning .params
    e_base, eA, eB = dedicated_engines(3, bundle, cfg)
    base_params = e_base.params
    eA.params = pA
    eB.params = pB
    rng = np.random.default_rng(31)
    compared = 0
    ties = 0

    def frames(n):
        return [rng.integers(0, 256, (64, 64, 3), np.uint8) for _ in range(n)]

    def step_pairs(sessions, dedicated, exact, fs):
        nonlocal compared, ties
        handles = [s.submit(f) for s, f in zip(sessions, fs)]
        outs = [s.fetch(h) for s, h in zip(sessions, handles)]
        for out, eng, ex, f in zip(outs, dedicated, exact, fs):
            ref = eng(f)
            if ex:
                assert_one_step(out, ref)
            else:
                d = np.abs(out.astype(np.int16) - ref.astype(np.int16))
                assert d.max() <= 1, (
                    f"adapter parity beyond a rounding tie (max {d.max()})"
                )
                ties += int((d == 1).sum())
            compared += 1

    s1 = sched.claim("ad-a", prompt="a red cat", seed=11, adapter="styleA")
    eA.prepare("a red cat", seed=11)
    s2 = sched.claim("ad-b", prompt="a blue dog", seed=22)  # no adapter
    e_base.prepare("a blue dog", seed=22)
    # k=2: styled slot within the tie class, zero-factor slot held to
    # assert_one_step (``exact``)
    for _ in range(2):
        step_pairs([s1, s2], [eA, e_base], [False, True], frames(2))

    # JOIN with a different style -> padded k=4, three styles live at once
    s3 = sched.claim("ad-c", prompt="green hills", seed=33, adapter="styleB")
    eB.prepare("green hills", seed=33)
    for _ in range(2):
        step_pairs([s1, s2, s3], [eA, e_base, eB],
                   [False, True, False], frames(3))

    # HOT-SWAP mid-stream: s2 None -> styleA; the dedicated mirror is a
    # params reassignment on the SAME engine (state history carries over
    # on both sides).  From here s2's pair is tie-class, not exact: its
    # pre-swap state already differs from the mirror's by association
    # rounding fed back through the latent ring.
    s2.update_adapter("styleA")
    e_base.params = pA
    for _ in range(2):
        step_pairs([s1, s2, s3], [eA, e_base, eB],
                   [False, False, False], frames(3))

    # swap BACK to no style + restart: a fresh zero-factor state against
    # a fresh plain engine state is ``exact`` again
    s2.update_adapter(None)
    e_base.params = base_params
    s2.restart()
    e_base.prepare("a blue dog", seed=22)
    for _ in range(2):
        step_pairs([s1, s2, s3], [eA, e_base, eB],
                   [False, True, False], frames(3))

    # LEAVE -> k=2; the styled survivor stays pinned to its factors
    s3.release()
    for _ in range(2):
        step_pairs([s1, s2], [eA, e_base], [False, True], frames(2))

    # restart() rebuilds the styled session's state WITH its adapter
    s1.restart()
    eA.prepare("a red cat", seed=11)
    for _ in range(2):
        step_pairs([s1, s2], [eA, e_base], [False, True], frames(2))

    snap = sched.snapshot()
    assert snap["adapter_rank"] == 4, snap
    assert snap["adapter_swaps_total"] >= 2, snap
    sched.close()
    print(f"EQUIV_ADAPTER_OK {compared} ties={ties}")
    return compared


def main(variants=True):
    bundle = registry.load_model_bundle("tiny-test")
    # 8 sub-timesteps with a single stage so update_t_index_list([5]) is a
    # REAL coefficient change (a 1-step schedule only admits index 0)
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        similar_image_filter=True, similar_image_threshold=1.0,
    )
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=2.0, prewarm=False, dp=1,
    )
    engines = dedicated_engines(3, bundle, cfg)
    rng = np.random.default_rng(0)
    compared = 0

    def frames(n):
        return [rng.integers(0, 256, (64, 64, 3), np.uint8) for _ in range(n)]

    def step_pairs(sessions, dedicated, fs):
        nonlocal compared
        handles = [s.submit(f) for s, f in zip(sessions, fs)]
        outs = [s.fetch(h) for s, h in zip(sessions, handles)]
        for out, eng, f in zip(outs, dedicated, fs):
            assert_one_step(out, eng(f))
            compared += 1

    e1, e2, e3 = engines
    s1 = sched.claim("sess-a", prompt="a red cat", seed=11)
    e1.prepare("a red cat", seed=11)
    s2 = sched.claim("sess-b", prompt="a blue dog", seed=22)
    e2.prepare("a blue dog", seed=22)

    # k=2 bucket
    for _ in range(3):
        step_pairs([s1, s2], [e1, e2], frames(2))

    # mid-stream JOIN -> padded k=4 bucket
    s3 = sched.claim("sess-c", prompt="green hills", seed=33)
    e3.prepare("green hills", seed=33)
    for _ in range(2):
        step_pairs([s1, s2, s3], [e1, e2, e3], frames(3))

    # per-session control plane: only the updated session changes
    s2.update_prompt("a completely different prompt")
    e2.update_prompt("a completely different prompt")
    s3.update_guidance(guidance_scale=1.7, delta=0.8)
    e3.update_guidance(1.7, 0.8)
    s1.update_t_index_list([5])
    e1.update_t_index_list([5])
    for _ in range(2):
        step_pairs([s1, s2, s3], [e1, e2, e3], frames(3))

    # mid-stream LEAVE: survivors keep following their engines
    s2.release()
    for _ in range(2):
        step_pairs([s1, s3], [e1, e3], frames(2))

    # down to one: the solo inline fast path
    s3.release()
    for _ in range(3):
        f = frames(1)[0]
        assert_one_step(s1(f), e1(f))
        compared += 1

    # rejoin on the freed slot: a fresh state, not the old tenant's
    s2b = sched.claim("sess-d", prompt="a blue dog", seed=22)
    e2.prepare("a blue dog", seed=22)
    step_pairs([s1, s2b], [e1, e2], frames(2))

    # restart() restores the LIVE control plane (t_index [5], not the
    # config default) on a fresh stream state
    s1.restart()
    e1.prepare("a red cat", seed=11)
    e1.update_t_index_list([5])
    step_pairs([s1, s2b], [e1, e2], frames(2))

    # similarity skips: per-session filters in lockstep with dedicated
    # engines; one session's static scene never perturbs the other
    s1._sim = SimilarityFilter(0.9, 3, seed=0)
    e1._sim_filter = SimilarityFilter(0.9, 3, seed=0)
    s2b._sim = SimilarityFilter(0.9, 3, seed=0)
    e2._sim_filter = SimilarityFilter(0.9, 3, seed=0)
    static = frames(1)[0]
    for _ in range(8):
        fresh = frames(1)[0]
        step_pairs([s1, s2b], [e1, e2], [static, fresh])
    assert s1.frames_skipped_similar > 0, "static scene never skipped"
    assert s2b.frames_skipped_similar == 0, "live scene skipped"

    snap = sched.snapshot()
    assert snap["batchsched_steps_total"] > 0
    assert snap["batchsched_occupancy_hist"]
    sched.close()

    # --- ISSUE 9 variant legs: same drive, quantized + cached-cadence ---
    # (skipped for --leg dense: each variant re-traces the full k=4/2/1
    # geometry set, which is most of this driver's wall clock — the dense
    # leg alone is the tier-1 sibling, the composition runs in slow)
    if not variants:
        print(f"EQUIV_OK {compared} ties={TIES}")
        return
    os.environ["QUANT_WEIGHTS"] = "w8"
    os.environ["QUANT_MIN_SIZE"] = "256"  # tiny-model kernels are small
    try:
        qparams = registry.cast_params(bundle.params, cfg.dtype)
    finally:
        del os.environ["QUANT_WEIGHTS"], os.environ["QUANT_MIN_SIZE"]
    from ai_rtc_agent_tpu.models.quant import quantized_bytes_saved

    assert quantized_bytes_saved(qparams) > 0, "quantization was a no-op"
    n_w8 = drive_variant("w8", bundle, cfg, qparams)
    compared += n_w8
    print(f"EQUIV_W8_OK {n_w8}")

    dc_cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        unet_cache_interval=3,
    )
    n_dc = drive_variant("dc3", bundle, dc_cfg, bundle.params)
    compared += n_dc
    print(f"EQUIV_DC_OK {n_dc}")

    n_fbs = drive_fbs(bundle)
    compared += n_fbs
    print(f"EQUIV_FBS_OK {n_fbs}")

    compared += drive_adapter(bundle)

    print(f"EQUIV_OK {compared} ties={TIES}")


if __name__ == "__main__":
    if "--leg" in sys.argv and "sharded" in sys.argv:
        drive_sharded()
    elif "--leg" in sys.argv and "dense" in sys.argv:
        main(variants=False)
    elif "--leg" in sys.argv and "adapter" in sys.argv:
        drive_adapter(registry.load_model_bundle("tiny-test"))
    else:
        main()
