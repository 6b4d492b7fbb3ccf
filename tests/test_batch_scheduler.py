"""Continuous batch scheduler (stream/scheduler.py) — ISSUE 7.

The load-bearing guarantee is EQUIVALENCE: a session served through the
cross-session batch scheduler must produce the frames a dedicated
StreamEngine would, across dynamic join/leave, bucket transitions
(k=1/2/4 with padding), per-session prompt/guidance/t-index updates and
similarity skips — to within one uint8 quantisation step in at most
0.1 % of a frame's elements, because a bucket executable and a batch-1
engine are different XLA programs (the driver's ``assert_one_step``).
That drive runs in a SUBPROCESS without the harness's 8-virtual-device
flag (tests/batchsched_equiv_driver.py), which changes XLA's CPU thread
partitioning per batch shape on top.  Everything else here is hermetic
in-process: the inline fast path or a huge window, no real-time waits.
"""

import asyncio
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.stream import scheduler as scheduler_mod
from ai_rtc_agent_tpu.stream.engine import StreamEngine
from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler, CapacityError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bundle():
    return registry.load_model_bundle("tiny-test")


@pytest.fixture(scope="module")
def cfg():
    return registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
    )


def test_equivalence_dense_subprocess():
    """The tier-1 acceptance pin: the full join/leave/prompt/guidance/
    t-index/similarity/restart drive, every frame held to the driver's
    one-quantisation-step tolerance against dedicated engines, on a
    clean single-device CPU runtime.
    The ISSUE 9/13 variant legs (w8, DeepCache, fbs — each re-tracing
    the whole k=4/2/1 geometry set) run in the slow composition test
    below (ISSUE 17 budget shave: this lighter sibling keeps the
    equivalence guarantee in tier-1 at a third of the compile bill)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "tests/batchsched_equiv_driver.py",
         "--leg", "dense"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("EQUIV_OK")]
    assert lines, r.stdout
    assert int(lines[0].split()[1]) >= 40  # the dense drive alone


# slow tier (ISSUE 17 budget shave): the variant COMPOSITION legs each
# re-trace k=4/2/1 — most of the driver's wall clock; tier-1 keeps the
# dense equivalence drive above as the lighter sibling
@pytest.mark.slow
def test_equivalence_bit_identical_subprocess():
    """The full composition: the dense drive PLUS the ISSUE 9 variant
    legs (w8 quant and the DeepCache cadence THROUGH the scheduler's
    bucket steps, k=4/2/1, same tolerance), the fbs=2 leg and the
    ISSUE 20 adapter leg (per-session LoRA factor banks vs offline-fused
    dedicated engines across join/leave/hot-swap/restart; tolerance =
    the documented rounding-tie class, zero-factor slots held to the
    driver's assert_one_step)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "tests/batchsched_equiv_driver.py"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("EQUIV_OK")]
    assert lines, r.stdout
    assert int(lines[0].split()[1]) >= 70  # dense + both variant legs
    for leg, floor in (("EQUIV_W8_OK", 15), ("EQUIV_DC_OK", 15),
                       ("EQUIV_ADAPTER_OK", 25)):
        leg_lines = [
            ln for ln in r.stdout.splitlines() if ln.startswith(leg)
        ]
        assert leg_lines, f"{leg} leg missing: {r.stdout}"
        assert int(leg_lines[0].split()[1]) >= floor


@pytest.mark.slow
def test_sharded_equivalence_subprocess():
    """ISSUE 12 acceptance pin: the dp=2 mesh-sharded scheduler vs
    dedicated engines across join/leave spanning the shard boundary,
    control-plane updates, restart and rejoin — run under the
    8-virtual-device flag (the sharded serving simulation).  Tolerance:
    a single uint8 rounding tie (the virtual-device flag changes XLA's
    CPU thread partitioning between the sharded batch-k and batch-1
    graphs — PR 7's documented tie class; the driver reports the count,
    5 elements over 25 comparisons at PR 31).

    Slow tier (ISSUE 14 budget shave): the dp COMPOSITION leg — tier-1
    keeps the single-device equivalence driver, the dp churn/retrace pin
    and the shard-aware key coverage as the lighter siblings."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)  # the driver forces its own 8-device flag
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "tests/batchsched_equiv_driver.py",
         "--leg", "sharded"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [
        ln for ln in r.stdout.splitlines() if ln.startswith("EQUIV_SHARD_OK")
    ]
    assert lines, r.stdout
    assert int(lines[0].split()[1]) >= 15


# slow tier (ISSUE 18 budget shave): prewarm=True compiles every
# (k, variant, dp) geometry before the churn even starts — most of this
# test's wall clock; tier-1 keeps
# test_shard_aware_bucket_keys_and_prewarm_coverage below, which pins
# the same prewarm-coverage + shard-keyed-executable mechanism without
# the compile bill
@pytest.mark.slow
def test_sharded_churn_never_retraces(bundle):
    """ISSUE 12 acceptance pin: a prewarmed dp-sharded scheduler serves a
    join -> leave -> rejoin churn (control-plane writes and a restart
    included) with ZERO devtel retrace breaches — prewarm covers every
    (k, variant, dp) geometry, attributed under the mesh-carrying scope
    name, and every serving-phase dispatch hits a warm executable."""
    from ai_rtc_agent_tpu.obs import devtel
    from ai_rtc_agent_tpu.obs.devtel import DevTelPlane

    cfg32 = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=32, width=32,
    )
    plane = devtel.activate(DevTelPlane())
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg32, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=True, dp=2,
    )
    rng = np.random.default_rng(5)

    def tick(sessions):
        fs = [
            rng.integers(0, 256, (32, 32, 3), np.uint8) for _ in sessions
        ]
        hs = [x.submit(f) for x, f in zip(sessions, fs)]
        return [x.fetch(h) for x, h in zip(sessions, hs)]

    try:
        # prewarm attributed with the mesh shape in the scope name,
        # expected (a serve-time re-prewarm must never false-alarm),
        # all in the warmup phase
        ctxs = {c["context"] for c in plane.compiles}
        assert "sbucket-2:full:dp2" in ctxs, ctxs
        assert all(
            c["expected"] for c in plane.compiles
            if c["context"] == "sbucket-2:full:dp2"
        )
        assert plane.retrace_breaches == 0
        a = s.claim("a", prompt="pa", seed=1)
        b = s.claim("b", prompt="pb", seed=2)
        tick([a, b])  # warm the host-side eager ops too (agent warmup)
        b.release()
        tick([a])
        plane.serving()
        # churn across the shard boundary on warm executables only
        tick([a])
        b2 = s.claim("b2", prompt="pb2", seed=9)  # rejoin -> shard 1
        tick([a, b2])
        a.update_prompt("new prompt")
        b2.update_guidance(guidance_scale=1.5)
        a.restart()
        tick([a, b2])
        a.release()
        tick([b2])
        assert plane.retrace_breaches == 0, [
            c for c in plane.compiles if c["phase"] == "serving"
        ]
    finally:
        devtel.deactivate(plane)
        s.close()


def test_shard_aware_bucket_keys_and_prewarm_coverage(bundle, cfg, tmp_path):
    """Unit pins for the dp key plane: bucket sizes are dp multiples
    (padding rows land on idle shards), every key carries the mesh shape
    (``dp-N`` via aot/cache.mesh_key_extra) so sharded executables never
    collide with single-device slots, prewarm covers every (k, variant,
    dp) geometry, AOT export refuses (a serialized program is
    per-topology), and slot->shard residence is slot-major."""
    import jax

    from ai_rtc_agent_tpu.aot.cache import mesh_key_extra

    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=2,
    )
    try:
        assert s.dp == 2
        assert s._bucket_sizes == [2, 4]  # dp multiples, never k=1
        keys = s.bucket_keys("tiny-test")
        assert set(keys) == {(2, "full"), (4, "full")}
        assert all("dp-2" in k for k in keys.values())
        assert mesh_key_extra(s.mesh) == {"dp": 2}
        assert mesh_key_extra(None) == {}
        # devtel attribution scope carries the mesh; dp=1 spelling intact
        assert s._bucket_label(2, "full") == "sbucket-2:full:dp2"
        # per-topology: the sharded scheduler never adopts/exports AOT
        assert s.use_aot_cache(
            "tiny-test", cache_dir=str(tmp_path), build_on_miss=True
        ) is False
        # slot-major shard residence: contiguous S/dp blocks per device
        devs = [s._slot_device(i) for i in range(4)]
        assert devs[0] == devs[1] and devs[2] == devs[3]
        assert devs[0] != devs[2]
        assert {devs[0], devs[2]} <= set(jax.devices())
        # the stacked states are born sharded over the session axis
        leaf = s.states["noise"]
        assert len(leaf.sharding.device_set) == 2
        snap = s.snapshot()
        assert snap["batchsched_dp"] == 2
        assert snap["batchsched_shard_sessions"] == {"0": 0, "1": 0}
    finally:
        s.close()


def test_capacity_and_window_shed(bundle, cfg):
    """Slot exhaustion raises CapacityError (503 at the agent); the
    bounded coalescing window sheds its OLDEST frame as an immediate
    passthrough (ShedFrame) — the waiter never hangs.  No device step is
    ever dispatched (huge window, partial batch), so this is compile-free."""
    from ai_rtc_agent_tpu.resilience.overload import ShedFrame

    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, queue_bound=2, prewarm=False,
    )
    try:
        a = s.claim("a")
        s.claim("b")
        with pytest.raises(CapacityError):
            s.claim("c")
        # only session a submits: the dispatcher holds the (huge) window
        # waiting for b, so a's queue fills — the 3rd submit evicts the
        # 1st, whose waiter resolves as ShedFrame RIGHT AWAY
        f = np.zeros((64, 64, 3), np.uint8)
        h1 = a.submit(f)
        a.submit(f + 1)
        a.submit(f + 2)
        out = h1.future.result(timeout=2.0)
        assert isinstance(out, ShedFrame)
        assert a.fetch(h1) is out  # fetch passes the marker through raw
        assert a.window_queue.shed_overflow == 1
        snap = s.snapshot()
        assert snap["batchsched_sessions"] == 2
        assert snap["batchsched_max_sessions"] == 2
        assert s.session_snapshots()["a"]["window_shed"] == 1
    finally:
        s.close()


def test_global_t_index_default_outlives_sessions(bundle):
    """POST /config semantics (review round 1): a global t_index update
    with ZERO live sessions must become the default future claims prepare
    with — exactly like the prompt/guidance defaults — and invalid
    updates must fail the call, not the next claim.  Compile-free (no
    frame is ever dispatched)."""
    from ai_rtc_agent_tpu.stream.engine import _coeff_state

    cfg8 = registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
    )
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg8, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=False,
    )
    try:
        with pytest.raises(ValueError):
            s.update_t_index_list([1, 2])  # wrong length, zero sessions
        s.update_t_index_list([5])
        sess = s.claim("late-joiner")
        assert sess.t_index_list == [5]
        want = _coeff_state(cfg8, s._template.schedule, (5,))
        got = np.asarray(s.states["coeffs"]["timesteps"][sess.slot])
        np.testing.assert_array_equal(got, np.asarray(want["timesteps"]))
    finally:
        s.close()


def test_refuses_incompatible_configs(bundle):
    # DeepCache COMPOSES with the scheduler since ISSUE 9: a cadence
    # config registers the capture+cached bucket pair instead of refusing
    # (parity with dedicated engines is pinned by the equivalence driver)
    deep = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        unet_cache_interval=2,
    )
    s = BatchScheduler(
        bundle.stream_models, bundle.params, deep, bundle.encode_prompt,
        max_sessions=2, prewarm=False,
    )
    try:
        assert s._cache_interval == 2
        assert s._variants == ("capture", "cached")
        # every bucket geometry keys a PAIR, each with the variant field
        keys = s.bucket_keys("tiny-test")
        assert set(keys) == {(1, "capture"), (1, "cached"),
                             (2, "capture"), (2, "cached")}
        assert "variant-capture" in keys[(1, "capture")]
        assert "variant-cached" in keys[(2, "cached")]
    finally:
        s.close()
    # fbs composes with the session axis since ISSUE 12 (a second
    # batching dimension: [k, fbs, ...] bucket steps) — but not with the
    # similarity filter, whose skips would desync the fbs groups
    fbs = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        frame_buffer_size=2,
    )
    s2 = BatchScheduler(
        bundle.stream_models, bundle.params, fbs, bundle.encode_prompt,
        max_sessions=2, prewarm=False,
    )
    try:
        assert s2.fbs == 2
        assert s2.queue_bound >= 2  # holds at least one group
        specs = s2._bucket_specs(2)
        assert specs[2].shape == (2, 2, 64, 64, 3)  # [k, fbs, H, W, 3]
    finally:
        s2.close()
    fbs_sim = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        frame_buffer_size=2, similar_image_filter=True,
    )
    with pytest.raises(ValueError, match="similarity filter"):
        BatchScheduler(
            bundle.stream_models, bundle.params, fbs_sim,
            bundle.encode_prompt, max_sessions=2, prewarm=False,
        )
    # the dp axis must divide the slot capacity evenly
    with pytest.raises(ValueError, match="multiple of the dp axis"):
        BatchScheduler(
            bundle.stream_models, bundle.params, deep, bundle.encode_prompt,
            max_sessions=3, prewarm=False, dp=2,
        )


def test_amortized_admission_feed_and_step_recovery(
    bundle, cfg, tmp_path, rng
):
    """One compile-bearing in-process test (ISSUE 20 budget shave: the
    AOT export->adopt roundtrip that used to ride here — three more
    compiles — moved to the slow sibling below): (a) on_step receives
    PER-BATCH-AMORTIZED latency (dt / occupancy — what the overload
    plane's step-EWMA is wired to); (b) the bucket step donates the
    stacked state; (c) a failed step rebuilds the donated state from the
    tracked control planes and serving resumes."""
    feeds = []
    # every phase below relies on a+b coalescing into ONE k=2 batch; a
    # wide window makes that deterministic on a throttled box (a 2 ms
    # window let the dispatcher fire session a's frame solo before b's
    # submit ever ran — observed once at 865 s of suite load).  The
    # happy path never waits the window out: b's submit completes the
    # batch and dispatches inline.
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id="tiny-test", max_sessions=2, window_ms=500.0,
        prewarm=False, aot_build_on_miss=False, cache_dir=str(tmp_path),
    )
    s.on_step = lambda dt, occ: feeds.append((dt, occ))
    try:
        status = s.aot_status("tiny-test", cache_dir=str(tmp_path))
        assert status == {(1, "full"): False, (2, "full"): False}
        a = s.claim("a", prompt="pa", seed=1)
        b = s.claim("b", prompt="pb", seed=2)
        f = np.zeros((64, 64, 3), np.uint8)
        pre_step_leaf = s.states["noise"]  # donation audit (ISSUE 9)
        ha, hb = a.submit(f), b.submit(f)
        oa, ob = a.fetch(ha), b.fetch(hb)
        assert oa.shape == (64, 64, 3) and ob.shape == (64, 64, 3)
        # the bucket step donates the stacked state pytree: the pre-step
        # buffers must be GONE (a defensive copy here doubles the HBM
        # footprint of every session's ring at real geometry)
        assert pre_step_leaf.is_deleted()
        # the FIRST dispatch at a bucket size carries its (lazy) compile —
        # the warm-step rule keeps it out of the admission feed
        assert feeds == []
        ha, hb = a.submit(f), b.submit(f)
        a.fetch(ha), b.fetch(hb)
        assert feeds and feeds[-1][1] == 2 and feeds[-1][0] > 0

        # (c) review round 3: a FAILED step must not brick the scheduler —
        # the donated stacked state is rebuilt from each session's tracked
        # control plane and serving resumes (the engine-restart recovery
        # semantics).  Sabotage the k=2 bucket for one dispatch.
        real_step = s._bucket_steps[(2, "full")]

        def _boom(*args, **kw):
            raise RuntimeError("injected step failure")

        s._bucket_steps[(2, "full")] = _boom
        ha = a.submit(f)
        with pytest.raises(RuntimeError, match="injected step failure"):
            b.submit(f)  # completes the batch -> inline dispatch raises
        with pytest.raises(RuntimeError, match="injected step failure"):
            a.fetch(ha)  # the rider's future carries the same failure
        s._bucket_steps[(2, "full")] = real_step
        ha, hb = a.submit(f), b.submit(f)
        oa, ob = a.fetch(ha), b.fetch(hb)  # fresh states serve again
        assert oa.shape == (64, 64, 3) and ob.shape == (64, 64, 3)
    finally:
        s.close()


# slow tier (ISSUE 20 budget shave): exporting every bucket geometry +
# the cold-scheduler adoption re-pays every tiny-model compile through
# jax.export; tier-1 keeps the admission-feed/donation/recovery sibling
# above (one lazy compile) and test_shard_aware_bucket_keys_and_prewarm_
# coverage's key-plane pins
@pytest.mark.slow
def test_aot_export_adopt_roundtrip(bundle, cfg, tmp_path, rng):
    """Every bucket geometry exports through the engine cache
    (sbucket/sessions keys), a fresh scheduler adopts WITHOUT building,
    and aot_status/EngineCache.has report the prebuilt set (the build
    CLI's pre-warm surface)."""
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id="tiny-test", max_sessions=2, window_ms=500.0,
        prewarm=False, aot_build_on_miss=False, cache_dir=str(tmp_path),
    )
    try:
        status = s.aot_status("tiny-test", cache_dir=str(tmp_path))
        assert status == {(1, "full"): False, (2, "full"): False}
        # export every bucket, then adopt from a cold scheduler
        assert s.use_aot_cache(
            "tiny-test", cache_dir=str(tmp_path), build_on_miss=True
        )
        assert all(
            s.aot_status("tiny-test", cache_dir=str(tmp_path)).values()
        )
    finally:
        s.close()

    s2 = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id="tiny-test", max_sessions=2, window_ms=500.0,
        prewarm=False, aot_build_on_miss=False, cache_dir=str(tmp_path),
    )
    try:
        assert s2._aot_adopted  # ctor adoption found every bucket
        sess = s2.claim("aot", prompt="aot check", seed=5)
        out = sess(rng.integers(0, 256, (64, 64, 3), np.uint8))
        assert out.shape == (64, 64, 3) and out.dtype == np.uint8
    finally:
        s2.close()


# ---------------------------------------------------------------------------
# per-session behaviour on ONE shared scheduler (four slots, the k=1 and
# k=2 bucket executables compile once for the block).  The window is huge,
# so a step is dispatched only by the submit that completes the batch (or
# by the solo inline path) — no real-time waits.  Every test releases what
# it claimed.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg8():
    """8 sub-timesteps, one stage: update_t_index_list([5]) is a REAL
    coefficient change (a 1-step schedule only admits index 0)."""
    return registry.default_stream_config(
        "tiny-test", t_index_list=(2,), num_inference_steps=8,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
    )


@pytest.fixture(scope="module")
def sched4(bundle, cfg8):
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg8, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=1,
    )
    yield s
    s.close()


def _frame(seed, hw=64):
    return np.random.default_rng(seed).integers(0, 256, (hw, hw, 3), np.uint8)


def _tick(sessions, frames):
    """One frame a session; the last submit completes the batch inline."""
    handles = [s.submit(f) for s, f in zip(sessions, frames)]
    return [s.fetch(h) for s, h in zip(sessions, handles)]


def _assert_within_one(a, b):
    d = np.abs(a.astype(np.int16) - b.astype(np.int16))
    assert d.max() <= 1, f"max diff {d.max()}"


def test_two_sessions_step_release_and_reclaim(sched4):
    """Two claimed sessions ride one k=2 step and get distinct streams
    back; a released slot is free at once and the next claim lands on it
    with a fresh state."""
    a = sched4.claim("a", prompt="a red cat")
    b = sched4.claim("b", prompt="a blue dog")
    try:
        assert (a.slot, b.slot) == (0, 1) and sched4.free_slots == 2
        f = _frame(0)
        oa, ob = _tick([a, b], [f, f])
        assert oa.shape == f.shape and oa.dtype == np.uint8
        assert ob.shape == f.shape and ob.dtype == np.uint8
        # one input, per-session prompt + seed -> two different streams
        assert not np.array_equal(oa, ob)
        assert sched4.snapshot()["batchsched_occupancy_hist"].get("2", 0) >= 1
        a.release()
        a.release()  # double release is harmless
        assert sched4.free_slots == 3
        c = sched4.claim("c", prompt="replacement")
        try:
            assert c.slot == 0
            oc, _ = _tick([c, b], [f, f])
            assert oc.shape == f.shape
        finally:
            c.release()
    finally:
        a.release()
        b.release()
    assert sched4.free_slots == 4


def test_per_session_prompt_isolation(sched4):
    """Two sessions with one prompt, one seed and one input agree; a
    prompt update on ONE of them changes only its stream — the per-session
    control plane the reference's global prompt mutation lacks
    (agent.py:423)."""
    a = sched4.claim("a", prompt="prompt A", seed=7)
    b = sched4.claim("b", prompt="prompt A", seed=7)
    try:
        f = _frame(1)
        oa, ob = _tick([a, b], [f, f])
        np.testing.assert_array_equal(oa, ob)  # two rows of ONE executable
        b.update_prompt("a completely different prompt")
        oa2, ob2 = _tick([a, b], [f, f])
        assert not np.array_equal(oa2, ob2)
    finally:
        a.release()
        b.release()


def test_per_session_t_index_update_isolated(sched4):
    """A per-session t-index update is a coefficient swap into that
    session's state row: the other session's stream is what it would have
    been without it, and a wrong-length list is refused."""
    f1, f2 = _frame(2), _frame(3)

    def run(update):
        a = sched4.claim("a", prompt="pa", seed=3)
        b = sched4.claim("b", prompt="pb", seed=4)
        try:
            _tick([a, b], [f1, f1])
            if update:
                with pytest.raises(ValueError):
                    b.update_t_index_list([5, 6])  # compiled length is 1
                b.update_t_index_list([5])
            return _tick([a, b], [f2, f2])
        finally:
            a.release()
            b.release()

    a_plain, b_plain = run(update=False)
    a_upd, b_upd = run(update=True)
    np.testing.assert_array_equal(a_plain, a_upd)
    assert not np.array_equal(b_plain, b_upd)


def test_solo_session_of_four_dispatches_at_k1(sched4):
    """One live session of four slots pays a k=1 step, not the capacity:
    the occupancy counter reads 1 and the step ran the k=1 bucket."""
    before = sched4.snapshot()["batchsched_occupancy_hist"].get("1", 0)
    a = sched4.claim("solo", prompt="solo style")
    try:
        f = _frame(4)
        out = a(f)
        assert out.shape == f.shape and out.dtype == np.uint8
    finally:
        a.release()
    snap = sched4.snapshot()
    assert snap["batchsched_occupancy_hist"]["1"] == before + 1
    assert snap["batchsched_dispatch_cause_total"]["solo"] >= 1
    assert (1, "full") in sched4._bucket_steps


def test_k1_bucket_matches_dedicated_engine(sched4, bundle, cfg8):
    """One live session of four slots, three frames: the k=1 bucket step
    (gather -> vmapped step -> scatter over the [4, ...] stack) against a
    dedicated StreamEngine.  Executables of different batch size may fuse
    differently: outputs within one uint8 quantisation step, state rows
    to float tolerance."""
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg8, bundle.encode_prompt
    )
    eng.prepare("peer zero", seed=5)
    a = sched4.claim("a", prompt="peer zero", seed=5)
    try:
        for i in range(3):
            f = _frame(10 + i)
            _assert_within_one(a(f), eng(f))
        row = jax.tree.map(lambda x: np.asarray(x[a.slot]), sched4.states)
        assert jax.tree.structure(row) == jax.tree.structure(eng.state)
        for got, want in zip(jax.tree.leaves(row), jax.tree.leaves(eng.state)):
            np.testing.assert_allclose(
                got, np.asarray(want), rtol=1e-5, atol=1e-5
            )
    finally:
        a.release()


def test_bucket_flops_scale_with_occupancy(sched4):
    """Compiler-level proof that idle slots cost no FLOPs: the k=1 bucket
    program of a four-slot scheduler is under half of its k=4's."""
    def flops(k):
        cost = sched4._bucket_step(k).lower(
            *sched4._bucket_specs(k)
        ).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))

    f1, f4 = flops(1), flops(4)  # lowered, never compiled
    assert f1 > 0 and f4 > 0
    # gather/scatter overhead is tiny; 1-of-4 occupancy must cost well
    # under half the full batch
    assert f1 < 0.5 * f4, (f1, f4)


def test_fetch_output_type_under_hw_encode(sched4, monkeypatch):
    """HW_ENCODE serving hands the track layer bare ndarrays from a
    scheduler session exactly as the shared pipeline's fetch does; the
    software path returns a pts-carrying frame."""
    from ai_rtc_agent_tpu.media.frames import VideoFrame

    a = sched4.claim("hw", prompt="style")
    try:
        src = VideoFrame.from_ndarray(_frame(5))
        src.pts = 3000
        monkeypatch.setenv("HW_ENCODE", "true")
        out = a.fetch(a.submit(src), src_frame=src)
        assert isinstance(out, np.ndarray)  # no VideoFrame wrap in hw path
        monkeypatch.delenv("HW_ENCODE")
        out2 = a.fetch(a.submit(src), src_frame=src)
        assert hasattr(out2, "pts")  # sw path: metadata-carrying frame
    finally:
        a.release()


def test_submit_refuses_malformed_frame(sched4):
    """The scheduler's input check: a frame that is not HxWx3 uint8 is
    refused at submit, before it is staged or enters the window."""
    a = sched4.claim("bad-input")
    try:
        with pytest.raises(ValueError, match="HxWx3 uint8"):
            a.submit(np.zeros((3, 64, 64, 3), np.uint8))
        with pytest.raises(ValueError, match="HxWx3 uint8"):
            a.submit(np.zeros((64, 64, 3), np.float32))
        with pytest.raises(TypeError):
            a.submit("not a frame")
        assert a.window_queue.depth == 0
    finally:
        a.release()


def test_dp2_step_matches_dp1(sched4, bundle, cfg8):
    """The session axis sharded over a dp=2 mesh (virtual devices): one
    k=2 step, one row a shard, and each session's frame within one uint8
    quantisation step of the single-device scheduler's."""
    f = _frame(6)

    def run(s):
        a = s.claim("a", prompt="pa", seed=1)
        b = s.claim("b", prompt="pb", seed=2)
        try:
            return _tick([a, b], [f, f]), (a.snapshot(), b.snapshot())
        finally:
            a.release()
            b.release()

    sharded = BatchScheduler(
        bundle.stream_models, bundle.params, cfg8, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, dp=2,
    )
    try:
        assert sharded.dp == 2
        (oa2, ob2), (sa, sb) = run(sharded)
        # balanced placement: the second claim lands on the other shard
        assert {sa["shard"], sb["shard"]} == {0, 1}
        assert len(sharded.states["noise"].sharding.device_set) == 2
    finally:
        sharded.close()
    (oa1, ob1), _ = run(sched4)
    assert oa2.shape == f.shape and oa2.dtype == np.uint8
    _assert_within_one(oa2, oa1)
    _assert_within_one(ob2, ob1)


@pytest.mark.parametrize(
    "dp,sizes,covering",
    [
        (1, [1, 2, 4, 8], {1: 1, 2: 2, 3: 4, 4: 4, 5: 8, 8: 8}),
        (2, [2, 4, 8], {1: 2, 2: 2, 3: 4, 5: 8, 8: 8}),
    ],
)
def test_bucket_sizes_and_covering_bucket(bundle, cfg, dp, sizes, covering):
    """Bucket geometries double from dp up to the capacity (every bucket a
    dp multiple), and an occupancy steps the smallest bucket that covers
    it.  Compile-free."""
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=8, window_ms=10_000.0, prewarm=False, dp=dp,
    )
    try:
        assert s._bucket_sizes == sizes
        assert {n: s._bucket_for(n) for n in covering} == covering
    finally:
        s.close()


def test_window_queue_sheds_oldest_with_source_pixels(bundle, cfg):
    """A session outrunning the step: its bounded window queue drops the
    OLDEST frame on overflow, and that frame's fetch returns a ShedFrame
    carrying its own source pixels — passthrough, never a hang, never
    another frame's pixels.  Compile-free: the second session never
    submits, so the (huge) window never dispatches."""
    from ai_rtc_agent_tpu.resilience.overload import DeadlineQueue, ShedFrame

    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, queue_bound=2, prewarm=False,
    )
    try:
        a = s.claim("a")
        s.claim("b")
        assert isinstance(a.window_queue, DeadlineQueue)
        frames = [np.full((64, 64, 3), i, np.uint8) for i in range(4)]
        handles = [a.submit(f) for f in frames]
        assert a.window_queue.depth == 2
        assert a.window_queue.shed_overflow == 2
        for h, f in zip(handles[:2], frames[:2]):
            out = a.fetch(h)
            assert isinstance(out, ShedFrame)
            np.testing.assert_array_equal(out.frame, f)
        assert not handles[2].future.done() and not handles[3].future.done()
    finally:
        s.close()


@pytest.mark.slow  # ~30 s of eager two-tower encodes and prepares: the
# tiny-model sibling test_per_session_prompt_isolation keeps per-session
# prompt-update isolation in tier-1
def test_prompt_update_swaps_pooled_embeds_on_two_tower_family():
    """A per-session prompt update on an SDXL-style scheduler must swap
    the POOLED embeds (``added_text``) of that session's row with its
    cond/uncond, and leave the other row alone.  Compile-free: no frame
    is stepped."""
    xl = registry.load_model_bundle("tiny-xl-test")
    s = BatchScheduler(
        xl.stream_models, xl.params,
        registry.default_stream_config("tiny-xl-test"), xl.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=False, dp=1,
    )
    try:
        a = s.claim("a", prompt="base prompt", seed=1)
        b = s.claim("b", prompt="base prompt", seed=1)
        before = np.asarray(s.states["added_text"])
        cond_before = np.asarray(s.states["cond"])
        b.update_prompt("a different sdxl prompt")
        after = np.asarray(s.states["added_text"])
        np.testing.assert_array_equal(before[a.slot], after[a.slot])
        assert not np.array_equal(before[b.slot], after[b.slot])
        assert not np.array_equal(
            cond_before[b.slot], np.asarray(s.states["cond"])[b.slot]
        )
    finally:
        s.close()


@pytest.mark.parametrize("scatter_output", [False, True])
def test_make_bucket_step_contract(scatter_output):
    """What the benchmark and the trace readers hold the step program to:
    ``stream.scheduler.make_bucket_step`` resolves (the harness patches it
    there), takes (vstep, capacity, scatter_output), its jitted function
    is named ``bucket`` (the trace's ``jit_bucket``), untouched rows keep
    their state, a padded (duplicate) index is sound, and the output is
    k-shaped or capacity-shaped as asked."""
    import inspect

    import jax.numpy as jnp

    fn = scheduler_mod.make_bucket_step
    assert list(inspect.signature(fn).parameters) == [
        "vstep", "capacity", "scatter_output",
    ]

    def vstep(params, states_k, frames_k):
        return {"x": states_k["x"] + params}, frames_k * 2

    bucket = fn(vstep, 4, scatter_output=scatter_output)
    assert bucket.__name__ == "bucket"
    states = {"x": jnp.arange(4.0)}
    idx = jnp.asarray([2, 0, 0], jnp.int32)  # row 0 padded twice
    frames_k = jnp.asarray([[5.0], [7.0], [7.0]])
    new_states, out = jax.jit(bucket)(10.0, states, frames_k, idx)
    np.testing.assert_array_equal(new_states["x"], [10.0, 1.0, 12.0, 3.0])
    if scatter_output:
        np.testing.assert_array_equal(out, [[14.0], [0.0], [10.0], [0.0]])
    else:
        np.testing.assert_array_equal(out, [[10.0], [14.0], [14.0]])


def test_parallel_package_imports_nothing_from_stream_or_server():
    """The layering the bucket step's move restored: ``parallel/`` holds
    meshes and sharding rules that ``stream/`` builds on, never the other
    way round."""
    import ast

    root = os.path.join(REPO, "ai_rtc_agent_tpu", "parallel")
    offenders = []
    for name in sorted(os.listdir(root)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(root, name)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = "." * node.level + (node.module or "")
            elif isinstance(node, ast.Import):
                mod = ",".join(a.name for a in node.names)
            else:
                continue
            if any(
                part in mod
                for part in ("..stream", "..server", "ai_rtc_agent_tpu.stream",
                             "ai_rtc_agent_tpu.server")
            ):
                offenders.append(f"{name}: {mod}")
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# when a partial batch waits out the coalescing window: enqueue schedules on
# a fake clock, a step that hands the frames back, and a device whose state
# the test sets
# ---------------------------------------------------------------------------

_WIN = 0.003  # the coalescing window, s
_PERIOD, _STAGGER = 0.066, 0.022  # three 15 fps sources, one every 22 ms
_STAGGERED = (0.0, _STAGGER, 2 * _STAGGER)
_ALIGNED = (0.0, 0.0001, 0.0002)


class _Clock:
    def __init__(self):
        self.t = 100.0

    def monotonic(self):
        return self.t


def _schedule(offsets, first_round, rounds=4):
    """(round, slot, t) of every enqueue in time order: slot i at its
    offset plus one source period a round, from round ``first_round[i]``."""
    ev = [
        (r, i, 100.0 + r * _PERIOD + off)
        for r in range(rounds)
        for i, off in enumerate(offsets)
        if r >= first_round[i]
    ]
    return sorted(ev, key=lambda e: e[2])


@pytest.mark.parametrize("fbs,offsets,first_round,drained,drain_last,want", [
    # staggered, the device drained: once the other two sessions' next frames
    # are predicted 22 and 44 ms off, each frame goes at once, alone
    (1, _STAGGERED, (0, 0, 0), True, False, [("none_due", 1)] * 3),
    # aligned: the other two are due inside the window, so it still
    # coalesces, and the third frame completes one k=3 step
    (1, _ALIGNED, (0, 0, 0), True, False, [("inline_full", 3)] * 3),
    # a busy device: the window is waited out
    (1, _STAGGERED, (0, 0, 0), False, False, [("window", 1)] * 3),
    # busy at the submit, drained inside the window: the dispatcher sends
    # the frame then, without waiting the rest of the window
    (1, _STAGGERED, (0, 0, 0), False, True, [("none_due", 1)] * 3),
    # slot 2 has one enqueue on record when slots 0 and 1 submit: nothing
    # predicts it, so they wait; its own frame finds the others not due
    (1, _STAGGERED, (0, 0, 2), True, False,
     [("window", 1), ("window", 1), ("none_due", 1)]),
    # fbs > 1 keeps its window
    (2, _STAGGERED, (0, 0, 0), True, False, [("window", 1)] * 3),
], ids=["staggered", "aligned", "busy", "drains_in_window", "fresh_slot", "fbs2"])
def test_a_partial_batch_waits_only_where_a_rider_can_come(
    bundle, monkeypatch, fbs, offsets, first_round, drained, drain_last, want,
):
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=32, width=32, frame_buffer_size=fbs,
    )
    clock = _Clock()
    monkeypatch.setattr(scheduler_mod, "time", clock)
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=3, window_ms=1e3 * _WIN, prewarm=False,
    )
    device = {"drained": drained}
    monkeypatch.setattr(s, "_bucket_step", lambda k, v="full": (
        lambda params, states, frames, idx: (states, frames)
    ))
    monkeypatch.setattr(s, "_device_drained_locked", lambda: device["drained"])
    try:
        sessions = [s.claim(f"s{i}", prompt="p", seed=i) for i in range(3)]
        events = _schedule(offsets, first_round)
        last = events[-1][0]
        frame = np.zeros((32, 32, 3), np.uint8)
        open_, got = [], []
        for n, (rnd, slot, t) in enumerate(events):
            clock.t = t
            device["drained"] = drained
            handles = sessions[slot].submit_batch([frame] * fbs)
            open_.append((rnd, handles[0], sessions[slot], handles))
            if n + 1 < len(events) and events[n + 1][2] < t + _WIN:
                continue  # the next frame comes inside the window
            if any(h.readback is None for _, h, _, _ in open_):
                # queued for the dispatcher: the device drains, or the
                # window runs out
                if drain_last and rnd == last:
                    device["drained"] = True
                else:
                    clock.t = t + 2 * _WIN
                with s._has_work:
                    s._has_work.notify()
            for rnd_, h, sess, hs in open_:
                sess.fetch_batch(hs)
                batch = h.readback[0]
                if rnd_ == last:
                    got.append((batch.cause, batch.occupancy))
                    if batch.cause == "none_due" and not drain_last:
                        assert batch.window is None  # no window_wait
            open_ = []
        assert got == want
        cause = s.snapshot()["batchsched_dispatch_cause_total"]
        assert set(cause) == set(scheduler_mod.DISPATCH_CAUSES)
    finally:
        s.close()


# ---------------------------------------------------------------------------
# agent wiring — a duck-typed scheduler stands in so the HTTP surface is
# covered without model compiles
# ---------------------------------------------------------------------------


class _StubPipeline:
    """Injected so on_startup never builds a real model pipeline; with a
    scheduler present the claim path ignores it entirely."""

    def __call__(self, frame):
        return frame


class _FakeSession:
    owns_step_signal = True

    def __init__(self, owner, slot, key):
        self._owner = owner
        self.slot = slot
        self.session_key = key
        self.prompt = None
        from ai_rtc_agent_tpu.resilience.overload import DeadlineQueue

        self.window_queue = DeadlineQueue(2)

    def __call__(self, frame):
        arr = frame if isinstance(frame, np.ndarray) else frame.to_ndarray()
        return 255 - arr

    def update_prompt(self, p):
        self.prompt = p

    def update_t_index_list(self, t):
        pass

    def release(self):
        self._owner.released.append(self.slot)

    def snapshot(self):
        return {"slot": self.slot, "frames_submitted": 0}


class _FakeScheduler:
    def __init__(self, max_sessions=2):
        self.max_sessions = max_sessions
        self.claimed = []
        self.released = []
        self.prompt = None
        self.on_step = None

    @property
    def free_slots(self):
        return self.max_sessions - (len(self.claimed) - len(self.released))

    def claim(self, session_key=None, prompt=None, seed=None):
        if self.free_slots <= 0:
            raise CapacityError("full")
        sess = _FakeSession(self, len(self.claimed), session_key)
        self.claimed.append(sess)
        return sess

    def update_prompt(self, p):
        self.prompt = p

    def update_t_index_list(self, t):
        pass

    def snapshot(self):
        return {
            "batchsched_sessions": len(self.claimed) - len(self.released),
            "batchsched_max_sessions": self.max_sessions,
            "batchsched_steps_total": 7,
        }

    def session_snapshots(self):
        return {
            s.session_key: s.snapshot()
            for s in self.claimed
            if s.slot not in self.released
        }

    def close(self):
        pass


def test_agent_serves_sessions_through_scheduler():
    """/offer claims a scheduler session (per-connection control plane),
    /metrics + /capacity + /health carry the scheduler view, the window
    queue joins the overload queue registry, and teardown releases the
    slot."""
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import (
        LoopbackProvider,
        make_loopback_offer,
    )
    from aiohttp.test_utils import TestClient, TestServer

    fake = _FakeScheduler()

    async def go():
        app = build_app(
            pipeline=_StubPipeline(),
            provider=LoopbackProvider(),
            batch_scheduler=fake,
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "r",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
            )
            assert r.status == 200
            assert len(fake.claimed) == 1
            key = fake.claimed[0].session_key
            ov = app["overload"]
            assert f"batchwin:{key}" in ov.queues

            body = await (await client.get("/metrics")).json()
            assert body["batchsched_sessions"] == 1
            assert body["batchsched_steps_total"] == 7
            body = await (await client.get("/capacity")).json()
            assert body["capacity"] == 1  # 2 slots, 1 claimed

            body = await (await client.get("/health")).json()
            assert body["sessions"][key]["batchsched"]["slot"] == 0

            # global /config routes to the scheduler (all live sessions)
            r = await client.post("/config", json={"prompt": "global p"})
            assert r.status == 200
            assert fake.prompt == "global p"

            pc = next(iter(app["pcs"]))
            await pc.close()
            await asyncio.sleep(0.05)
            assert fake.released == [0]
            assert f"batchwin:{key}" not in ov.queues
        finally:
            await client.close()

    asyncio.run(go())


def test_agent_scheduler_full_returns_503():
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import (
        LoopbackProvider,
        make_loopback_offer,
    )
    from aiohttp.test_utils import TestClient, TestServer

    fake = _FakeScheduler(max_sessions=0)

    async def go():
        app = build_app(
            pipeline=_StubPipeline(),
            provider=LoopbackProvider(),
            batch_scheduler=fake,
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "r",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
            )
            assert r.status == 503
            assert "Retry-After" in r.headers
        finally:
            await client.close()

    asyncio.run(go())


def test_agent_datachannel_prompt_reaches_one_session_and_slot_recovers(
    monkeypatch,
):
    """Two /offer connections on a two-slot scheduler: a prompt sent over
    ONE connection's datachannel lands on that connection's session alone
    (never the other's, never the global default); with both slots taken
    a third offer gets 503, and closing a connection frees its slot for
    the next offer."""
    import json

    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import (
        LoopbackProvider,
        make_loopback_offer,
    )
    from aiohttp.test_utils import TestClient, TestServer

    monkeypatch.setenv("WARMUP_FRAMES", "0")
    fake = _FakeScheduler(max_sessions=2)

    async def go():
        app = build_app(
            pipeline=_StubPipeline(),
            provider=LoopbackProvider(),
            batch_scheduler=fake,
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async def post_offer(room):
                return await client.post(
                    "/offer",
                    json={
                        "room_id": room,
                        "offer": {
                            "sdp": make_loopback_offer(), "type": "offer",
                        },
                    },
                )

            assert (await post_offer("room1")).status == 200
            assert (await post_offer("room2")).status == 200
            assert fake.free_slots == 0
            assert (await post_offer("room3")).status == 503

            pcs = [pc for pc in app["pcs"] if pc.datachannel is not None]
            await pcs[0].datachannel.deliver(
                json.dumps({"prompt": "peer0 style"})
            )
            prompts = [s.prompt for s in fake.claimed]
            assert prompts.count("peer0 style") == 1
            assert prompts.count(None) == 1
            assert fake.prompt is None  # the global default never moved

            # release is scheduled off the event loop: poll the ledger
            await pcs[0].close()
            for _ in range(50):
                if fake.free_slots == 1:
                    break
                await asyncio.sleep(0.02)
            assert fake.free_slots == 1
            assert (await post_offer("room4")).status == 200
        finally:
            await client.close()

    asyncio.run(go())


def test_deepcache_uncaptured_rider_forces_capture(bundle):
    """code-review r1: the global tick reset at install only guarantees
    the NEXT batch captures — a slot that sits that batch out (no frame
    yet) must still never ride a cached step over its zeroed deep-feature
    row.  Any batch carrying an uncaptured rider is FORCED to capture,
    then the cadence resumes."""
    cfg = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        unet_cache_interval=3,
    )
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=False,
    )
    try:
        variants = []
        orig = s._bucket_step

        def spy(k, variant="full"):
            variants.append((k, variant))
            return orig(k, variant)

        s._bucket_step = spy
        a = s.claim("a", prompt="pa", seed=1)
        c = s.claim("c", prompt="pc", seed=9)
        assert a.slot in s._uncaptured and c.slot in s._uncaptured
        # pretend the cadence advanced while the slots sat out the
        # post-install capture batch (mid-cadence: 4 % 3 != 0 -> the
        # unforced choice would be the CACHED graph over zeroed rows)
        s._tick = 4
        f = np.zeros((64, 64, 3), np.uint8)
        ha, hc = a.submit(f), c.submit(f)  # huge window -> inline k=2
        a.fetch(ha), c.fetch(hc)
        assert variants[-1] == (2, "capture"), variants
        assert a.slot not in s._uncaptured and c.slot not in s._uncaptured
        # with the riders captured and the tick mid-cadence, the NEXT
        # batch's unforced choice is the cached graph (asserted on the
        # selection state, not by paying the cached compile — the
        # capture->cached alternation itself is pinned by the equivalence
        # driver's DC leg; tier-1 budget)
        assert s._tick % s._cache_interval != 0
    finally:
        s.close()


def _mk_adapter_registry(bundle, r=2):
    """Synthetic two-style registry over the tiny UNet: styleA touches one
    attn linear, styleB two (the bank target set is the union, so styleA
    rows carry explicit zeros at the second target); rank 2 pads to the
    smallest blessed bucket, 4."""
    from ai_rtc_agent_tpu.adapters import AdapterRegistry
    from ai_rtc_agent_tpu.models import loader as LD

    mq = "down_blocks.0.attentions.0.transformer_blocks.0.attn1.to_q"
    mv = "down_blocks.0.attentions.0.transformer_blocks.0.attn1.to_v"
    rng = np.random.default_rng(7)

    def groups(mods):
        return {
            m: {
                "down": (rng.normal(size=(r, 8)) * 0.2).astype(np.float32),
                "up": (rng.normal(size=(8, r)) * 0.2).astype(np.float32),
                "alpha": float(r),
            }
            for m in mods
        }

    reg = AdapterRegistry(
        bundle.params["unet"], LD.unet_key_map(bundle.unet_cfg)
    )
    reg.add("styleA", groups([mq]))
    reg.add("styleB", groups([mq, mv]))
    return reg


def test_adapter_bucket_keys_bank_shape_and_metrics(bundle, cfg):
    """Unit pins for the adapter key plane (ISSUE 20): a bound factor bank
    joins the AOT key space as its padded rank (``lrank-R`` via
    aot/cache.adapter_key_extra — empty-when-disabled like the dp extra),
    the devtel bucket label carries ``:rR``, the stacked bank is
    [S, ...]-shaped over the union target set, snapshot/fingerprint expose
    the bank, and style validation refuses BEFORE touching a slot.
    Compile-free (prewarm off, no frame dispatched)."""
    from ai_rtc_agent_tpu.aot.cache import adapter_key_extra

    assert adapter_key_extra(0) == {}
    assert adapter_key_extra(4) == {"lrank": 4}

    reg = _mk_adapter_registry(bundle)
    assert reg.bank_rank == 4 and reg.rank_of("styleA") == 4
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=4, window_ms=10_000.0, prewarm=False, adapters=reg,
    )
    try:
        # rank joins the key space: (k, variant, rank, dp)
        assert s._bucket_label(2, "full") == "sbucket-2:full:r4"
        keys = s.bucket_keys("tiny-test")
        assert keys and all("lrank-4" in k for k in keys.values())
        # the stacked bank rides the session pytree: [S, R, in]/[S, out, R]
        bank = s.states["adapters"]
        assert set(bank) == set(reg.targets)
        for f in bank.values():
            assert f["down"].shape == (4, 4, 8)
            assert f["up"].shape == (4, 8, 4)
        # validation refuses BEFORE slot allocation / bank writes
        with pytest.raises(KeyError):
            s.claim("x", adapter="nope")
        assert s.snapshot()["batchsched_sessions"] == 0
        a = s.claim("a", adapter="styleA")
        assert a.adapter == "styleA"
        with pytest.raises(KeyError):
            a.update_adapter("nope")
        assert a.adapter == "styleA"  # refused swap never lands
        a.update_adapter("styleB")
        assert a.adapter == "styleB"
        # global update: live slots swap AND future claims inherit
        s.update_adapter("styleA")
        assert a.adapter == "styleA"
        b = s.claim("b")
        assert b.adapter == "styleA"
        snap = s.snapshot()
        assert snap["adapter_rank"] == 4
        assert snap["adapter_sessions"] == 2
        assert snap["adapter_swaps_total"] >= 2
        fp = s.snapshot_fingerprint()
        assert fp["adapter_rank"] == 4 and fp["adapter_targets"]
        assert a.snapshot()["adapter"] == "styleA"
    finally:
        s.close()
    # an adapterless scheduler keeps every pre-existing surface unchanged
    s2 = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=False,
    )
    try:
        assert s2._bucket_label(2, "full") == "sbucket-2:full"
        assert "adapters" not in s2.states
        assert "adapter_rank" not in s2.snapshot_fingerprint()
        with pytest.raises(ValueError, match="ADAPTER_DIR"):
            s2.claim("x", adapter="styleA")
        with pytest.raises(ValueError, match="ADAPTER_DIR"):
            s2.update_adapter("styleA")
    finally:
        s2.close()


# slow tier: prewarm=True pays every (k, variant, rank) compile up front —
# tier-1 keeps test_adapter_bucket_keys_bank_shape_and_metrics above,
# which pins the same key/bank mechanism compile-free
@pytest.mark.slow
def test_adapter_hot_swap_never_retraces(bundle):
    """ISSUE 20 acceptance pin: join/leave/hot-swap/clear/restart on a
    prewarmed adapter-carrying scheduler with ZERO devtel retrace
    breaches — the closed rank-bucket contract makes every swap a
    same-shaped ``.at[slot].set`` bank write, never a new graph."""
    from ai_rtc_agent_tpu.obs import devtel
    from ai_rtc_agent_tpu.obs.devtel import DevTelPlane

    cfg32 = registry.default_stream_config(
        "tiny-test", t_index_list=(0,), num_inference_steps=1,
        timestep_spacing="trailing", scheduler="turbo", cfg_type="none",
        height=32, width=32,
    )
    reg = _mk_adapter_registry(bundle)
    plane = devtel.activate(DevTelPlane())
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg32, bundle.encode_prompt,
        max_sessions=2, window_ms=10_000.0, prewarm=True, dp=1,
        adapters=reg,
    )
    rng = np.random.default_rng(5)

    def tick(sessions):
        fs = [
            rng.integers(0, 256, (32, 32, 3), np.uint8) for _ in sessions
        ]
        hs = [x.submit(f) for x, f in zip(sessions, fs)]
        return [x.fetch(h) for x, h in zip(sessions, hs)]

    try:
        # prewarm attributed under the rank-carrying scope, all expected
        ctxs = {c["context"] for c in plane.compiles}
        assert "sbucket-2:full:r4" in ctxs, ctxs
        assert plane.retrace_breaches == 0
        a = s.claim("a", prompt="pa", seed=1, adapter="styleA")
        b = s.claim("b", prompt="pb", seed=2)
        tick([a, b])  # warm the host-side eager ops too
        a.update_adapter("styleB")  # ...including the bank-write path
        b.release()
        tick([a])
        plane.serving()
        # churn on warm executables ONLY: swap, clear, rejoin with a
        # style, swap the rejoiner, restart a styled session, global clear
        a.update_adapter(None)
        tick([a])
        b2 = s.claim("b2", prompt="pb2", seed=9, adapter="styleB")
        tick([a, b2])
        b2.update_adapter("styleA")
        tick([a, b2])
        a.update_adapter("styleA")
        a.restart()
        tick([a, b2])
        s.update_adapter(None)
        tick([a, b2])
        assert plane.retrace_breaches == 0, [
            c for c in plane.compiles if c["phase"] == "serving"
        ]
        assert s.snapshot()["adapter_swaps_total"] >= 5
    finally:
        devtel.deactivate(plane)
        s.close()
