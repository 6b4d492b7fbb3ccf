"""ControlNet conditioned streaming (BASELINE config 4: ControlNet-canny).

Covers: in-graph canny annotator, zero-conv no-op property (an untrained
ControlNet must not perturb the base UNet — reference ControlNet wiring at
lib/wrapper.py:617-643), conditioning ring rotation alongside the latent
ring, runtime conditioning-scale swap, and diffusers key-map coverage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import loader as LD
from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.models import unet as U
from ai_rtc_agent_tpu.models.controlnet import (
    apply_controlnet,
    canny_soft,
    cond_embed_widths,
    init_controlnet,
)
from ai_rtc_agent_tpu.stream.engine import StreamEngine

MODEL = "tiny-test"


def _engine(**cfg_overrides):
    bundle = registry.load_model_bundle(MODEL, controlnet="tiny-cnet")
    cfg = registry.default_stream_config(MODEL, use_controlnet=True, **cfg_overrides)
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=False, donate=False,
    )
    eng.prepare("ctrl", guidance_scale=1.0, seed=3)
    return eng, bundle, cfg


def test_canny_soft_shape_and_range():
    img = jnp.asarray(
        np.random.default_rng(0).random((2, 16, 16, 3), dtype=np.float32)
    )
    edge = canny_soft(img)
    assert edge.shape == (2, 16, 16, 3)
    assert float(edge.min()) >= 0.0 and float(edge.max()) <= 1.0
    # all three channels identical (edge map broadcast)
    np.testing.assert_array_equal(np.asarray(edge[..., 0]), np.asarray(edge[..., 1]))


@pytest.mark.slow  # builds TWO engines (~17s); the zero-conv plumbing
# stays tier-1 via test_apply_controlnet_residual_shapes_match_unet_skips
# and test_nonzero_controlnet_changes_output_and_scale_swaps (ISSUE 11
# shave)
def test_untrained_controlnet_is_noop():
    """Zero convs make an untrained ControlNet an exact no-op on the UNet."""
    rng = np.random.default_rng(1)
    frame = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)

    eng_c, bundle, cfg = _engine()
    out_c = eng_c(frame)

    bundle2 = registry.load_model_bundle(MODEL)
    cfg2 = registry.default_stream_config(MODEL)
    eng_p = StreamEngine(
        bundle2.stream_models, bundle2.params, cfg2, bundle2.encode_prompt,
        jit_compile=False, donate=False,
    )
    eng_p.prepare("ctrl", guidance_scale=1.0, seed=3)
    out_p = eng_p(frame)
    np.testing.assert_allclose(out_c, out_p, atol=1)  # uint8 rounding slack


@pytest.mark.slow  # THREE engine builds for the nonzero-conditioning x
# runtime-scale-swap composition (~14s; ISSUE 15 budget pairing):
# test_cond_ring_rotates_with_latent_ring and
# test_apply_controlnet_residual_shapes_match_unet_skips keep the
# controlnet stream path compiled + pinned in tier-1
def test_nonzero_controlnet_changes_output_and_scale_swaps():
    rng = np.random.default_rng(2)
    frame = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)

    eng, bundle, cfg = _engine()
    # make the mid zero conv non-zero -> conditioning now perturbs the UNet
    zc = eng.params["controlnet"]["mid_zero_conv"]
    zc["kernel"] = jnp.asarray(
        rng.standard_normal(zc["kernel"].shape), zc["kernel"].dtype
    )
    out1 = np.asarray(eng(frame))

    eng2, bundle2, _ = _engine()
    eng2.params["controlnet"]["mid_zero_conv"]["kernel"] = zc["kernel"]
    eng2.update_controlnet_scale(0.0)  # scale 0 must restore the no-op
    out_scale0 = np.asarray(eng2(frame))

    eng3, bundle3, _ = _engine()
    out_base = np.asarray(eng3(frame))

    assert np.abs(out1.astype(int) - out_base.astype(int)).max() > 1
    np.testing.assert_allclose(out_scale0, out_base, atol=1)


def test_cond_ring_rotates_with_latent_ring():
    eng, bundle, cfg = _engine()
    assert cfg.batch_size > cfg.frame_buffer_size  # ring exists
    rng = np.random.default_rng(3)
    f1 = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)
    f2 = rng.integers(0, 256, (64, 64, 3), dtype=np.uint8)
    eng(f1)
    ring1 = np.asarray(eng.state["cnet_cond"])
    eng(f2)
    ring2 = np.asarray(eng.state["cnet_cond"])
    # head of the ring is always the latest frame's annotation
    img1 = jnp.asarray(f1[None], jnp.float32) / 255.0
    np.testing.assert_allclose(
        ring1[0], np.asarray(canny_soft(img1))[0], atol=1e-5
    )
    # f1's annotation advanced one slot when f2 entered
    np.testing.assert_allclose(ring2[1], ring1[0], atol=1e-5)


def test_controlnet_key_map_covers_params():
    """Every real-checkpoint leaf path must exist in the param tree."""
    cfg = U.UNetConfig.tiny()
    p = init_controlnet(jax.random.PRNGKey(0), cfg, num_down=2)
    km = LD.controlnet_key_map(cfg)
    # round-trip: export -> reload reproduces the tree (non-strict: the tiny
    # config has fewer cond-embed blocks than the full diffusers ladder)
    sd = LD.tree_to_state_dict(p, km)
    assert len(sd) > 20
    p2, n = LD.load_into_tree(p, sd, km, strict=False)
    assert n == len(sd)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_apply_controlnet_residual_shapes_match_unet_skips():
    cfg = U.UNetConfig.tiny()
    key = jax.random.PRNGKey(0)
    cnet = init_controlnet(key, cfg, num_down=2)
    unet = U.init_unet(key, cfg)
    B, h, w = 2, 8, 8
    x = jnp.zeros((B, h, w, 4))
    t = jnp.zeros((B,), jnp.int32)
    ctx = jnp.zeros((B, 7, cfg.cross_attention_dim))
    cond = jnp.zeros((B, h * 4, w * 4, 3))
    dres, mres = apply_controlnet(cnet, x, t, ctx, cond, cfg)
    # feeding them into apply_unet must not raise (shape agreement)
    out = U.apply_unet(
        unet, x, t, ctx, cfg, down_residuals=dres, mid_residual=mres
    )
    assert out.shape == (B, h, w, 4)


# -- the side network through the batch scheduler (ISSUE 34) -------------------
#
# The scheduler's bucket step on seeded weights against the plain float32
# reference ``benchmark/reference/sd_control_stream.py``, at the tiny size
# (``benchmark/tests/data/configs/tiny64canny.json``: the program's
# ``tiny-test+tiny-cnet``).

import json  # noqa: E402
import os  # noqa: E402

from ai_rtc_agent_tpu.stream.scheduler import (  # noqa: E402
    BatchScheduler,
    SnapshotMismatch,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CANNY_FILE = os.path.join(
    _ROOT, "benchmark", "tests", "data", "configs", "tiny64canny.json"
)
_PUBLISHED_FILE = os.path.join(_ROOT, "benchmark", "configs", "lcm4x512canny.json")
_PROMPT = "a street at night, neon style"
# uint8 levels between a served frame and the reference's unrounded one:
# half a level is the rounding to uint8, the rest float32 summation order
# (both sides float32 on the CPU, the reference at ``highest``; read 3e-5).
# The side network left out moves single pixels by 15-170 levels, a ring
# that hands a row another frame's edge map by 5-60 (the cases below)
_PARITY_LEVELS = 0.5 + 0.01


def _source_frames(n, seed=9):
    """Frames with edges in them (a moving texture, as the benchmark's
    source makes): uniform noise has an edge at every pixel."""
    from benchmark.source import frame_at, session_texture

    tex = session_texture(seed, 64, 64)
    return [frame_at(tex, k, 64, 64) for k in range(n)]


@pytest.fixture(scope="module")
def canny():
    """-> (bundle, configuration file, StreamConfig): the composite model id
    alone resolves the side network and the annotator; seeded weights under
    the file's rules (the zero convolutions are not zero)."""
    from benchmark.reference import sd_control_stream
    from benchmark.weights import make_weights, same_layout

    with open(_CANNY_FILE) as f:
        cfg_file = json.load(f)
    model_id = cfg_file["program_model_id"]
    bundle = registry.load_model_bundle(model_id)
    assert same_layout(sd_control_stream.weight_shapes(cfg_file), bundle.params) is None
    bundle.params.update(make_weights(
        sd_control_stream.weight_shapes(cfg_file), 7, jnp.float32,
        cfg_file["weights"]["rules"],
    ))
    cfg = registry.default_stream_config(model_id, dtype="float32")
    assert cfg.use_controlnet and cfg.annotator == "canny"
    return bundle, cfg_file, cfg


def _scheduler(canny, slots=1, bundle=None, cfg=None, **kw):
    b, cfg_file, c = canny
    b, c = bundle or b, cfg or c
    s = cfg_file["stream"]
    return BatchScheduler(
        b.stream_models, b.params, c, b.encode_prompt,
        max_sessions=slots, guidance_scale=s["guidance_scale"], delta=s["delta"],
        prewarm=False, dp=1, **kw,
    )


@pytest.fixture(scope="module")
def canny_served(canny):
    """[(source frame, served uint8 frame)] of one session through
    ``claim`` / ``submit`` / ``fetch`` at the file's scale, 7 frames (the
    ring is 3 deep), and the scheduler's counters as the session left them."""
    sched = _scheduler(canny)
    try:
        sess = sched.claim("parity", prompt=_PROMPT, seed=5)
        served = [(f, np.asarray(sess.fetch(sess.submit(f)))) for f in _source_frames(7)]
        snap, per_session = sched.snapshot(), sess.snapshot()
        sess.release()
        return served, snap, per_session
    finally:
        sched.close()


def _reference(canny, **kw):
    from benchmark.reference import sd_control_stream

    bundle, cfg_file, _ = canny
    return sd_control_stream.Reference(dict(cfg_file, **kw), bundle.params)


def _worst(ref_session, served):
    return max(
        np.abs(got.astype(np.float32) - ref_session.step(frame)).max()
        for frame, got in served
    )


def test_scheduler_frames_agree_with_the_plain_reference(canny, canny_served):
    """Annotator -> conditioning ring -> side network -> UNet with the
    thirteen residuals, through the vmapped bucket step."""
    served, snap, per_session = canny_served
    sess = _reference(canny).session(_PROMPT, 5)
    for i, (frame, got) in enumerate(served):
        want = sess.step(frame)
        assert 20 < want.std() and ((want <= 0) | (want >= 255)).mean() < 0.1, i
        assert got.dtype == np.uint8
        assert np.abs(got.astype(np.float32) - want).max() <= _PARITY_LEVELS, i
    # every step's one rider ran the side network; nothing wrote a scale
    assert snap["batchsched_controlnet_rows_total"] == snap["batchsched_steps_total"] == 7
    assert snap["batchsched_controlnet_scale_writes_total"] == 0
    assert per_session["controlnet_scale"] == 1.0


@pytest.mark.parametrize("part", [
    "side_network", "annotator", "ring_not_rotated", "ring_one_frame_late", "scale",
])
def test_parity_sees_each_part_of_the_mechanism(canny, canny_served, monkeypatch, part):
    """The same comparison with one part wrong on the reference's side:
    far outside the tolerance, so the tolerance is one in which a missing
    side network, another annotator, a ring that hands a row another
    frame's edge map, or another scale cannot hide."""
    from benchmark.reference import sd_control_stream

    served, _, _ = canny_served
    ref = _reference(canny, **({"conditioning_scale": 0.0} if part == "side_network" else {}))
    sess = ref.session(_PROMPT, 5)
    if part == "scale":
        sess.scale = 0.8
    elif part == "annotator":  # a harder threshold
        ref._edge = jax.jit(lambda f: sd_control_stream.soft_canny(
            f.astype(jnp.float32)[None] / 255.0, 0.2, 0.4
        ))
    elif part.startswith("ring"):
        real = sd_control_stream.Session.step

        def step(self, frame):
            out = real(self, frame)
            if part == "ring_not_rotated":  # every row sees the newest map
                self.edges = jnp.broadcast_to(self.edges[:1], self.edges.shape)
            else:  # the frame-shift control: every row's map is a frame older
                self.edges = jnp.concatenate([self.edges[1:], self.edges[-1:]])
            return out

        monkeypatch.setattr(sd_control_stream.Session, "step", step)
    assert _worst(sess, served) > 10 * _PARITY_LEVELS


def test_scale_zero_is_the_base_configuration_bit_for_bit(canny):
    """The side network switched off by data (scale 0 in the row) serves the
    frames of ``tiny-test`` on the same UNet, text tower and TAESD: every
    uint8 equal, and the state rows they share equal as floats."""
    bundle, cfg_file, cfg = canny
    frames = _source_frames(5)
    sched = _scheduler(canny)
    try:
        sched.update_controlnet_scale(0.0)  # the default of every later claim
        sess = sched.claim("off", prompt=_PROMPT, seed=5)
        assert sess.controlnet_scale == 0.0
        off = [np.asarray(sess.fetch(sess.submit(f))) for f in frames]
        ring_off = np.asarray(sched.states["x_buf"][sess.slot])
        sess.release()
    finally:
        sched.close()
    base = registry.load_model_bundle("tiny-test")
    base.params.update({k: bundle.params[k] for k in ("unet", "clip", "taesd")})
    base_cfg = registry.default_stream_config("tiny-test", dtype="float32")
    assert not base_cfg.use_controlnet
    sched = _scheduler(canny, bundle=base, cfg=base_cfg)
    try:
        assert "cnet_cond" not in sched.states and "cnet_scale" not in sched.states
        assert "batchsched_controlnet_rows_total" not in sched.snapshot()
        sess = sched.claim("base", prompt=_PROMPT, seed=5)
        plain = [np.asarray(sess.fetch(sess.submit(f))) for f in frames]
        ring_plain = np.asarray(sched.states["x_buf"][sess.slot])
        with pytest.raises(ValueError, match="no side network"):
            sess.update_controlnet_scale(0.5)
        sess.release()
    finally:
        sched.close()
    for a, b in zip(off, plain):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ring_off, ring_plain)


def test_two_sessions_with_different_scales_ride_one_k2_step(canny):
    """The scale is data in the row, not a key of the executable: two
    sessions at 1.0 and 0.4 in ONE k=2 bucket step each match their own
    reference, a write mid-stream takes with the next step, and nothing
    is traced again."""
    frames = _source_frames(6)
    sched = _scheduler(canny, slots=2, window_ms=2000.0)
    try:
        a = sched.claim("a", prompt=_PROMPT, seed=5)
        b = sched.claim("b", prompt="a watercolor garden at noon", seed=6)
        b.update_controlnet_scale(0.4)
        ra = _reference(canny).session(_PROMPT, 5)
        rb = _reference(canny).session("a watercolor garden at noon", 6)
        rb.scale = 0.4
        traced = None
        for i, f in enumerate(frames):
            if i == 3:
                a.update_controlnet_scale(1.6)
                ra.scale = 1.6
            # the second submit completes the batch: one inline k=2 step
            ha, hb = a.submit(f), b.submit(frames[-1 - i])
            got_a, got_b = np.asarray(a.fetch(ha)), np.asarray(b.fetch(hb))
            assert np.abs(got_a.astype(np.float32) - ra.step(f)).max() <= _PARITY_LEVELS, i
            assert np.abs(
                got_b.astype(np.float32) - rb.step(frames[-1 - i])
            ).max() <= _PARITY_LEVELS, i
            if i == 0:
                traced = sched._bucket_step(2)._cache_size()
        snap = sched.snapshot()
        assert snap["batchsched_occupancy_hist"] == {"2": 6}
        assert snap["batchsched_controlnet_rows_total"] == 12
        assert snap["batchsched_controlnet_scale_writes_total"] == 2
        assert sched._bucket_step(2)._cache_size() == traced == 1
        assert sorted(sched._bucket_steps) == [(2, "full")]  # k=1 never built
        assert a.snapshot()["controlnet_scale"] == 1.6
        assert b.snapshot()["controlnet_scale"] == 0.4
    finally:
        sched.close()


def test_snapshot_and_restore_carry_the_ring_and_the_scale(canny):
    """A session exported mid-stream resumes on another scheduler with its
    conditioning ring and scale: the next frames are bit for bit the
    unmigrated control's; a scheduler without the side network refuses."""
    frames = _source_frames(6)
    src, dst = _scheduler(canny), _scheduler(canny)
    try:
        sess = src.claim("m", prompt=_PROMPT, seed=5)
        sess.update_controlnet_scale(0.7)
        for f in frames[:3]:
            sess.fetch(sess.submit(f))
        snap = src.snapshot_session("m")
        assert snap["fingerprint"]["cnet"] == "canny"
        assert snap["controlnet_scale"] == 0.7
        moved = dst.restore_session(snap)
        assert moved.controlnet_scale == 0.7
        np.testing.assert_array_equal(
            np.asarray(dst.states["cnet_cond"][moved.slot]),
            np.asarray(src.states["cnet_cond"][sess.slot]),
        )
        assert float(dst.states["cnet_scale"][moved.slot]) == np.float32(0.7)
        assert np.asarray(dst.states["cnet_cond"][moved.slot]).max() > 0.5  # real edges
        for f in frames[3:]:
            np.testing.assert_array_equal(
                np.asarray(moved.fetch(moved.submit(f))),
                np.asarray(sess.fetch(sess.submit(f))),
            )
        # restart() keeps the live scale (the restart-defaults invariant)
        moved.restart()
        assert float(dst.states["cnet_scale"][moved.slot]) == np.float32(0.7)
        assert np.asarray(dst.states["cnet_cond"][moved.slot]).max() == 0.0  # fresh ring
    finally:
        src.close()
        dst.close()
    base = registry.load_model_bundle("tiny-test")
    plain = _scheduler(
        canny, bundle=base,
        cfg=registry.default_stream_config("tiny-test", dtype="float32"),
    )
    try:
        assert "cnet" not in plain.snapshot_fingerprint()
        with pytest.raises(SnapshotMismatch, match="cnet"):
            plain.restore_session(snap)
    finally:
        plain.close()


def test_config_write_reaches_the_session_rows(canny):
    """``POST /config {"controlnet_scale": x}`` (``apply_runtime_config``)
    writes every live session's row and the default of later claims; a
    plane without a side network, or a value outside [0, 2], is a 400 that
    applied nothing."""
    from ai_rtc_agent_tpu.server.agent import apply_runtime_config

    sched = _scheduler(canny, slots=2)
    try:
        a = sched.claim("a", prompt=_PROMPT, seed=5)
        apply_runtime_config(sched, {"controlnet_scale": 0.25})
        b = sched.claim("b", prompt=_PROMPT, seed=6)
        assert [float(x) for x in np.asarray(sched.states["cnet_scale"])] == [0.25, 0.25]
        assert a.controlnet_scale == b.controlnet_scale == sched.controlnet_scale == 0.25
        apply_runtime_config(a, {"controlnet_scale": 1.5})  # one session's datachannel
        assert [float(x) for x in np.asarray(sched.states["cnet_scale"])] == [1.5, 0.25]
        with pytest.raises(ValueError, match=r"\[0, 2\]"):
            apply_runtime_config(sched, {"prompt": "never applied", "controlnet_scale": 3})
        assert sched.prompt != "never applied"
        assert sched.snapshot()["batchsched_controlnet_scale_writes_total"] == 2
    finally:
        sched.close()

    class NoSideNetwork:
        has_controlnet = False

    with pytest.raises(ValueError, match="no side network"):
        apply_runtime_config(NoSideNetwork(), {"controlnet_scale": 1.0})


def test_one_model_id_names_base_side_network_and_annotator():
    assert registry.split_model_id("lykon/dreamshaper-8") == ("lykon/dreamshaper-8", None)
    both = registry.compose_model_id(
        "lykon/dreamshaper-8", "lllyasviel/control_v11p_sd15_canny"
    )
    assert both == "lykon/dreamshaper-8+lllyasviel/control_v11p_sd15_canny"
    assert registry.split_model_id(both) == (
        "lykon/dreamshaper-8", "lllyasviel/control_v11p_sd15_canny"
    )
    assert registry.family_of(both) == "sd15"
    with pytest.raises(ValueError, match="already names"):
        registry.compose_model_id(both, "another")
    cfg, base = registry.default_stream_config(both), registry.default_stream_config(
        "lykon/dreamshaper-8"
    )
    assert cfg.use_controlnet and cfg.annotator == "canny" and not base.use_controlnet
    import dataclasses

    assert dataclasses.replace(cfg, use_controlnet=False) == base
    with open(_PUBLISHED_FILE) as f:
        assert json.load(f)["program_model_id"] == both
    # every other id the benchmark builds resolves no side network (the
    # sdxl branch has a local of its own named for the image's side)
    import glob

    for path in glob.glob(os.path.join(_ROOT, "benchmark", "configs", "*.json")):
        with open(path) as f:
            model_id = json.load(f)["program_model_id"]
        assert registry.default_stream_config(model_id).use_controlnet == ("+" in model_id), path


def test_published_tree_is_the_benchmark_files_and_the_counts():
    """``load_model_bundle(<composite id>)`` as shapes against
    ``benchmark/configs/lcm4x512canny.json``: the same leaves; 361,279,120
    parameters in the side network (the published checkpoint's count);
    46 attention calls a pass, 14 of them the side network's."""
    from benchmark.flops import sd_control_stream as flops
    from benchmark.flops import sd_stream as base_flops
    from benchmark.reference import sd_control_stream
    from benchmark.weights import same_layout

    with open(_PUBLISHED_FILE) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == [] and cfg["conditioning_scale"] == 1.0
    shapes = jax.eval_shape(
        lambda: registry.load_model_bundle(cfg["program_model_id"]).params
    )
    ours = sd_control_stream.weight_shapes(cfg)
    assert same_layout(ours, shapes) is None
    side = jax.tree.leaves(ours["controlnet"], is_leaf=lambda x: isinstance(x, tuple))
    assert sum(int(np.prod(s)) for s in side) == 361_279_120
    assert len(ours["controlnet"]["zero_convs"]) == 12
    calls = flops.attention_calls(cfg)
    assert len(calls) == 46 and calls[14:] == base_flops.attention_calls(cfg)
    assert sum(c["lk"] == 77 for c in calls) == 23
    assert calls[0] == {"lq": 4096, "lk": 4096, "heads": 8, "head_dim": 40}
    assert calls[12] == {"lq": 64, "lk": 64, "heads": 8, "head_dim": 160}  # its middle block
    total, plain = flops.frame_flops(cfg), base_flops.frame_flops(cfg)
    assert 3.3e12 < plain < 3.6e12 and 4.5e12 < total < 4.7e12
    assert 0.30 < flops.side_network_flops(cfg, 4) / plain < 0.35


def test_flops_agree_with_xla_cost_analysis_of_the_plain_step():
    """As ``benchmark/tests/test_flops.py`` holds ``sd_stream``'s count:
    XLA counts the elementwise work too, a few percent at the tiny widths."""
    from benchmark.flops import sd_control_stream as flops
    from benchmark.reference import sd_control_stream

    with open(_CANNY_FILE) as f:
        cfg = json.load(f)
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        sd_control_stream.weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple),
    )
    ref = sd_control_stream.Reference(cfg, None)
    s, t = cfg["stream"], cfg["text_encoder"]
    B = len(s["t_index_list"])
    h, wd = s["height"] // s["latent_scale"], s["width"] // s["latent_scale"]
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    cond = {
        "ctx": spec(1, t["max_position_embeddings"], t["hidden_size"]),
        "edges": spec(B, s["height"], s["width"], 3), "scale": spec(),
    }
    cost = jax.jit(ref._step_fn).lower(
        w, cond, spec(B, h, wd, 4), spec(B - 1, h, wd, 4), spec(B, h, wd, 4),
        jax.ShapeDtypeStruct((s["height"], s["width"], 3), jnp.uint8),
    ).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = flops.frame_flops(cfg) - flops.annotator_flops(cfg)  # outside _step_fn
    assert 0.90 < ours / cost["flops"] <= 1.0, (ours, cost["flops"])


def test_canny_soft_is_the_reference_operator_in_float32_whatever_comes_in():
    """The program's shifted slices against the reference's convolution, and
    a bfloat16 frame read as float32 inside (a threshold of slope 60 would
    turn bfloat16's rounding into a tenth of the range)."""
    from benchmark.reference import sd_control_stream

    img = jnp.asarray(_source_frames(2)[1][None], jnp.float32) / 255.0
    want = np.asarray(sd_control_stream.soft_canny(img, 0.1, 0.3))
    got = np.asarray(canny_soft(img))
    assert 0.02 < want.mean() < 0.6 and want.max() > 0.99  # some edges, not all
    np.testing.assert_allclose(got, want, atol=2e-5)
    low = canny_soft(img.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(low, np.float32),
        np.asarray(canny_soft(img.astype(jnp.bfloat16).astype(jnp.float32))),
        atol=2 ** -8,
    )
