"""AOT engine-build CLI on the tiny family (reference build.py parity)."""

import os

import pytest

from ai_rtc_agent_tpu.assets.build_engines import build


@pytest.mark.slow
def test_build_engine_tiny(tmp_path, monkeypatch):
    """`slow` tier (ISSUE 12 budget satellite, ~16s of CLI build): the
    serving-side adoption of a prebuilt engine stays tier-1
    (test_serving_adopts_prebuilt_engine), as do the EngineCache
    build/load/donation pins in tests/test_aot_cache.py — this is the
    CLI-driver composition over the same machinery."""
    (key,), _ = build("tiny-test", cache_dir=str(tmp_path))
    d = os.path.join(tmp_path, key)
    assert os.path.isdir(d)
    blobs = [f for f in os.listdir(d) if f.endswith(".jaxexport")]
    metas = [f for f in os.listdir(d) if f.endswith(".json")]
    assert len(blobs) == 1 and len(metas) == 1

    # second build: cache hit (no new blob)
    build("tiny-test", cache_dir=str(tmp_path))
    assert len([f for f in os.listdir(d) if f.endswith(".jaxexport")]) == 1


def test_serving_adopts_prebuilt_engine(tmp_path, monkeypatch):
    """The pipeline must hit the deserialize fast path when the CLI built an
    engine (reference _load_trt_model fast path, lib/wrapper.py:409-512)."""
    import numpy as np

    from ai_rtc_agent_tpu.stream.pipeline import StreamDiffusionPipeline

    monkeypatch.setenv("XLA_ENGINES_CACHE", str(tmp_path))
    build("tiny-test", cache_dir=str(tmp_path))

    pipe = StreamDiffusionPipeline("tiny-test")
    assert pipe.engine.use_aot_cache("tiny-test", build_on_miss=False)
    frame = np.random.default_rng(0).integers(0, 256, (64, 64, 3), np.uint8)
    out = pipe(frame)
    assert out.shape == (64, 64, 3) and out.dtype == np.uint8


def test_no_adoption_without_prebuilt_engine(tmp_path, monkeypatch):
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    monkeypatch.setenv("XLA_ENGINES_CACHE", str(tmp_path))
    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config("tiny-test")
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=False,
    )
    eng.prepare("x")
    assert not eng.use_aot_cache("tiny-test", build_on_miss=False)


@pytest.mark.slow  # a second full build with the ControlNet graph
# (~11s); the tiny build + serving-adoption tests keep the CLI covered
# in tier-1, and the variant keying itself is pinned by stream_engine_key
# unit tests
def test_build_controlnet_engine_variant(tmp_path):
    """ControlNet engine variant gets its own cache key (reference compiles a
    separate UNet+ControlNet engine, lib/wrapper.py:870-877)."""
    (key_plain,), _ = build("tiny-test", cache_dir=str(tmp_path))
    (key_cnet,), _ = build("tiny-test+tiny-cnet", cache_dir=str(tmp_path))
    assert key_plain != key_cnet and "tiny-test+tiny-cnet" in key_cnet
    assert os.path.isdir(os.path.join(tmp_path, key_cnet))


def test_build_deepcache_pair(tmp_path, monkeypatch):
    """UNET_CACHE config builds BOTH variants (capture + cached) with
    distinct keys — serve-time adoption is pair-atomic."""
    monkeypatch.setenv("UNET_CACHE", "2")
    keys, _ = build("tiny-test", cache_dir=str(tmp_path))
    assert len(keys) == 2 and keys[0] != keys[1]
    assert any("capture" in k for k in keys)
    assert any("cached" in k for k in keys)
    for k in keys:
        d = os.path.join(tmp_path, k)
        assert [f for f in os.listdir(d) if f.endswith(".jaxexport")]


def test_build_engines_sched_buckets_flag(tmp_path, monkeypatch):
    """--sched-buckets S prebuilds the batch scheduler's bucket geometries
    through the scheduler's own adoption path (keys can't drift): a fresh
    BatchScheduler then adopts every bucket without building and serves a
    frame — tier-1's AOT build + adopt round trip."""
    import numpy as np

    from ai_rtc_agent_tpu.assets import build_engines
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler
    from ai_rtc_agent_tpu.utils import device

    # main() is a process entry point and places XLA's persistent compile
    # cache; called in-process it would engage that cache for the rest of
    # the suite, which conftest.py keeps cache-free
    monkeypatch.setattr(device, "configure_compile_cache", lambda: "off")
    build_engines.main([
        "--model-id", "tiny-test", "--cache-dir", str(tmp_path),
        "--sched-buckets", "2",
    ])
    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config("tiny-test")
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id="tiny-test", max_sessions=2, prewarm=False,
        aot_build_on_miss=False, cache_dir=str(tmp_path), dp=1,
    )
    try:
        assert sched._aot_adopted  # ctor adoption found every bucket
        assert all(
            sched.aot_status("tiny-test", cache_dir=str(tmp_path)).values()
        )
        sess = sched.claim("adopt prebuilt")
        frame = np.random.default_rng(0).integers(
            0, 256, (cfg.height, cfg.width, 3), np.uint8
        )
        out = sess(frame)
        assert out.shape == frame.shape and out.dtype == np.uint8
    finally:
        sched.close()
    # another capacity is another key family: nothing to adopt
    other = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id="tiny-test", max_sessions=4, prewarm=False,
        aot_build_on_miss=False, cache_dir=str(tmp_path), dp=1,
    )
    try:
        assert not other._aot_adopted
    finally:
        other.close()
