"""The harness takes a second model family by files alone: two text towers
and the ``text_time`` addition embedding (``tinyxl64``: the program's
``tinyxl-test`` family, ``reference/sdxl_stream.py``), and the output check
holds the weights once, in the dtype they are served in."""

import gc
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .conftest import HERE


def _cfg(name, **stream):
    with open(os.path.join(HERE, "data", "configs", name + ".json")) as f:
        cfg = dict(json.load(f), name=name)
    cfg["stream"] = dict(cfg["stream"], **stream)
    return cfg


def test_build_scheduler_puts_back_both_towers_and_serves_a_frame():
    from benchmark.program import build_scheduler
    from benchmark.reference import sdxl_stream

    cfg = _cfg("tinyxl64")
    sched, stream_cfg = build_scheduler(cfg, sdxl_stream.weight_shapes(cfg), 11, 1)
    try:
        assert stream_cfg.use_added_cond
        cond, uncond, extras = sched._template.encode_prompt("neon")
        assert cond.shape == uncond.shape == (1, 16, 32)  # the towers' 16 + 16
        assert extras["pooled"].shape == (1, 16)
        sess = sched.claim("t", prompt="neon", seed=5)
        frame = np.random.default_rng(0).integers(0, 256, (64, 64, 3), dtype=np.uint8)
        out = np.asarray(sess(frame))
        sess.release()
        assert out.dtype == np.uint8 and out.shape == (64, 64, 3)
        assert not np.array_equal(out, frame)
    finally:
        sched.close()


def test_a_text_subtree_the_file_does_not_list_is_a_wrong_graph_that_names_it():
    from benchmark.program import WrongGraph, build_scheduler
    from benchmark.reference import sdxl_stream

    cfg = _cfg("tinyxl64")
    del cfg["program_text_subtrees"]  # the default: one tower, "clip"
    with pytest.raises(WrongGraph, match=r"reads the subtree 'clip2'"):
        build_scheduler(cfg, sdxl_stream.weight_shapes(cfg), 11, 1)
    cfg["program_text_subtrees"] = ["clip", "clip3"]
    with pytest.raises(WrongGraph, match=r"names \['clip3'\]"):
        build_scheduler(cfg, sdxl_stream.weight_shapes(cfg), 11, 1)


@pytest.mark.parametrize("part", ["addition_embedding", "second_tower_context", "text_embedding"])
def test_each_part_of_the_second_family_is_in_what_is_compared(run_cell, monkeypatch, part):
    """The reference with one part of the family left out, against the
    program as it is: not correct."""
    from benchmark.reference import models

    if part == "addition_embedding":
        real = models.unet
        monkeypatch.setattr(
            models, "unet", lambda p, x, t, ctx, u, added=None: real(p, x, t, ctx, u)
        )
    else:
        real = models.clip_text_projected
        keep = (0.0, 1.0) if part == "second_tower_context" else (1.0, 0.0)

        def broken(p, ids, t):
            hidden, text = real(p, ids, t)
            return hidden * keep[0], text * keep[1]

        monkeypatch.setattr(models, "clip_text_projected", broken)
    code, line, err = run_cell("tinyxl64.duo20", seed=48)
    assert code == 0, err
    c = line["compared"]["session_bias_rel_max"]
    assert line["correct"] is False and c["value"] > 10 * c["limit"], c


@pytest.mark.parametrize("name,module", [
    ("tiny64", "sd_stream"), ("tinyturbo64", "sd_stream"), ("tinyxl64", "sdxl_stream"),
])
def test_the_tree_held_once_gives_the_frames_of_a_float32_copy(name, module):
    """The old route (a float32 copy of the whole served tree, built here)
    against the new (the served tree itself, widened leaf by leaf where it
    is read): bfloat16 -> float32 is exact, so the operands are the same
    numbers and the frames agree to float32 rounding."""
    import importlib

    from benchmark import check
    from benchmark.source import frame_at, session_texture

    mod = importlib.import_module(f"benchmark.reference.{module}")
    cfg = _cfg(name, dtype="bfloat16")
    new = check.reference_for(cfg, 21, mod)
    assert {a.dtype for a in jax.tree.leaves(new.w)} == {jnp.dtype("bfloat16")}
    old = mod.Reference(cfg, jax.tree.map(lambda a: a.astype(jnp.float32), new.w))
    tex = session_texture(9, 64, 64)
    a, b = new.session("neon", 9), old.session("neon", 9)
    for k in range(6):
        fa, fb = a.step(frame_at(tex, k, 64, 64)), b.step(frame_at(tex, k, 64, 64))
        assert fa.std() > 5  # an image, in uint8 levels
        assert np.abs(fa - fb).max() <= 1e-3, (k, np.abs(fa - fb).max())


def _live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


@pytest.mark.parametrize("name,module", [("tiny64", "sd_stream"), ("tinyxl64", "sdxl_stream")])
def test_the_check_holds_the_weights_once(name, module):
    """While the reference is built and stepped the live arrays stay under
    1.25 x the served tree plus the largest leaf in float32, plus what a
    session carries (its state before and after a step, and the frame: at the
    tiny size a quarter of the tree, at the published widths a thousandth);
    a float32 copy beside the tree, as the check held it before, is 3 x."""
    import importlib

    from benchmark import check
    from benchmark.source import frame_at, session_texture
    from benchmark.weights import make_weights

    mod = importlib.import_module(f"benchmark.reference.{module}")
    cfg = _cfg(name, dtype="bfloat16")
    shapes = jax.tree.leaves(mod.weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    tree = 2 * sum(int(np.prod(s)) for s in shapes)
    largest = 4 * max(int(np.prod(s)) for s in shapes)
    gc.collect()  # what earlier tests left to the collector is not ours
    base = _live_bytes()
    seen, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            seen.append(_live_bytes() - base)
            stop.wait(0.002)

    t = threading.Thread(target=watch)
    t.start()
    try:
        ref = check.reference_for(cfg, 22, mod)
        sess = ref.session("neon", 9)
        tex = session_texture(9, 64, 64)
        for k in range(3):
            sess.step(frame_at(tex, k, 64, 64))
            seen.append(_live_bytes() - base)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive()
    carried = sum(
        a.nbytes for a in jax.tree.leaves((sess.noise, sess.ring, sess.stock, sess.cond))
    ) + 4 * 64 * 64 * 3
    ceiling = 1.25 * tree + largest + 2 * carried
    assert tree <= max(seen) <= ceiling, (tree, max(seen), ceiling)
    # the old route, for the measure: the same tree with its float32 copy
    old = jax.tree.map(lambda a: a.astype(jnp.float32), ref.w)
    assert _live_bytes() - base > 2.9 * tree
    del old
