"""The FLOPs-from-shapes function against XLA's own count and the record."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.flops import sd_stream as flops
from benchmark.reference.sd_stream import Reference, weight_shapes

from .conftest import HERE, REPO


def _cfg(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["tiny64", "tinyturbo64"])
def test_agrees_with_xla_cost_analysis_of_the_plain_step(name):
    """XLA counts the elementwise work too (norms, softmax), which at the
    tiny widths is a few percent and at the published widths under one."""
    cfg = _cfg(os.path.join(HERE, "data", "configs", name + ".json"))
    shapes = weight_shapes(cfg)
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    ref = Reference(cfg, None)
    s = cfg["stream"]
    B = len(s["t_index_list"])
    h, wd = s["height"] // s["latent_scale"], s["width"] // s["latent_scale"]
    t = cfg["text_encoder"]
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    cost = jax.jit(ref._step_fn).lower(
        w, spec(1, t["max_position_embeddings"], t["hidden_size"]),
        spec(B, h, wd, 4), spec(B - 1, h, wd, 4), spec(B, h, wd, 4),
        jax.ShapeDtypeStruct((s["height"], s["width"], 3), jnp.uint8),
    ).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    ours = flops.frame_flops(cfg)
    assert 0.90 < ours / cost["flops"] <= 1.0, (ours, cost["flops"])


def test_published_widths_give_the_recorded_counts():
    turbo = _cfg(os.path.join(REPO, "benchmark", "configs", "turbo512.json"))
    lcm = _cfg(os.path.join(REPO, "benchmark", "configs", "lcm4x512.json"))
    assert 1.0e12 < flops.frame_flops(turbo) < 1.1e12   # PERF.md: 1.05e12 by HLO
    assert 3.3e12 < flops.frame_flops(lcm) < 3.6e12
    for cfg in (turbo, lcm):
        calls = flops.attention_calls(cfg)
        assert len(calls) == 32          # the 32 flash_attention calls of a step
        assert sum(c["lk"] == 77 for c in calls) == 16
    big = flops.attention_calls(turbo)[0]
    assert big == {"lq": 4096, "lk": 4096, "heads": 5, "head_dim": 64}
    assert flops.attention_flops(big) == 4 * 4096 * 4096 * 320
    assert flops.attention_bytes(big) == 2 * 320 * 2 * (4096 + 4096)
    assert flops.attention_calls(lcm)[0]["head_dim"] == 40
