"""The harness takes FLUX's double- and single-stream transformer by files
alone (``tinyflux_64``: the program's ``tinyflux-test`` family,
``reference/flux_stream.py``, ``flops/flux_stream.py``): the cell is
correct, its controls are not, each part of the family is in what is
compared, the operations from shapes agree with a hand count and with XLA,
and ``rope_attention_roofline`` reads its kernel's events."""

import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

from .conftest import HERE, REPO, make_root, tiny_spec

CELL = "tinyflux_64.duo20"


def _cfg():
    with open(os.path.join(HERE, "data", "configs", "tinyflux_64.json")) as f:
        return dict(json.load(f), name="tinyflux_64")


def _published():
    with open(os.path.join(REPO, "benchmark", "configs", "fluxschnell2x512.json")) as f:
        return json.load(f)


def _spec():
    spec = tiny_spec()
    spec["configs"] = [{
        "name": "tinyflux_64", "source": "tests",
        "file": "benchmark/configs/tinyflux_64.json", "reduced": [],
        "why": "tiny FLUX family",
    }]
    spec["workloads"] = [{
        "name": CELL, "config": "tinyflux_64", "traffic": "duo20", "chips": 1,
        "why": "two sessions, CPU test",
    }]
    return spec


@pytest.fixture(scope="module")
def flux_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("fluxroot"), _spec())


def _root_with(tmp_path, old: str, new: str) -> str:
    """A root whose reference has ``old`` replaced by ``new`` (once)."""
    root = make_root(tmp_path, _spec())
    path = os.path.join(root, "benchmark", "reference", "flux_stream.py")
    with open(path) as f:
        text = f.read()
    assert text.count(old) == 1, old
    with open(path, "w") as f:
        f.write(text.replace(old, new))
    return root


def test_the_program_tree_is_the_reference_layout():
    from ai_rtc_agent_tpu.models import registry
    from benchmark.reference import flux_stream
    from benchmark.weights import same_layout

    shapes = jax.eval_shape(lambda: registry.load_model_bundle("tinyflux-test").params)
    assert same_layout(flux_stream.weight_shapes(_cfg()), shapes) is None


def test_published_widths_give_the_published_counts():
    """A double block holds 339.8e6 parameters, a single block 141.6e6, the
    embedders and the last layer 54e6: 11.89e9 whole at 19 + 38 blocks,
    3.17e9 in the stage of 5 + 10 the file holds.  A block over a row's 1280
    joint tokens is 310.1e9 operations, 20.13e9 of them attention."""
    from benchmark.flops import flux_stream as flops
    from benchmark.reference import flux_stream

    cfg = _published()
    p = flux_stream.weight_shapes(cfg)["flux"]
    count = lambda tree: sum(  # noqa: E731
        math.prod(s) for s in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple))
    )
    double, single = count(p["double_blocks"][0]), count(p["single_blocks"][0])
    rest = count(p) - 5 * double - 10 * single
    assert double == pytest.approx(339.8e6, rel=1e-3)
    assert single == pytest.approx(141.6e6, rel=1e-3)
    assert rest == pytest.approx(54e6, rel=1e-2)
    assert 19 * double + 38 * single + rest == pytest.approx(11.89e9, rel=1e-3)
    assert count(p) == pytest.approx(3.17e9, rel=1e-3)
    calls = flops.rope_attention_calls(cfg)
    assert len(calls) == 15
    assert calls[0] == {"lq": 1280, "lk": 1280, "heads": 24, "head_dim": 128}
    one = flops.rope_attention_flops(calls[0])
    assert one == 4 * 1280 * 1280 * 3072 == pytest.approx(20.13e9, rel=1e-3)
    # by hand: 12 w^2 multiply-adds a token in each kind of block
    w = 3072
    block = 2 * 12 * w * w * 1280 + one
    assert block == pytest.approx(310.1e9, rel=1e-3)
    assert flops.transformer_flops(cfg) == pytest.approx(15 * block, rel=0.005)
    assert 9.3e12 < flops.frame_flops(cfg) < 9.6e12
    assert flops.rope_attention_bytes(calls[0]) == pytest.approx(32.8e6, rel=1e-2)


def test_flops_agree_with_xla_cost_analysis_of_the_plain_step():
    """XLA counts the elementwise work too, a few percent at the tiny
    widths: the rotations, norms and activations the count leaves out."""
    from benchmark.flops import flux_stream as flops
    from benchmark.reference.flux_stream import Reference, weight_shapes

    cfg = _cfg()
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), weight_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    ref = Reference(cfg, None)
    s = cfg["stream"]
    B = len(s["t_index_list"])
    h = s["height"] // s["latent_scale"]
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    cond = {"ctx": spec(1, 8, 64), "pooled": spec(1, 32)}
    cost = jax.jit(ref._step_fn).lower(
        w, cond, spec(B, h, h, 16), spec(B - 1, h, h, 16),
        jax.ShapeDtypeStruct((s["height"], s["width"], 3), jnp.uint8),
    ).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert 0.85 < flops.frame_flops(cfg) / cost["flops"] <= 1.0


def test_the_cell_is_correct_by_files_alone(run_cell, flux_root):
    code, line, err = run_cell(CELL, seed=2**31 + 17, root=flux_root)
    assert code == 0, err
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("control", ["w8", "wrong_frame"])
def test_the_controls_are_not_correct(run_cell, flux_root, control):
    code, line, err = run_cell(CELL, seed=29, root=flux_root, control=control)
    assert code == 0, err
    assert line["correct"] is False, line["compared"]


_PARTS = {
    # the text stream: T5's stand-in states
    "text_stream": (
        'return {"ctx": jnp.asarray(ctx), "pooled": pooled}',
        'return {"ctx": jnp.zeros_like(jnp.asarray(ctx)), "pooled": pooled}',
    ),
    # the rotary positions: every token at the origin
    "rotary_positions": (
        "    return np.concatenate([np.zeros((text_tokens, 3)), img.reshape(-1, 3)])\n",
        "    return np.zeros((text_tokens + gh * gw, 3))\n",
    ),
    # the q/k RMS norm
    "qk_norm": (
        "    return _rms(norm[\"query_norm\"], q), _rms(norm[\"key_norm\"], k), v\n",
        "    return q, k, v\n",
    ),
    # the single blocks' parallel MLP
    "single_mlp": (
        "jnp.concatenate([a, _gelu_tanh(y[..., 3 * w:])], -1)",
        "jnp.concatenate([a, 0.0 * y[..., 3 * w:]], -1)",
    ),
    # the pooled text
    "pooled_text": (
        'return {"ctx": jnp.asarray(ctx), "pooled": pooled}',
        'return {"ctx": jnp.asarray(ctx), "pooled": 0.0 * pooled}',
    ),
}


@pytest.mark.parametrize("part", sorted(_PARTS))
def test_each_part_of_the_family_is_in_what_is_compared(run_cell, tmp_path, part):
    """The reference with one part of the family left out, against the
    program as it is: not correct, past the limit by far."""
    root = _root_with(tmp_path, *_PARTS[part])
    code, line, err = run_cell(CELL, seed=48, root=root)
    assert code == 0, err
    c = line["compared"]["session_bias_rel_max"]
    assert line["correct"] is False and c["value"] > 3 * c["limit"], c


def test_rope_attention_roofline_reads_its_kernels_events():
    """The bound of a step's calls times the rows of the span's steps, over
    the kernel's device time; nothing without its events or its calls."""
    from benchmark.flops import flux_stream as flops
    from benchmark.harness import Benchmark

    read = Benchmark(REPO).reader({"name": "rope_attention_roofline"})
    cfg = _published()

    class Result:
        @staticmethod
        def steps_by_riders(traced=False):
            return {1: 3}  # three whole k=1 steps in the span

    class Ctx:
        traffic, result = {"slots": 1}, Result
        peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        trace = {"kernels": {"rope_attention": [0.0002] * 90}}

    Ctx.cfg, Ctx.flops = cfg, flops
    bound = 15 * max(20.1326592e9 / 197e12, 32.768e6 / 819e9)
    # 15 calls a pass, two rows a step, three steps, in 90 events of 0.2 ms
    assert read(Ctx) == pytest.approx(100 * bound * 3 * 2 / 0.018)
    assert read(Ctx) == pytest.approx(100 * 20.1326592e9 / 197e12 / 0.0002)
    Ctx.trace = {"kernels": {"joint_attention": [0.010]}}
    assert read(Ctx) is None
