"""The system under test, built the way the agent builds it, minus the agent:
``registry.load_model_bundle`` / ``default_stream_config`` / ``cast_params``
-> ``BatchScheduler`` with as many slots as the cell has sessions.

The only files of the benchmark that import the program are this one and
``serve.py``.  Nothing here computes a number: it builds, checks that what
was built is the graph the configuration names, and hands it over.
"""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp

from .weights import make_weights, same_layout

logger = logging.getLogger("benchmark")

# stream settings the configuration file states and the program's resolved
# StreamConfig must agree on (file key -> StreamConfig attribute)
_STREAM_KEYS = {
    "mode": "mode", "height": "height", "width": "width",
    "latent_scale": "latent_scale", "t_index_list": "t_index_list",
    "num_inference_steps": "num_inference_steps",
    "timestep_spacing": "timestep_spacing", "scheduler": "scheduler",
    "cfg_type": "cfg_type", "frame_buffer_size": "frame_buffer_size",
    "prediction_type": "prediction_type", "dtype": "dtype",
    "fused_epilogue": "use_fused_epilogue",
}


class WrongGraph(RuntimeError):
    """What was built is not what the configuration names."""


def build_scheduler(cfg: dict, ours, seed: int, slots: int, quant: str | None = None):
    """-> (scheduler, stream_cfg).  ``ours``: the configuration's weight tree
    as shapes, from its reference module.  ``quant``: None for the configuration
    as stated; "w8" switches on the program's own int8-weight path
    (``QUANT_WEIGHTS``), which is the control of the output check."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import current_attn_impl
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

    s = cfg["stream"]
    model_id = cfg["program_model_id"]
    t_last = [time.monotonic()]

    def stage(what: str):
        now = time.monotonic()
        logger.info("set-up: %s took %.1f s", what, now - t_last[0])
        t_last[0] = now
    # the program's own tree, as shapes only: its initialisers run under
    # eval_shape (nothing is materialised, no float32 transient), and the
    # bundle's closures are kept for the real arrays made below
    kept = {}

    def _shapes_only():
        kept["bundle"] = registry.load_model_bundle(model_id)
        return kept["bundle"].params

    program_shapes = jax.eval_shape(_shapes_only)
    bundle = kept["bundle"]
    diff = same_layout(ours, program_shapes)
    if diff:
        raise WrongGraph(f"{cfg['name']}: weight trees differ: {diff}")

    stage("the program's tree as shapes")
    served = make_weights(
        ours, seed, jnp.dtype(s["dtype"]), cfg.get("weights", {}).get("rules", ())
    )
    jax.block_until_ready(served)
    stage("weights from the seed")
    # encode_prompt closes over the dict load_model_bundle filled; in the
    # agent that dict keeps the float32 text towers while the step gets the
    # cast tree.  Same here: float32 copies of the served values, of every
    # subtree the configuration file says the text side reads.
    texts = cfg.get("program_text_subtrees", ["clip"])
    absent = [t for t in texts if t not in served]
    if absent:
        raise WrongGraph(
            f"{cfg['name']}: program_text_subtrees names {absent}, the weight "
            f"tree has {sorted(served)}"
        )
    bundle.params.clear()
    for t in texts:
        bundle.params[t] = jax.tree.map(lambda a: a.astype(jnp.float32), served[t])

    def encode_prompt(prompt: str):
        # the scheduler encodes its first prompt while it is built, so a
        # subtree the file does not list is named here, in set-up
        try:
            return bundle.encode_prompt(prompt)
        except KeyError as e:
            raise WrongGraph(
                f"{cfg['name']}: the program's encode_prompt reads the subtree "
                f"{e.args[0]!r}, which the configuration file's "
                f"program_text_subtrees ({texts}) does not list"
            ) from None

    overrides = {
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg.get("program_stream_overrides", {}).items()
    }
    stream_cfg = registry.default_stream_config(model_id, **overrides)
    for key, attr in _STREAM_KEYS.items():
        got = getattr(stream_cfg, attr)
        want = tuple(s[key]) if isinstance(s[key], list) else s[key]
        if got != want:
            raise WrongGraph(
                f"{cfg['name']}: the program resolves stream.{key} to {got!r}, "
                f"the configuration file states {want!r}"
            )
    if current_attn_impl() != s["attn_impl"]:
        raise WrongGraph(
            f"{cfg['name']}: attention is {current_attn_impl()!r}, "
            f"the configuration file states {s['attn_impl']!r}"
        )

    if quant == "w8":
        # the program reads its int8-weight switch from the environment
        os.environ["QUANT_WEIGHTS"] = "w8"
        min_size = cfg["check"].get("control_quant_min_size")
        if min_size is not None:  # tiny test widths sit under the default
            os.environ["QUANT_MIN_SIZE"] = str(min_size)
    try:
        params = registry.cast_params(served, stream_cfg.dtype)
    finally:
        os.environ.pop("QUANT_WEIGHTS", None)
        os.environ.pop("QUANT_MIN_SIZE", None)
    stage("cast_params")
    sched = BatchScheduler(
        bundle.stream_models, params, stream_cfg, encode_prompt,
        max_sessions=slots, guidance_scale=s["guidance_scale"], delta=s["delta"],
        prewarm=True, dp=1,
    )
    stage("BatchScheduler (template state, bucket executables)")
    for label, kernels in sched.mosaic_kernels.items():
        missing = [k for k in s["mosaic_kernels"] if not kernels.get(k)]
        if missing:
            raise WrongGraph(
                f"{cfg['name']}: bucket {label} compiled without {missing} "
                f"(found {kernels})"
            )
    sched.rehearse()
    stage("rehearsal")
    return sched, stream_cfg
