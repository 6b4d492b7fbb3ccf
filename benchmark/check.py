"""The comparison that decides ``correct``.

What is compared: the uint8 frames the timed path returned to the sinks
inside the window (every session; the window's first frames and its last,
and where a step carries nothing from frame to frame also a seeded sample
over the rest), against the plain float32 reference (the module under
``reference/`` that the configuration names) given the same seed, the same
prompts and the same source frames.  A configuration whose step carries
state (the latent ring, R-CFG stock noise) is followed by the reference from
the session's claim through the window's first frames: every frame the
session consumed, warm-up included, in order.  For the window's last frames
a second reference session starts afresh ``warm_in_steps`` consumed frames
before them, after which its state is the followed one's to a thousandth
(``reference/sd_stream.py``), so the reference never walks the whole window.

Runs once the window has closed, the peak memory has been read and the
program is freed.  The reference makes its own weights from the seed
(``weights.py``), holds them once (``reference_for``) and takes nothing from
the program.

The number compared, ``session_bias_rel_max``: per session, the signed
error (served minus reference, uint8 levels, the reference unrounded) is
averaged over 8x8 pixel blocks (one latent cell) and then over the session's
compared frames; the root mean square of that map, over the spatial
standard deviation of the reference's own pooled image (its contrast, which
with random weights varies from seed to seed and scales every error with
it); the worst session.  Averaging takes out what differs from frame to
frame (uint8 rounding, the part of bf16 rounding that moves with the input)
and keeps what a wrong or coarser computation adds to every frame.  A
session with fewer than ``check.min_frames_per_session`` compared frames
reads ``NOTHING_COMPARED`` (1e9: a number JSON can carry).  The limit comes from the configuration file
(``check.limits``); the readings it was set from are in PERF.md.  The other
numbers in ``readings`` are printed for the record and judged by nothing.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .source import frame_at, session_texture
from .weights import make_weights


def reference_for(cfg: dict, seed: int, ref_module):
    """The configuration's reference (``harness.Benchmark.reference``) on the
    weights the seed gives, held once and in the dtype they are served in:
    the reference widens each leaf to float32 where it reads it
    (``reference/nn.py``), which is exact, so it computes on the numbers a
    float32 copy of the tree would hold without the copy's 4 bytes a
    parameter beside the tree's 2."""
    s = cfg["stream"]
    return ref_module.Reference(cfg, make_weights(
        ref_module.weight_shapes(cfg), seed, jnp.dtype(s["dtype"]),
        cfg.get("weights", {}).get("rules", ()),
    ))


POOL = 8  # pixels a side: one latent cell
NOTHING_COMPARED = 1e9


def pooled(e: np.ndarray, p: int = POOL) -> np.ndarray:
    """[H,W,C] -> [H/p,W/p,C], the mean over p x p blocks."""
    h, w, c = e.shape
    return e.reshape(h // p, p, w // p, p, c).mean(axis=(1, 3))


def frame_stats(served_u8: np.ndarray, ref_levels: np.ndarray) -> dict:
    e = served_u8.astype(np.float32) - ref_levels
    d = np.abs(e)
    pe = pooled(e)
    return {
        "mean": float(d.mean()),
        "max": float(d.max()),
        "pooled_rms": float(np.sqrt((pe ** 2).mean())),
        "_pe": pe,
        "_pref": pooled(ref_levels),
    }


def _runs(ordinals) -> list:
    """Sorted ordinals -> lists of consecutive ones."""
    runs = []
    for o in sorted(ordinals):
        if runs and o == runs[-1][-1] + 1:
            runs[-1].append(o)
        else:
            runs.append([o])
    return runs


def compare(cfg: dict, ref, result, frame_shift: int = 0) -> dict:
    """-> {"frames": [per-frame stats...], "numbers": {name: value}}.
    ``ref``: ``reference_for`` the run's seed.  ``frame_shift``: a control
    of the comparison itself: the reference is fed source frame
    ``k + frame_shift`` where the session consumed ``k``."""
    s = cfg["stream"]
    h, w = s["height"], s["width"]
    frames = []
    for log in result.sessions:
        if not log.kept:
            continue
        texture = session_texture(log.seed, h, w)
        # (source frame index, ordinal in the session's returned frames) of
        # every step the reference takes; None: a new session starts afresh
        plan: list = []
        if not ref.stateful:
            plan = [(log.records[o].k, o) for o in sorted(log.kept)]
        else:
            pos = 0  # index into consumed of the next frame to step on
            for run in _runs(log.kept):
                first, last = log.warmup + run[0], log.warmup + run[-1]
                if first - pos > ref.warm_in_steps:
                    plan.append(None)
                    pos = first - ref.warm_in_steps
                plan += [(log.consumed[i], i - log.warmup) for i in range(pos, last + 1)]
                pos = last + 1
        sess = ref.session(log.prompt, log.seed)
        for entry in plan:
            if entry is None:
                sess = ref.session(log.prompt, log.seed)
                continue
            k, ordinal = entry
            out = sess.step(frame_at(texture, k + frame_shift, h, w))
            if ordinal in log.kept:
                st = frame_stats(log.kept[ordinal], out)
                frames.append(dict(st, session=log.index, ordinal=ordinal))
    need = cfg["check"].get("min_frames_per_session", 1)
    rel, bias = [], []
    for log in result.sessions:
        mine = [f for f in frames if f["session"] == log.index]
        if len(mine) < need:
            rel.append(NOTHING_COMPARED)
            continue
        b = np.mean([f["_pe"] for f in mine], axis=0)
        contrast = np.mean([f["_pref"].std(axis=(0, 1)) for f in mine])
        bias.append(float(np.sqrt((b ** 2).mean())))
        rel.append(bias[-1] / float(contrast))
    numbers = {
        "session_bias_rel_max": max(rel, default=NOTHING_COMPARED),
        "frames_compared": float(len(frames)),
        "session_bias_rms_max": max(bias, default=NOTHING_COMPARED),
        "frame_pooled_rms_max": max((f["pooled_rms"] for f in frames), default=NOTHING_COMPARED),
        "frame_diff_mean_max": max((f["mean"] for f in frames), default=NOTHING_COMPARED),
        "frame_diff_max": max((f["max"] for f in frames), default=NOTHING_COMPARED),
    }
    return {"frames": frames, "numbers": numbers}


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; every limit is a ceiling."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = {"value": value, "limit": limit}
        ok = bool(ok and np.isfinite(value) and value <= limit)
    return ok, compared
