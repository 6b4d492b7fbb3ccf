"""Shared PERF_LOG.jsonl banking + the paired-ratio estimator for the
bench scripts.

Four bench scripts (host_plane, trace_overhead, batch_scheduler,
device_path) grew byte-identical ``_bank`` helpers; any change to the
banking contract had to be replicated in each.  This is the one
implementation they all import.  :func:`paired` is the same story for
the throttle-jitter measurement discipline (batch_scheduler,
device_path and mesh_sched each carried a copy).

Semantics:
* ``PERF_LOG_PATH`` unset -> append to the repo's ``PERF_LOG.jsonl``;
* ``PERF_LOG_PATH`` set EMPTY (or to os.devnull) -> banking DISABLED;
* an OSError never raises: the contract line must still print, the
  failure is recorded on the entry as ``bank_error``.
"""

from __future__ import annotations

import json
import os

from . import env

#: repo root (this file lives at <repo>/ai_rtc_agent_tpu/utils/)
_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def bank(entry: dict, repo_root: str | None = None) -> None:
    """Append one contract line to the banked trajectory (see module
    docstring for the PERF_LOG_PATH semantics)."""
    default = os.path.join(repo_root or _REPO, "PERF_LOG.jsonl")
    path = env.perf_log_path(default)
    if not path or path == os.devnull:
        return
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError as e:
        entry["bank_error"] = str(e)


def paired(leg_a, leg_b, reps: int):
    """Alternating paired reps: run both legs adjacently ``reps`` times,
    swapping order each pair, and take the MEDIAN of per-pair a/b ratios.
    Absolute times are meaningless on a box whose throughput swings up to
    5x in sub-second throttle bursts — but two short legs measured
    back-to-back see the same box state, so the median paired ratio
    converges (the batch_scheduler_bench estimator discipline, now the
    one implementation every bench script imports).
    -> (min_a, min_b, median a/b)."""
    a_vals, b_vals, ratios = [], [], []
    for i in range(reps):
        if i % 2 == 0:
            a, b = leg_a(), leg_b()
        else:
            b, a = leg_b(), leg_a()
        a_vals.append(a)
        b_vals.append(b)
        ratios.append(a / b if b > 0 else 0.0)
    ratios.sort()
    return min(a_vals), min(b_vals), ratios[len(ratios) // 2]
