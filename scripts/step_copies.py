#!/usr/bin/env python
"""What a benchmark cell's compiled bucket step holds besides its math: the
``copy`` instructions (layout changes XLA inserted), the activation-sized
float32 ones among them, the bytes XLA says it accesses, the Mosaic kernels,
and the ``flash_attention`` calls by operand layout.

On the chip, through the chip tool:

    python3 scripts/step_copies.py --workload sdxlturbo512.solo60 --seed 7

builds the cell's scheduler exactly as ``benchmark.tools.trace_report`` does
(it wraps that tool with ``--trace 0``: one untraced 20 s window, whose
counters are printed last), and before that prints one ``STEP_COPIES`` JSON
line per process: per bucket executable the number of HLO instructions, of
``copy`` instructions in all and under a ``self_attn`` / ``cross_attn``
scope (``BatchScheduler.compiled_steps()``), the count and bytes of
activation-sized (over 1e5 elements) float32 ``copy`` / ``copy_*_fusion``
outputs (``ops/pallas f32_relayout_copies``), ``cost_analysis()``'s ``bytes
accessed``, ``mosaic_kernels`` and ``attention_paths``.  PR 32 read 827 ->
267 copies for ``sdxlturbo512`` with it, PR 35 the float32 re-layouts
(PERF.md section 6).  Counts, not speeds.

The same counts with no chip: libtpu compiles for a described topology.
Under ``JAX_PLATFORMS=cpu`` (and ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` where
another process of the sandbox holds the library) take
``topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")``,
give every argument as a ``jax.ShapeDtypeStruct`` whose ``sharding`` is a
``SingleDeviceSharding`` of ``topo.devices[0]``, and read
``jax.jit(fn).lower(*shapes).compile()``'s ``as_text()`` with ``count_copies``
and its ``cost_analysis()``: ``tests/test_pallas_aot_v5e.py`` does so for one
``_resnet`` (a few seconds; the whole SD1.5 UNet at B=4 takes minutes).  A
function that asks ``jax.default_backend()`` (the Pallas kernels'
``interpret_default``) sees the CPU there: steer it in the script.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_COPY = re.compile(r"= \w+\[[\d,]*\]\S* copy\(")


def count_copies(hlo_text: str) -> dict:
    """{"instructions", "copy", "copy_in_attention_scope", "f32_relayout"} of
    one executable's text."""
    from ai_rtc_agent_tpu.ops.pallas import f32_relayout_copies

    copies = [line for line in hlo_text.splitlines() if _COPY.search(line)]
    scoped = sum("self_attn" in line or "cross_attn" in line for line in copies)
    return {
        "instructions": hlo_text.count(" = "),
        "copy": len(copies),
        "copy_in_attention_scope": scoped,
        "f32_relayout": f32_relayout_copies(hlo_text),
    }


def bytes_accessed(compiled) -> float | None:
    """``cost_analysis()``'s ``bytes accessed`` of a compiled executable (a
    Mosaic call counts as nothing there), or None where it gives none."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return (cost or {}).get("bytes accessed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from benchmark import program
    from benchmark.tools import trace_report

    build = program.build_scheduler

    def build_and_count(*a, **kw):
        sched, stream_cfg = build(*a, **kw)
        print("STEP_COPIES " + json.dumps({
            "workload": args.workload,
            "executables": {
                label: {
                    **count_copies(step.as_text()),
                    "bytes_accessed": bytes_accessed(step),
                }
                for label, step in sched.compiled_steps().items()
            },
            "mosaic_kernels": sched.mosaic_kernels,
            "attention_paths": sched.attention_paths,
        }), flush=True)
        return sched, stream_cfg

    program.build_scheduler = build_and_count
    try:
        return trace_report.main([
            "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "0",
        ])
    finally:
        program.build_scheduler = build


if __name__ == "__main__":
    sys.exit(main())
