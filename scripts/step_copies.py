#!/usr/bin/env python
"""What a benchmark cell's compiled bucket step holds besides its math: the
``copy`` instructions (layout changes XLA inserted), the Mosaic kernels, and
the ``flash_attention`` calls by operand layout.

On the chip, through the chip tool:

    python3 scripts/step_copies.py --workload sdxlturbo512.solo60 --seed 7

builds the cell's scheduler exactly as ``benchmark.tools.trace_report`` does
(it wraps that tool with ``--trace 0``: one untraced 20 s window, whose
counters are printed last), and before that prints one ``STEP_COPIES`` JSON
line per process: per bucket executable the number of HLO instructions, of
``copy`` instructions in all and under a ``self_attn`` / ``cross_attn``
scope (``BatchScheduler.compiled_text()``), ``mosaic_kernels`` and
``attention_paths``.  PR 32 read 827 -> 267 copies for ``sdxlturbo512`` with
it (PERF.md section 5).  Counts, not speeds.
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
    """{"instructions", "copy", "copy_in_attention_scope"} of one executable."""
    copies = [line for line in hlo_text.splitlines() if _COPY.search(line)]
    scoped = sum("self_attn" in line or "cross_attn" in line for line in copies)
    return {
        "instructions": hlo_text.count(" = "),
        "copy": len(copies),
        "copy_in_attention_scope": scoped,
    }


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
                label: count_copies(text)
                for label, text in sched.compiled_text().items()
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
