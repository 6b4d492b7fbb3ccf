"""The readings a limit of the output check is set from, many seeds in one
process (set-up is most of a run, and every process pays ~15 s to reach the
chip):

    python3 -m benchmark.tools.readings --workload <cell> \
        --seeds 101,102,... --control-seeds 201,202,203 --seconds 4 --out <file>

For each seed a short window of the cell at its own load and size with the
program as the configuration states it, compared as a run compares it
(``check.compare`` and ``check.judge`` with the configuration's limits, the
committed sample sizes); for each control seed the same with the program's
int8-weight path switched on.  ``--frame-shifts 1,7`` compares each sound
window again with the reference fed source frame ``k + shift`` for ``k``:
how far the comparison sees the input path.  One JSON line per comparison
with every number ``check.compare`` reads, and ``correct``.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--frame-shifts", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    from .. import check, metrics, run, serve
    from ..harness import ROOT, Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    cfg, traffic = bench.config(cell), bench.traffic(cell)
    ref_module = bench.reference(cfg)
    limits = cfg["check"]["limits"]
    run.require_chips(cell["chips"])
    run.place_compile_cache(ROOT)
    shifts = [int(s) for s in args.frame_shifts.split(",") if s]
    plan = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), "w8") for s in args.control_seeds.split(",") if s
    ]
    with open(args.out, "a") as out:
        for seed, quant in plan:
            t0 = time.monotonic()
            result = serve.run_window(
                cfg, ref_module.weight_shapes, traffic, seed, args.seconds, False,
                time.monotonic(), quant=quant,
            )
            run_s = time.monotonic() - t0
            fps = metrics.end_to_end(result)["stylized_fps"]
            ref = check.reference_for(cfg, seed, ref_module)
            for shift in [0] + ([] if quant else shifts):
                t1 = time.monotonic()
                numbers = check.compare(cfg, ref, result, shift)["numbers"]
                row = {
                    "workload": args.workload, "seed": seed, "control": quant,
                    "frame_shift": shift, "correct": check.judge(numbers, limits)[0],
                    "fps": fps, "compiles_in_window": result.compiles_in_window,
                    "run_s": run_s, "check_s": time.monotonic() - t1, **numbers,
                }
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()
            del result, ref
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
