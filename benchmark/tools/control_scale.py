"""The control that only a configuration with a side network needs:

    python3 -m benchmark.tools.control_scale --workload <cell> --seed <n> \
        --seconds <s> [--scale 0.0]

One window of the cell as ``benchmark.run`` runs it, but with the program's
conditioning scale written to ``--scale`` before the sessions are claimed
(``BatchScheduler.update_controlnet_scale``: the default of every later
claim; 0 switches the side network off by data, in the same executable),
checked against the reference at the scale the configuration file states.
It has to read NOT correct: if it reads correct, the comparison does not
see the side network.  Prints ONE JSON line (``correct``, ``compared``,
``readings``, the window's end-to-end numbers) and, like ``benchmark.run``,
the compared numbers beside their limits on standard error.  Not for the
driver; the other two controls (``--control w8`` / ``wrong_frame``) are
``benchmark.run``'s own.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time

T_PROCESS_START = time.monotonic()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.tools.control_scale")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scale", type=float, default=0.0)
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(message)s",
    )

    from .. import check, metrics, program, run, serve
    from ..harness import ROOT, Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    cfg, traffic = bench.config(cell), bench.traffic(cell)
    ref_module = bench.reference(cfg)
    device = run.require_chips(cell["chips"])
    run.place_compile_cache(ROOT)

    # run_window builds the scheduler through this module attribute
    build = program.build_scheduler

    def build_and_write_scale(*a, **kw):
        sched, stream_cfg = build(*a, **kw)
        sched.update_controlnet_scale(args.scale)
        return sched, stream_cfg

    program.build_scheduler = build_and_write_scale
    try:
        result = serve.run_window(
            cfg, ref_module.weight_shapes, traffic, args.seed, args.seconds,
            False, T_PROCESS_START,
        )
    finally:
        program.build_scheduler = build
    attempted, failed = metrics.attempted_failed(result)
    verdict = check.compare(cfg, check.reference_for(cfg, args.seed, ref_module), result)
    correct, compared = check.judge(verdict["numbers"], cfg["check"]["limits"])
    print(json.dumps({
        "control": {"program_conditioning_scale": args.scale,
                    "reference_conditioning_scale": cfg["conditioning_scale"]},
        "correct": correct, "attempted": attempted, "failed": failed,
        "device": device, "end_to_end_window": metrics.end_to_end(result),
        "readings": verdict["numbers"], "compared": compared,
    }), flush=True)
    for name, c in compared.items():
        print(f"compared {name} = {c['value']:.6g} (limit {c['limit']:g})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
