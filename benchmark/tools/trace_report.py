"""One traced window of a cell, reduced by the program's own names:

    python3 -m benchmark.tools.trace_report --workload <cell> --seed <n> \
        --seconds <s> [--trace 0] [--depth 2] [--save-trace <file.gz>]

Runs the window through ``serve.run_window`` exactly as ``benchmark.run``
does with ``--trace 1`` (same set-up, same load, same traced span), then
prints ONE JSON line: the step by model part (``scope_reduce.by_scope``:
seconds, events, share, milliseconds a step), the longest idle gaps of the
device each put down to an ``rtc:`` span of the program or to ``no span``
(``scope_reduce.blame_gaps``), the rider-to-program join (the n-th
``rtc:launch`` span against the n-th ``jit_bucket`` event), and what the
program's counters moved by over the window and over the traced span
(``--trace 0``: no profiler, the window's counters alone).
No output check, no end-to-end metric: those are ``benchmark.run``'s.  The
builder's and the operator's tool until ``benchmark.run`` reads the scopes
itself (PERF.md section 7).
"""

from __future__ import annotations

import argparse
import gzip
import json
import logging
import sys
import time

T_PROCESS_START = time.monotonic()


def launch_join(pd) -> dict:
    """The join of host to device: the n-th ``rtc:launch`` span on the host
    against the n-th ``jit_bucket`` event of the chip's ``XLA Modules``
    line (one in-order stream), from the first program that starts after
    the trace's first launch span opened.  -> the pairs made, whether the
    ``step`` numbers their spans carry rise by one, and the median and
    largest delay from a launch span's start to its program's start (at
    depth 2 a program queues behind the one running: most of a step)."""
    from ..scope_reduce import STEP_MODULE, load
    from ..trace_reduce import leading_unjoined

    _, hosts, chips = load(pd)
    launches = sorted(
        (start, ids.get("step")) for start, _, name, _, ids in hosts
        if name == "launch"
    )
    programs = sorted(
        m.start_ns for _, mods in chips for m in mods if STEP_MODULE in m.name
    )
    # a program launched before the trace began has no span, and the host
    # tracer stops before the device's: skip leading programs until every
    # program starts after its launch span opened, pair from there
    skipped = leading_unjoined([l[0] for l in launches], programs)
    pairs = list(zip(launches, programs[skipped:]))
    delays = sorted(p - l[0] for l, p in pairs)
    steps = [l[1] for l, _ in pairs]
    return {
        "pairs": len(pairs),
        "leading_programs_skipped": skipped,
        "launches_unpaired": len(launches) - len(pairs),
        "steps_consecutive": (
            all(b == a + 1 for a, b in zip(steps, steps[1:]))
            if steps and None not in steps else None
        ),
        "delay_ms_median": delays[len(delays) // 2] / 1e6 if delays else None,
        "delay_ms_max": delays[-1] / 1e6 if delays else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.tools.trace_report")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: no profiler, the counters' window deltas only")
    ap.add_argument("--depth", type=int, default=2,
                    help="scope components of the second, finer table")
    ap.add_argument("--save-trace", default=None,
                    help="write the serialized trace here, gzipped")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(message)s",
    )

    from .. import program, run, scope_reduce, serve, trace_reduce
    from ..harness import ROOT, Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    cfg, traffic = bench.config(cell), bench.traffic(cell)
    ref_module = bench.reference(cfg)
    device = run.require_chips(cell["chips"])
    run.place_compile_cache(ROOT)

    # the one thing asked of the program beyond what a run asks: the
    # compiled text of its bucket executables, where an HLO instruction's
    # scope is written.  run_window builds the scheduler through this
    # module attribute and hands back only the window's result.
    texts: dict = {}
    build = program.build_scheduler

    def build_and_keep_text(*a, **kw):
        sched, stream_cfg = build(*a, **kw)
        texts.update(getattr(sched, "compiled_text", dict)())
        return sched, stream_cfg

    program.build_scheduler = build_and_keep_text
    try:
        result = serve.run_window(
            cfg, ref_module.weight_shapes, traffic, args.seed, args.seconds,
            bool(args.trace), T_PROCESS_START,
        )
    finally:
        program.build_scheduler = build
    out = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "compiles_in_window": result.compiles_in_window,
        "counters_window": result.counters_window(),
    }
    if not args.trace:
        print(json.dumps(out), flush=True)
        return 0
    if args.save_trace:
        with gzip.open(args.save_trace, "wb", compresslevel=3) as f:
            f.write(result.xspace)
    kernels = tuple(cfg["stream"]["mosaic_kernels"])
    pd = trace_reduce.from_bytes(result.xspace)
    reduced = trace_reduce.reduce_trace(pd, kernel_names=kernels)
    tables = {
        label: scope_reduce.op_names_from_hlo(text) for label, text in texts.items()
    }
    parts = scope_reduce.by_scope(pd, tables, kernels)
    steps = max(1, parts["steps"])
    out.update({
        "traced_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "executables_read": {k: len(v) for k, v in tables.items()},
        "by_part": dict(parts, parts=[
            [name, s, n, share, 1e3 * s / steps] for name, s, n, share in parts["parts"]
        ]),
        "by_depth": scope_reduce.by_scope(pd, tables, kernels, depth=args.depth)["parts"],
        "idle_gaps": scope_reduce.blame_gaps(pd),
        "launch_join": launch_join(pd),
        "counters_traced": serve.counters_delta(*result.counters_trace),
    })
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
