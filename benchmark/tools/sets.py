"""The sets of runs a bound is set from, a process a run:

    python3 -m benchmark.tools.sets --workload <cell> --seeds 1,2,3,4,5,6 \
        [--sets 2] [--seconds 20] [--trace 0] --out <file.jsonl>

Runs ``benchmark.run`` once per seed, ``--sets`` times over the same seeds
(this process never touches JAX, so each run has the chip to itself),
appends every run's result line to ``--out`` with the workload, the set and
the seed beside it, and prints per metric each set's median and spread: the
distance between the first and third quartile (``statistics.quantiles(n=4)``)
over the median.  The first run of the first set is the one that may
compile; it is in the set like any other and marked in the file."""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.tools.sets")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets: list = []
    for n in range(args.sets):
        rows = []
        for seed in seeds:
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "-m", "benchmark.run", "--workload", args.workload,
                 "--seed", str(seed), "--seconds", str(args.seconds),
                 "--trace", str(args.trace)],
                capture_output=True, text=True,
            )
            lines = [l for l in p.stdout.splitlines() if l.strip()]
            if p.returncode or not lines:
                print(f"{args.workload} set {n} seed {seed}: exit {p.returncode}\n"
                      + p.stderr[-3000:], file=sys.stderr, flush=True)
                return 1
            row = {"workload": args.workload, "set": n, "seed": seed,
                   "first": n == 0 and seed == seeds[0],
                   "wall_s": time.monotonic() - t0, **json.loads(lines[-1])}
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            rows.append(row)
            # all four end-to-end numbers, also one the cell is not judged on
            row["values"] = {**{k: v["value"] for k, v in row["metrics"].items()},
                             **row.get("end_to_end_window", {})}
            values = {k: round(v, 3) for k, v in row["values"].items()}
            print(f"{args.workload} set {n} seed {seed}: correct {row['correct']} "
                  f"failed {row['failed']} {values} "
                  f"{row['compared']} wall {row['wall_s']:.0f} s", flush=True)
        sets.append(rows)
    for name in sets[0][0]["values"]:
        for n, rows in enumerate(sets):
            values = [r["values"][name] for r in rows if name in r["values"]]
            if len(values) >= 2:
                print(f"{args.workload} {name} set {n}: median "
                      f"{statistics.median(values):.4f} spread {100 * spread(values):.2f} % "
                      f"({min(values):.4f}-{max(values):.4f}, {len(values)} runs)", flush=True)
    return 0 if all(r["correct"] and not r["failed"] for rows in sets for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
