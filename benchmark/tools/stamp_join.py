"""The program's ready stamps against the profiler's trace of one window:

    python3 -m benchmark.tools.trace_report --workload <cell> --seed <n> \
        --seconds <s> --save-trace <file.gz>
    python3 -m benchmark.tools.stamp_join <file.gz>

Reads the trace ``trace_report`` saved and prints ONE JSON line:

``ready``      each ``rtc:step_ready`` span's end (the ready watcher's wake-up
               after step n) minus the end of step n's ``jit_bucket`` event
               (steps joined as ``trace_report`` joins them: the n-th
               ``rtc:launch`` span against the n-th program): median,
               quartiles, extremes, in ms.
``gaps``       between two joined programs, the device's idle time in the
               trace against what the host's spans say of it: ``host`` =
               the next launch's return less the previous ``rtc:step_ready``
               end where it returned later (what the program books as a
               gap, from the watcher's own wake-up), ``unbooked`` = the
               rest, the device starting a program it already held.  Sums,
               and the longest unbooked ones.
"""

from __future__ import annotations

import argparse
import gzip
import json
import statistics
import sys


def join_stamps(pd) -> dict:
    """-> ``ready`` and ``gaps`` (module docstring) from a trace."""
    from ..scope_reduce import STEP_MODULE, load
    from ..trace_reduce import leading_unjoined

    (lo, hi), hosts, chips = load(pd)
    launches = sorted(
        (s, e, ids.get("step")) for s, e, name, _, ids in hosts if name == "launch"
    )
    ready = {ids.get("step"): e for _, e, name, _, ids in hosts if name == "step_ready"}
    programs = sorted(
        (m.start_ns, m.start_ns + m.duration_ns)
        for _, mods in chips[:1] for m in mods if STEP_MODULE in m.name
    )
    skipped = leading_unjoined([l[0] for l in launches], [p[0] for p in programs])
    steps = [
        (launch, prog) for launch, prog in zip(launches, programs[skipped:])
        if lo <= prog[0] and prog[1] <= hi
    ]
    offsets = sorted(
        (ready[launch[2]] - prog[1]) / 1e6 for launch, prog in steps if launch[2] in ready
    )
    gaps, host_ms, unbooked_ms = [], 0.0, 0.0
    for (l0, p0), (l1, p1) in zip(steps, steps[1:]):
        if l1[2] != l0[2] + 1 or l0[2] not in ready:
            continue
        idle = max(0, p1[0] - p0[1]) / 1e6
        host = max(0, l1[1] - ready[l0[2]]) / 1e6
        host_ms += min(host, idle)
        unbooked_ms += idle - min(host, idle)
        gaps.append((round(idle - min(host, idle), 3), round(idle, 3), round(host, 3), l1[2]))
    q = statistics.quantiles(offsets, n=4) if len(offsets) > 1 else offsets * 3
    return {
        "ready": {
            "steps": len(offsets),
            "offset_ms_median": statistics.median(offsets) if offsets else None,
            "offset_ms_quartiles": [q[0], q[2]] if offsets else None,
            "offset_ms_min": offsets[0] if offsets else None,
            "offset_ms_max": offsets[-1] if offsets else None,
        },
        "gaps": {
            "pairs": len(gaps),
            "idle_ms_total": round(host_ms + unbooked_ms, 3),
            "host_ms_total": round(host_ms, 3),
            "unbooked_ms_total": round(unbooked_ms, 3),
            "unbooked_over_5ms": sum(1 for g in gaps if g[0] > 5.0),
            "longest_unbooked": [
                {"unbooked_ms": u, "idle_ms": i, "host_ms": h, "step": s}
                for u, i, h, s in sorted(gaps, reverse=True)[:5]
            ],
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.tools.stamp_join")
    ap.add_argument("trace", help="a gzipped trace, as trace_report --save-trace writes it")
    args = ap.parse_args(argv)
    from ..trace_reduce import from_bytes

    with gzip.open(args.trace, "rb") as f:
        pd = from_bytes(f.read())
    print(json.dumps(join_stamps(pd)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
