"""From a profiler trace (a serialized ``XSpace``) to numbers.

Read with ``jax.profiler.ProfileData`` alone.  What a TPU trace of this
program looks like (checked by hand on a v5e trace, PERF.md section 5):
one plane per chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO op (fusions, custom calls) and its line
``XLA Modules`` one event per executed program; host threads are lines of
the plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear
under the name they were given.  All on one clock, in nanoseconds.

The traced window is the benchmark's own ``bench:trace_window`` annotation
(so it is the same interval the host counted frames in); device events are
clipped to it.
"""

from __future__ import annotations

import re

WINDOW_SPAN = "bench:trace_window"
SPAN_PREFIX = "bench:"
DISPATCH_SPAN = "rtc:dispatch"  # the program's own, one per step: stats k, riders, cause
STEP_MODULE = "bucket"          # the scheduler's step programs: jit_bucket(<fingerprint>)
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def from_bytes(xspace: bytes):
    """A serialized trace (``ProfilerSession.stop()``) -> ``ProfileData``."""
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(xspace)


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi) -> list:
    """The complement of the union inside [lo, hi]: (start, end) list."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(s, e) for s, e in out if e > s]


def _label(event) -> str:
    """An op's searchable text: its own names and no other op's.  The trace
    names a device event by its whole HLO instruction, operands included
    (``%fusion.3 = bf16[..] fusion(%flash_attention.7, ..)``), so of the
    name and of any string stat only the part before `` = `` is kept: the
    instruction's own name, or a JAX op name (which carries a Pallas
    kernel's ``name=``) whole.  An op that consumes a kernel's result is
    not the kernel."""
    parts = [event.name]
    try:
        for _, v in event.stats:
            if isinstance(v, str):
                parts.append(v)
    except Exception:  # a stat the binding cannot decode: the name suffices
        pass
    return " ".join(p.split(" = ", 1)[0] for p in parts)


def leading_unjoined(spans: list, programs: list) -> int:
    """Host spans against device programs, one in-order stream: the n-th
    span (a dispatch, a launch) started the n-th program.  ``spans`` and
    ``programs`` are start times in order.  A program started before the
    trace began has no span: -> how many leading programs to skip until
    every program starts after its span opened."""
    skipped = 0
    while skipped < len(programs) and any(
        p < s for s, p in zip(spans, programs[skipped:])
    ):
        skipped += 1
    return skipped


def join_riders(dispatches: list, programs: list) -> list:
    """Riders of each step program.  ``dispatches``: (start, riders) of the
    program's ``rtc:dispatch`` spans; ``programs``: the start of every step
    program on one chip; both in time order (``leading_unjoined``).
    -> riders per program, None for the skipped and for any past the last
    span."""
    skipped = leading_unjoined([d[0] for d in dispatches], programs)
    riders = [None] * skipped + [d[1] for d in dispatches]
    return (riders + [None] * len(programs))[: len(programs)]


def reduce_trace(pd, kernel_names=()) -> dict:
    """-> dict with, over the chips: ``window_s``, ``busy_s`` (mean over
    chips of the union of op intervals), ``device_ops`` (top 10 [name, s]),
    ``idle_gaps`` (top 10 [host span, s]), ``kernels`` {name: [seconds...]},
    ``modules`` {name: [seconds...]}, ``steps`` [[program, seconds, riders
    or None]...] (the step programs whole inside the window, in order, each
    with the riders of the dispatch that launched it), ``chips``."""
    host_spans, dispatches, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name == DISPATCH_SPAN:
                        riders = dict(ev.stats).get("riders")
                        if riders is not None:
                            dispatches.append((ev.start_ns, int(riders)))
                    elif ev.name.startswith(SPAN_PREFIX):
                        host_spans.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name[len(SPAN_PREFIX):])
                        )
    chips = [p for p in pd.planes if _DEVICE_PLANE.match(p.name)]
    if not chips:
        raise ValueError(
            "no /device:TPU:<n> plane in the trace: planes are "
            + ", ".join(p.name for p in pd.planes)
        )
    busy, op_time, all_gaps = [], {}, []
    kernels = {k: [] for k in kernel_names}
    named: dict = {}
    modules: dict = {}
    steps: list = []
    dispatches.sort()
    for plane in chips:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = list(line.events)
            elif line.name == MODULES_LINE:
                mods = list(line.events)
        if window is None:  # no annotation: the span of the device's own events
            starts = [e.start_ns for e in ops]
            window = (min(starts), max(e.start_ns + e.duration_ns for e in ops))
        lo, hi = window
        spans = []
        for e in ops:
            s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if t <= s:
                continue
            spans.append((s, t))
            # the trace names an op by its whole HLO instruction; the part
            # before " = " is the instruction's name
            short = e.name.split(" = ", 1)[0]
            op_time[short] = op_time.get(short, 0.0) + (t - s)
            if kernel_names:
                # an op's stats belong to its instruction: read them once
                # per name, not once per event (a second of trace holds a
                # quarter of a million events under five thousand names)
                hit = named.get(e.name)
                if hit is None:
                    text = _label(e)
                    hit = named[e.name] = [k for k in kernel_names if k in text]
                for k in hit:
                    kernels[k].append((t - s) / 1e9)
        busy.append(union_seconds(spans) / 1e9)
        all_gaps.extend(gaps(spans, lo, hi))
        programs = sorted((e for e in mods if STEP_MODULE in e.name), key=lambda e: e.start_ns)
        rode = dict(zip(
            (e.start_ns for e in programs),
            join_riders(dispatches, [e.start_ns for e in programs]),
        ))
        for e in mods:
            if e.start_ns < lo or e.start_ns + e.duration_ns > hi:
                continue  # a program cut by the window's edge is not a whole step
            modules.setdefault(e.name, []).append(e.duration_ns / 1e9)
            if STEP_MODULE in e.name:
                steps.append([e.name, e.duration_ns / 1e9, rode[e.start_ns]])

    def blame(gap):
        """The host span covering most of a device gap."""
        s, e = gap
        best, cover = "none", 0.0
        for hs, he, name in host_spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        return best

    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "chips": len(chips),
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "device_ops": [
            [n, t / 1e9 / len(chips)]
            for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [[blame(g), (g[1] - g[0]) / 1e9] for g in longest],
        "kernels": kernels,
        "modules": modules,
        "steps": steps,
    }
