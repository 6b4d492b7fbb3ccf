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
    """An op's searchable text: its name plus any string stat (the trace
    keeps the JAX op name, which carries a Pallas kernel's ``name=``, in a
    stat, while the event name is the HLO instruction's)."""
    parts = [event.name]
    try:
        for _, v in event.stats:
            if isinstance(v, str):
                parts.append(v)
    except Exception:  # a stat the binding cannot decode: the name suffices
        pass
    return " ".join(parts)


def reduce_trace(pd, kernel_names=()) -> dict:
    """-> dict with, over the chips: ``window_s``, ``busy_s`` (mean over
    chips of the union of op intervals), ``device_ops`` (top 10 [name, s]),
    ``idle_gaps`` (top 10 [host span, s]), ``kernels`` {name: [seconds...]},
    ``modules`` {name: [seconds...]}, ``chips``."""
    host_spans, window = [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
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
        for e in mods:
            if e.start_ns < lo or e.start_ns + e.duration_ns > hi:
                continue  # a program cut by the window's edge is not a whole step
            modules.setdefault(e.name, []).append(e.duration_ns / 1e9)

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
    }
