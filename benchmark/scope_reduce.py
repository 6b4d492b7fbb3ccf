"""From a profiler trace to the step by model part, and the idle gaps by the
program's own host spans.

The program names its work in the one trace the profiler writes: every
model part of the stream step runs under a ``jax.named_scope``
(``unet/down_1/resnet_0`` ...), and every host hop is a
``jax.profiler.TraceAnnotation`` named ``rtc:<hop>`` on the device's clock
(``ai_rtc_agent_tpu/obs/trace.py hop``).  Two reductions, read with
``jax.profiler.ProfileData`` alone like ``trace_reduce``:

``by_scope``   device seconds, event count and share of the step by model
               part, over the whole ``jit_bucket`` programs inside
               ``bench:trace_window``.
``blame_gaps`` each of the longest idle gaps of the device put down to the
               innermost ``rtc:`` span, on any host thread, that covers
               most of it, or to ``no span``.

What a TPU trace holds (checked by hand on a v5e trace, PERF.md section 5):
an event of the line ``XLA Ops`` is named by its whole HLO instruction
(``%fusion.12 = bf16[...] fusion(...), calls=...``) and carries no JAX op
name; the scope lives in the compiled module's text, as the instruction's
``metadata={op_name="jit(bucket)/vmap(unet)/down_1/resnet_0/..."}``.  So the
reduction takes a table {instruction name: op name} made from that text
(``op_names_from_hlo``; the program hands the text over,
``BatchScheduler.compiled_text()``).  A fusion belongs to the scope its
instruction's op name carries (XLA gives a fusion one op name, its root's
as a rule); an instruction with no metadata of its own takes the op name
most of the instructions of the computation it calls carry; what is left
(layout copies, parameter prefetches) is ``unscoped``, printed, never
dropped.
"""

from __future__ import annotations

import re
from collections import Counter

from .trace_reduce import (
    MODULES_LINE, OPS_LINE, STEP_MODULE, WINDOW_SPAN, _DEVICE_PLANE, gaps,
)

HOP_PREFIX = "rtc:"
# spans in which a thread is parked on the device or on the window: such a
# thread cannot have fed the chip, so a gap is put down to one of them only
# by name (``waiting``), never as its blame
WAIT_HOPS = ("fetch", "await_row", "window_wait")

_TRANSFORM = re.compile(r"^[a-z_]+\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[^\s,}]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) ")

# model parts a path under ``unet`` rolls up to, innermost first
_UNET_PARTS = ("self_attn", "cross_attn", "ff", "proj")
_UNET_SINGLE = ("time_embed", "conv_in", "conv_out", "downsample", "upsample")
_TOP = (
    "preprocess", "vae_encode", "add_noise", "annotate", "controlnet",
    "epilogue", "vae_decode", "postprocess",
)


def op_names_from_hlo(text: str) -> dict:
    """{HLO instruction name (``%fusion.12``): JAX op name} over every
    computation of a compiled module's text (``compiled.as_text()``)."""
    own: dict = {}       # instruction -> its own op_name
    calls: dict = {}     # instruction -> computation it calls
    inside: dict = {}    # computation -> Counter of op names of its body
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.endswith("{"):
                c = _COMPUTATION.match(line)
                comp = c.group(1) if c else None
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if comp is not None:
                inside.setdefault(comp, Counter())[op.group(1)] += 1
        else:
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    for name, called in calls.items():
        body = inside.get(called)
        if body:
            own[name] = body.most_common(1)[0][0]
    return own


def scope_path(op_name: str) -> tuple:
    """``jit(bucket)/vmap(unet)/down_1/resnet_0/jit(silu)/mul`` ->
    ``("unet", "down_1", "resnet_0", "silu")``: the transformations are
    unwrapped, the program's own name and the primitive are dropped."""
    parts = []
    for c in op_name.split("/"):
        while True:
            m = _TRANSFORM.match(c)
            if m is None:
                break
            c = m.group(1)
        if c:
            parts.append(c)
    return tuple(parts[1:-1])


def model_part(path: tuple, kernels=()) -> str:
    """The model part a scope path rolls up to."""
    for k in kernels:
        if k in path:
            return k  # the Mosaic kernel itself, apart from its wrapper
    if not path:
        return "unscoped"
    head = path[0]
    if head in ("gather", "scatter"):
        return "gather/scatter"
    if head == "unet":
        for part in _UNET_PARTS:
            if part in path:
                return part
        for c in path[1:]:
            if c.startswith("resnet_"):
                return "resnet"
            if c in _UNET_SINGLE:
                return c
        return "unet (other)"
    return head if head in _TOP else "unscoped"


def load(pd):
    """-> (window, hosts, chips): the traced window as (start, end) in ns
    (``bench:trace_window``; without it the span of the device's own
    events), the program's host spans as (start, end, hop, thread, ids),
    and per chip its (``XLA Ops`` events, ``XLA Modules`` events)."""
    window, hosts = None, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOP_PREFIX):
                        hosts.append((
                            ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name[len(HOP_PREFIX):], line.name, dict(ev.stats),
                        ))
    planes = [p for p in pd.planes if _DEVICE_PLANE.match(p.name)]
    if not planes:
        raise ValueError(
            "no /device:TPU:<n> plane in the trace: planes are "
            + ", ".join(p.name for p in pd.planes)
        )
    chips = []
    for plane in planes:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = list(line.events)
            elif line.name == MODULES_LINE:
                mods = list(line.events)
        chips.append((ops, mods))
    if window is None:
        every = [e for ops, _ in chips for e in ops]
        window = (
            min(e.start_ns for e in every),
            max(e.start_ns + e.duration_ns for e in every),
        )
    return window, hosts, chips


def pick_table(tables: dict, seen: set) -> dict:
    """Of the op-name tables of several executables ({label: table}), the
    one that knows most of the instruction names ``seen`` in one program's
    events: ``%fusion.12`` means another op in every executable."""
    best, hits = {}, -1
    for table in tables.values():
        n = sum(1 for name in seen if name in table)
        if n > hits:
            best, hits = table, n
    return best


def by_scope(pd, tables: dict, kernels=(), depth: int | None = None) -> dict:
    """Device time of the step by scope, over the ``jit_bucket`` programs
    that lie whole inside the traced window.  ``tables``: {label: {HLO
    instruction: op name}}, one per bucket executable.  ``depth`` None
    rolls every path up to its model part; a number keeps that many
    leading components instead (2: ``unet/down_1``).  -> ``steps``,
    ``module_s`` (sum of the programs' own durations), ``ops_s`` (sum of
    their ops'), ``parts`` [[name, seconds, events, share of ops_s]]
    longest first, ``unscoped_share``, ``other_programs_s``."""
    (lo, hi), _, chips = load(pd)
    part_s, part_n = Counter(), Counter()
    steps, module_s, other_s = 0, 0.0, 0.0
    for ops, mods in chips:
        whole = sorted(
            (m.start_ns, m.start_ns + m.duration_ns, m.name) for m in mods
            if m.start_ns >= lo and m.start_ns + m.duration_ns <= hi
        )
        by_module: dict = {}
        i = 0
        for e in sorted(ops, key=lambda e: e.start_ns):
            while i < len(whole) and whole[i][1] <= e.start_ns:
                i += 1
            if i < len(whole) and whole[i][0] <= e.start_ns:
                if STEP_MODULE in whole[i][2]:
                    by_module.setdefault(whole[i][2], []).append(e)
                else:
                    other_s += e.duration_ns
        for s, t, name in whole:
            if STEP_MODULE in name:
                steps += 1
                module_s += t - s
        for events in by_module.values():
            short = [e.name.split(" = ", 1)[0] for e in events]
            table = pick_table(tables, set(short))
            cache: dict = {}
            for e, name in zip(events, short):
                part = cache.get(name)
                if part is None:
                    path = scope_path(table.get(name, ""))
                    if depth is None:
                        part = model_part(path, kernels)
                    else:
                        part = "/".join(path[:depth]) or "unscoped"
                    cache[name] = part
                part_s[part] += e.duration_ns
                part_n[part] += 1
    ops_s = sum(part_s.values())
    return {
        "steps": steps,
        "module_s": module_s / 1e9,
        "ops_s": ops_s / 1e9,
        "parts": [
            [name, t / 1e9, part_n[name], t / ops_s if ops_s else 0.0]
            for name, t in part_s.most_common()
        ],
        "unscoped_share": part_s["unscoped"] / ops_s if ops_s else 1.0,
        "other_programs_s": other_s / 1e9,
    }


def blame_gaps(pd, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the device inside the traced
    window, each put down to the innermost ``rtc:`` span, on any host
    thread, that covers most of it (over half), or to ``no span``.  A span
    in which its thread only waits (WAIT_HOPS) is never the blame: it is
    listed under ``waiting``, so a gap that only waits cover reads ``no
    span`` and names who waited.  -> [{gap_s, blame, cover, thread, ids,
    waiting}] longest first."""
    (lo, hi), hosts, chips = load(pd)
    all_gaps = []
    for ops, _ in chips:
        spans = [
            (max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
            for e in ops
        ]
        all_gaps.extend(gaps([(s, t) for s, t in spans if t > s], lo, hi))
    out = []
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        best, waiting = None, set()
        for hs, he, name, thread, ids in hosts:
            cover = (min(e, he) - max(s, hs)) / (e - s)
            if cover <= 0.5:
                continue
            if name in WAIT_HOPS:
                waiting.add(name)
                continue
            # most cover first; of spans that cover alike, the innermost
            key = (round(cover, 3), -(he - hs))
            if best is None or key > best[0]:
                best = (key, name, cover, thread, ids)
        out.append({
            "gap_s": (e - s) / 1e9,
            "blame": best[1] if best else "no span",
            "cover": round(best[2], 3) if best else 0.0,
            "thread": best[3] if best else None,
            "ids": best[4] if best else {},
            "waiting": sorted(waiting),
        })
    return out
