"""Render flight-recorder captures: Chrome trace-event JSON + JSONL.

``to_chrome_trace`` turns a snapshot (obs/recorder.py) into the Trace
Event Format that Perfetto and ``chrome://tracing`` load directly:

* one *process* per capture (named after the session — and, when the
  capture is bound to a fleet journey, stamped with the
  journey/agent/leg so merged multi-agent exports stay
  distinguishable; :func:`merge_chrome_traces` renders each source
  under its own pid),
* one *track* (tid) per pipeline stage (obs/trace.py ``STAGES``) — spans
  that overlap within a stage (pipelined serving keeps several frames in
  flight) are spilled onto ``<stage> #2``-style overflow lanes so every
  track stays well-formed (strictly nested / disjoint ``X`` events, which
  the export tests pin),
* instant events for frame terminal markers (``terminal:shed`` …) on a
  ``lifecycle`` track and for resilience/overload transitions from the
  event log on an ``events`` track.

``to_jsonl`` is the grep-friendly rendering: one JSON object per line
(header, then events, then frame timelines).

``start_jax_bridge``/``stop_jax_bridge`` are the opt-in hook that opens a
``jax.profiler`` trace over the same window as the host-side capture.
That trace holds the device's ops (under the model's named scopes) and
the program's own ``rtc:`` host spans (obs/trace.py ``hop``) on one clock;
the frame timeline rendered here is a second file on the host's
monotonic clock, lined up with it by wall time only.  jax is imported lazily and every failure degrades
to a reported string — observability must never take the media path down.
"""

from __future__ import annotations

import json

from .trace import STAGES

# tid layout: events/lifecycle low, then 16 reserved lanes per taxonomy
# stage; unknown stages and lane spill past 16 allocate unique tids from
# the region above _DYNAMIC_BASE (never shared — tracks must stay disjoint)
_EVENTS_TID = 1
_LIFECYCLE_TID = 2
_STAGE_BASE = {name: 16 * (i + 1) for i, name in enumerate(STAGES)}
_MAX_LANES = 15
_DYNAMIC_BASE = 16 * (len(STAGES) + 1)


def _lane_out(spans):
    """Greedy interval-lane assignment: spans (t0, t1, payload) sorted by
    t0 go to the first lane whose previous span already ended — tracks
    come out disjoint, which is what keeps the rendering honest."""
    lanes: list = []  # lane -> last end
    out = []
    for t0, t1, payload in sorted(spans, key=lambda s: (s[0], s[1])):
        for i, end in enumerate(lanes):
            if t0 >= end:
                lanes[i] = t1
                out.append((i, t0, t1, payload))
                break
        else:
            lanes.append(t1)
            out.append((len(lanes) - 1, t0, t1, payload))
    return out, len(lanes)


def to_chrome_trace(snapshot: dict, pid: int = 1,
                    meta: dict | None = None) -> dict:
    """Snapshot -> ``{"traceEvents": [...]}`` (Perfetto-loadable).

    ``pid``/``meta`` serve the multi-source merge
    (:func:`merge_chrome_traces`): each source renders under its own
    process id, and the journey/agent/leg metadata
    (``{"journey_id", "agent", "leg"}`` — defaulting to the snapshot's
    own ``journey`` binding) is stamped into the process-name metadata
    event and every span/instant's ``args`` so merged multi-agent
    exports stay distinguishable inside Perfetto."""
    session = snapshot.get("session", "?")
    if meta is None:
        meta = snapshot.get("journey") or None
    proc_name = f"session {session}"
    stamp: dict = {}
    if meta:
        stamp = {
            k: v for k, v in (
                ("journey_id", meta.get("journey_id")),
                ("agent", meta.get("agent")),
                ("leg", meta.get("leg")),
            ) if v not in (None, "")
        }
        label = " ".join(
            f"{k.replace('_id', '')} {v}" for k, v in stamp.items()
        )
        if label:
            proc_name = f"{label} session {session}"
    events: list = [
        {
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": proc_name, **stamp},
        },
        {
            "ph": "M", "name": "thread_name", "pid": pid, "tid": _EVENTS_TID,
            "args": {"name": "events"},
        },
        {
            "ph": "M", "name": "thread_name", "pid": pid,
            "tid": _LIFECYCLE_TID, "args": {"name": "lifecycle"},
        },
    ]

    frames = snapshot.get("frames", [])
    log = snapshot.get("events", [])
    # common time base: ts starts near 0 so the viewport opens on the data
    t_min = None
    for fr in frames:
        for _n, t0, _t1 in fr.get("spans", []):
            t_min = t0 if t_min is None else min(t_min, t0)
        for _n, t in fr.get("marks", []):
            t_min = t if t_min is None else min(t_min, t)
    for ev in log:
        t = ev.get("t")
        if t is not None:
            t_min = t if t_min is None else min(t_min, t)
    base = t_min or 0.0

    def us(t: float) -> float:
        return round(1e6 * (t - base), 1)

    # spans, one track per stage (+ overflow lanes for in-flight overlap)
    per_stage: dict = {}
    for fr in frames:
        fid = fr.get("frame_id")
        for name, t0, t1 in fr.get("spans", []):
            per_stage.setdefault(name, []).append((t0, t1, fid))
    # unknown stages + lane spill past the 16 reserved per-stage tids
    # draw UNIQUE tids from here — folding spill onto one shared tid
    # would render overlapping X events, exactly the malformed track the
    # export tests forbid
    dyn_next = [_DYNAMIC_BASE]

    def _alloc_dynamic() -> int:
        tid = dyn_next[0]
        dyn_next[0] += 1
        return tid

    for stage in sorted(per_stage):
        spans = per_stage[stage]
        tid_base = _STAGE_BASE.get(stage)
        laned, n_lanes = _lane_out(spans)
        lane_tid = {}
        for lane in range(n_lanes):
            if tid_base is not None and lane <= _MAX_LANES:
                lane_tid[lane] = tid_base + lane
            else:  # unknown stage, or in-flight overlap deeper than 16
                lane_tid[lane] = _alloc_dynamic()
            label = stage if lane == 0 else f"{stage} #{lane + 1}"
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid,
                "tid": lane_tid[lane], "args": {"name": label},
            })
        for lane, t0, t1, fid in laned:
            events.append({
                "ph": "X", "name": stage, "cat": "frame", "pid": pid,
                "tid": lane_tid[lane],
                "ts": us(t0), "dur": max(0.0, round(1e6 * (t1 - t0), 1)),
                "args": {"frame_id": fid, **stamp},
            })

    # frame marks (terminal markers, similarity skips, ingest sheds)
    for fr in frames:
        fid = fr.get("frame_id")
        for name, t in fr.get("marks", []):
            events.append({
                "ph": "i", "s": "t", "name": name, "cat": "lifecycle",
                "pid": pid, "tid": _LIFECYCLE_TID, "ts": us(t),
                "args": {"frame_id": fid, "terminal": fr.get("terminal"),
                         **stamp},
            })

    # event log (supervisor/overload/restart/webhook) as instants
    for ev in log:
        ev = dict(ev)
        t = ev.pop("t", base)
        kind = ev.pop("kind", "event")
        events.append({
            "ph": "i", "s": "p", "name": kind, "cat": "resilience",
            "pid": pid, "tid": _EVENTS_TID, "ts": us(t),
            "args": {**ev, **stamp},
        })

    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "session": session,
            "reason": snapshot.get("reason"),
            "snapshot_id": snapshot.get("id"),
            **stamp,
        },
    }


def merge_chrome_traces(sources, journey: str | None = None) -> dict:
    """Merge several flight-recorder captures — typically one per leg of
    a fleet journey, pulled from different agent processes — into ONE
    Perfetto-loadable document.

    ``sources``: iterable of ``(snapshot, meta)`` where ``meta`` is the
    ``{"journey_id", "agent", "leg"}`` stamp (falls back to the
    snapshot's own ``journey`` binding).  Each source renders under its
    own process id, so two agents' identically-named stage tracks can
    never collide; within a source the per-stage lane discipline of
    :func:`to_chrome_trace` holds unchanged.

    Time bases are per-source: every process's monotonic clock is
    normalized to start near 0 (cross-host clocks do not line up; the
    journey ring's wall-clock stamps in the JSON bundle give the
    absolute ordering)."""
    events: list = []
    rendered = []
    for i, (snapshot, meta) in enumerate(sources):
        doc = to_chrome_trace(snapshot, pid=i + 1, meta=meta)
        events.extend(doc["traceEvents"])
        rendered.append({
            "pid": i + 1,
            "session": snapshot.get("session"),
            "agent": (meta or {}).get("agent"),
            "leg": (meta or {}).get("leg"),
            "snapshot_id": snapshot.get("id"),
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "journey_id": journey,
            "sources": rendered,
        },
    }


def to_jsonl(snapshot: dict) -> str:
    """One JSON object per line: header, event-log entries, frame
    timelines — the grep/jq-friendly rendering of the same capture."""
    lines = [json.dumps({
        "record": "header",
        "session": snapshot.get("session"),
        "reason": snapshot.get("reason"),
        "id": snapshot.get("id"),
        "taken_at": snapshot.get("taken_at"),
    })]
    for ev in snapshot.get("events", []):
        lines.append(json.dumps({"record": "event", **ev}))
    for fr in snapshot.get("frames", []):
        lines.append(json.dumps({"record": "frame", **fr}))
    return "\n".join(lines) + "\n"


# -- jax.profiler bridge ------------------------------------------------------

_JAX_TRACE_ACTIVE = False


def start_jax_bridge(log_dir: str) -> str | None:
    """Open a ``jax.profiler`` trace into ``log_dir`` alongside the host
    capture window.  -> None on success, else a human-readable reason
    (missing jax, profiler already running, …) — never raises."""
    global _JAX_TRACE_ACTIVE
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is present in CI
        return f"jax unavailable: {e}"
    if _JAX_TRACE_ACTIVE:
        return "jax profiler trace already active"
    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:
        return f"jax profiler start failed: {e}"
    _JAX_TRACE_ACTIVE = True
    return None


def stop_jax_bridge() -> str | None:
    """Close the bridge opened by :func:`start_jax_bridge` (no-op when
    none is active).  -> None on success, else the reason."""
    global _JAX_TRACE_ACTIVE
    if not _JAX_TRACE_ACTIVE:
        return None
    _JAX_TRACE_ACTIVE = False
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as e:
        return f"jax profiler stop failed: {e}"
    return None
