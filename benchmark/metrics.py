"""The end-to-end metrics, from the window's frame records.  Taken by the
benchmark itself on the host's clock, over all the work and all the time of
the window: nothing is read from the program."""

from __future__ import annotations

import numpy as np


def window_frames(result) -> list:
    """Every stylized frame returned inside the window, all sessions."""
    return [
        r for log in result.sessions for r in log.records
        if r.stylized and result.t_open <= r.done < result.t_close
    ]


def attempted_failed(result) -> tuple:
    """Source frames picked up (and so submitted) inside the window, and
    those of them that did not come back stylized."""
    picked = [
        r for log in result.sessions for r in log.records
        if result.t_open <= r.handed < result.t_close
    ]
    return len(picked), sum(1 for r in picked if not r.stylized)


def end_to_end(result) -> dict:
    frames = window_frames(result)
    if not frames:
        raise RuntimeError("no stylized frame came back inside the window")
    lat_ms = np.array([r.done - r.due for r in frames]) * 1e3
    return {
        "stylized_fps": len(frames) / (result.t_close - result.t_open),
        "frame_latency_p50_ms": float(np.percentile(lat_ms, 50)),
        "frame_latency_p95_ms": float(np.percentile(lat_ms, 95)),
        "setup_s": result.setup_s,
    }
