"""Observability subsystem: per-frame tracing + black-box flight recorder.

* obs/trace.py — :class:`FrameTrace` span timelines threaded through every
  hop of the media path (decode → … → send), zero-cost when off; and
  :func:`hop`, the one span helper that also writes each hop into JAX's
  profiler trace as ``rtc:<hop>``, beside the device's ops.
* obs/recorder.py — :class:`FlightRecorder`: bounded per-session rings of
  completed timelines + an always-on structured event log, snapshotted
  automatically on StreamDegraded/FAILED and on demand via
  ``GET /debug/flight``.
* obs/export.py — Chrome trace-event JSON (Perfetto) / JSONL renderings,
  plus the opt-in ``jax.profiler`` bridge.
* obs/slo.py — always-on per-stage latency budgets + burn-rate breaches.
* obs/devtel.py — device telemetry: the serve-time compile watchdog
  (retrace breaches on the alert path), AOT cache + H2D/D2H transfer
  accounting, device-memory snapshots.

Full tour: docs/observability.md.
"""

from .recorder import FlightRecorder, SessionRecorder  # noqa: F401
from .trace import (  # noqa: F401
    STAGES,
    TERMINALS,
    FrameTrace,
    SessionTracer,
    TraceController,
    get_trace,
    hop,
)
