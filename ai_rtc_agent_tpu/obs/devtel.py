"""Device telemetry: compile watchdog + AOT/transfer accounting.

The host-side planes (span timelines, the SLO budgets) watch what *this
process* does to a frame; on a jax stack the dominant latency cliffs
live one layer down — an XLA compile at serve time is a multi-second
freeze that used to surface only as an unexplained SLO burn.  This
module makes the device side first-class, with the SLO plane's
always-on/zero-cost-off discipline (``DEVTEL_ENABLE=0`` removes it —
the jax monitoring listener is never registered and every ``note_*``
hot-path hook is one module-global read + None test):

* **Compile watchdog** — every XLA compile is recorded via
  ``jax.monitoring``'s ``backend_compile_duration`` event with its
  *phase* (``warmup`` while the process builds/prewars engines,
  ``serving`` once the agent finishes startup), duration, and the
  engine/AOT key or bucket ``(k, variant)`` it belongs to — sharded
  scheduler geometries carry the mesh shape,
  ``sbucket-<k>:<variant>:dp<N>``, so a serve-time reshard retrace
  alerts with the right key (a thread-local :func:`compile_scope` set by
  the compile sites: the AOT cache build path, the scheduler's bucket
  steps, the engine step).  A
  compile in the serving phase that no :func:`expected_scope` blessed
  (host-side state builds do tiny eager-op compiles; operator actions
  like a prompt-encode are costs, not bugs) and that runs at least
  ``DEVTEL_RETRACE_MIN_MS`` is a **serve-time retrace breach** — the
  "join/leave never retraces" guarantee (PR 7/9) watched in production,
  not just in tests.  Breaches ride the existing alert path (the agent
  wires :attr:`DevTelPlane.on_breach` to the flight-recorder event log,
  the StreamDegraded webhook with ``state="RETRACE_BREACH"``, and the
  ``retrace_breaches_total`` counter at ``/metrics``, incl. the
  Prometheus exposition).
* **AOT accounting** — hit/miss/build counters, build seconds and the
  on-disk inventory (``aot_cache_entries``/``aot_cache_bytes``) emitted
  by aot/cache.py at each (rare) cache touch, so scrapes never scan
  disk.
* **Persistent compile cache** — hits and misses of XLA's on-disk
  executable cache (``compile_cache_hits_total`` /
  ``compile_cache_misses_total``, from ``jax.monitoring``'s
  ``/jax/compilation_cache/*`` events): a warm boot shows hits, a boot
  that compiled everything shows misses.
* **Transfer accounting** — H2D bytes/count from the single
  :func:`~..stream.engine.stage_frame` staging path, D2H bytes/count
  from the blessed readback sites (the scheduler's per-row resolve, the
  engine's fetch) — "fetch isolation" and "staged H2D" as
  dashboards instead of banked bench numbers.  The static checker
  (analysis/device_transfers.py) holds that these blessed paths stay
  the ONLY transfer sites, so the accounting cannot silently go blind.
* **Device memory** — ``memory_stats()`` (where the backend exposes it;
  CPU returns nothing) and the live-buffer count, sampled on the
  overload ladder tick (``DEVTEL_MEM_INTERVAL_S`` rate limit; the
  /metrics scrape itself only reads the cached sample).

Knobs (docs/environment.md "Device telemetry"): ``DEVTEL_ENABLE``,
``DEVTEL_RETRACE_MIN_MS``, ``DEVTEL_MEM_INTERVAL_S``,
``DEVTEL_COMPILE_LOG``.
"""

from __future__ import annotations

import collections
import logging
import threading
import time

from ..utils import env
from .trace import safe_list

logger = logging.getLogger(__name__)

PHASE_WARMUP = "warmup"
PHASE_SERVING = "serving"

# the jax.monitoring event one XLA compile fires exactly once — also when
# the persistent cache serves the executable, then with the retrieval time
# (lowering/tracing durations ride separate events we deliberately ignore:
# backend compile time IS the serve-time freeze)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent compile cache: one event per executable found / written
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class DevTelPlane:
    """Process-wide device telemetry state.  One instance per process,
    activated via :func:`activate` (the module-level dispatcher pattern:
    jax.monitoring listeners cannot be unregistered, so ONE forwarding
    listener is installed once and routes to whatever plane is active —
    tests swap planes freely)."""

    def __init__(self, stats=None, on_breach=None):
        self.enabled = env.devtel_enabled()
        self.stats = stats  # FrameStats: breaches land as retrace_breaches_total
        self.on_breach = on_breach  # callable(info dict)
        # a real step retrace is a multi-second freeze; stray eager-op
        # compiles (a first-use jnp.concatenate shape, an index-array
        # constant) run tens of ms even on a throttled box — the
        # threshold keeps them recorded-but-quiet
        self.retrace_min_ms = max(
            0.0, env.get_float("DEVTEL_RETRACE_MIN_MS", 250.0)
        )
        self.mem_interval_s = max(
            0.5, env.get_float("DEVTEL_MEM_INTERVAL_S", 5.0)
        )
        # one logical retrace fires several backend_compile events (XLA
        # compiles helper computations too): the counters record every
        # one, the alert fan-out (webhook + black-box events) coalesces
        # to at most one volley per window
        self.breach_coalesce_s = max(
            0.0, env.get_float("DEVTEL_BREACH_COALESCE_S", 5.0)
        )
        self._breach_fired_at = None
        self.phase = PHASE_WARMUP
        self.watchdog = "inactive"  # set by activate()
        # compile log: bounded ring of the most recent compile records
        # (the /health rendering; counters below are the /metrics one)
        self.compiles: collections.deque = collections.deque(
            maxlen=max(1, env.get_int("DEVTEL_COMPILE_LOG", 64))
        )
        self.compiles_total = 0
        self.compile_seconds_total = 0.0
        self.warmup_compiles = 0
        self.serving_compiles = 0
        self.retrace_breaches = 0
        self.last_breach = None
        # AOT accounting (fed by aot/cache.py)
        self.aot_hits = 0
        self.aot_misses = 0
        self.aot_builds = 0
        self.aot_build_seconds = 0.0
        self.aot_entries = 0
        self.aot_bytes = 0
        # XLA persistent compile cache (jax.monitoring events)
        self.compile_cache_hits = 0
        self.compile_cache_misses = 0
        # transfer accounting (fed by the blessed staging/readback paths)
        self.h2d_transfers = 0
        self.h2d_bytes = 0
        self.d2h_transfers = 0
        self.d2h_bytes = 0
        # device memory snapshot (sampled, rate-limited)
        self._mem: dict = {}
        self._mem_at = 0.0
        self._lock = threading.Lock()  # compile/aot paths (rare events)
        self._tlock = threading.Lock()  # transfer counters (per-frame)

    # -- phase machine ---------------------------------------------------------

    def serving(self):
        """Prewarm is done: from here on a compile is a retrace breach.
        The agent calls this at the end of on_startup — after the
        pipeline build, AOT adoption and bucket prewarm all ran."""
        self.phase = PHASE_SERVING

    def warmup(self):
        """Back to the grace phase (operator-triggered rebuild flows)."""
        self.phase = PHASE_WARMUP

    # -- compile watchdog ------------------------------------------------------

    def record_compile(self, duration_s: float, context=None,
                       expected: bool = False):
        """One XLA compile (listener dispatch).  Breach
        iff serving-phase, not blessed by an expected scope, and at
        least ``DEVTEL_RETRACE_MIN_MS`` (host-side state builds compile
        tiny eager ops; a sub-threshold compile is recorded but is not
        the multi-second freeze the watchdog pages on)."""
        ms = duration_s * 1e3
        entry = {
            "phase": self.phase,
            "duration_ms": round(ms, 3),
            "context": context or "unattributed",
            "expected": bool(expected),
        }
        with self._lock:
            self.compiles_total += 1
            self.compile_seconds_total += duration_s
            if entry["phase"] == PHASE_SERVING:
                self.serving_compiles += 1
            else:
                self.warmup_compiles += 1
            breach = (
                entry["phase"] == PHASE_SERVING
                and not expected
                and ms >= self.retrace_min_ms
            )
            fire = False
            if breach:
                self.retrace_breaches += 1
                self.last_breach = entry
                now = time.monotonic()
                fire = (
                    self._breach_fired_at is None
                    or now - self._breach_fired_at >= self.breach_coalesce_s
                )
                if fire:
                    self._breach_fired_at = now
            self.compiles.append(entry)
        if breach:
            if self.stats is not None:
                self.stats.count("retrace_breaches")
            cb = self.on_breach
            if cb is not None and fire:
                try:
                    cb(dict(entry))
                except Exception:  # observability must never break serving
                    logger.exception("devtel on_breach handler failed")

    def note_compile_cache(self, hit: bool):
        with self._lock:
            if hit:
                self.compile_cache_hits += 1
            else:
                self.compile_cache_misses += 1

    # -- AOT accounting (aot/cache.py) -----------------------------------------

    def note_aot(self, event: str, seconds: float = 0.0):
        with self._lock:
            if event == "hit":
                self.aot_hits += 1
            elif event == "miss":
                self.aot_misses += 1
            elif event == "build":
                self.aot_builds += 1
                self.aot_build_seconds += seconds

    def set_aot_inventory(self, entries: int, nbytes: int):
        with self._lock:  # a scrape must never see a torn entry/bytes pair
            self.aot_entries = int(entries)
            self.aot_bytes = int(nbytes)

    # -- transfer accounting ---------------------------------------------------

    def note_h2d(self, nbytes: int):
        with self._tlock:
            self.h2d_transfers += 1
            self.h2d_bytes += nbytes

    def note_d2h(self, nbytes: int):
        with self._tlock:
            self.d2h_transfers += 1
            self.d2h_bytes += nbytes

    # -- device memory ---------------------------------------------------------

    def sample_memory(self, force: bool = False):
        """Refresh the device-memory gauges (rate-limited; hooked on the
        overload ladder tick and consulted lazily by snapshot()).  Every
        probe is best-effort: a backend without the API simply omits the
        gauges — absent is how /metrics spells "not exposed here"."""
        if not self.enabled:
            return
        now = time.monotonic()
        if not force and now - self._mem_at < self.mem_interval_s:
            return
        self._mem_at = now
        mem: dict = {}
        try:
            import jax

            dev = jax.local_devices()[0]
            stats = None
            ms = getattr(dev, "memory_stats", None)
            if ms is not None:
                try:
                    stats = ms()
                except Exception:
                    stats = None
            if stats:
                for src, dst in (
                    ("bytes_in_use", "device_mem_bytes_in_use"),
                    ("peak_bytes_in_use", "device_mem_peak_bytes_in_use"),
                    ("bytes_limit", "device_mem_bytes_limit"),
                ):
                    if src in stats:
                        mem[dst] = int(stats[src])
            try:
                mem["device_live_buffers"] = len(jax.live_arrays())
            except Exception:
                pass
        except Exception:
            pass
        self._mem = mem

    # -- observability ---------------------------------------------------------

    def snapshot(self) -> dict:
        """/metrics gauges — flat int reads (the memory sample is the
        rate-limited cached one, never a fresh device probe per scrape).
        ``retrace_breaches_total`` itself rides the FrameStats counter
        (the SLO-plane pattern), so the name exists exactly once."""
        self.sample_memory()  # no-op within DEVTEL_MEM_INTERVAL_S
        out = {
            "devtel_enabled": int(self.enabled),
            "devtel_phase_serving": int(self.phase == PHASE_SERVING),
            "devtel_compiles_total": self.compiles_total,
            "devtel_compile_ms_total": round(
                1e3 * self.compile_seconds_total, 3
            ),
            "devtel_serving_compiles_total": self.serving_compiles,
            "aot_cache_hits_total": self.aot_hits,
            "aot_cache_misses_total": self.aot_misses,
            "aot_cache_builds_total": self.aot_builds,
            "aot_cache_entries": self.aot_entries,
            "aot_cache_bytes": self.aot_bytes,
            "compile_cache_hits_total": self.compile_cache_hits,
            "compile_cache_misses_total": self.compile_cache_misses,
            "devtel_h2d_transfers_total": self.h2d_transfers,
            "devtel_h2d_bytes_total": self.h2d_bytes,
            "devtel_d2h_transfers_total": self.d2h_transfers,
            "devtel_d2h_bytes_total": self.d2h_bytes,
        }
        out.update(self._mem)
        return out

    def session_view(self) -> dict:
        """The /health per-session rendering: a serve-time compile
        freezes EVERY live session, so each one carries the same breach
        state next to its own supervisor/SLO dicts."""
        return {
            "phase": self.phase,
            "retrace_breaches": self.retrace_breaches,
            "serving_compiles": self.serving_compiles,
            "last_breach": self.last_breach,
        }

    def health(self) -> dict:
        """The /health process-level dict: phase + the recent compile
        log (bounded ring, safe_list against the lock-free appender)."""
        return {
            "phase": self.phase,
            "watchdog": self.watchdog,
            "compiles_total": self.compiles_total,
            "retrace_breaches": self.retrace_breaches,
            "recent_compiles": safe_list(self.compiles)[-8:],
        }

    def fragment(self) -> dict:
        """The incident-bundle rendering (``/debug/flight?journey=``):
        the /health view plus the breach that fired, so a merged fleet
        bundle explains a frozen leg without a second pull — composed
        from health() so new watchdog fields can never drift out of
        the bundle."""
        return {**self.health(), "last_breach": self.last_breach}


# ---------------------------------------------------------------------------
# module-level dispatch: ONE forwarding jax.monitoring listener (listeners
# cannot be unregistered) routed to the active plane; the note_* hooks the
# hot paths call are one global read + None test when no plane is active
# ---------------------------------------------------------------------------

_ACTIVE: DevTelPlane | None = None
_LISTENER_INSTALLED = False
_CTX = threading.local()  # .label / .expected: the compile attribution


def _dispatch(event: str, duration_s: float, **_kw):
    if event != _COMPILE_EVENT:
        return
    plane = _ACTIVE
    if plane is None or not plane.enabled:
        return
    plane.record_compile(
        duration_s,
        context=getattr(_CTX, "label", None),
        expected=getattr(_CTX, "expected", False),
    )


def _dispatch_event(event: str, **_kw):
    if event != _CACHE_HIT_EVENT and event != _CACHE_MISS_EVENT:
        return
    plane = _ACTIVE
    if plane is not None and plane.enabled:
        plane.note_compile_cache(event == _CACHE_HIT_EVENT)


def activate(plane: DevTelPlane) -> DevTelPlane:
    """Make ``plane`` the process's telemetry sink and (once) register
    the monitoring listeners.  Disabled planes are still activated so
    their no-op hooks are the measured off-path."""
    global _ACTIVE, _LISTENER_INSTALLED
    _ACTIVE = plane
    if plane.enabled and not _LISTENER_INSTALLED:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_dispatch)
        monitoring.register_event_listener(_dispatch_event)
        _LISTENER_INSTALLED = True
    plane.watchdog = "jax-monitoring" if plane.enabled else "disabled"
    return plane


def deactivate(plane: DevTelPlane | None = None):
    """Detach (idempotent).  With a plane given, only deactivates if it
    is still the active one — a stale shutdown can't detach a newer
    plane (test apps overlap)."""
    global _ACTIVE
    if plane is None or _ACTIVE is plane:
        _ACTIVE = None


def active() -> DevTelPlane | None:
    return _ACTIVE


# -- hot-path hooks (one global read + None test when off) -------------------

def note_h2d(nbytes: int):
    plane = _ACTIVE
    if plane is not None and plane.enabled:
        plane.note_h2d(int(nbytes))


def note_d2h(nbytes: int):
    plane = _ACTIVE
    if plane is not None and plane.enabled:
        plane.note_d2h(int(nbytes))


def note_aot(event: str, seconds: float = 0.0, cache=None):
    """AOT cache touch (aot/cache.py).  ``cache``: the EngineCache, so
    the inventory gauges refresh at the (rare) touch instead of per
    scrape (entry bytes live there — cache.stats())."""
    plane = _ACTIVE
    if plane is None or not plane.enabled:
        return
    plane.note_aot(event, seconds=seconds)
    if cache is not None:
        try:
            entries, total = cache.stats()
        except Exception:
            pass
        else:
            plane.set_aot_inventory(entries, total)


# -- attribution scopes ------------------------------------------------------

class _NullScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullScope()


class _Scope:
    """Thread-local compile attribution.  Save/restore (not set/clear)
    so nested scopes compose — a scheduler state build (expected) inside
    a prewarm attribution keeps both truthful."""

    __slots__ = ("label", "expected", "_prev")

    def __init__(self, label, expected):
        self.label = label
        self.expected = expected

    def __enter__(self):
        self._prev = (
            getattr(_CTX, "label", None), getattr(_CTX, "expected", False)
        )
        _CTX.label = self.label
        _CTX.expected = self.expected
        return self

    def __exit__(self, *exc):
        _CTX.label, _CTX.expected = self._prev
        return False


def compile_scope(label: str, expected: bool = False):
    """Attribute any compile fired inside the body to ``label`` (an
    engine/AOT key or a bucket ``sbucket-<k>:<variant>`` — sharded
    geometries carry the mesh shape as ``sbucket-<k>:<variant>:dp<N>``).
    ``expected=True`` additionally blesses the body's compiles (recorded
    + attributed, never a breach): the prewarm sites, which are
    legitimate even at serve time when an operator reshapes the mesh and
    re-prewarms — a LAZY compile at dispatch keeps expected=False, so a
    serve-time reshard retrace still alerts with the right key."""
    plane = _ACTIVE
    if plane is None or not plane.enabled:
        return _NULL
    return _Scope(label, expected)


def expected_scope(label: str = "host-state-build"):
    """Bless the body's compiles: recorded + attributed, never a breach.
    For legitimate serving-phase host work (session state builds, an
    operator prompt-encode) whose tiny eager-op compiles are costs the
    operator chose, not retrace bugs."""
    plane = _ACTIVE
    if plane is None or not plane.enabled:
        return _NULL
    return _Scope(label, True)
