"""The serving agent: HTTP signaling + WebRTC lifecycle + control plane.

Endpoint-for-endpoint parity with reference agent.py:

  POST/DELETE /whip    publish a stream (OBS/browser)     agent.py:285-395
  POST/DELETE /whep    subscribe to the processed stream  agent.py:211-282
  POST /offer          bidirectional browser session      agent.py:123-208
  POST /config         runtime prompt / t_index update    agent.py:398-412
  GET  /               health                             agent.py:415-416
  GET  /metrics        fps/latency gauges                 (new — SURVEY sec.5
                                                          says the rebuild
                                                          must add these)

Also carried over behavior-for-behavior: UDP port pinning via the event-loop
datagram patch (agent.py:32-69), H264 codec forcing on send+receive
(agent.py:72-77, 149-152), Twilio TURN on /offer only with the documented
rationale for avoiding TURN on /whip (agent.py:299-314), the OBS
full-gather-before-answer workaround (agent.py:256-263), webhooks on
connect/close (agent.py:185-196), CORS-allow-all, and graceful shutdown
closing all pcs (agent.py:433-437).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import random
import time
import types
import uuid
from typing import List, Tuple

from aiohttp import web

from ..obs.recorder import FlightRecorder
from ..resilience.engine_guard import EngineGuard
from ..resilience.overload import OverloadControlPlane, QueueProbe, ShedFrame
from ..resilience.supervisor import (
    ResilientPipeline,
    SessionSupervisor,
    worst_state,
)
from ..utils import env
from ..utils.dispatch import spawn
from ..utils.profiling import FrameStats
from . import turn, wire
from .events import StreamEventHandler
from .signaling import get_provider
from .tracks import VideoStreamTrack

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# session resilience (resilience/supervisor.py): every media session gets a
# health state machine + passthrough degradation; SUPERVISOR=0 disables
# ---------------------------------------------------------------------------

def _journey_of(app, session_key: str) -> dict | None:
    """The session's fleet-journey binding ({"journey_id","leg","agent"})
    or None on single-process deployments."""
    return app.get("journey_map", {}).get(session_key)


def _parse_journey(app, request) -> dict | None:
    """The router's ``X-Journey-Id``/``X-Journey-Leg`` headers as a
    journey binding dict — None (and zero residue) without the headers
    or with ``JOURNEY_ENABLE=0``."""
    if not app.get("journey_enabled", True):
        return None
    journey_id = request.headers.get(wire.JOURNEY_ID)
    if not journey_id:
        return None
    try:
        leg = max(1, int(request.headers.get(wire.JOURNEY_LEG, "1")))
    except ValueError:
        leg = 1
    return {
        "journey_id": journey_id,
        "leg": leg,
        "agent": env.get_str("WORKER_ID") or "",
    }


def _bind_journey(app, request, session_key: str) -> dict | None:
    """Thread the journey headers into this session: the journey map
    (webhooks, /health context) and the flight recorder + tracer (every
    snapshot and sealed timeline), so the fleet's incident bundle can
    join this process's records to the other legs'.  WHEP viewers echo
    the header without binding — they own no recorder to thread."""
    meta = _parse_journey(app, request)
    if meta is None:
        return None
    app.setdefault("journey_map", {})[session_key] = meta
    flight = app.get("flight")
    if flight is not None:
        # register is idempotent get-or-create — binding here means the
        # recorder is born journeyed even before supervision wraps it
        flight.register(session_key).set_journey(**meta)
    return meta


def _journey_headers(meta: dict | None) -> dict:
    """Response-header echo: the client learns its journey id from the
    signaling answer (and the router confirms the agent threaded it)."""
    if not meta:
        return {}
    return {
        wire.JOURNEY_ID: meta["journey_id"],
        wire.JOURNEY_LEG: str(meta["leg"]),
    }


def _supervise_session(app, pc, pipeline, session_key: str, room_id: str = ""):
    """Wrap a session pipeline in the resilience layer and register its
    supervisor for /health.  Returns the pipeline unchanged when
    supervision is disabled.  Must run on the event loop (starts the
    output-age watchdog there)."""
    if not env.get_bool("SUPERVISOR", True):
        return pipeline
    stats: FrameStats = app["stats"]
    handler: StreamEventHandler = app["stream_event_handler"]
    loop = asyncio.get_event_loop()
    flight: FlightRecorder | None = app.get("flight")
    rec = flight.register(session_key) if flight is not None else None

    def resync():
        # PLI-driven keyframe re-sync on recovery: force OUR encoder to
        # IDR (viewers decode the first post-recovery frame) and ask the
        # publisher for a fresh keyframe (our decoder re-syncs too)
        force = getattr(pc, "_force_sink_keyframe", None)
        if force is not None:
            force()
        proto = getattr(pc, "_recv_protocol", None)
        if proto is not None:
            proto._send_pli()

    def on_transition(old, new, reason):
        # tpurtc: allow[metrics-registry] -- closed enum: new is one of the 4 supervisor states, keys supervisor_{healthy,degraded,recovering,failed}_total
        stats.count(f"supervisor_{new.lower()}")
        snap_id = None
        recent = None
        if rec is not None:
            rec.event("supervisor", old=old, new=new, reason=reason)
            if new in (
                "DEGRADED", "FAILED"
            ) and flight is not None:
                # black-box moment: freeze the event log + frame timelines
                # NOW, before recovery churn overwrites the rings — the
                # snapshot id rides the StreamDegraded webhook so external
                # orchestrators can pull GET /debug/flight?id= later
                snap_id = flight.take_snapshot(
                    session_key, reason=f"{new}: {reason}"
                )
            recent = rec.recent_events()

        def fire():
            handler.handle_session_state(
                session_key, room_id, new, reason,
                flight_snapshot_id=snap_id, recent_events=recent,
                journey=_journey_of(app, session_key),
            )

        try:  # may fire from a worker thread — webhooks belong on the loop
            loop.call_soon_threadsafe(fire)
        except RuntimeError:
            pass  # loop already closed (teardown race)

    sup = SessionSupervisor(
        session_key, resync=resync, on_transition=on_transition
    )
    # the recycle handoff's AGENT_RECYCLED re-announce needs each
    # session's room — the supervisor context is the one per-session
    # home every serving path already fills
    sup.context["room_id"] = room_id
    jmeta = _journey_of(app, session_key)
    if jmeta is not None:
        # /health shows which journey this session is a leg of
        sup.context["journey"] = jmeta
    if rec is not None:
        sup.on_event = rec.event  # restart attempts/outcomes -> event log
    wrapped = ResilientPipeline(pipeline, sup)
    ov = app.get("overload")
    if ov is not None:
        # overload ladder (resilience/overload.py): the wrapper consults it
        # per frame; sustained box-wide pressure walks this session down
        # the shedding ladder and back up on recovery
        wrapped.throttle = ov.register_session(session_key, sup)
        # network ladder (resilience/netadapt.py): RTCP loss telemetry
        # walks a quality rung joined to the compute ladder above —
        # registered after it so the skip-floor join binds; providers
        # without an RTCP plane (loopback/aiortc) just never feed it
        na = ov.register_netadapt(session_key)
        attach = getattr(pc, "attach_netadapt", None)
        if na is not None and attach is not None:
            attach(na)
    app.setdefault("supervisors", {})[session_key] = sup
    sup.start_watchdog()
    return wrapped


def _register_ingest_queue(app, session_key: str, track):
    """Expose the session's source queue depth at /metrics when the track
    has one (loopback tier; the native tier's ring is latest-wins by
    construction).  Unregistered with the session."""
    ov = app.get("overload")
    src_q = getattr(track, "_q", None)
    if ov is not None and src_q is not None:
        ov.register_queue(f"ingest:{session_key}", QueueProbe(src_q))


def _session_tracer(app, session_key: str, src_track=None):
    """The session's frame tracer (obs/trace.py), registered with the
    flight recorder; None when the recorder is disabled.  Native-tier
    sources (H264RingSource) get the tracer bound directly so frame ids
    mint at DECODE; other tiers mint at the track's ingest hop."""
    flight = app.get("flight")
    if flight is None:
        return None
    tracer = flight.register(session_key).tracer
    if src_track is not None and hasattr(src_track, "tracer"):
        src_track.tracer = tracer
    return tracer


def _end_supervision(app, session_key: str):
    sup = app.get("supervisors", {}).pop(session_key, None)
    app.get("journey_map", {}).pop(session_key, None)
    if sup is not None:
        sup.stop()
    ov = app.get("overload")
    if ov is not None:
        ov.unregister_session(session_key)
    flight = app.get("flight")
    if flight is not None:
        # live rings go with the session; stored snapshots survive (the
        # black box outlives the crash it recorded)
        flight.unregister(session_key)


# ---------------------------------------------------------------------------
# UDP port pinning (reference agent.py:32-69; rationale: restrictive
# firewalls / serverless platforms need operator-chosen media ports)
# ---------------------------------------------------------------------------

def patch_loop_datagram(local_ports: List[int]):
    loop = asyncio.get_event_loop()
    if getattr(loop, "_patch_done", False):
        return

    old_create = loop.create_datagram_endpoint

    async def create_datagram_endpoint(
        self, protocol_factory, local_addr: Tuple[str, int] = None, **kwargs
    ):
        if local_addr and local_addr[1]:
            return await old_create(protocol_factory, local_addr=local_addr, **kwargs)
        if local_addr is None:
            return await old_create(protocol_factory, local_addr=None, **kwargs)
        ports = [int(p) for p in local_ports]
        random.shuffle(ports)
        last_exc = None
        for port in ports:
            try:
                ret = await old_create(
                    protocol_factory, local_addr=(local_addr[0], port), **kwargs
                )
                logger.debug("create_datagram_endpoint chose port %s", port)
                return ret
            except OSError as exc:
                last_exc = exc
        if last_exc is not None:
            raise last_exc
        raise ValueError("local_ports must not be empty")

    loop.create_datagram_endpoint = types.MethodType(create_datagram_endpoint, loop)
    loop._patch_done = True


# ---------------------------------------------------------------------------
# control-plane application of runtime config JSON (shared by datachannel
# and POST /config — reference agent.py:154-168, 324-337, 398-412)
# ---------------------------------------------------------------------------

def _encoder_surface(provider):
    """The provider's runtime encoder-config surface (validate + apply),
    or None when it has none (loopback/aiortc tiers)."""
    if provider is not None and hasattr(provider, "apply_encoder_config"):
        return provider
    return None


def apply_runtime_config(pipeline, config: dict, encoders=None):
    """``encoders``: an object with ``validate_encoder_config`` /
    ``apply_encoder_config`` (NativeRtpProvider), or None when this
    surface has no encoder plane."""
    if not isinstance(config, dict):
        raise ValueError("config must be a JSON object")
    guidance_scale = config.get("guidance_scale")
    delta = config.get("delta")
    update_guidance = getattr(pipeline, "update_guidance", None)
    # capability AND value checks BEFORE any mutation: a 400 must mean
    # "nothing was applied", not "the prompt changed but guidance was
    # refused" — so non-numeric values fail here, not mid-apply
    if guidance_scale is not None or delta is not None:
        if update_guidance is None:  # an injected pipeline may lack it
            raise ValueError(
                "guidance_scale/delta not supported by this pipeline"
            )
        guidance_scale = None if guidance_scale is None else float(guidance_scale)
        delta = None if delta is None else float(delta)
    # encoder bitrate/GOP reconfigure (ISSUE 6): rides the same runtime
    # config surface, applied through the provider's single blessed path
    # (NativeRtpProvider.apply_encoder_config -> H264Sink.reconfigure) —
    # same contract: validated here, applied only after every other check
    encoder = config.get("encoder")
    if encoder is not None:
        if encoders is None:
            raise ValueError(
                "encoder reconfigure not supported by this provider"
            )
        encoder = encoders.validate_encoder_config(encoder)  # BEFORE mutation
    # style-adapter hot-swap (adapters/, ISSUE 20): PRESENCE-keyed so JSON
    # null clears back to the zero bank ({"adapter": null} != key absent);
    # capability-checked here like guidance — only the batch scheduler's
    # factor-bank surface carries it
    has_adapter = "adapter" in config
    update_adapter = getattr(pipeline, "update_adapter", None)
    if has_adapter:
        if update_adapter is None:
            raise ValueError(
                "adapter hot-swap not supported by this pipeline (the "
                "batch scheduler with a bound adapter registry owns it)"
            )
        adapter = config["adapter"]
        if adapter is not None and not isinstance(adapter, str):
            raise ValueError("adapter must be a string name or null")
    # the side network's conditioning strength: capability and value
    # checked here, so a 400 still means nothing was applied
    controlnet_scale = config.get("controlnet_scale")
    if controlnet_scale is not None:
        if not getattr(pipeline, "has_controlnet", False):
            raise ValueError(
                "controlnet_scale: no side network is served (start the "
                "agent with --controlnet <id>)"
            )
        controlnet_scale = float(controlnet_scale)
        if not 0.0 <= controlnet_scale <= 2.0:  # diffusers' documented range
            raise ValueError("controlnet_scale must lie in [0, 2]")
    if has_adapter:
        # applied FIRST: update_adapter validates the name against the
        # registry before touching any slot (unknown -> ValueError -> 400
        # with nothing else applied yet)
        update_adapter(adapter)
    t_index_list = config.get("t_index_list")
    if t_index_list is not None:
        pipeline.update_t_index_list(t_index_list)
    prompt = config.get("prompt")
    if prompt is not None:
        pipeline.update_prompt(prompt)
    if guidance_scale is not None or delta is not None:
        update_guidance(guidance_scale=guidance_scale, delta=delta)
    if controlnet_scale is not None:
        pipeline.update_controlnet_scale(controlnet_scale)
    if encoder is not None:
        encoders.apply_encoder_config(encoder)


def _wire_datachannel(pipeline, channel, guard=None, encoders=None):
    @channel.on("message")
    async def on_message(message):
        if guard is not None and not guard():
            return
        logger.info("received config: %s", message)
        try:
            # prompt updates run a text-encoder forward — never on the loop
            await asyncio.to_thread(
                apply_runtime_config, pipeline, json.loads(message), encoders
            )
        except (ValueError, KeyError, TypeError) as e:
            # TypeError: structurally-wrong JSON from a hostile/buggy client
            # (e.g. t_index_list [18, null]) must not escape the handler
            logger.error("bad config message: %s", e)


def _overloaded_response(
    app, text: str = "overloaded", retry_after: float | None = None
) -> web.Response:
    """503 with a Retry-After hint scaled to live pressure — clients back
    off instead of hammering a saturated box (DAGOR-style early refusal).
    ``retry_after`` lets the admission gate pass through the exact value
    it computed when refusing (the cap refusal deliberately returns the
    unscaled base) instead of re-deriving one here."""
    if retry_after is None:
        ov = app.get("overload")
        retry_after = ov.admission.retry_after_s() if ov is not None else 2.0
    return web.Response(
        status=503,
        text=text,
        headers={wire.RETRY_AFTER: str(max(1, int(round(retry_after))))},
    )


def _admission_gate(app, session_key: str | None = None) -> web.Response | None:
    """Cost-aware admission for the session-creating endpoints: refuse a
    new stream BEFORE claiming anything when live signals (engine
    step-latency EWMA, event-loop lag, session cap, ladder freeze) say the
    box cannot hold it.  ``session_key`` turns the admit into a counted
    reservation (consumed when on_track registers the ladder, released by
    :func:`_release_admission` / :func:`_end_supervision` on failure) so a
    burst of concurrent offers cannot race past OVERLOAD_MAX_SESSIONS
    before any of their tracks arrive.  None = admitted."""
    guard = app.get("engine_guard")
    if guard is not None and guard.quarantined:
        # engine fault domain (resilience/engine_guard.py): a quarantined
        # device plane cannot serve ANY new stream — refuse before touching
        # overload accounting, Retry-After from the rebuild backoff
        return _overloaded_response(
            app, text="engine quarantined", retry_after=guard.retry_after_s()
        )
    ov = app.get("overload")
    if ov is None:
        return None
    ok, retry_after = ov.admission_gate(key=session_key)
    if ok:
        return None
    return _overloaded_response(app, retry_after=retry_after)


def _release_admission(app, session_key: str):
    """Cancel an admission reservation for an offer that failed before its
    video track (and therefore its supervisor/ladder) ever existed."""
    ov = app.get("overload")
    if ov is not None:
        ov.release_admission(session_key)


# the 503 text names the pool that refused (the only slot pool there is)
_SLOTS_FULL_TEXT = "all batch-scheduler session slots in use"


async def _claim_pipeline(app, session_key: str | None = None,
                          imported=None):
    """-> (pipeline, release_fn).  With the continuous batch scheduler
    active (the default) each connection claims a scheduler session —
    per-session stream state batched into one cross-session device step,
    (None, None) via CapacityError when every slot is taken; otherwise
    every connection shares the single pipeline (reference semantics,
    agent.py:423).  Claim runs a prepare() (text-encode + UNet stock
    pass), so it is pushed off the event loop; the returned release_fn is
    loop-safe (schedules its work on a thread).

    ``imported``: a restored ScheduledSession parked by /migrate/import —
    adopted AS the claim (renamed to this connection's session key, no
    fresh prepare: the migrated stream resumes exactly where the source
    froze it)."""
    sched = app.get("batch_scheduler")
    if imported is not None:
        imported.session_key = session_key
        ov = app.get("overload")
        if ov is not None and session_key is not None:
            ov.register_queue(
                f"batchwin:{session_key}", imported.window_queue
            )

        def release_imported():
            spawn(asyncio.to_thread(imported.release))

        return imported, release_imported
    if sched is None:
        return app["pipeline"], lambda: None
    from ..stream.scheduler import CapacityError

    try:
        session = await asyncio.to_thread(sched.claim, session_key)
    except CapacityError:
        return None, None
    ov = app.get("overload")
    if ov is not None and session_key is not None:
        # the session's coalescing-window queue joins the /metrics queue
        # registry; unregistered with the session (":<key>" suffix rule)
        ov.register_queue(
            f"batchwin:{session_key}", session.window_queue
        )

    def release_session():
        spawn(asyncio.to_thread(session.release))

    return session, release_session


# ---------------------------------------------------------------------------
# live session migration (ISSUE 15, docs/fleet.md "Drain runbook"):
# export/import of one session's stream state, plus the adoption handshake
# a migrated client's re-offer completes
# ---------------------------------------------------------------------------

_IMPORTED_TTL_S = 30.0  # setup-sized, matches the admission reservation TTL

# control-plane-only snapshots (serving tiers without a scheduler state
# row to move — the target re-primes like a fresh offer); scheduler
# snapshots carry stream/scheduler.SESSION_SNAPSHOT_SCHEMA instead
_CONTROL_SNAPSHOT_SCHEMA = 1


def _expire_imported(app, token: str | None = None):
    """Drop stale parked imports (or one specific token whose timer
    fired): release the restored scheduler slot and the admission
    reservation the import took — a client that never re-offers must not
    leak capacity."""
    imp = app.setdefault("imported_sessions", {})
    if token is not None:
        keys = [token] if token in imp else []
    else:
        now = time.monotonic()
        keys = [
            k for k, e in imp.items() if now - e["ts"] >= _IMPORTED_TTL_S
        ]
    for k in keys:
        entry = imp.pop(k, None)
        if entry is None:
            continue
        sess = entry.get("session")
        if sess is not None:
            spawn(asyncio.to_thread(sess.release))
        _release_admission(app, k)
        logger.warning("imported session %s expired unadopted", k)


def _admit_or_adopt(app, request, stream_id: str):
    """Admission for the session-creating endpoints, migration-aware: a
    re-offer carrying ``X-Migrated-Session`` claims the parked import —
    its admission reservation transfers to the minted stream id (the
    import already paid the counted gate) and, when the import restored
    scheduler state, that session is adopted instead of a fresh claim.
    -> (imported session | None, rejection response | None)."""
    token = request.headers.get(wire.MIGRATED_SESSION)
    entry = None
    if token:
        _expire_imported(app)
        entry = app.setdefault("imported_sessions", {}).pop(token, None)
    ov = app.get("overload")
    adopted = False
    if entry is not None:
        adopted = (
            ov.adopt_reservation(token, stream_id)
            if ov is not None else True
        )
    if not adopted:
        # tpurtc: allow[reservation-pairing] -- the admitted reservation deliberately outlives this helper: ownership transfers to the caller (offer/whip), which consumes it via on_track's register_session or releases it via _release_admission/_end_supervision on every failure path
        rejected = _admission_gate(app, stream_id)
        if rejected is not None:
            if entry is not None and entry.get("session") is not None:
                # the import's reservation lapsed AND the box refuses:
                # release the restored slot — a refused adoption must
                # not leak capacity
                sess = entry["session"]
                spawn(asyncio.to_thread(sess.release))
            return None, rejected
    return (entry or {}).get("session"), None


async def migrate_export(request):
    """``GET /migrate/export?session=<stream-id>``: serialize one live
    session for migration.  Batch-scheduler sessions export their full
    stream state (stream/scheduler.snapshot_session — versioned schema,
    bit-exact state row, control plane, similarity-filter state); other
    serving tiers export a control-plane-only snapshot (the target
    re-primes like a fresh offer).  Exporting leaves the session serving
    untouched — the source keeps stepping until the client moves."""
    app = request.app
    if not env.migrate_enabled():
        return _debug_error(
            404, "session migration disabled (MIGRATE_ENABLE=0)"
        )
    sid = request.query.get("session")
    if not sid:
        return _debug_error(400, "session= query required")
    sched = app.get("batch_scheduler")
    if (
        sched is not None
        and hasattr(sched, "snapshot_session")
        and getattr(sched, "session", lambda _k: None)(sid) is not None
    ):
        try:
            # the row read takes the scheduler's step lock — never on
            # the loop
            snap = await asyncio.to_thread(sched.snapshot_session, sid)
        except KeyError:
            # released between the existence check and the read: a gone
            # session is a terminal 404, not a 500 the router's policy
            # would retry three times for nothing
            return _debug_error(404, f"unknown session {sid!r}")
        snap.setdefault("kind", "scheduler")
        snap["session"] = sid
        return web.json_response(snap)
    if sid not in app.get("supervisors", {}):
        return _debug_error(404, f"unknown session {sid!r}")
    return web.json_response({
        "schema": _CONTROL_SNAPSHOT_SCHEMA,
        "kind": "control-plane",
        "session": sid,
    })


async def migrate_import(request):
    """``POST /migrate/import {"token", "snapshot"}``: land a migrated
    session.  The admission gate takes a COUNTED reservation under the
    token BEFORE any state lands (the same ledger a fresh offer pays, so
    concurrent imports and offers see each other at the cap); a
    scheduler snapshot then restores into a claimed slot, parked until
    the client's re-offer arrives carrying ``X-Migrated-Session``
    (unadopted imports expire with the reservation and release
    everything).  A versioned-schema/fingerprint mismatch is 409 —
    terminal for the router's retry policy (the retry-4xx rule); slot or
    admission exhaustion is 503 + Retry-After."""
    app = request.app
    if not env.migrate_enabled():
        return _debug_error(
            404, "session migration disabled (MIGRATE_ENABLE=0)"
        )
    try:
        body = await request.json()
    except (ValueError, LookupError):
        return _debug_error(400, "invalid JSON body")
    if not isinstance(body, dict):
        return _debug_error(400, "body must be an object")
    token = str(body.get("token") or "")
    snap = body.get("snapshot")
    if not token or not isinstance(snap, dict):
        return _debug_error(400, "token and snapshot object required")
    _expire_imported(app)
    parked = app.setdefault("imported_sessions", {}).get(token)
    if parked is not None:
        # idempotent retry (the router re-POSTs when a response is lost
        # mid-restore): the first import already landed and holds its
        # reservation — restoring AGAIN would orphan the parked session's
        # slot behind the overwritten entry
        return web.json_response({
            "ok": True, "token": token,
            "restored": parked.get("session") is not None,
        })
    importing: set = app.setdefault("importing_tokens", set())
    if token in importing:
        # a retry racing a FIRST import still inside its restore (the
        # check-then-park spans the to_thread await): refuse transiently
        # — the router backs off and the next attempt hits the parked
        # idempotent path above instead of restoring a second slot
        return _overloaded_response(app, "import already in progress")
    rejected = _admission_gate(app, token)  # the reservation comes FIRST
    if rejected is not None:
        return rejected
    kind = snap.get("kind")
    sess = None
    importing.add(token)
    try:
        if kind == "scheduler":
            sched = app.get("batch_scheduler")
            if sched is None or not hasattr(sched, "restore_session"):
                _release_admission(app, token)
                return _debug_error(
                    409, "no batch scheduler on this agent to restore into"
                )
            from ..stream.scheduler import CapacityError, SnapshotMismatch

            try:
                sess = await asyncio.to_thread(
                    sched.restore_session, snap, token
                )
            except SnapshotMismatch as e:
                _release_admission(app, token)
                return _debug_error(409, f"snapshot refused: {e}")
            except CapacityError:
                _release_admission(app, token)
                return _overloaded_response(app, _SLOTS_FULL_TEXT)
            except BaseException:
                # anything unexpected (XLA OOM, runtime error inside the
                # install): the 500 the router will retry must not strand
                # the counted reservation for its full TTL
                _release_admission(app, token)
                raise
        elif kind == "control-plane":
            if snap.get("schema") != _CONTROL_SNAPSHOT_SCHEMA:
                _release_admission(app, token)
                return _debug_error(
                    409,
                    f"control-plane snapshot schema {snap.get('schema')!r} "
                    f"unsupported (this build speaks "
                    f"{_CONTROL_SNAPSHOT_SCHEMA})",
                )
        else:
            _release_admission(app, token)
            return _debug_error(400, f"unknown snapshot kind {kind!r}")
        # parked BEFORE the in-flight mark clears: a racing retry sees
        # either "importing" (503, backs off) or the parked entry
        app.setdefault("imported_sessions", {})[token] = {
            "session": sess, "ts": time.monotonic(),
        }
    finally:
        importing.discard(token)
    # the expiry timer mirrors the reservation TTL; an adopted (popped)
    # token makes the callback a no-op
    asyncio.get_running_loop().call_later(
        _IMPORTED_TTL_S + 1.0, _expire_imported, app, token
    )
    app["stats"].count("migrate_imports")
    return web.json_response(
        {"ok": True, "token": token, "restored": sess is not None}
    )


# ---------------------------------------------------------------------------
# restart-in-place (ISSUE 16, docs/fleet.md "Rolling upgrades"): export
# every live session into a handoff file, respawn, exit; the replacement
# adopts the handoff during startup — before its socket binds
# ---------------------------------------------------------------------------


async def _export_all_sessions(app) -> list:
    """Every live session as a handoff entry: the migration snapshot
    (scheduler state when the tier has it, control-plane otherwise) plus
    the journey binding and room — everything the replacement needs to
    park the session and re-announce it."""
    sched = app.get("batch_scheduler")
    sups = app.get("supervisors", {})
    out = []
    for sid in list(sups):
        snap = None
        if (
            sched is not None
            and hasattr(sched, "snapshot_session")
            and getattr(sched, "session", lambda _k: None)(sid) is not None
        ):
            try:
                snap = await asyncio.to_thread(sched.snapshot_session, sid)
                snap.setdefault("kind", "scheduler")
                snap["session"] = sid
            except KeyError:
                snap = None  # released mid-export: nothing left to move
        if snap is None:
            snap = {
                "schema": _CONTROL_SNAPSHOT_SCHEMA,
                "kind": "control-plane",
                "session": sid,
            }
        sup = sups.get(sid)
        out.append({
            "session": sid,
            "snapshot": snap,
            "journey": _journey_of(app, sid),
            "room_id": (
                str(sup.context.get("room_id") or "")
                if sup is not None and hasattr(sup, "context") else ""
            ),
        })
    return out


def _spawn_recycle_exit(app, respawn: bool, handoff: str):
    """Background exit for a 202'd recycle: give the response (and any
    in-flight webhook posts) a beat to flush, spawn the replacement off
    the loop, then hard-exit — the replacement retry-binds the freed
    port.  Strong-ref'd + reaped like every background task."""
    from . import lifecycle

    async def run():
        await asyncio.sleep(env.get_float("RECYCLE_EXIT_DELAY_S", 0.2))
        ok = True
        if respawn:
            ok = await asyncio.to_thread(lifecycle.spawn_replacement, handoff)
        if not ok:
            # no backend could spawn: aborting beats exiting into a hole
            # — the sessions keep serving HERE and the sweep's prewarm
            # wait times out cleanly on the router side
            logger.error("recycle aborted: replacement spawn failed")
            app["recycling"] = False
            return
        logger.info(
            "recycling: exiting (respawn=%s, handoff=%s)", respawn, handoff
        )
        lifecycle.exit_process(0)

    tasks = app.setdefault("recycle_tasks", set())
    task = asyncio.get_running_loop().create_task(run())
    tasks.add(task)
    task.add_done_callback(tasks.discard)


async def admin_recycle(request):
    """``POST /admin/recycle {"respawn": true|false}``: restart (or
    retire) this agent process in place.  Every live session is exported
    through the migration snapshot path into a handoff file; the
    replacement — spawned via ``RECYCLE_EXEC_HOOK`` or argv re-exec —
    imports them during its startup, BEFORE its socket binds (so a 200
    ``/health`` from the new process means the sessions are already
    parked: that ordering is the upgrade sweep's prewarm gate), and
    announces each with an AGENT_RECYCLED webhook that sends the client
    back through the router as journey leg+1 on the SAME box.  Responds
    202 immediately; the exit happens a beat later so the response
    leaves first.  ``respawn: false`` (the autoscaler's retire path)
    skips the spawn — the sessions were drained away already and the
    process just exits."""
    app = request.app
    if not env.get_bool("RECYCLE_ENABLE", True):
        return _debug_error(404, "recycle disabled (RECYCLE_ENABLE=0)")
    if app.get("recycling"):
        return _debug_error(409, "recycle already in progress")
    try:
        body = await request.json()
    except (ValueError, LookupError):
        body = {}
    respawn = (
        bool(body.get("respawn", True)) if isinstance(body, dict) else True
    )
    from . import lifecycle

    app["recycling"] = True
    sessions = await _export_all_sessions(app)
    path = lifecycle.handoff_path()
    if respawn:
        handler = app.get("stream_event_handler")
        meta = {
            "worker_id": env.get_str("WORKER_ID") or "",
            # webhook config survives the swap: in fleet tests it was set
            # at runtime (/_test/webhook), and the replacement's
            # AGENT_RECYCLED announces are the whole point of the handoff
            "webhook": {
                "url": getattr(handler, "webhook_url", None),
                "token": getattr(handler, "token", None),
            },
        }
        await asyncio.to_thread(lifecycle.write_handoff, path, sessions, meta)
    _spawn_recycle_exit(app, respawn, path)
    app["stats"].count("recycles")
    return web.json_response(
        {
            "recycling": True,
            "respawn": respawn,
            "sessions": len(sessions),
            "handoff": path if respawn else None,
        },
        status=202,
    )


async def _import_handoff(app):
    """Recycled-replacement startup: adopt the predecessor's handoff
    (``RECYCLE_HANDOFF``).  Every exported session takes a counted
    admission reservation and parks exactly like a ``/migrate/import``
    under the deterministic token ``rcy-<stream-id>`` (the router
    self-constructs the same token from the AGENT_RECYCLED webhook and
    pins the client's re-offer HERE with it); an AGENT_RECYCLED webhook
    then sends each client back through the router.  Runs as the LAST
    on_startup hook — after the serving planes exist, still before the
    socket binds.  The file is consumed whatever happens: a crash loop
    must not re-adopt a stale generation forever."""
    path = env.get_str("RECYCLE_HANDOFF")
    if not path or not os.path.exists(path):
        return
    from . import lifecycle

    data = await asyncio.to_thread(lifecycle.read_handoff, path)
    await asyncio.to_thread(lifecycle.consume_handoff, path)
    if data is None:
        logger.warning("recycle handoff at %s unreadable — booting clean",
                       path)
        return
    handler: StreamEventHandler = app["stream_event_handler"]
    webhook = data.get("webhook")
    if isinstance(webhook, dict):
        if handler.webhook_url is None and webhook.get("url"):
            handler.webhook_url = webhook["url"]
            handler.token = webhook.get("token")
    sched = app.get("batch_scheduler")
    restored = 0
    for entry in data.get("sessions", ()):
        if not isinstance(entry, dict):
            continue
        sid = str(entry.get("session") or "")
        snap = entry.get("snapshot")
        if not sid or not isinstance(snap, dict):
            continue
        token = f"rcy-{sid}"
        rejected = _admission_gate(app, token)
        if rejected is not None:
            logger.warning("handoff session %s refused at admission", sid)
            continue
        sess = None
        if (snap.get("kind") == "scheduler" and sched is not None
                and hasattr(sched, "restore_session")):
            from ..stream.scheduler import CapacityError, SnapshotMismatch

            try:
                sess = await asyncio.to_thread(
                    sched.restore_session, snap, token
                )
            except (SnapshotMismatch, CapacityError) as e:
                _release_admission(app, token)
                logger.warning("handoff restore of %s refused: %s", sid, e)
                continue
        app.setdefault("imported_sessions", {})[token] = {
            "session": sess, "ts": time.monotonic(),
        }
        asyncio.get_running_loop().call_later(
            _IMPORTED_TTL_S + 1.0, _expire_imported, app, token
        )
        jmeta = entry.get("journey")
        journey = (
            jmeta if isinstance(jmeta, dict) and jmeta.get("journey_id")
            else None
        )
        handler.handle_session_state(
            sid, str(entry.get("room_id") or ""), "AGENT_RECYCLED",
            "agent recycled in place — re-offer through the router to "
            "resume on the same box",
            journey=journey,
        )
        restored += 1
        app["stats"].count("recycle_imports")
    if restored:
        logger.info("recycle handoff adopted: %d session(s) parked",
                    restored)


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------

async def offer(request):
    app = request.app
    pcs = app["pcs"]
    provider = app["provider"]
    stream_event_handler = app["stream_event_handler"]
    stats: FrameStats = app["stats"]

    try:
        params = await request.json()
        room_id = params["room_id"]
        offer_params = params["offer"]
    except (ValueError, LookupError) as e:  # LookupError covers KeyError +
        return web.Response(status=400, text=f"invalid offer request: {e}")  # unknown charset=
    stream_id = str(uuid.uuid4())
    imported, rejected = _admit_or_adopt(app, request, stream_id)
    if rejected is not None:
        return rejected
    pipeline, release_pipeline = await _claim_pipeline(
        app, stream_id, imported=imported
    )
    if pipeline is None:
        _release_admission(app, stream_id)
        return _overloaded_response(app, _SLOTS_FULL_TEXT)
    # fleet journey correlation: bound BEFORE the SDP dance so on_track
    # (which fires inside setRemoteDescription) supervises a session
    # that already knows its journey
    jmeta = _bind_journey(app, request, stream_id)
    # everything between the claim and the connection handlers taking over
    # must release the slot on failure — a leaked slot is permanent 503s
    pc = None
    try:
        offer_sdp = provider.session_description(
            sdp=offer_params["sdp"], type=offer_params["type"]
        )

        # blocking HTTP to Twilio (up to 10 s) — never on the event loop
        ice_servers = await asyncio.to_thread(turn.get_ice_servers)
        pc = provider.peer_connection(ice_servers if ice_servers else None)
        pcs.add(pc)

        tracks = {"video": None}

        # Prefer H264 on the receive transceiver (reference agent.py:149-152)
        transceiver = pc.addTransceiver("video")
        transceiver.setCodecPreferences(provider.h264_codec_preferences("video"))

        @pc.on("datachannel")
        def on_datachannel(channel):
            _wire_datachannel(
                pipeline, channel, guard=lambda: tracks["video"] is not None,
                encoders=_encoder_surface(provider),
            )

        @pc.on("track")
        def on_track(track):
            logger.info("Track received: %s", track.kind)
            if track.kind == "video":
                supervised = _supervise_session(
                    app, pc, _TimedPipeline(pipeline, stats), stream_id, room_id
                )
                _register_ingest_queue(app, stream_id, track)
                video_track = VideoStreamTrack(
                    track, supervised, overload=app.get("overload"),
                    tracer=_session_tracer(app, stream_id, track),
                )
                tracks["video"] = video_track
                sender = pc.addTrack(video_track)
                provider.force_codec(pc, sender, "video/H264")

            @track.on("ended")
            async def on_ended():
                logger.info("%s track ended", track.kind)

        @pc.on("connectionstatechange")
        async def on_connectionstatechange():
            logger.info("Connection state is: %s", pc.connectionState)
            if pc.connectionState == "failed":
                await pc.close()
                pcs.discard(pc)
                release_pipeline()
                _end_supervision(app, stream_id)
            elif pc.connectionState == "closed":
                await pc.close()
                pcs.discard(pc)
                release_pipeline()
                journey = _journey_of(app, stream_id)  # before the map clears
                _end_supervision(app, stream_id)
                stream_event_handler.handle_stream_ended(
                    stream_id, room_id, journey=journey
                )
            elif pc.connectionState == "connected":
                stream_event_handler.handle_stream_started(
                    stream_id, room_id, journey=_journey_of(app, stream_id)
                )

        await pc.setRemoteDescription(offer_sdp)
        answer = await pc.createAnswer()
        await pc.setLocalDescription(answer)
    except (KeyError, ValueError) as e:
        release_pipeline()
        await _discard_pc(pc, pcs)
        # on_track may already have registered supervision (it fires during
        # setRemoteDescription) — a failed offer must not leave a watchdog
        # task and overload ladder behind
        _end_supervision(app, stream_id)
        return web.Response(status=400, text=f"invalid offer request: {e}")
    except Exception:
        release_pipeline()
        await _discard_pc(pc, pcs)
        _end_supervision(app, stream_id)
        raise

    return web.Response(
        content_type="application/json",
        text=json.dumps(
            {"sdp": pc.localDescription.sdp, "type": pc.localDescription.type}
        ),
        # the session's server-side identity: the fleet router maps the
        # session to this agent with it (WHIP/WHEP get the same from
        # their Location headers) so DELETEs route back and a crash can
        # re-point exactly the affected clients; the journey echo
        # confirms the correlation id was threaded end to end
        headers={wire.STREAM_ID: stream_id, **_journey_headers(jmeta)},
    )


async def _discard_pc(pc, pcs: set):
    """Close + drop a half-built peer connection on a failed /offer so its
    transport (e.g. a bound native-rtp UDP socket) doesn't linger until
    server shutdown (ADVICE r2)."""
    if pc is None:
        return
    try:
        await pc.close()
    except Exception:
        logger.exception("closing half-built pc failed")
    pcs.discard(pc)


async def _close_sessions(app, pcs_key: str, session: str | None) -> bool:
    """Shared session-scoped teardown for WHIP/WHEP DELETE (a deliberate
    fix over the reference's do-nothing 200, VERDICT r1 weak #6): closes
    ONE session (False when unknown) or, with session=None, all of them
    (bare DELETE = operator teardown)."""
    sessions: dict = app["state"].setdefault(pcs_key, {})
    if session is not None:
        pc = sessions.pop(session, None)
        if pc is None:
            return False
        await pc.close()
        app["pcs"].discard(pc)
        return True
    pcs = list(sessions.values())
    await asyncio.gather(*[pc.close() for pc in pcs])
    for pc in pcs:
        app["pcs"].discard(pc)
    sessions.clear()
    return True


def _refresh_source_track(app):
    """Point source_track AND source_relay at the most recent
    still-connected publisher (or None) — keeps WHEP viewers off a closed
    publisher's track, and stops/discards relays of dead sessions."""
    live = app["state"].get("whip_pcs", {})
    tracks = app["state"].get("whip_tracks", {})
    relays = app["state"].get("whip_relays", {})
    groups = app["state"].get("broadcast_groups", {})
    # sweep EVERY dead session first: an older publisher disconnecting while
    # a newer one stays live must not leave entries behind forever
    # (unbounded growth under publisher churn — ADVICE r2)
    for sid in [s for s in tracks if s not in live]:
        tracks.pop(sid, None)
        dead = relays.pop(sid, None)
        if dead is not None:
            dead.stop()
        group = groups.pop(sid, None)
        if group is not None:
            # the publisher is gone: tear the shared TX plane down too
            # (viewer sessions outlive it harmlessly — their group ref
            # just stops fanning out)
            spawn(group.close())
    for sid in reversed(list(tracks)):
        app["state"]["source_track"] = tracks[sid]
        app["state"]["source_relay"] = relays.get(sid)
        return
    app["state"]["source_track"] = None
    app["state"]["source_relay"] = None


async def _ensure_broadcast_group(app):
    """The broadcast TX plane for the CURRENT publisher (or the edge-pulled
    stream), created on first viewer demand.  None => no group possible
    (no relay to subscribe — e.g. a bare source_track test rig) and the
    caller keeps the dedicated per-viewer chain."""
    groups = app["state"].setdefault("broadcast_groups", {})
    edge = groups.get("edge")
    if edge is not None and not edge.closed:
        return edge
    relay = app["state"].get("source_relay")
    if relay is None:
        return None
    sid = next(
        (
            s
            for s, r in app["state"].get("whip_relays", {}).items()
            if r is relay
        ),
        None,
    )
    if sid is None:
        return None
    group = groups.get(sid)
    if group is None or group.closed:
        from .broadcast import BroadcastGroup

        provider = app["provider"]
        group = BroadcastGroup(
            sid,
            width=getattr(provider, "default_width", 512),
            height=getattr(provider, "default_height", 512),
            use_h264=getattr(provider, "use_h264", None),
            stats=relay.stats,
        )
        await group.start(relay.subscribe())
        groups[sid] = group
    return group


def _broadcast_gauges(app) -> dict:
    """Aggregate broadcast-plane gauges (/capacity /health /metrics):
    group count + audience size vs the viewer cap — O(groups) int reads."""
    groups = {
        k: g
        for k, g in app["state"].get("broadcast_groups", {}).items()
        if not g.closed
    }
    viewers = sum(g.viewer_count for g in groups.values())
    cap = env.broadcast_max_viewers()
    return {
        "broadcast_groups": len(groups),
        "broadcast_viewers": viewers,
        "broadcast_max_viewers": cap,
        "broadcast_viewer_slots_free": max(0, cap - viewers) if cap else -1,
    }


async def whep(request):
    app = request.app
    if request.method == "DELETE":
        ok = await _close_sessions(app, "whep_pcs", request.match_info.get("session"))
        return web.Response(status=200 if ok else 404)
    if request.content_type != "application/sdp":
        return web.Response(status=400)

    source_track = app["state"].get("source_track")
    edge_group = app["state"].get("broadcast_groups", {}).get("edge")
    if edge_group is not None and edge_group.closed:
        edge_group = None
    if source_track is None and edge_group is None:
        # nothing to serve: no local publisher AND no pulled edge stream
        return web.Response(status=401)

    provider = app["provider"]
    pcs = app["pcs"]

    try:
        body = await request.text()
    except (ValueError, LookupError) as e:
        # undecodable body (ValueError covers UnicodeDecodeError) or an
        # unknown charset= parameter (LookupError) -> client error
        return web.Response(status=400, text=f"invalid offer body: {e}")
    offer_sdp = provider.session_description(sdp=body, type="offer")
    pc = provider.peer_connection()
    session_id = str(uuid.uuid4())

    # broadcast fan-out (ISSUE 17): viewers of a native-provider stream
    # share ONE encode/packetize plane and stop charging the engine —
    # admission is a cheap viewer-count cap, not an engine slot.  The
    # aiortc provider (no join_broadcast) keeps the dedicated chain.
    group = None
    if env.broadcast_fanout_enabled() and hasattr(pc, "join_broadcast"):
        group = await _ensure_broadcast_group(app)
    if group is None and source_track is None:
        # edge-pulled stream exists but this provider can't join a group —
        # the ONE refusal that used to ship without Retry-After (the
        # refusal-discipline checker's real-world fixture shape): an edge
        # whose group is still warming refuses exactly like a saturated
        # box, and the client must know when to come back
        await _discard_pc(pc, pcs)
        return _overloaded_response(
            app, "edge stream requires the broadcast plane"
        )
    if group is not None:
        cap = env.broadcast_max_viewers()
        if cap and group.viewer_count >= cap:
            await _discard_pc(pc, pcs)
            return _overloaded_response(
                app, "broadcast viewer capacity reached", retry_after=2.0
            )
        pc.join_broadcast(group)

    pcs.add(pc)
    app["state"].setdefault("whep_pcs", {})[session_id] = pc

    # dedicated tier only: each viewer gets its own relayed view of the
    # processed stream — never concurrent recv() on the shared track
    # (reference MediaRelay parity).  Broadcast viewers don't subscribe:
    # the GROUP holds the one subscription.
    relay = app["state"].get("source_relay") if group is None else None
    viewer_track = relay.subscribe() if relay is not None else source_track

    async def _fail_cleanup():
        await _discard_pc(pc, pcs)
        app["state"].get("whep_pcs", {}).pop(session_id, None)
        if relay is not None:
            viewer_track.stop()

    @pc.on("iceconnectionstatechange")
    async def on_iceconnectionstatechange():
        logger.info("ICE connection state is %s", pc.iceConnectionState)
        if pc.iceConnectionState == "failed":
            await pc.close()
            pcs.discard(pc)

    @pc.on("connectionstatechange")
    async def on_connectionstatechange():
        logger.info("Connection state is: %s", pc.connectionState)
        if pc.connectionState in ("failed", "closed"):
            await pc.close()
            pcs.discard(pc)
            app["state"].get("whep_pcs", {}).pop(session_id, None)
            if relay is not None:
                viewer_track.stop()

    try:
        if group is None:
            sender = pc.addTrack(viewer_track)
            provider.force_codec(pc, sender, "video/H264")

        await pc.setRemoteDescription(offer_sdp)
        # OBS WHIP: gather ALL ICE candidates before answering (reference
        # agent.py:256-263 — OBS does not trickle)
        await pc._RTCPeerConnection__gather()
        answer = await pc.createAnswer()
        await pc.setLocalDescription(answer)
    except ValueError as e:
        await _fail_cleanup()
        return web.Response(status=400, text=f"invalid offer: {e}")
    except Exception:
        await _fail_cleanup()
        raise

    return web.Response(
        status=201,
        content_type="application/sdp",
        headers={
            "Access-Control-Allow-Origin": "*",
            "Access-Control-Allow-Headers": "*",
            wire.LOCATION: f"/whep/{session_id}",
            # viewers carry the correlation id too (the router placed
            # this leg); no recorder binds — a WHEP leg has no pipeline
            **_journey_headers(_parse_journey(app, request)),
        },
        text=answer.sdp,
    )


async def whip(request):
    app = request.app
    if request.method == "DELETE":
        ok = await _close_sessions(app, "whip_pcs", request.match_info.get("session"))
        _refresh_source_track(app)
        return web.Response(status=200 if ok else 404)
    if request.content_type != "application/sdp":
        return web.Response(status=400)

    pcs = app["pcs"]
    provider = app["provider"]
    stats: FrameStats = app["stats"]
    session_id = str(uuid.uuid4())
    imported, rejected = _admit_or_adopt(app, request, session_id)
    if rejected is not None:
        return rejected
    pipeline, release_pipeline = await _claim_pipeline(
        app, session_id, imported=imported
    )
    if pipeline is None:
        _release_admission(app, session_id)
        return _overloaded_response(app, _SLOTS_FULL_TEXT)
    jmeta = _bind_journey(app, request, session_id)

    pc = None

    def _cleanup_failed():
        release_pipeline()
        app["state"].get("whip_pcs", {}).pop(session_id, None)
        app["state"].get("whip_tracks", {}).pop(session_id, None)
        _refresh_source_track(app)
        # on_track may already have registered supervision (and the
        # admission reservation rides unregister_session) — a failed
        # publish must not leave a watchdog task or ladder behind
        _end_supervision(app, session_id)

    try:
        offer_sdp = provider.session_description(
            sdp=await request.text(), type="offer"
        )

        # No TURN here by design: OBS doesn't trickle ICE, so the TURN
        # permission dance can't complete; rely on STUN + pinned UDP ports
        # instead (full rationale preserved from reference agent.py:299-314).
        pc = provider.peer_connection()
        pcs.add(pc)
        app["state"].setdefault("whip_pcs", {})[session_id] = pc

        transceiver = pc.addTransceiver("video")
        transceiver.setCodecPreferences(provider.h264_codec_preferences("video"))

        @pc.on("datachannel")
        def on_datachannel(channel):
            _wire_datachannel(
                pipeline, channel, encoders=_encoder_surface(provider)
            )

        @pc.on("iceconnectionstatechange")
        async def on_iceconnectionstatechange():
            logger.info("ICE connection state is %s", pc.iceConnectionState)
            if pc.iceConnectionState == "failed":
                await pc.close()
                pcs.discard(pc)

        @pc.on("track")
        def on_track(track):
            logger.info("Track received: %s", track.kind)
            if track.kind == "video":
                supervised = _supervise_session(
                    app, pc, _TimedPipeline(pipeline, stats), session_id
                )
                _register_ingest_queue(app, session_id, track)
                vt = VideoStreamTrack(
                    track, supervised, overload=app.get("overload"),
                    tracer=_session_tracer(app, session_id, track),
                )
                app["state"].setdefault("whip_tracks", {})[session_id] = vt
                app["state"]["source_track"] = vt  # latest publisher wins
                # one relay per publisher SESSION: N WHEP viewers share the
                # stream without concurrent recv() on one track (the
                # reference's MediaRelay, agent.py:424-430); earlier
                # publishers keep their relays and become active again if
                # the newest disconnects (_refresh_source_track)
                from .relay import TrackRelay

                # per-publisher aggregate stats: viewer-queue drops +
                # delivery freshness land here (never per-viewer), and a
                # broadcast group for this publisher adopts the SAME
                # FrameStats so the whole fan-out story reads in one place
                relay = TrackRelay(vt, stats=FrameStats())
                app["state"].setdefault("whip_relays", {})[session_id] = relay
                app["state"]["source_relay"] = relay

            @track.on("ended")
            async def on_ended():
                logger.info("%s track ended", track.kind)

        @pc.on("connectionstatechange")
        async def on_connectionstatechange():
            logger.info("Connection state is: %s", pc.connectionState)
            if pc.connectionState in ("failed", "closed"):
                await pc.close()
                pcs.discard(pc)
                app["state"].get("whip_pcs", {}).pop(session_id, None)
                _refresh_source_track(app)
                release_pipeline()
                _end_supervision(app, session_id)

        await pc.setRemoteDescription(offer_sdp)
        await pc._RTCPeerConnection__gather()
        answer = await pc.createAnswer()
        await pc.setLocalDescription(answer)
    except (ValueError, LookupError) as e:
        # bad client SDP (e.g. no video m= section), an undecodable body or
        # an unknown charset= is a 400, and the half-built pc + session
        # entries must not leak (code-review r3)
        await _discard_pc(pc, pcs)
        _cleanup_failed()
        return web.Response(status=400, text=f"invalid offer: {e}")
    except Exception:
        await _discard_pc(pc, pcs)
        _cleanup_failed()
        raise

    return web.Response(
        status=201,
        content_type="application/sdp",
        headers={
            "Access-Control-Allow-Origin": "*",
            "Access-Control-Allow-Headers": "*",
            wire.LOCATION: f"/whip/{session_id}",
            **_journey_headers(jmeta),
        },
        text=answer.sdp,
    )


async def update_config(request):
    try:
        config = await request.json()
    except (ValueError, LookupError):
        return web.Response(status=400, text="invalid JSON body")
    logger.info("received config: %s", config)
    # the operator surface targets the serving plane actually in use:
    # the batch scheduler (applies to every live session AND becomes the
    # default for future claims — the shared-pipeline semantics operators
    # already rely on), else the shared pipeline itself
    target = request.app.get("batch_scheduler") or request.app["pipeline"]
    encoders = _encoder_surface(request.app.get("provider"))
    try:
        await asyncio.to_thread(apply_runtime_config, target, config, encoders)
    except (ValueError, TypeError, KeyError) as e:
        # TypeError/KeyError: structurally-wrong JSON (t_index_list with
        # nulls, config that is not an object) is a client error, not a 500
        return web.Response(status=400, text=str(e))
    return web.Response(content_type="application/json", text="OK")


async def health(_):
    return web.Response(content_type="application/json", text="OK")


async def health_detail(request):
    """Supervisor rollup: overall status is the worst live session state
    (HEALTHY when idle); per-session snapshots carry the state machine's
    recent transitions — the operator's first stop when a stream degrades
    (docs/resilience.md maps each state to an action).  O(sessions): each
    snapshot reads counters and a bounded transition ring, never a frame
    queue — the endpoint itself survives overload."""
    app = request.app
    sups = app.get("supervisors", {})
    sessions = {k: s.snapshot() for k, s in sups.items()}
    ov = app.get("overload")
    if ov is not None:
        for k, ladder in ov.ladders.items():
            if k in sessions:
                sessions[k]["overload_rung"] = ladder.rung
                sessions[k]["effective_rung"] = ladder.effective_rung
        for k, na in ov.netadapt.items():
            if k in sessions:
                sessions[k]["netadapt"] = na.snapshot()
    sched = app.get("batch_scheduler")
    if sched is not None:
        for k, snap in sched.session_snapshots().items():
            if k in sessions:
                sessions[k]["batchsched"] = snap
    slo_plane = app.get("slo")
    if slo_plane is not None:
        # per-session SLO state (obs/slo.py): stage → budget/burn/breach;
        # O(stages) int reads per session, like everything else here
        for k in sessions:
            snap = slo_plane.session_snapshot(k)
            if snap is not None:
                sessions[k]["slo"] = snap
    devtel_plane = app.get("devtel")
    if devtel_plane is not None:
        # a serve-time retrace freezes EVERY live session — each session
        # dict carries the breach state next to its supervisor/SLO view
        dv = devtel_plane.session_view()
        for k in sessions:
            sessions[k]["devtel"] = dv
    body = {
        "status": worst_state(s["state"] for s in sessions.values()),
        "sessions": sessions,
        # platform / device_kind / device_count and the graph that serves
        "serving": app.get("serving", {}),
    }
    # broadcast fan-out plane: audience size next to session health —
    # a publisher with zero engine pressure can still be at viewer cap
    body["broadcast"] = _broadcast_gauges(app)
    if ov is not None:
        body["overload"] = {
            "pressure": round(ov.admission.pressure(), 4),
            "frozen": ov.admission.frozen,
            "draining": ov.draining,
        }
    if devtel_plane is not None:
        body["devtel"] = devtel_plane.health()
    guard = app.get("engine_guard")
    if guard is not None:
        # engine fault domain: QUARANTINED/REBUILDING here explains why
        # every session above just flipped to passthrough at once
        body["engine"] = guard.health()
    return web.json_response(body)


async def capacity(request):
    """Remaining session capacity for orchestrators (the worker sidecar
    publishes this instead of a boolean "ready").  ``capacity``: sessions
    this box will still admit (-1 = no structural bound); ``saturated``:
    admission is currently refusing; ``retry_after_s``: backpressure hint."""
    app = request.app
    sched = app.get("batch_scheduler")
    free = sched.free_slots if sched is not None else None
    ov = app.get("overload")
    if ov is None:
        body = {
            "capacity": free if free is not None else -1,
            "saturated": free == 0,
            "retry_after_s": 0.0,
        }
    else:
        # plane-level view: counts live ladders PLUS in-flight admission
        # reservations, so a burst of half-set-up offers is not double-sold
        body = ov.capacity(free_slots=free)
    # the process nonce rides the capacity feed: the worker publishes it
    # and the registry bumps the agent's epoch when it changes (a
    # recycled replacement on the same address is a NEW process)
    body["boot_id"] = app.get("boot_id", "")
    # viewer capacity is a SEPARATE pool from engine slots (ISSUE 17):
    # broadcast viewers never charge admission
    body["broadcast"] = _broadcast_gauges(app)
    guard = app.get("engine_guard")
    if guard is not None and guard.quarantined:
        # engine fault domain: a quarantined device plane admits NOTHING,
        # whatever the slot arithmetic says — saturate the feed so the
        # fleet router routes around this agent while it rebuilds
        body["saturated"] = True
        body["retry_after_s"] = guard.retry_after_s()
    if guard is not None:
        body["engine"] = guard.health()
    return web.json_response(body)


async def broadcast_pull(request):
    """Edge-pull trigger (fleet tier, docs/fleet.md): the router asks this
    agent to pull ONE copy of the publisher's stream from the OWNING agent
    (``{"owner_url": "http://host:port"}``) so local WHEP viewers fan out
    from here instead of all landing on the owner.  Idempotent while the
    same owner's pull is live; a new owner_url replaces the old pull."""
    app = request.app
    if not (
        env.broadcast_fanout_enabled() and env.broadcast_edge_pull_enabled()
    ):
        return web.Response(status=409, text="broadcast edge pull disabled")
    try:
        body = await request.json()
    except (ValueError, LookupError):
        return web.Response(status=400, text="invalid JSON body")
    owner_url = body.get("owner_url") if isinstance(body, dict) else None
    if not owner_url or not isinstance(owner_url, str):
        return web.Response(status=400, text="owner_url required")
    groups = app["state"].setdefault("broadcast_groups", {})
    puller = app["state"].get("edge_puller")
    if (
        puller is not None
        and not puller.closed
        and puller.owner_url == owner_url.rstrip("/")
    ):
        group = groups.get("edge")
        if group is not None and not group.closed:
            return web.json_response(
                {
                    "status": "exists",
                    "aus": puller.aus,
                    "viewers": group.viewer_count,
                }
            )
    from .broadcast import BroadcastGroup, EdgePuller

    provider = app["provider"]
    old_group = groups.pop("edge", None)
    if old_group is not None:
        await old_group.close()
    if puller is not None:
        await puller.close()
        app["state"]["edge_puller"] = None
    group = BroadcastGroup(
        "edge",
        width=getattr(provider, "default_width", 512),
        height=getattr(provider, "default_height", 512),
        use_h264=getattr(provider, "use_h264", None),
    )
    await group.start()  # AU mode: feed_au from the puller, no local sink
    try:
        puller = await EdgePuller(group, owner_url).open()
    except Exception as e:
        # native runtime missing, owner unreachable, or owner refused —
        # the viewer leg will fall back to the owning agent
        await group.close()
        return web.Response(status=502, text=f"edge pull failed: {e}")
    groups["edge"] = group
    app["state"]["edge_puller"] = puller
    return web.json_response(
        {"status": "pulling", "owner_url": puller.owner_url}
    )


async def drain(request):
    """Drain-for-recycle (fleet tier, docs/fleet.md): ``{"action":
    "freeze"}`` engages the overload plane's admission-freeze rung — new
    sessions 503, live sessions finish untouched, /capacity advertises
    ``draining`` so the fleet router stops routing here; ``unfreeze``
    reverts.  409 without the overload plane: there is no freeze rung to
    drain with (OVERLOAD_CONTROL=0)."""
    ov = request.app.get("overload")
    if ov is None:
        return web.Response(
            status=409,
            text="overload control disabled — no admission-freeze rung "
                 "to drain with",
        )
    try:
        body = await request.json()
    except (ValueError, LookupError):
        return web.Response(status=400, text="invalid JSON body")
    action = body.get("action") if isinstance(body, dict) else None
    if action not in ("freeze", "unfreeze"):
        return web.Response(status=400, text="action must be freeze|unfreeze")
    changed = ov.begin_drain() if action == "freeze" else ov.end_drain()
    return web.json_response({
        "draining": ov.draining,
        "changed": changed,
        "live_sessions": len(request.app.get("supervisors", {})),
    })


def _debug_error(status: int, message: str) -> web.Response:
    """Debug-surface errors are JSON bodies (tooling consumes these
    endpoints; an empty 200 or a bare text body reads as success to a
    naive ``jq`` pipeline)."""
    return web.json_response({"error": message}, status=status)


async def debug_flight(request):
    """The flight recorder's pull surface (docs/observability.md):

      GET /debug/flight                     index (sessions, snapshots)
      GET /debug/flight?session=<key>       live capture of a session
      GET /debug/flight?id=<snapshot-id>    stored post-mortem snapshot
      GET /debug/flight?journey=<jid>       journey fragment: every live
                                            capture + stored snapshot +
                                            recent devtel compiles bound
                                            to that fleet journey (the
                                            router's bundle fan-out
                                            pulls exactly this)
      &format=chrome | jsonl                Perfetto / grep renderings
    """
    flight = request.app.get("flight")
    if flight is None:
        return _debug_error(404, "flight recorder disabled")
    q = request.query
    unknown = sorted(k for k in q if k not in ("id", "session", "format",
                                               "journey"))
    if unknown:
        # a mistyped selector must not quietly serve the index as a 200
        return _debug_error(
            400, f"unknown query param(s): {', '.join(unknown)}"
        )
    fmt = q.get("format", "json")
    if fmt not in ("json", "chrome", "jsonl"):
        return _debug_error(400, f"unknown format {fmt!r}")
    if "journey" in q:
        if "id" in q or "session" in q:
            return _debug_error(
                400, "journey= is a selector of its own — drop id=/session="
            )
        if fmt != "json":
            return _debug_error(
                400, "journey fragments are JSON — the router's "
                     "/fleet/debug/journey endpoint renders the merged "
                     "chrome trace",
            )
        return _journey_fragment(request.app, flight, q["journey"])
    if "id" in q:
        snap = flight.get_snapshot(q["id"])
        if snap is None:
            return _debug_error(404, f"unknown snapshot {q['id']!r}")
    elif "session" in q:
        rec = flight.session(q["session"])
        if rec is None:
            return _debug_error(404, f"unknown session {q['session']!r}")
        snap = rec.snapshot(reason="on-demand")
    else:
        if fmt != "json":
            # the index is not a capture — a tooling URL whose id/session
            # variable expanded empty should fail loudly, not feed the
            # index dict to a Perfetto loader
            return _debug_error(
                400, "format= applies to a capture — pass id= or session="
            )
        return web.json_response(flight.index())
    if fmt == "chrome":
        from ..obs.export import to_chrome_trace

        return web.json_response(to_chrome_trace(snap))
    if fmt == "jsonl":
        from ..obs.export import to_jsonl

        return web.Response(
            text=to_jsonl(snap), content_type="application/x-ndjson"
        )
    return web.json_response(snap)  # fmt == "json", validated above


def _journey_fragment(app, flight, journey_id: str) -> web.Response:
    """This agent's share of a fleet journey: live captures of sessions
    bound to it, stored snapshots that carry it, and the recent devtel
    compiles — the one body the router's incident bundle pulls per
    agent.  404 when this agent holds no records for the journey (the
    router treats that as "this leg left nothing here")."""
    from ..obs.trace import safe_list

    sessions = {}
    for sid, rec in list(flight.sessions.items()):
        if (rec.journey or {}).get("journey_id") == journey_id:
            sessions[sid] = rec.snapshot(reason="journey-pull")
    snapshots = [
        s for s in safe_list(flight.snapshots)
        if (s.get("journey") or {}).get("journey_id") == journey_id
    ]
    if not sessions and not snapshots:
        return _debug_error(
            404, f"no records for journey {journey_id!r} on this agent"
        )
    fragment = {
        "agent": env.get_str("WORKER_ID") or "",
        "journey_id": journey_id,
        "sessions": sessions,
        "snapshots": snapshots,
    }
    devtel_plane = app.get("devtel")
    if devtel_plane is not None:
        # the device side of the incident (compile watchdog state) rides
        # the fragment so a frozen leg explains itself in one pull
        fragment["devtel"] = devtel_plane.fragment()
    return web.json_response(fragment)


async def debug_trace(request):
    """Start/stop the per-frame tracing window:

      GET  /debug/trace                       status
      POST /debug/trace {"action": "start", "duration_s": 30,
                         "jax_profiler_dir": "/tmp/tpu-trace"}  (dir opt-in)
      POST /debug/trace {"action": "stop"}

    Captures are bounded by TRACE_MAX_CAPTURE_S — a forgotten start can
    never leave per-frame allocation on forever.  The optional
    jax.profiler bridge opens a TPU trace over the same window: that one
    trace holds the device's ops under the model's named scopes and the
    program's ``rtc:`` host spans (obs/trace.py ``hop``) on one clock.
    The per-frame FrameTrace timeline stays a second file on the host's
    monotonic clock."""
    flight = request.app.get("flight")
    if flight is None:
        return web.Response(status=404, text="flight recorder disabled")
    if request.method == "GET":
        return web.json_response(flight.controller.status())
    try:
        body = await request.json()
    except (ValueError, LookupError):
        return web.Response(status=400, text="invalid JSON body")
    action = body.get("action")
    from ..obs import export as obs_export

    if action == "start":
        duration = body.get("duration_s")
        if duration is not None:
            try:
                duration = float(duration)
            except (TypeError, ValueError):
                return web.Response(
                    status=400, text="duration_s must be a number"
                )
        granted = flight.controller.start(duration)
        out = {"tracing": True, "duration_s": round(granted, 3)}
        jax_dir = body.get("jax_profiler_dir")
        if jax_dir:
            # profiler start touches the device runtime — off the loop
            err = await asyncio.to_thread(obs_export.start_jax_bridge, jax_dir)
            out["jax_profiler"] = err or f"tracing to {jax_dir}"
        return web.json_response(out)
    if action == "stop":
        flight.controller.stop()
        err = await asyncio.to_thread(obs_export.stop_jax_bridge)
        out = {"tracing": False}
        if err:
            out["jax_profiler"] = err
        return web.json_response(out)
    return web.Response(status=400, text="action must be start|stop")


async def demo(_):
    """Self-contained browser client for the /offer path — the reference
    depends on a hosted web app for this (ref docs/connect.md:3-5)."""
    path = os.path.join(os.path.dirname(__file__), "static", "demo.html")
    if not os.path.exists(path):
        return web.Response(status=404, text="demo page not bundled")
    return web.FileResponse(path)  # non-blocking file serving


async def metrics(request):
    out = request.app["stats"].snapshot()
    # per-session host-plane stage histograms (packetize/protect/send/recv
    # µs — ISSUE 2): native provider only; absent key means the provider
    # has no batched host plane, empty dict means no live sessions
    provider = request.app.get("provider")
    snapshot = getattr(provider, "host_plane_snapshot", None)
    if snapshot is not None:
        out["host_plane_sessions"] = snapshot()
    # overload control plane (resilience/overload.py): pressure, lag,
    # freshness percentiles, per-queue depth/shed — O(sessions) int reads,
    # so this endpoint stays cheap exactly when the box is drowning
    ov = request.app.get("overload")
    if ov is not None:
        out.update(ov.snapshot())
    # continuous batch scheduler (stream/scheduler.py): occupancy
    # histogram + window-wait percentiles — the cost-per-user story's
    # primary gauges, O(1) reads like everything else here
    sched = request.app.get("batch_scheduler")
    if sched is not None:
        out.update(sched.snapshot())
    # engine fault domain (resilience/engine_guard.py): trip/rebuild
    # counters + quarantine gauge + rebuild-latency percentiles
    eng = request.app.get("engine_guard")
    if eng is not None:
        out.update(eng.snapshot())
    # tracing / flight recorder (obs/): cheap int reads, like the overload
    # snapshot — observability endpoints must survive the incidents they
    # exist to explain
    flight = request.app.get("flight")
    if flight is not None:
        out["trace_enabled"] = int(flight.controller.active())
        out["flight_sessions"] = len(flight.sessions)
        out["flight_snapshots_stored"] = len(flight.snapshots)
    # stage-latency SLO plane (obs/slo.py): aggregate histograms summary
    # + breach counts — per-session burn state stays on /health
    slo_plane = request.app.get("slo")
    if slo_plane is not None:
        out.update(slo_plane.snapshot())
    # device telemetry (obs/devtel.py): compile watchdog counters, AOT
    # hit/miss/inventory, H2D/D2H bytes, device memory — cached int
    # reads (the memory sample refreshes on the ladder tick, never here)
    devtel_plane = request.app.get("devtel")
    if devtel_plane is not None:
        out.update(devtel_plane.snapshot())
    # broadcast fan-out plane (server/broadcast.py): aggregate audience
    # gauges + per-publisher-session group snapshots (drop counts, GOP
    # cache state, rewrite/send/freshness µs percentiles) — bounded by
    # publisher count, NEVER keyed by viewer (metric cardinality)
    out["broadcast"] = _broadcast_gauges(request.app)
    bsessions = {}
    for sid, g in request.app["state"].get("broadcast_groups", {}).items():
        if g.closed:
            continue
        snap = g.snapshot()
        snap.update(g.stats.stage_snapshot_us())
        bsessions[sid] = snap
    if bsessions:
        out["broadcast_sessions"] = bsessions
    fmt = request.query.get("format", "json")
    if fmt == "prom":
        # genuine Prometheus text exposition (obs/promexport.py): the
        # same scalars plus the SLO stage histograms with cumulative
        # le-buckets; the JSON body above stays the default
        from ..obs.promexport import CONTENT_TYPE, render

        return web.Response(
            body=render(out, slo=slo_plane).encode("utf-8"),
            headers={"Content-Type": CONTENT_TYPE},
        )
    if fmt != "json":
        return web.Response(status=400, text=f"unknown format {fmt!r}")
    return web.json_response(out)


class _TimedPipeline:
    """Wraps a pipeline with per-frame fps/latency accounting.

    Forwards the submit/fetch pipelined surface when the underlying pipeline
    has one, so VideoStreamTrack can keep PIPELINE_DEPTH frames in flight;
    latency is measured submit->fetch (the true glass-to-glass slice)."""

    def __init__(self, pipeline, stats: FrameStats):
        self._pipeline = pipeline
        self._stats = stats
        if hasattr(pipeline, "submit"):
            self.submit = self._submit
            self.fetch = self._fetch
        if hasattr(pipeline, "submit_batch"):
            self.submit_batch = self._submit_batch
            self.fetch_batch = self._fetch_batch

    def __getattr__(self, name):
        # delegate the rest of the pipeline surface (restart(), control
        # plane) — the hot-path methods are bound explicitly above so
        # delegation can't bypass the timing wrap
        if name == "_pipeline":  # not yet set — avoid recursion
            raise AttributeError(name)
        return getattr(self._pipeline, name)

    @property
    def frame_buffer_size(self) -> int:
        return int(getattr(self._pipeline, "frame_buffer_size", 1) or 1)

    def __call__(self, frame):
        t0 = time.monotonic()
        out = self._pipeline(frame)
        if not isinstance(out, ShedFrame):
            self._stats.record(time.monotonic() - t0)
        return out

    def _submit(self, frame):
        return self._pipeline.submit(frame), time.monotonic()

    def _fetch(self, handle, src_frame=None):
        inner, t_sub = handle
        out = self._pipeline.fetch(inner, src_frame)
        # a bounded-queue shed is submit-to-EVICTION time, not a latency
        # sample — recording it would collapse latency_p50 and inflate
        # fps exactly under overload, when the dashboard matters most
        if not isinstance(out, ShedFrame):
            self._stats.record(time.monotonic() - t_sub)
        return out

    def _submit_batch(self, frames):
        return self._pipeline.submit_batch(frames), time.monotonic()

    def _fetch_batch(self, handle, src_frames=None):
        inner, t_sub = handle
        outs = self._pipeline.fetch_batch(inner, src_frames)
        dt = time.monotonic() - t_sub
        # shed positions are submit-to-eviction time, not latency samples
        # (the single-frame rule above) — record only stepped outputs
        for o in outs:
            if not isinstance(o, ShedFrame):
                self._stats.record(dt)
        return outs


# ---------------------------------------------------------------------------
# app assembly
# ---------------------------------------------------------------------------

@web.middleware
async def cors_middleware(request, handler):
    """Allow-all CORS (replaces aiohttp_middlewares.cors_middleware —
    reference agent.py:459 — without the extra dependency)."""
    if request.method == "OPTIONS":
        resp = web.Response(status=200)
    else:
        resp = await handler(request)
    resp.headers.setdefault("Access-Control-Allow-Origin", "*")
    resp.headers.setdefault("Access-Control-Allow-Headers", "*")
    resp.headers.setdefault(
        "Access-Control-Allow-Methods", "GET,POST,DELETE,OPTIONS"
    )
    return resp


async def on_startup(app):
    if app["udp_ports"]:
        patch_loop_datagram(app["udp_ports"])

    # device telemetry (obs/devtel.py): activated BEFORE any model build
    # so every warmup compile (pipeline probe, AOT adoption, bucket
    # prewarm) is recorded in the warmup phase; DEVTEL_ENABLE=0 means no
    # plane, no listener, no hot-path residue.  The breach fan-out is
    # wired further down once the flight recorder exists; the phase
    # flips to "serving" at the END of startup — from there on, a
    # compile is a serve-time retrace breach.
    devtel_plane = None
    if env.devtel_enabled():
        from ..obs import devtel as _devtel
        from ..obs.devtel import DevTelPlane

        devtel_plane = _devtel.activate(DevTelPlane())
    app["devtel"] = devtel_plane

    # config overrides shared by both serving modes (no silent flag drops)
    overrides = {}
    if app.get("fbs", 0) > 1:
        overrides["frame_buffer_size"] = app["fbs"]
    if app.get("unet_cache", 0) >= 2:
        overrides["unet_cache_interval"] = app["unet_cache"]
    if app.get("mode") and app["mode"] != "img2img":
        overrides["mode"] = app["mode"]
    if app.get("annotator"):
        from ..models.registry import split_model_id

        if split_model_id(app["model_id"])[1] is None:
            raise ValueError("--annotator requires --controlnet")
        overrides["annotator"] = app["annotator"]
    if app.get("sp", 0) > 1:
        # --sp allocates an sp>1 mesh, but the token axis only actually
        # shards when the attention impl is ring/ulysses — any other impl
        # would make the flag a silent no-op computing single-chip on an
        # N-chip mesh (ADVICE r2).  Default to ring and say so.
        from ..stream.engine import current_attn_impl

        if current_attn_impl() not in ("ring", "ulysses"):
            overrides["attn_impl"] = "ring"
            logger.warning(
                "--sp %d: attention impl defaulted to 'ring' so the "
                "sequence axis shards over the sp mesh (set ATTN_IMPL="
                "ring|ulysses to choose explicitly)", app["sp"],
            )

    def _build_config():
        if not overrides:
            return None
        from ..models import registry as _registry

        return _registry.default_stream_config(app["model_id"], **overrides)

    built_scheduler = False  # an injected (test) scheduler is left as given
    if app.get("pipeline") is None:
        from ..stream.pipeline import StreamDiffusionPipeline

        mesh = None
        # MESH_SHAPE declares the serving mesh declaratively ("dp,tp,sp"):
        # tp/sp feed the pipeline mesh when the CLI flags are unset, dp
        # feeds the scheduler's session axis below (BATCHSCHED_DP reads it)
        mesh_dp, mesh_tp, mesh_sp = env.mesh_shape()
        tp = app.get("tp", 0) or mesh_tp
        sp = app.get("sp", 0) or mesh_sp
        if tp > 1 or sp > 1:
            from ..parallel import mesh as M

            mesh = M.make_mesh(tp=max(1, tp), sp=max(1, sp))
            if env.batchsched_dp() > 1:
                # a tp/sp mesh keeps the shared-engine path, which has no
                # session axis to shard — a declared dp would otherwise
                # vanish into a silent ~dp-x capacity loss (dp x tp/sp
                # compound meshes are ROADMAP follow-up work)
                logger.warning(
                    "MESH_SHAPE/BATCHSCHED_DP dp=%d IGNORED: tp=%d/sp=%d "
                    "route serving through the shared-engine mesh path, "
                    "which does not shard the session axis — drop the "
                    "tp/sp axes to use the dp-sharded scheduler",
                    env.batchsched_dp(), tp, sp,
                )
        app["pipeline"] = StreamDiffusionPipeline(
            app["model_id"],
            config=_build_config(),
            mesh=mesh,
        )
        # Continuous batch scheduler (stream/scheduler.py): the DEFAULT
        # serving path — concurrent sessions coalesce into one vmapped
        # device step instead of serializing through the shared engine.
        # BATCHSCHED=0 kill-switch restores the shared pipeline; tp/sp
        # meshes keep it (those axes shard the MODEL, not the sessions).
        # With BATCHSCHED_DP=N (or a MESH_SHAPE dp axis) the scheduler's
        # session axis shards over a dp mesh of N devices (ISSUE 12) and
        # --fbs rides THROUGH the scheduler as a second batching
        # dimension (consecutive frames per session row); UNET_CACHE and
        # QUANT_WEIGHTS serve through it too (ISSUE 9) — parity pinned
        # by tests/batchsched_equiv_driver.py.
        if (
            app.get("batch_scheduler") is None
            and env.batchsched_enabled()
            and mesh is None
        ):
            from ..stream.scheduler import BatchScheduler

            # per-session style adapters (adapters/, ISSUE 20): load the
            # ADAPTER_DIR catalog against THIS pipeline's UNet and bind its
            # factor bank into the scheduler's stacked state.  With
            # BATCHSCHED on, a scheduler (or catalog) that cannot be built
            # is a failed boot — never a quiet switch to another plane.
            adapters = None
            adir = env.adapter_dir()
            if adir:
                from ..adapters import build_registry

                pipe = app["pipeline"]
                adapters = build_registry(
                    pipe.engine.params["unet"], pipe._bundle.unet_cfg,
                    adir,
                )
            app["batch_scheduler"] = BatchScheduler.from_pipeline(
                app["pipeline"], dp=env.batchsched_dp(),
                adapters=adapters,
            )
            built_scheduler = True
    app["pcs"] = set()
    app["supervisors"] = {}
    app["stream_event_handler"] = StreamEventHandler()
    app["state"] = {
        "source_track": None,
        "source_relay": None,
        "whip_pcs": {},
        "whip_tracks": {},
        "whip_relays": {},
        "whep_pcs": {},
        # publisher session id -> BroadcastGroup (server/broadcast.py):
        # the shared TX plane every broadcast viewer of that publisher
        # rides; "edge" holds the pulled-stream group on edge agents
        "broadcast_groups": {},
    }
    app["stats"] = FrameStats()
    if devtel_plane is not None:
        # breaches land as retrace_breaches_total in the shared gauges
        devtel_plane.stats = app["stats"]
    # media-plane providers share the agent's gauges so /metrics carries
    # decode/encode/glass-to-glass stages next to submit->fetch latency
    if hasattr(app["provider"], "attach_stats"):
        app["provider"].attach_stats(app["stats"])
    # stage-latency SLO plane (obs/slo.py): always-on per-hop budget
    # tracking fed by the tracer mint path below; SLO_ENABLE=0 restores
    # the PR-5 hot path exactly.  Built BEFORE the recorder so every
    # session tracer is born with the feed attached.
    slo_plane = None
    if env.slo_enabled() and env.get_bool("FLIGHT_RECORDER", True):
        from ..obs.slo import SloPlane

        slo_plane = SloPlane(stats=app["stats"])
        loop = asyncio.get_event_loop()
        handler = app["stream_event_handler"]

        def _slo_breach(session_key, stage, state, info):
            rec = (
                app["flight"].session(session_key)
                if app.get("flight") is not None
                else None
            )
            if rec is not None:
                rec.event("slo", stage=stage, state=state, **info)
            if state != "breach":
                return
            recent = rec.recent_events() if rec is not None else None
            reason = (
                f"slo breach: {stage} over {info['budget_ms']}ms budget "
                f"(burn fast={info['burn_fast']} slow={info['burn_slow']})"
            )

            def fire():
                # rides the StreamDegraded webhook path so orchestrators
                # hear about a blown budget without polling /health
                handler.handle_session_state(
                    session_key, "", "SLO_BREACH", reason,
                    recent_events=recent,
                    journey=_journey_of(app, session_key),
                )

            try:  # tick may one day run off-loop; webhooks belong on it
                loop.call_soon_threadsafe(fire)
            except RuntimeError:
                pass  # loop already closed (teardown race)

        slo_plane.on_breach = _slo_breach
        await slo_plane.start()
    app["slo"] = slo_plane
    # flight recorder + frame tracing (obs/): the black box every session
    # writes into; FLIGHT_RECORDER=0 removes the whole subsystem (and the
    # /debug endpoints 404) — including the SLO plane's feed
    if env.get_bool("FLIGHT_RECORDER", True):
        flight = FlightRecorder(stats=app["stats"], slo=slo_plane)
        app["flight"] = flight

        def _webhook_emitted(event_name, stream_id):
            rec = flight.session(stream_id)
            if rec is not None:
                rec.event("webhook", event=event_name)

        app["stream_event_handler"].on_emit = _webhook_emitted
    else:
        app["flight"] = None
    if devtel_plane is not None:
        # serve-time retrace breach -> the existing alert path: an event
        # in EVERY live session's black box (the compile froze all of
        # them), a StreamDegraded-style webhook (state=RETRACE_BREACH),
        # and the FrameStats counter wired above (retrace_breaches_total
        # at /metrics, incl. ?format=prom)
        loop = asyncio.get_event_loop()
        handler = app["stream_event_handler"]

        def _retrace_breach(info):
            flight = app.get("flight")
            if flight is not None:
                for rec in list(flight.sessions.values()):
                    rec.event("retrace", **info)
            reason = (
                f"serve-time retrace: {info['context']} compiled "
                f"{info['duration_ms']}ms after prewarm completed"
            )

            def fire():
                handler.handle_session_state(
                    "device-telemetry", "", "RETRACE_BREACH", reason
                )

            try:  # the compile listener fires on worker threads
                loop.call_soon_threadsafe(fire)
            except RuntimeError:
                pass  # loop already closed (teardown race)

        devtel_plane.on_breach = _retrace_breach
    # overload control plane: admission, lag watchdog, shedding ladders
    # (OVERLOAD_CONTROL=0 restores the pre-overload-plane agent)
    if env.get_bool("OVERLOAD_CONTROL", True):
        ov = OverloadControlPlane(app["stats"])
        app["overload"] = ov
        if app["flight"] is not None:
            flight = app["flight"]

            def _overload_event(session_key, kind, **data):
                rec = flight.session(session_key)
                if rec is not None:
                    rec.event(kind, **data)

            ov.on_event = _overload_event
        await ov.start()
    else:
        app["overload"] = None
    sched = app.get("batch_scheduler")
    if sched is not None and app["overload"] is not None:
        # overload joins at batch composition: the admission step-EWMA is
        # fed PER-BATCH-AMORTIZED latency (dt / occupancy), so advertised
        # capacity reflects the batching gain — N coalesced sessions cost
        # one step, not N (the resilient wrapper skips its own raw feed
        # for scheduler sessions: owns_step_signal)
        admission = app["overload"].admission
        sched.on_step = lambda dt_s, occ: admission.note_step_latency(dt_s)
    if (
        sched is not None
        and hasattr(sched, "attach_guard")  # duck-typed test schedulers
        and env.get_bool("ENGINE_GUARD", True)
    ):
        # engine fault domain (resilience/engine_guard.py): every device
        # dispatch now rides the guard's step deadline; a trip quarantines
        # the whole plane (sessions passthrough, admission refuses), the
        # rebuild loop restores it bit-exact from the snapshot bank, and
        # exhaustion self-evacuates through the fleet router.  Transition
        # callbacks fire on guard worker threads — webhooks hop to the
        # loop exactly like the retrace-breach path above.
        loop = asyncio.get_event_loop()
        handler = app["stream_event_handler"]

        def _engine_transition(event_name, info):
            extra = {
                k: v
                for k, v in info.items()
                if k not in ("state", "reason")
            }

            def fire():
                handler.handle_engine_state(
                    event_name,
                    info.get("state", ""),
                    reason=str(info.get("reason", "")),
                    **extra,
                )

            try:  # guard trips/rebuilds happen off-loop
                loop.call_soon_threadsafe(fire)
            except RuntimeError:
                pass  # loop already closed (teardown race)

        app["engine_guard"] = EngineGuard(
            sched,
            on_transition=_engine_transition,
            on_exhausted=lambda: _evacuate_agent(app),
        )
    if built_scheduler:
        # the last warm-up act: compile the small eager programs around the
        # bucket steps NOW (the guard is attached, so the snapshot bank's
        # are in), and the first real session compiles nothing
        sched.rehearse()
    app["serving"] = _serving_info(app)
    logger.info("serving: %s", json.dumps(app["serving"], sort_keys=True))
    if devtel_plane is not None:
        if app["overload"] is not None:
            # device-memory snapshot rides the ladder tick (rate-limited
            # by DEVTEL_MEM_INTERVAL_S on the plane's side); with the
            # overload plane off, snapshot() samples lazily instead
            app["overload"].on_tick = devtel_plane.sample_memory
        # startup is done: pipeline built, AOT adopted, buckets
        # prewarmed — any compile from here on is a serve-time retrace.
        # (With BATCHSCHED=0 or BATCHSCHED_PREWARM=0 the lazily compiled
        # first step WILL be reported: that config genuinely does
        # compile at serve time, and the watchdog's job is to say so.)
        devtel_plane.serving()


def _serving_info(app) -> dict:
    """What serves, for ``/health`` and the start-up log: the device as JAX
    reports it, the serving plane, and the graph variant that plane was
    built with — dtype, attention implementation, fused epilogue, and per
    prewarmed bucket executable the Mosaic kernels found in its compiled
    HLO.  Read once at the end of startup; nothing here changes while the
    process serves."""
    from ..utils.device import device_info

    info = device_info()
    sched = app.get("batch_scheduler")
    info["model_id"] = app["model_id"]
    info["plane"] = "batchsched" if sched is not None else "shared-engine"
    # injected test doubles carry no stream config
    cfg = getattr(app["pipeline"], "config", None)
    if cfg is not None:
        from ..stream.engine import current_attn_impl

        info["height"], info["width"] = cfg.height, cfg.width
        info["t_index_list"] = list(cfg.t_index_list)
        info["num_inference_steps"] = cfg.num_inference_steps
        info["dtype"] = cfg.dtype
        info["attn_impl"] = cfg.attn_impl or current_attn_impl()
        info["fused_epilogue"] = bool(cfg.use_fused_epilogue)
    kernels = getattr(sched, "mosaic_kernels", None)
    if kernels is not None:
        info["mosaic_kernels"] = dict(kernels)
        info["attention_paths"] = dict(sched.attention_paths)
        info["f32_relayout_copies"] = dict(sched.relayout_copies)
    return info


def _evacuate_agent(app):
    """Self-evacuation client (engine fault domain): on rebuild
    exhaustion the guard calls this from its daemon thread — ask the
    fleet router to move every live session off this agent (``POST
    /fleet/evacuate``, fleet/router.py migrate-places them on healthy
    agents) and park this agent FAILED.  Synchronous stdlib HTTP on
    purpose: the loop may be wedged along with the device, and the
    AgentEvacuating webhook has already fired — an unset EVACUATE_URL
    just means no router-driven move (standalone agent)."""
    url = env.get_str("EVACUATE_URL")
    if not url:
        return
    import urllib.request

    guard = app.get("engine_guard")
    payload = json.dumps(
        {
            "agent": env.get_str("WORKER_ID") or "",
            "reason": (guard.last_trip_reason or "") if guard else "",
        }
    ).encode("utf-8")
    headers = {"Content-Type": "application/json"}
    token = env.get_str("AUTH_TOKEN")
    if token:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(url, data=payload, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=10.0) as resp:
            logger.warning(
                "self-evacuation accepted by router (%d)", resp.status
            )
    except Exception:
        logger.exception("self-evacuation POST failed (%s)", url)


async def on_shutdown(app):
    devtel_plane = app.get("devtel")
    if devtel_plane is not None:
        from ..obs import devtel as _devtel

        _devtel.deactivate(devtel_plane)
    slo_plane = app.get("slo")
    if slo_plane is not None:
        slo_plane.stop()
    ov = app.get("overload")
    if ov is not None:
        ov.stop()
    for sup in app.get("supervisors", {}).values():
        sup.stop()
    app.get("supervisors", {}).clear()
    pcs = app["pcs"]
    await asyncio.gather(*[pc.close() for pc in pcs])
    pcs.clear()
    if "state" in app:
        for relay in app["state"].get("whip_relays", {}).values():
            relay.stop()
        puller = app["state"].get("edge_puller")
        if puller is not None:
            await puller.close()
        groups = app["state"].get("broadcast_groups", {})
        await asyncio.gather(*[g.close() for g in groups.values()])
        groups.clear()
    sched = app.get("batch_scheduler")
    if sched is not None:
        for entry in app.get("imported_sessions", {}).values():
            # unadopted migrated-in sessions die with the scheduler
            sess = entry.get("session")
            if sess is not None:
                try:
                    sess.release()
                except Exception:
                    logger.exception("releasing imported session failed")
        app.get("imported_sessions", {}).clear()
        guard = app.get("engine_guard")
        if guard is not None:
            guard.close()
        sched.close()


def build_app(
    model_id: str = "stabilityai/sd-turbo",
    udp_ports=None,
    pipeline=None,
    provider=None,
    controlnet: str | None = None,
    annotator: str | None = None,
    batch_scheduler=None,
    tp: int = 0,
    sp: int = 0,
    fbs: int = 0,
    mode: str = "img2img",
    unet_cache: int = 0,
) -> web.Application:
    app = web.Application(middlewares=[cors_middleware])
    app["udp_ports"] = udp_ports
    if controlnet:
        # one id names base + side network from here on: the pipeline, the
        # scheduler's AOT keys and snapshot fingerprint, /health
        from ..models.registry import compose_model_id

        model_id = compose_model_id(model_id, controlnet)
    app["model_id"] = model_id
    app["annotator"] = annotator
    app["pipeline"] = pipeline  # injectable for tests; built on startup if None
    app["batch_scheduler"] = batch_scheduler  # injectable for tests
    app["tp"] = tp
    app["sp"] = sp
    app["fbs"] = fbs
    app["mode"] = mode
    app["unet_cache"] = unet_cache
    app["provider"] = provider or get_provider()
    # fleet journey correlation (fleet/journey.py): session -> binding
    # threaded off the router's X-Journey-Id header; JOURNEY_ENABLE=0
    # makes the agent ignore the headers entirely
    app["journey_enabled"] = env.journey_enabled()
    app["journey_map"] = {}
    # migrated-in sessions parked by /migrate/import until the client's
    # re-offer adopts them (X-Migrated-Session); TTL'd with their
    # admission reservations
    app["imported_sessions"] = {}
    # per-process nonce: rides /capacity so the fleet registry can tell
    # a recycled replacement from the process it replaced (epoch bump)
    app["boot_id"] = uuid.uuid4().hex[:12]
    app["recycling"] = False

    app.on_startup.append(on_startup)
    # handoff adoption runs LAST in startup — planes exist, socket not
    # yet bound: a replacement that answers /health has already parked
    # its predecessor's sessions (the upgrade sweep's prewarm gate)
    app.on_startup.append(_import_handoff)
    app.on_shutdown.append(on_shutdown)

    app.router.add_post("/whip", whip)
    app.router.add_delete("/whip", whip)
    app.router.add_delete("/whip/{session}", whip)
    app.router.add_post("/whep", whep)
    app.router.add_delete("/whep", whep)
    app.router.add_delete("/whep/{session}", whep)
    app.router.add_post("/broadcast/pull", broadcast_pull)
    app.router.add_post("/offer", offer)
    app.router.add_post("/config", update_config)
    app.router.add_get("/", health)
    app.router.add_get("/health", health_detail)
    app.router.add_get("/capacity", capacity)
    app.router.add_post("/drain", drain)
    app.router.add_get("/migrate/export", migrate_export)
    app.router.add_post("/migrate/import", migrate_import)
    app.router.add_post("/admin/recycle", admin_recycle)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_post("/debug/trace", debug_trace)
    app.router.add_get("/demo", demo)
    return app


def main(argv=None):
    parser = argparse.ArgumentParser(description="Run agent")
    parser.add_argument(
        "--model-id",
        default="stabilityai/sd-turbo",
        help="HuggingFace model ID (sd15 / sd-turbo / sdxl-turbo families)",
    )
    parser.add_argument("--port", default=8888, type=int, help="HTTP signaling port")
    parser.add_argument(
        "--udp-ports", default=None, help="comma-separated UDP media ports"
    )
    parser.add_argument(
        "--controlnet",
        default=None,
        help="optional ControlNet model id: serves <model-id>+<this id>, the "
        "edge-conditioned stream, on whichever plane serves (POST /config "
        '{"controlnet_scale": x} turns the control up or down)',
    )
    parser.add_argument(
        "--annotator",
        default=None,
        choices=["canny", "hed", "identity"],
        help="ControlNet conditioning processor (default canny; hed = the "
        "reference's detector, in-graph, weights from lllyasviel/Annotators)",
    )
    parser.add_argument(
        "--tp",
        default=0,
        type=int,
        metavar="N",
        help="tensor-parallel serving over N chips (Megatron-style UNet "
        "sharding, psums over ICI); 0 = single chip",
    )
    parser.add_argument(
        "--sp",
        default=0,
        type=int,
        metavar="N",
        help="sequence-parallel serving over N chips (latent tokens over "
        "the sp axis; pair with ATTN_IMPL=ring or ulysses); 0 = off",
    )
    parser.add_argument(
        "--fbs",
        default=0,
        type=int,
        metavar="N",
        help="frame_buffer_size: batch N consecutive frames per device "
        "step (throughput up, +N frames latency); 0 = per-frame",
    )
    parser.add_argument(
        "--mode",
        default="img2img",
        choices=["img2img", "txt2img"],
        help="txt2img ignores incoming pixels and generates from the "
        "prompt each tick (reference txt2img dispatch, "
        "lib/wrapper.py:236-260)",
    )
    parser.add_argument(
        "--unet-cache",
        default=0,
        type=int,
        metavar="N",
        help="DeepCache interval: full UNet every Nth frame, outermost-"
        "tier-only between (cached step ~0.54x FLOPs at 512^2; equivalent "
        "env UNET_CACHE=N); 0 = off",
    )
    parser.add_argument(
        "--log-level",
        default="INFO",
        choices=["DEBUG", "INFO", "WARNING", "ERROR", "CRITICAL"],
    )
    parser.add_argument(
        "--profile-port",
        default=0,
        type=int,
        help="start a jax.profiler trace server on this port (tensorboard-"
        "connectable; the nvtx/pynvml analog, SURVEY sec.5)",
    )
    args = parser.parse_args(argv)
    logging.basicConfig(level=args.log_level.upper())
    # the program runs where JAX_PLATFORMS says; unset means the TPU, and a
    # silent CPU fallback exits here instead of serving (utils/device.py)
    from ..utils.device import require_device

    require_device()
    if args.profile_port:
        from ..utils.profiling import start_profiler_server

        start_profiler_server(args.profile_port)
        logging.getLogger(__name__).info(
            "jax profiler server on :%d", args.profile_port
        )

    app = build_app(
        model_id=args.model_id,
        udp_ports=args.udp_ports.split(",") if args.udp_ports else None,
        controlnet=args.controlnet,
        annotator=args.annotator,
        tp=args.tp,
        sp=args.sp,
        fbs=args.fbs,
        mode=args.mode,
        unet_cache=args.unet_cache,
    )
    web.run_app(app, host="0.0.0.0", port=args.port)


if __name__ == "__main__":
    main()
