"""Overload control plane units (resilience/overload.py): bounded deadline
queues, admission, lag watchdog, shedding ladder, O(sessions) snapshots —
all on injected clocks, no wall-time sleeps."""

import asyncio
import json
import threading

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_rtc_agent_tpu.resilience.overload import (
    RUNG_FROZEN,
    RUNG_PASSTHROUGH,
    AdmissionController,
    DeadlineQueue,
    OverloadControlPlane,
    OverloadLadder,
)
from ai_rtc_agent_tpu.resilience.supervisor import (
    DEGRADED,
    HEALTHY,
    RECOVERING,
    ResilientPipeline,
    SessionSupervisor,
)


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# DeadlineQueue
# ---------------------------------------------------------------------------

def test_deadline_queue_sheds_oldest_on_overflow():
    clock = Clock()
    sheds = []
    q = DeadlineQueue(bound=3, clock=clock, on_shed=lambda r, n: sheds.append((r, n)))
    for i in range(5):
        q.push(i)
    assert q.depth == 3
    assert q.shed_overflow == 2
    assert sheds == [("overflow", 1), ("overflow", 1)]
    # freshest-frame-wins: the two OLDEST entries (0, 1) were shed
    assert [q.pop()[0] for _ in range(3)] == [2, 3, 4]
    assert q.pop() is None


def test_deadline_queue_pop_sheds_stale_entries():
    clock = Clock()
    q = DeadlineQueue(bound=8, deadline_s=0.5, clock=clock)
    q.push("old")
    clock.tick(0.6)  # "old" is now past its deadline
    q.push("fresh")
    item, stamp = q.pop()
    assert item == "fresh"
    assert q.shed_stale == 1
    assert q.shed_overflow == 0


def test_deadline_queue_all_stale_returns_none():
    clock = Clock()
    q = DeadlineQueue(bound=4, deadline_s=0.1, clock=clock)
    q.push("a")
    q.push("b")
    clock.tick(1.0)
    assert q.pop() is None
    assert q.shed_stale == 2
    assert q.depth == 0


def test_deadline_queue_never_blocks_push():
    q = DeadlineQueue(bound=1)
    for i in range(100):
        q.push(i)  # returns immediately, sheds synchronously
    assert q.depth == 1
    assert q.shed_overflow == 99


# ---------------------------------------------------------------------------
# AdmissionController
# ---------------------------------------------------------------------------

def test_admission_pressure_is_max_of_signals():
    a = AdmissionController(step_budget_s=1.0, lag_budget_s=0.1)
    assert a.pressure() == 0.0
    a.note_step_latency(0.5)
    assert a.pressure() == pytest.approx(0.5)
    a.note_loop_lag(0.2)  # 2x the lag budget dominates
    assert a.pressure() == pytest.approx(2.0)


def test_admission_refuses_over_budget_with_retry_after():
    a = AdmissionController(step_budget_s=0.1, retry_after_s=2.0)
    ok, _ = a.admit()
    assert ok
    a.note_step_latency(0.4)  # 4x budget
    ok, retry_after = a.admit()
    assert not ok
    assert retry_after == pytest.approx(8.0)  # base * pressure, capped at 8x
    assert a.rejected == 1


def test_admission_retry_after_clamps():
    a = AdmissionController(step_budget_s=0.01, retry_after_s=2.0)
    a.note_step_latency(10.0)  # 1000x over budget
    assert a.retry_after_s() == pytest.approx(16.0)  # 8x cap


def test_admission_session_cap():
    a = AdmissionController(max_sessions=2)
    assert a.admit(live_sessions=1)[0]
    ok, retry_after = a.admit(live_sessions=2)
    assert not ok and retry_after > 0


def test_admission_freeze_holds_compose():
    a = AdmissionController()
    a.hold_freeze()
    a.hold_freeze()
    assert not a.admit()[0]
    a.release_freeze()
    assert a.frozen  # one hold still out
    a.release_freeze()
    assert a.admit()[0]
    a.release_freeze()  # over-release never goes negative
    assert not a.frozen


def test_admission_step_timeout_registers_as_severe():
    a = AdmissionController(step_budget_s=1.0)
    a.note_step_timeout(1.5)
    assert a.pressure() == pytest.approx(3.0)  # 2x the blown budget


def test_capacity_shapes():
    a = AdmissionController(max_sessions=4)
    assert a.capacity(live_sessions=1) == {
        "capacity": 3, "saturated": False, "retry_after_s": 0.0,
    }
    # the TIGHTEST structural bound wins: advertising engine slots beyond
    # the session-cap headroom would oversell (admit() 503s the excess)
    assert a.capacity(live_sessions=1, free_slots=7)["capacity"] == 3
    assert a.capacity(live_sessions=1, free_slots=2)["capacity"] == 2
    # at the structural cap: admit() refuses, so /capacity must say
    # saturated too (an orchestrator reading it never routes to a 503)
    cap = a.capacity(live_sessions=4)
    assert cap == {
        "capacity": 0, "saturated": True,
        "retry_after_s": a.retry_after_base_s,
    }
    a.note_loop_lag(1e9)
    cap = a.capacity(live_sessions=1, free_slots=7)
    assert cap["capacity"] == 0 and cap["saturated"]
    assert cap["retry_after_s"] > 0
    # unbounded box: -1, not a made-up number
    b = AdmissionController()
    assert b.capacity()["capacity"] == -1


def test_capacity_slot_exhaustion_is_saturated():
    """Review finding: a slot-exhausted box (free_slots=0) with
    pressure under budget and no session cap reported saturated=False —
    an orchestrator routing on the flag would send a session straight
    into /offer's 'all ... slots in use' 503."""
    a = AdmissionController()  # no cap, no pressure
    cap = a.capacity(live_sessions=4, free_slots=0)
    assert cap["capacity"] == 0
    assert cap["saturated"] is True
    assert cap["retry_after_s"] == a.retry_after_base_s
    # headroom left -> not saturated
    assert a.capacity(live_sessions=3, free_slots=1)["saturated"] is False


# ---------------------------------------------------------------------------
# OverloadLadder
# ---------------------------------------------------------------------------

def _ladder(sup=None, clock=None, **kw):
    a = AdmissionController(step_budget_s=1.0)
    kw.setdefault("up_after", 2)
    kw.setdefault("down_after", 3)
    return OverloadLadder("s", a, sup, clock=clock or Clock(), **kw), a


def test_ladder_escalates_with_hysteresis():
    ladder, _ = _ladder()
    ladder.tick(True)
    assert ladder.rung == 0  # one hot tick is not sustained pressure
    ladder.tick(True)
    assert ladder.rung == 1
    ladder.tick(True)
    ladder.tick(True)
    assert ladder.rung == 2
    # a single quiet tick resets the climb but does not descend
    ladder.tick(False)
    assert ladder.rung == 2
    ladder.tick(False)
    ladder.tick(False)
    assert ladder.rung == 1  # down_after=3 quiet ticks -> one rung down


def test_ladder_passthrough_rung_degrades_supervisor_once():
    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    ladder, _ = _ladder(sup=sup, clock=clock)
    for _ in range(2 * RUNG_PASSTHROUGH):
        ladder.tick(True)
    assert ladder.rung == RUNG_PASSTHROUGH
    assert sup.state == DEGRADED
    assert "overload" in sup.snapshot()["reason"]
    # no restart budget was spent — this is capacity, not a fault
    assert sup.snapshot()["restarts"] == 0


def test_ladder_top_rung_freezes_admission_and_close_releases():
    ladder, adm = _ladder()
    for _ in range(2 * RUNG_FROZEN):
        ladder.tick(True)
    assert ladder.rung == RUNG_FROZEN
    assert adm.frozen
    ladder.close()
    assert not adm.frozen


def test_ladder_unfreezes_on_deescalation():
    ladder, adm = _ladder()
    for _ in range(2 * RUNG_FROZEN):
        ladder.tick(True)
    assert adm.frozen
    for _ in range(3):
        ladder.tick(False)
    assert ladder.rung == RUNG_FROZEN - 1
    assert not adm.frozen


def test_ladder_skip_ratios_and_probe_rung():
    clock = Clock()
    ladder, _ = _ladder(clock=clock, probe_interval_s=1.0)
    ladder.rung = 1  # skip2: every 2nd frame processes
    admitted = sum(ladder.admit_frame() for _ in range(10))
    assert admitted == 5
    assert ladder.frames_skipped == 5
    ladder.rung = RUNG_PASSTHROUGH  # probe-only
    assert ladder.admit_frame()  # first probe fires immediately
    assert not ladder.admit_frame()  # inside the probe interval
    clock.tick(1.1)
    assert ladder.admit_frame()


def test_supervisor_recovers_from_overload_degrade_via_ok_steps():
    clock = Clock()
    sup = SessionSupervisor(
        "s", clock=clock, sleep=lambda s: None, healthy_after=2
    )
    sup.note_overload("overload shedding: passthrough")
    assert sup.state == DEGRADED
    # probe steps succeed while shedding continues: the hold keeps the
    # session DEGRADED — a fast probe proves nothing about capacity
    sup.on_step_ok(0.01)
    assert sup.state == DEGRADED
    # ladder de-escalates below passthrough: hold released, real steps
    # walk the session back through RECOVERING to HEALTHY
    sup.note_overload_clear()
    sup.on_step_ok(0.01)
    assert sup.state == RECOVERING  # frames flowing again
    sup.on_step_ok(0.01)
    sup.on_step_ok(0.01)
    assert sup.state == HEALTHY


def test_passthrough_probe_cadence_not_halved_by_supervisor_throttle():
    """Review finding: at the passthrough rung the ladder's probe token
    (one per OVERLOAD_PROBE_S) was consumed by the pipeline's _admit_frame
    and then discarded by the supervisor's own DEGRADED probe throttle
    (2s default) — every probe landing inside the supervisor's window was
    burned, halving the cadence to exactly the stale-decay threshold and
    starving the step EWMA the probes exist to feed.  While the overload
    hold is set, the ladder owns the probe cadence and the supervisor
    gate must admit."""
    clock = Clock(100.0)
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    ladder, adm = _ladder(sup=sup, clock=clock, up_after=1)
    while ladder.rung < RUNG_PASSTHROUGH:
        ladder.tick(True)
    assert sup.state == DEGRADED  # overload hold set by note_overload
    probes = 0
    for _ in range(10):
        clock.tick(ladder.probe_interval_s)
        if ladder.admit_frame() and sup.should_try_engine():
            probes += 1
    assert probes == 10  # every ladder probe reaches the engine
    # a REAL wedge during shedding: recovery owns the engine — the
    # pipeline-level gate refuses BEFORE the ladder token is consumed,
    # so the probe fires the moment recovery releases instead of
    # waiting out a fresh interval
    rp = ResilientPipeline(lambda f: f, sup, warm_steps=0)
    rp.throttle = ladder
    try:
        sup._recovery_pending = True
        clock.tick(ladder.probe_interval_s)
        token_at = ladder._next_probe
        assert rp("src") == "src"  # passthrough, engine untouched
        assert ladder._next_probe == token_at  # probe token preserved
        sup._recovery_pending = False
        assert rp._admit_frame() and sup.should_try_engine()
    finally:
        rp.close()


# ---------------------------------------------------------------------------
# ResilientPipeline x throttle
# ---------------------------------------------------------------------------

def test_resilient_pipeline_throttle_sheds_to_passthrough():
    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    calls = []
    rp = ResilientPipeline(
        lambda f: calls.append(f) or ("processed", f), sup, warm_steps=0
    )
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        ladder.rung = 1  # skip2
        outs = [rp(i) for i in range(4)]
        assert len(calls) == 2  # half the frames ran the engine
        assert ("processed", 1) in outs and 0 in outs  # passthrough = source
        assert sup.passthrough_frames == 2
        # processed steps fed the admission EWMA
        assert adm.step_ewma.samples == 2
    finally:
        rp.close()


def test_resilient_pipeline_timeout_feeds_admission():
    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    wedge = threading.Event()
    rp = ResilientPipeline(
        lambda f: wedge.wait(5), sup, step_timeout_s=0.05,
        first_step_timeout_s=0.05, warm_steps=0,
    )
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        out = rp("src")
        assert out == "src"  # passthrough, not a hang
        assert adm.step_ewma.value == pytest.approx(0.1)  # 2x the budget
    finally:
        wedge.set()
        rp.close()


def test_warm_up_steps_never_feed_admission():
    """Review finding: the first steps of a session carry the JAX compile
    (tens of seconds by design — first_step_timeout_s exists for them);
    feeding them to the admission EWMA pinned pressure far over budget on
    EVERY cold start, 503ing concurrent offers.  Only steady-state steps
    measure capacity — for both completed steps and blown ones."""
    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    rp = ResilientPipeline(lambda f: ("processed", f), sup, warm_steps=2)
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        rp(0)
        rp(1)
        assert adm.step_ewma.samples == 0  # compile-sized, not capacity
        rp(2)
        assert adm.step_ewma.samples == 1  # steady state measures
    finally:
        rp.close()

    # a blown WARM-UP step is a fault (restart), not a capacity signal
    sup2 = SessionSupervisor("s2", clock=clock, sleep=lambda s: None)
    wedge = threading.Event()
    rp2 = ResilientPipeline(
        lambda f: wedge.wait(5), sup2, step_timeout_s=0.05,
        first_step_timeout_s=0.05, warm_steps=2,
    )
    ladder2, adm2 = _ladder(clock=clock)
    rp2.throttle = ladder2
    try:
        assert rp2("src") == "src"
        assert adm2.step_ewma.samples == 0
    finally:
        wedge.set()
        rp2.close()


# ---------------------------------------------------------------------------
# OverloadControlPlane: registry, tick, O(sessions) snapshot
# ---------------------------------------------------------------------------

def test_plane_tick_drives_all_ladders(monkeypatch):
    monkeypatch.setenv("OVERLOAD_UP_TICKS", "1")
    plane = OverloadControlPlane()
    a = plane.register_session("a")
    b = plane.register_session("b")
    plane.admission.note_step_latency(1e9)  # pressure >> 1
    plane.tick()
    assert a.rung == 1 and b.rung == 1
    plane.unregister_session("a")
    plane.tick()
    assert a.rung == 0  # closed ladders reset and stop moving
    assert b.rung == 2


def test_stale_step_pressure_decays_when_sessions_leave(monkeypatch):
    """Review finding: the step EWMA's only feed is live-session steps, so
    a wedged step followed by the session disconnecting used to pin
    pressure >= 1 FOREVER — an idle box 503ing every new session until
    restart.  The tick loop now decays the signal once samples stop
    arriving."""
    monkeypatch.setenv("OVERLOAD_STEP_BUDGET_MS", "100")
    clock = Clock()
    plane = OverloadControlPlane(clock=clock)
    ladder = plane.register_session("s")
    ladder.note_step_timeout(0.8)  # wedged step: EWMA pinned at 1.6s
    assert not plane.admission.admit()[0]
    plane.unregister_session("s")
    # no sessions, no samples: pressure must drain, not persist
    for _ in range(60):
        clock.tick(0.25)
        plane.tick()
    ok, _ = plane.admission.admit()
    assert ok, f"idle box still refusing (pressure={plane.admission.pressure()})"


def test_fresh_step_samples_hold_off_decay():
    """Decay fires only on stale evidence: while samples keep arriving the
    EWMA is live data and must not be eroded under it."""
    clock = Clock()
    a = AdmissionController(step_budget_s=0.1, clock=clock)
    a.note_step_latency(0.4)
    before = a.step_ewma.value
    clock.tick(0.5)
    a.decay_stale_step_signal(stale_after_s=2.0)  # sample only 0.5s old
    assert a.step_ewma.value == before
    clock.tick(2.0)
    a.decay_stale_step_signal(stale_after_s=2.0)  # now stale
    assert a.step_ewma.value < before


def test_admission_gate_counts_inflight_reservations(monkeypatch):
    """Review finding: OVERLOAD_MAX_SESSIONS was checked against
    len(ladders), which only grows when on_track fires (inside the awaited
    setRemoteDescription) — a burst of concurrent offers all saw zero
    ladders and sailed past the cap.  The gate now takes the session key
    as a counted reservation."""
    monkeypatch.setenv("OVERLOAD_MAX_SESSIONS", "2")
    plane = OverloadControlPlane(clock=Clock())
    assert plane.admission_gate(key="a")[0]
    assert plane.admission_gate(key="b")[0]
    ok, retry_after = plane.admission_gate(key="c")
    assert not ok and retry_after > 0  # zero ladders, cap still enforced
    # registration converts the reservation — no double count
    plane.register_session("a")
    assert plane.snapshot()["overload_admission_pending"] == 1
    assert not plane.admission_gate(key="c")[0]  # 1 ladder + 1 pending
    # a failed offer releases its reservation before any ladder exists
    plane.release_admission("b")
    assert plane.admission_gate(key="c")[0]
    # unregister clears a stray reservation too (failed-offer _end_supervision)
    plane.unregister_session("c")
    assert plane.snapshot()["overload_admission_pending"] == 0


def test_admission_reservations_expire(monkeypatch):
    """A session admitted but never delivering a video track must not
    shrink the cap forever: reservations expire after the setup-sized
    TTL (swept by the tick loop and by the gate itself)."""
    monkeypatch.setenv("OVERLOAD_MAX_SESSIONS", "1")
    clock = Clock()
    plane = OverloadControlPlane(clock=clock)
    assert plane.admission_gate(key="ghost")[0]
    assert not plane.admission_gate(key="next")[0]
    clock.tick(plane._pending_ttl_s + 1.0)
    plane.tick()
    assert plane.admission_gate(key="next")[0]


def test_plane_unregister_releases_freeze(monkeypatch):
    monkeypatch.setenv("OVERLOAD_UP_TICKS", "1")
    plane = OverloadControlPlane()
    plane.register_session("a")
    plane.admission.note_step_latency(1e9)
    for _ in range(RUNG_FROZEN):
        plane.tick()
    assert plane.admission.frozen
    plane.unregister_session("a")
    assert not plane.admission.frozen


class _OpaqueQueue:
    """Queue stub whose CONTENTS cannot be observed — proves the snapshot
    reads counters only, never traverses frames."""

    bound = 8
    shed_overflow = 3
    shed_stale = 1
    depth = 5

    def __iter__(self):
        raise AssertionError("snapshot traversed a frame queue")

    def __getitem__(self, i):
        raise AssertionError("snapshot indexed a frame queue")


def test_snapshot_is_counter_reads_only():
    plane = OverloadControlPlane()
    for i in range(32):
        plane.register_session(f"s{i}")
    plane.register_queue("rx", _OpaqueQueue())
    for _ in range(100):
        plane.note_delivered(0.01)
    snap = plane.snapshot()  # must not touch queue contents
    assert snap["overload_sessions"] == 32
    assert snap["overload_admission_pending"] == 0
    assert snap["overload_queues"]["rx"] == {
        "depth": 5, "bound": 8, "shed_overflow": 3, "shed_stale": 1,
    }
    assert snap["overload_freshness_p50_ms"] == pytest.approx(10.0)
    assert snap["overload_freshness_p99_ms"] == pytest.approx(10.0)
    assert snap["overload_pressure"] == 0.0


def test_queue_probe_adapts_foreign_queues_and_unregisters_with_session():
    from ai_rtc_agent_tpu.resilience.overload import QueueProbe

    async def go():
        q = asyncio.Queue(maxsize=16)
        await q.put(1)
        await q.put(2)
        plane = OverloadControlPlane()
        plane.register_session("sess")
        plane.register_queue("ingest:sess", QueueProbe(q))
        snap = plane.snapshot()["overload_queues"]["ingest:sess"]
        assert snap == {"depth": 2, "bound": 16,
                        "shed_overflow": 0, "shed_stale": 0}
        plane.unregister_session("sess")
        assert plane.snapshot()["overload_queues"] == {}

    asyncio.run(go())


def test_deadline_queue_satisfies_snapshot_surface():
    plane = OverloadControlPlane()
    q = plane.register_queue("q", DeadlineQueue(bound=2))
    q.push(b"a")
    q.push(b"b")
    q.push(b"c")
    snap = plane.snapshot()["overload_queues"]["q"]
    assert snap == {"depth": 2, "bound": 2, "shed_overflow": 1, "shed_stale": 0}


# ---------------------------------------------------------------------------
# agent surface: /capacity, admission 503 + Retry-After, /metrics keys
# ---------------------------------------------------------------------------

def _offer_body():
    from ai_rtc_agent_tpu.server.signaling import make_loopback_offer

    return {"room_id": "r", "offer": {"sdp": make_loopback_offer(), "type": "offer"}}


def test_agent_admission_503_and_capacity(monkeypatch):
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    monkeypatch.setenv("WARMUP_FRAMES", "0")

    async def go():
        app = build_app(pipeline=lambda f: f, provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            ov = app["overload"]
            assert ov is not None

            r = await client.get("/capacity")
            body = await r.json()
            assert body["capacity"] == -1 and body["saturated"] is False

            # saturate the step signal -> admission refuses BEFORE any claim
            ov.admission.note_step_latency(1e9)
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 503
            assert int(r.headers["Retry-After"]) >= 1
            r = await client.post(
                "/whip",
                data=json.dumps({"loopback": True, "video": True}),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 503
            assert "Retry-After" in r.headers

            body = await (await client.get("/capacity")).json()
            assert body["capacity"] == 0 and body["saturated"] is True

            m = await (await client.get("/metrics")).json()
            assert m["overload_pressure"] >= 1.0
            assert m.get("overload_admission_rejected_total", 0) >= 2

            # pressure clears -> admitted again (EWMA washes down)
            for _ in range(64):
                ov.admission.note_step_latency(0.001)
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 200
        finally:
            await client.close()

    asyncio.run(go())


def test_metrics_and_health_never_traverse_frame_queues(monkeypatch):
    """The observability endpoints themselves must survive overload: with
    a live session and an opaque (untraversable) queue registered, GET
    /metrics and GET /health still answer — any per-request traversal of
    frame-queue contents would 500."""
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    monkeypatch.setenv("WARMUP_FRAMES", "0")

    async def go():
        app = build_app(pipeline=lambda f: f, provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 200
            app["overload"].register_queue("opaque", _OpaqueQueue())
            m = await client.get("/metrics")
            assert m.status == 200
            body = await m.json()
            assert body["overload_queues"]["opaque"]["depth"] == 5
            h = await client.get("/health")
            assert h.status == 200
            (snap,) = (await h.json())["sessions"].values()
            assert snap["overload_rung"] == 0
        finally:
            await client.close()

    asyncio.run(go())


def test_agent_session_cap(monkeypatch):
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    monkeypatch.setenv("WARMUP_FRAMES", "0")
    monkeypatch.setenv("OVERLOAD_MAX_SESSIONS", "1")

    async def go():
        app = build_app(pipeline=lambda f: f, provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 200
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 503
            cap = await (await client.get("/capacity")).json()
            assert cap["capacity"] == 0
        finally:
            await client.close()

    asyncio.run(go())


def test_overload_control_kill_switch(monkeypatch):
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    monkeypatch.setenv("OVERLOAD_CONTROL", "0")

    async def go():
        app = build_app(pipeline=lambda f: f, provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert app["overload"] is None
            m = await (await client.get("/metrics")).json()
            assert "overload_pressure" not in m
            body = await (await client.get("/capacity")).json()
            assert body["capacity"] == -1
        finally:
            await client.close()

    asyncio.run(go())


def test_worker_publishes_capacity(monkeypatch):
    """The sidecar publish carries remaining capacity, not a boolean."""
    import threading as _threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from ai_rtc_agent_tpu.server import worker

    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = (
                json.dumps({"capacity": 3, "saturated": False,
                            "retry_after_s": 0.0})
                if self.path == "/capacity"
                else "OK"
            ).encode()
            self.send_response(200)
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    port = srv.server_address[1]
    t = _threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    published = []
    # the republish lease loop (ISSUE 11) is wall-clock driven: a fake
    # sleep MUST advance a fake clock or the loop spins the real lease
    now = {"t": 0.0}

    def fake_sleep(s):
        now["t"] += s

    try:
        rc = worker.handler(
            port, publish=published.append, sleep=fake_sleep,
            clock=lambda: now["t"],
        )
    finally:
        srv.shutdown()
    assert rc == 0
    info = published[0]
    assert info["capacity"] == 3
    assert info["saturated"] is False
    assert info["status"] == "ready"  # kept for orchestrator compat


def test_fetch_capacity_tolerates_garbled_response(monkeypatch):
    """Review finding: a truncated/garbled /capacity response raises
    http.client.HTTPException (BadStatusLine, IncompleteRead) — not
    URLError/OSError/ValueError — and used to escape the best-effort
    helper, killing the worker handler before publish() ran: the lease
    burned unpublished behind a perfectly healthy agent."""
    import http.client as _http_client
    import urllib.request as _urllib_request

    from ai_rtc_agent_tpu.server import worker

    def garbled(url, timeout=None):
        raise _http_client.BadStatusLine("HTP/1.1 garbage")

    monkeypatch.setattr(_urllib_request, "urlopen", garbled)
    assert worker.fetch_capacity("http://127.0.0.1:1/capacity") is None


def test_shed_frames_do_not_feed_admission_ewma():
    """Review finding: a shed frame used to resolve its Future
    with raw source pixels, which the resilience wrapper counted as a
    ~0ms healthy engine step — diluting the step EWMA exactly when the
    shed condition (slow batch steps) was evidence of overload.  The
    ShedFrame marker makes the wrapper deliver passthrough and feed
    nothing."""
    from ai_rtc_agent_tpu.resilience.overload import ShedFrame

    class _SheddingInner:
        def __call__(self, frame):
            raise AssertionError("pipelined surface expected")

        def submit(self, frame):
            return ("h", frame)

        def fetch(self, handle, src_frame=None):
            return ShedFrame(handle[1])  # queue shed it: source pixels back

    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    rp = ResilientPipeline(_SheddingInner(), sup, warm_steps=0)
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        out = rp.fetch(rp.submit("px"), "src")
        assert out == "src"  # passthrough delivery of the source frame
        assert adm.step_ewma.samples == 0  # shed never measures capacity
        assert sup.passthrough_frames == 1
        assert sup.processed_frames == 0
    finally:
        rp.close()


def test_shed_in_batch_delivers_passthrough_per_position():
    """ISSUE 12 review finding: routing fbs>1 through the BatchScheduler
    made a per-position batch shed reachable (the scheduler's bounded
    window can evict part of a group).  The batched wrapper must deliver
    source pixels for the shed position, the stepped output for the
    rest, and feed only the stepped frames to the counters — a raw
    ShedFrame object must never escape toward the encoder."""
    from ai_rtc_agent_tpu.resilience.overload import ShedFrame

    class _PartialShedInner:
        def __call__(self, frame):
            raise AssertionError("batched surface expected")

        def submit_batch(self, frames):
            return list(frames)

        def fetch_batch(self, handles, src_frames=None):
            return ["out0", ShedFrame(handles[1])]

    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    rp = ResilientPipeline(_PartialShedInner(), sup, warm_steps=0)
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        outs = rp.fetch_batch(rp.submit_batch(["f0", "f1"]), ["s0", "s1"])
        assert outs == ["out0", "s1"]
        assert sup.passthrough_frames == 1
        assert sup.processed_frames == 1
    finally:
        rp.close()


def test_shed_marker_sync_path_delivers_passthrough():
    """Same invariant on the sync (depth-1) surface: __call__ returning a
    ShedFrame marker must deliver passthrough and feed neither the step
    EWMA nor the processed-frame counter."""
    from ai_rtc_agent_tpu.resilience.overload import ShedFrame

    class _SheddingSync:
        def __call__(self, frame):
            return ShedFrame(frame)

    clock = Clock()
    sup = SessionSupervisor("s", clock=clock, sleep=lambda s: None)
    rp = ResilientPipeline(_SheddingSync(), sup, warm_steps=0)
    ladder, adm = _ladder(clock=clock)
    rp.throttle = ladder
    try:
        out = rp("px")
        assert out == "px"
        assert adm.step_ewma.samples == 0
        assert sup.passthrough_frames == 1
        assert sup.processed_frames == 0
    finally:
        rp.close()


def test_track_ingest_sheds_stale_frames(monkeypatch):
    """Freshest-frame-wins at the track: stale stamped frames with fresher
    ones queued behind are shed and counted; the fresh frame is delivered."""
    from ai_rtc_agent_tpu.media.frames import VideoFrame
    from ai_rtc_agent_tpu.server.signaling import LoopbackTrack
    from ai_rtc_agent_tpu.server.tracks import VideoStreamTrack

    monkeypatch.setenv("WARMUP_FRAMES", "0")
    monkeypatch.setenv("OVERLOAD_FRAME_DEADLINE_MS", "100")

    from ai_rtc_agent_tpu.utils.profiling import FrameStats

    stats = FrameStats()
    plane = OverloadControlPlane(stats)

    async def go():
        src = LoopbackTrack()
        vt = VideoStreamTrack(src, lambda f: f, overload=plane)
        now = plane._clock()
        for i in range(5):
            f = VideoFrame.from_ndarray(np.full((4, 4, 3), i, np.uint8))
            f.wall_ts = now - 10.0  # ancient
            await src.push(f)
        fresh = VideoFrame.from_ndarray(np.full((4, 4, 3), 99, np.uint8))
        fresh.wall_ts = now
        await src.push(fresh)
        out = await vt.recv()
        assert out.to_ndarray()[0, 0, 0] == 99
        assert stats.snapshot().get("overload_shed_ingest_total") == 5
        snap = plane.snapshot()
        assert snap["overload_freshness_p99_ms"] < 100.0

    asyncio.run(go())
