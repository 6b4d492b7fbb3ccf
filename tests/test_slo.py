"""Stage-latency SLO plane (obs/slo.py): histograms, budgets, burn-rate
windows, breach hysteresis, and the tracer feed path (ISSUE 8 tentpole).

All clockless: ticks are driven directly (the SloPlane.tick discipline
shared with the overload/netadapt ladders), so nothing here sleeps.
"""

import pytest

from ai_rtc_agent_tpu.obs.slo import (
    BUCKET_BOUNDS_MS,
    STATE_BREACH,
    STATE_OK,
    SloPlane,
    StageHistogram,
    stage_budgets_ms,
)
from ai_rtc_agent_tpu.obs.trace import STAGES, SessionTracer, TraceController


class _Frame:
    pass


def _plane(monkeypatch=None, **env):
    if monkeypatch is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, str(v))
    return SloPlane()


def _tracer(plane, session="s1", tracing=False):
    ctrl = TraceController()
    ctrl.enabled = bool(tracing)
    return SessionTracer(session, ctrl, slo=plane)


def _feed(tracer, n, stage="engine_step", ms=20.0, terminal="sent"):
    for _ in range(n):
        f = _Frame()
        tr = tracer.attach(f)
        assert tr is not None
        tr.add_span(stage, 0.0, ms / 1e3)
        tr.finish(terminal)


# -- histogram ---------------------------------------------------------------

def test_histogram_buckets_cumulative_and_inf_terminal():
    h = StageHistogram(budget_ms=10.0)
    for ms in (0.05, 0.3, 3.0, 30.0, 30.0, 9999.0):
        h.observe(ms)
    cum = h.cumulative()
    # strictly the prom shape: one entry per bound + the +Inf terminal
    assert len(cum) == len(BUCKET_BOUNDS_MS) + 1
    assert cum[-1] == ("+Inf", 6)
    counts = [c for _, c in cum]
    assert counts == sorted(counts), "buckets must be cumulative"
    # a value past the last bound lands ONLY in +Inf
    assert cum[-2][1] == 5
    assert h.count == 6 and h.over == 3  # 30, 30, 9999 past the 10ms budget
    assert h.sum_ms == pytest.approx(0.05 + 0.3 + 3.0 + 30.0 + 30.0 + 9999.0)


def test_histogram_boundary_value_lands_in_its_le_bucket():
    # le is INCLUSIVE: an observation exactly at a bound belongs in it
    h = StageHistogram(budget_ms=10.0)
    h.observe(1.0)
    cum = dict(h.cumulative())
    assert cum["1"] == 1
    assert cum["0.5"] == 0


def test_histogram_quantiles():
    h = StageHistogram(budget_ms=10.0)
    assert h.quantile_ms(0.5) is None  # no data yet
    for _ in range(90):
        h.observe(3.0)  # -> le=5 bucket
    for _ in range(10):
        h.observe(400.0)  # -> le=500 bucket
    assert h.quantile_ms(0.5) == 5.0
    assert h.quantile_ms(0.99) == 500.0


def test_histogram_quantile_past_last_bound_is_json_safe():
    """A tail past the last bucket (compile stall) must CENSOR to the top
    finite bound, never float('inf') — json.dumps would emit bare
    `Infinity`, invalid JSON, breaking /health mid-incident."""
    import json

    h = StageHistogram(budget_ms=10.0)
    for _ in range(10):
        h.observe(60_000.0)  # one minute: past every bound
    q = h.quantile_ms(0.99)
    assert q == BUCKET_BOUNDS_MS[-1]
    json.loads(json.dumps({"p99_ms": q}))  # round-trips as legal JSON


# -- budgets -----------------------------------------------------------------

def test_budgets_cover_every_stage_and_read_env(monkeypatch):
    # the budgeted stages lead the taxonomy; the hops below a stage
    # (profiler spans + scheduler counters) follow and carry no budget
    budgets = stage_budgets_ms()
    assert tuple(budgets) == STAGES[: len(budgets)]
    assert "fetch" in budgets and "await_row" in STAGES
    assert "await_row" not in budgets
    monkeypatch.setenv("SLO_ENGINE_STEP_BUDGET_MS", "123.5")
    assert stage_budgets_ms()["engine_step"] == 123.5


def test_bad_objective_refused(monkeypatch):
    monkeypatch.setenv("SLO_OBJECTIVE", "1.5")
    with pytest.raises(ValueError, match="SLO_OBJECTIVE"):
        SloPlane()


# -- feed path (SessionTracer integration) -----------------------------------

def test_slo_only_mint_feeds_histograms_but_not_ring():
    plane = _plane()
    tracer = _tracer(plane, tracing=False)
    _feed(tracer, 5, stage="decode", ms=2.0)
    assert plane.frames_observed == 5
    assert plane.global_hist["decode"].count == 5
    assert plane.sessions["s1"].stages["decode"].hist.count == 5
    # timelines are only RETAINED while tracing proper is on
    assert len(tracer.ring) == 0 and tracer.frames_completed == 0


def test_tracing_on_keeps_ring_and_feeds_slo():
    plane = _plane()
    tracer = _tracer(plane, tracing=True)
    _feed(tracer, 3)
    assert plane.frames_observed == 3
    assert len(tracer.ring) == 3 and tracer.frames_completed == 3


def test_both_off_is_a_no_mint_fast_path():
    plane = _plane()
    plane.enabled = False
    tracer = _tracer(plane, tracing=False)
    f = _Frame()
    assert tracer.attach(f) is None
    assert not hasattr(f, "trace")
    assert plane.frames_observed == 0


def test_disabled_plane_observe_is_noop():
    plane = _plane()
    plane.enabled = False
    tracer = _tracer(plane, tracing=True)  # tracing without SLO
    _feed(tracer, 2)
    assert plane.frames_observed == 0
    assert len(tracer.ring) == 2  # tracing itself unaffected


def test_non_stage_spans_are_ignored():
    plane = _plane()
    tracer = _tracer(plane)
    f = _Frame()
    tr = tracer.attach(f)
    tr.add_span("not_a_stage", 0.0, 1.0)
    tr.finish("sent")
    assert plane.frames_observed == 1
    assert set(plane.global_hist) == set(stage_budgets_ms())
    assert all(h.count == 0 for h in plane.global_hist.values())


def test_unregister_drops_session_keeps_global():
    plane = _plane()
    tracer = _tracer(plane)
    _feed(tracer, 4)
    assert "s1" in plane.sessions
    plane.unregister("s1")
    assert "s1" not in plane.sessions
    assert plane.global_hist["engine_step"].count == 4
    assert plane.session_snapshot("s1") is None


# -- burn rate + breach hysteresis -------------------------------------------

def _breach_plane(monkeypatch, **extra):
    env = {
        "SLO_TICK_S": "1.0",
        "SLO_FAST_WINDOW_S": "3",      # 3 ticks
        "SLO_SLOW_WINDOW_S": "10",     # 10 ticks
        "SLO_OBJECTIVE": "0.99",
        "SLO_BURN_THRESHOLD": "2.0",
        "SLO_UP_TICKS": "2",
        "SLO_DOWN_TICKS": "3",
        "SLO_ENGINE_STEP_BUDGET_MS": "50",
    }
    env.update({k: str(v) for k, v in extra.items()})
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    return SloPlane()


def test_breach_requires_both_windows_and_up_ticks(monkeypatch):
    plane = _breach_plane(monkeypatch)
    moves = []
    plane.on_breach = lambda sid, stage, state, info: moves.append(
        (sid, stage, state, info)
    )
    tracer = _tracer(plane)
    # sustained over-budget traffic: burn = 1.0/0.01 = 100 >> threshold
    _feed(tracer, 10, ms=200.0)
    plane.tick()
    st = plane.sessions["s1"].stages["engine_step"]
    assert st.state == STATE_OK, "one firing tick must not breach (up=2)"
    _feed(tracer, 10, ms=200.0)
    plane.tick()
    assert st.state == STATE_BREACH
    assert moves == [
        ("s1", "engine_step", STATE_BREACH, {
            "budget_ms": 50.0,
            "burn_fast": round(st.burn_fast, 3),
            "burn_slow": round(st.burn_slow, 3),
        })
    ]
    assert plane.breaches_total == 1


def test_breach_clears_on_quiet_fast_window_after_down_ticks(monkeypatch):
    plane = _breach_plane(monkeypatch)
    moves = []
    plane.on_breach = lambda sid, stage, state, info: moves.append(state)
    tracer = _tracer(plane)
    for _ in range(2):
        _feed(tracer, 10, ms=200.0)
        plane.tick()
    st = plane.sessions["s1"].stages["engine_step"]
    assert st.state == STATE_BREACH
    # clean traffic: the fast window (3 ticks) must drain, then 3 quiet
    # ticks clear the breach — the slow window may still remember the burn
    ticks_to_clear = 0
    for _ in range(20):
        _feed(tracer, 10, ms=5.0)
        plane.tick()
        ticks_to_clear += 1
        if st.state == STATE_OK:
            break
    assert st.state == STATE_OK
    # fast window (3) must drain the over-samples + 3 down ticks
    assert 3 <= ticks_to_clear <= 7
    assert moves == [STATE_BREACH, STATE_OK]


def test_idle_session_never_breaches(monkeypatch):
    """No frames = no evidence: burn must read 0, not NaN or breach."""
    plane = _breach_plane(monkeypatch)
    tracer = _tracer(plane)
    _feed(tracer, 1, ms=200.0)  # one bad frame, then silence
    for _ in range(10):
        plane.tick()
    st = plane.sessions["s1"].stages["engine_step"]
    assert st.state == STATE_OK
    # fast window saw no NEW frames once the old sample aged out
    assert st.burn_fast == 0.0


def test_breach_counts_frames_before_first_tick(monkeypatch):
    """Lazy registration: a burst observed before the plane's first tick
    (the seed sample) still counts toward burn."""
    plane = _breach_plane(monkeypatch)
    tracer = _tracer(plane)
    _feed(tracer, 50, ms=200.0)
    plane.tick()
    plane.tick()
    assert plane.sessions["s1"].stages["engine_step"].state == STATE_BREACH


def test_stats_counter_and_snapshot(monkeypatch):
    from ai_rtc_agent_tpu.utils.profiling import FrameStats

    stats = FrameStats()
    plane = _breach_plane(monkeypatch)
    plane.stats = stats
    tracer = _tracer(plane)
    for _ in range(2):
        _feed(tracer, 10, ms=200.0)
        plane.tick()
    assert stats.snapshot()["slo_breaches_total"] == 1
    snap = plane.snapshot()
    assert snap["slo_enabled"] == 1
    assert snap["slo_sessions"] == 1
    assert snap["slo_stages_breached"] == 1
    assert snap["slo_frames_observed"] == 20
    stage = snap["slo_stages"]["engine_step"]
    assert stage["count"] == 20 and stage["over"] == 20
    assert stage["budget_ms"] == 50.0
    # untouched stages are omitted (bounded, not padded)
    assert "decode" not in snap["slo_stages"]


def test_session_snapshot_shape(monkeypatch):
    plane = _breach_plane(monkeypatch)
    tracer = _tracer(plane)
    _feed(tracer, 10, ms=5.0)
    plane.tick()
    snap = plane.session_snapshot("s1")
    assert set(snap) == {"engine_step"}
    s = snap["engine_step"]
    assert s["state"] == STATE_OK
    assert s["count"] == 10 and s["over"] == 0
    assert s["budget_ms"] == 50.0
    assert isinstance(s["burn_fast"], float)
    assert s["p50_ms"] == 5.0


def test_agent_breach_rides_webhook_and_event_log(monkeypatch):
    """The agent wiring (server/agent.py on_startup): an SLO breach lands
    in the flight-recorder event log AND fires the StreamDegraded webhook
    path with state=SLO_BREACH + the session's recent black-box events."""
    import asyncio

    for k, v in {
        "SLO_TICK_S": "1.0", "SLO_FAST_WINDOW_S": "3",
        "SLO_SLOW_WINDOW_S": "10", "SLO_UP_TICKS": "2",
        "SLO_ENGINE_STEP_BUDGET_MS": "50",
    }.items():
        monkeypatch.setenv(k, v)

    from aiohttp.test_utils import TestClient, TestServer

    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider

    class Pipe:
        def __call__(self, frame):
            return frame

        def restart(self):
            pass

    async def go():
        app = build_app(pipeline=Pipe(), provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            plane = app["slo"]
            flight = app["flight"]
            assert plane is not None and flight.slo is plane
            rec = flight.register("sess-1")
            # arm the webhook with a fake transport (no real HTTP)
            posted = []

            class _Resp:
                status = 200

            class _Sess:
                async def post(self, url, headers=None, json=None):
                    posted.append(json)
                    return _Resp()

            handler = app["stream_event_handler"]
            handler.webhook_url = "http://orchestrator/hook"
            handler.token = "tok"
            handler._session_factory = lambda: _Sess()

            for _ in range(2):
                _feed(rec.tracer, 10, ms=200.0)
                plane.tick()
            for _ in range(10):  # call_soon_threadsafe + webhook task
                await asyncio.sleep(0.01)
                if posted:
                    break
            slo_events = [e for e in rec.events if e["kind"] == "slo"]
            assert slo_events and slo_events[0]["stage"] == "engine_step"
            assert slo_events[0]["state"] == STATE_BREACH
            assert posted, "breach did not reach the webhook"
            body = posted[0]
            assert body["event"] == "StreamDegraded"
            assert body["state"] == "SLO_BREACH"
            assert "engine_step" in body["reason"]
            assert body["stream_id"] == "sess-1"
            assert body["recent_events"], "black-box context missing"
            # /health carries the per-session burn state... for supervised
            # sessions; the plane's own snapshot always has it
            snap = plane.session_snapshot("sess-1")
            assert snap["engine_step"]["state"] == STATE_BREACH
            # /metrics counts the breach
            r = await client.get("/metrics")
            j = await r.json()
            assert j["slo_breaches_total"] == 1
            assert j["slo_stages_breached"] == 1
        finally:
            await client.close()

    asyncio.run(go())


def test_breach_callback_failure_never_breaks_tick(monkeypatch):
    plane = _breach_plane(monkeypatch)

    def boom(*a):
        raise RuntimeError("handler bug")

    plane.on_breach = boom
    tracer = _tracer(plane)
    for _ in range(2):
        _feed(tracer, 10, ms=200.0)
        plane.tick()  # must not raise
    assert plane.sessions["s1"].stages["engine_step"].state == STATE_BREACH
