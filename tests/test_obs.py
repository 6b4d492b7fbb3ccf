"""obs/ subsystem (ISSUE 5): per-frame tracing, flight recorder, exports.

Four layers, all hermetic and fast:

* trace.py unit contract — zero-cost-when-off attach, span/mark stamping,
  first-terminal-wins sealing, bounded rings, the capture-window clamp;
* recorder.py unit contract — always-on event log, bounded snapshot
  store, snapshot survival past session teardown;
* export.py validity — the Chrome trace-event rendering parses, its
  ``ph``/``ts``/``pid``/``tid`` fields conform, per-track spans stay
  disjoint (lane spill), a shed frame renders with its terminal marker,
  and the JSONL rendering round-trips;
* the chaos acceptance — a seeded FAULT_PLAN drives a live loopback
  session to DEGRADED: the flight recorder auto-captures a snapshot whose
  event log holds the supervisor transition and whose frame timelines
  carry shed/passthrough terminals; ``GET /debug/flight`` serves it and
  the Chrome-trace export of it validates.
"""

import asyncio
import json
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_rtc_agent_tpu.media.frames import VideoFrame
from ai_rtc_agent_tpu.obs.export import stop_jax_bridge, to_chrome_trace, to_jsonl
from ai_rtc_agent_tpu.obs.recorder import FlightRecorder
from ai_rtc_agent_tpu.obs.trace import (
    STAGES,
    FrameTrace,
    SessionTracer,
    TraceController,
    get_trace,
)
from ai_rtc_agent_tpu.resilience import faults
from ai_rtc_agent_tpu.resilience.faults import FaultPlan, FaultSpec
from ai_rtc_agent_tpu.server.agent import build_app
from ai_rtc_agent_tpu.server.events import StreamEventHandler
from ai_rtc_agent_tpu.server.signaling import (
    LoopbackProvider,
    make_loopback_offer,
)
from ai_rtc_agent_tpu.utils.profiling import FrameStats


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.deactivate()
    yield
    faults.deactivate()


def _on_controller() -> TraceController:
    c = TraceController()
    c.enabled = True
    return c


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------

def test_attach_off_is_none_and_leaves_frame_untouched():
    ctrl = TraceController()
    ctrl.stop()
    tracer = SessionTracer("s", ctrl)
    f = VideoFrame.from_ndarray(np.zeros((4, 4, 3), dtype=np.uint8))
    assert tracer.attach(f) is None
    assert f.trace is None
    assert get_trace(f) is None
    assert get_trace(np.zeros(3)) is None  # bare ndarray: guard, no raise


def test_attach_on_mints_binds_and_reuses():
    tracer = SessionTracer("s", _on_controller())
    f = VideoFrame.from_ndarray(np.zeros((4, 4, 3), dtype=np.uint8))
    tr = tracer.attach(f)
    assert tr is not None and f.trace is tr
    assert tracer.attach(f) is tr  # second attach returns the same trace
    # ndarrays cannot carry the attribute: no downstream hop could ever
    # stamp such a trace, so attach declines to mint one (no per-frame
    # allocation for timelines that can only leak uncompleted)
    assert tracer.attach(np.zeros((4, 4, 3), dtype=np.uint8)) is None


def test_span_mark_finish_and_first_terminal_wins():
    tracer = SessionTracer("s", _on_controller())
    tr = tracer.mint()
    with tr.span("encode"):
        pass
    tr.add_span("ingest", 1.0, 2.0)
    tr.mark("similar_skip")
    tr.finish("shed")
    assert tr.done and tr.terminal == "shed"
    # sealed: further stamps and terminals are no-ops
    tr.add_span("send", 3.0, 4.0)
    tr.mark("late")
    tr.finish("sent")
    assert tr.terminal == "shed"
    names = [n for n, *_ in tr.spans]
    assert names == ["encode", "ingest"]
    assert ("similar_skip",) == tuple(n for n, _ in tr.marks if n == "similar_skip")
    assert any(n == "terminal:shed" for n, _ in tr.marks)
    # completion published it to the session ring
    assert tracer.frames_completed == 1
    assert tracer.snapshot_frames()[0]["terminal"] == "shed"


def test_begin_end_pairing_and_dangling_begin_closes_at_finish():
    tr = FrameTrace(1)
    tr.begin("submit", t=1.0)
    tr.begin("fetch", t=2.0)
    tr.end(t=3.0)  # bare end closes the innermost (fetch)
    tr.begin("engine_step", t=3.5)
    tr.end("submit", t=4.0)  # named end closes by name
    tr.finish("sent", t=5.0)  # dangling engine_step closes at the terminal
    spans = {n: (t0, t1) for n, t0, t1 in tr.spans}
    assert spans["fetch"] == (2.0, 3.0)
    assert spans["submit"] == (1.0, 4.0)
    assert spans["engine_step"] == (3.5, 5.0)
    assert tr.span_end("submit") == 4.0
    assert tr.span_end("never") is None


def test_ring_is_bounded_oldest_evicted():
    tracer = SessionTracer("s", _on_controller(), ring_frames=3)
    for i in range(7):
        tracer.mint(frame_id=i).finish("sent")
    snap = tracer.snapshot_frames()
    assert [d["frame_id"] for d in snap] == [4, 5, 6]
    assert tracer.frames_completed == 7  # the counter is not windowed


def test_controller_window_clamps_and_expires():
    now = [100.0]
    ctrl = TraceController(clock=lambda: now[0])
    ctrl.max_capture_s = 30.0
    granted = ctrl.start(10_000.0)
    assert granted == 30.0  # clamped to TRACE_MAX_CAPTURE_S
    assert ctrl.active()
    now[0] += 31.0
    assert not ctrl.active()  # lazy expiry flipped it off
    assert ctrl.enabled is False
    assert ctrl.status()["enabled"] is False


def test_trace_enable_env_turns_tracing_on(monkeypatch):
    monkeypatch.setenv("TRACE_ENABLE", "1")
    assert TraceController().active()  # unbounded startup enable
    monkeypatch.setenv("TRACE_ENABLE", "0")
    assert not TraceController().active()


# ---------------------------------------------------------------------------
# recorder.py
# ---------------------------------------------------------------------------

def test_event_log_is_bounded_and_always_on(monkeypatch):
    monkeypatch.setenv("FLIGHT_EVENTS", "4")
    flight = FlightRecorder()  # tracing OFF: the event log records anyway
    rec = flight.register("s1")
    for i in range(10):
        rec.event("supervisor", old="HEALTHY", new="DEGRADED", i=i)
    assert len(rec.events) == 4
    assert rec.recent_events(2)[-1]["i"] == 9
    assert all(e["kind"] == "supervisor" for e in rec.events)


def test_snapshot_store_bounded_and_survives_unregister(monkeypatch):
    monkeypatch.setenv("FLIGHT_SNAPSHOTS", "2")
    stats = FrameStats()
    flight = FlightRecorder(stats=stats)
    flight.register("s1").event("webhook", event="StreamDegraded")
    ids = [flight.take_snapshot("s1", reason=f"r{i}") for i in range(3)]
    assert all(ids)
    assert flight.get_snapshot(ids[0]) is None  # evicted (bounded store)
    assert flight.get_snapshot(ids[2])["reason"] == "r2"
    assert flight.take_snapshot("nope") is None  # unknown session
    flight.unregister("s1")
    # the black box outlives the session it recorded
    assert flight.get_snapshot(ids[2]) is not None
    assert flight.session("s1") is None
    assert stats.snapshot()["flight_snapshots_total"] == 3
    idx = flight.index()
    assert [s["id"] for s in idx["snapshots"]] == ids[1:]
    assert idx["trace"]["enabled"] is False


def test_snapshot_carries_frames_and_events():
    flight = FlightRecorder()
    flight.controller.enabled = True
    rec = flight.register("s1")
    tr = rec.tracer.mint(frame_id=7)
    tr.add_span("ingest", 1.0, 2.0)
    tr.finish("passthrough")
    rec.event("overload_rung", old="normal", new="skip2")
    snap_id = flight.take_snapshot("s1", reason="DEGRADED: test")
    snap = flight.get_snapshot(snap_id)
    assert snap["session"] == "s1" and snap["reason"] == "DEGRADED: test"
    assert snap["frames"][0]["terminal"] == "passthrough"
    assert snap["events"][0]["kind"] == "overload_rung"
    assert json.loads(json.dumps(snap)) == snap  # json-safe by construction


# ---------------------------------------------------------------------------
# export.py — Chrome trace validity
# ---------------------------------------------------------------------------

def _validate_chrome(doc: dict):
    """The satellite's conformance gate: parses, fields conform, spans per
    track are well-formed (disjoint — nesting is spilled to lanes).
    Tracks are identified by (pid, tid): a merged multi-agent export
    (obs/export.merge_chrome_traces) renders each source under its own
    process id, and two processes' identically-numbered tids are
    DIFFERENT tracks in the trace-event format."""
    doc = json.loads(json.dumps(doc))  # must survive a JSON round-trip
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    by_track: dict = {}
    for ev in events:
        assert ev["ph"] in ("M", "X", "i"), ev
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert "name" in ev["args"]
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0.0
            by_track.setdefault((ev["pid"], ev["tid"]), []).append(
                (ev["ts"], ev["ts"] + ev["dur"])
            )
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
    for track, spans in by_track.items():
        spans.sort()
        for (_, end0), (start1, _) in zip(spans, spans[1:]):
            assert start1 >= end0, f"overlapping spans on {track}: {spans}"
    return events


def _synthetic_snapshot() -> dict:
    """Three frames: pipelined overlap on engine_step (lane spill), one
    shed at ingest, one passthrough — plus a supervisor event-log entry."""
    return {
        "id": "flt-1",
        "session": "s1",
        "reason": "DEGRADED: step timeout",
        "taken_at": 110.0,
        "events": [
            {"t": 103.0, "kind": "supervisor", "old": "HEALTHY",
             "new": "DEGRADED", "reason": "step timeout"},
            {"t": 103.5, "kind": "webhook", "event": "StreamDegraded"},
        ],
        "frames": [
            {"frame_id": 1, "session": "s1", "born": 100.0, "terminal": "sent",
             "spans": [["ingest", 100.0, 100.1], ["submit", 100.1, 100.2],
                       ["engine_step", 100.2, 101.5], ["send", 101.6, 101.7]],
             "marks": [["terminal:sent", 101.7]]},
            {"frame_id": 2, "session": "s1", "born": 100.5, "terminal": "sent",
             # engine_step overlaps frame 1's (two frames in flight)
             "spans": [["ingest", 100.5, 100.6], ["engine_step", 100.7, 102.0]],
             "marks": [["terminal:sent", 102.1]]},
            {"frame_id": 3, "session": "s1", "born": 102.5, "terminal": "shed",
             "spans": [],
             "marks": [["ingest_shed", 102.6], ["terminal:shed", 102.6]]},
            {"frame_id": 4, "session": "s1", "born": 103.0,
             "terminal": "passthrough",
             "spans": [["ingest", 103.0, 103.1]],
             "marks": [["terminal:passthrough", 103.2]]},
        ],
    }


def test_chrome_trace_export_validates_and_renders_terminals():
    snap = _synthetic_snapshot()
    events = _validate_chrome(to_chrome_trace(snap))
    # the shed frame renders with its terminal marker (instant event)
    terminals = [e for e in events if e["ph"] == "i" and e["name"].startswith("terminal:")]
    assert any(e["name"] == "terminal:shed" for e in terminals)
    assert any(e["name"] == "terminal:passthrough" for e in terminals)
    shed = next(e for e in terminals if e["name"] == "terminal:shed")
    assert shed["args"]["frame_id"] == 3 and shed["args"]["terminal"] == "shed"
    # the event log renders on the events track
    sup = [e for e in events if e["ph"] == "i" and e["name"] == "supervisor"]
    assert sup and sup[0]["args"]["new"] == "DEGRADED"
    # overlapping engine_step spans spilled onto an overflow lane
    step_tids = {
        e["tid"] for e in events if e["ph"] == "X" and e["name"] == "engine_step"
    }
    assert len(step_tids) == 2
    lane_names = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "engine_step #2" in lane_names
    # ts normalized: the viewport opens on the data, not at hours offset
    assert min(e["ts"] for e in events if "ts" in e) == 0.0


def test_chrome_trace_handles_empty_and_unknown_stage():
    _validate_chrome(to_chrome_trace(
        {"session": "s", "reason": "r", "events": [], "frames": []}
    ))
    events = _validate_chrome(to_chrome_trace({
        "session": "s", "reason": "r", "events": [],
        "frames": [{"frame_id": 1, "terminal": "sent",
                    "spans": [["weird_stage", 1.0, 2.0]],
                    "marks": []}],
    }))
    assert any(e["ph"] == "X" and e["name"] == "weird_stage" for e in events)
    # unknown stages park on tids past the taxonomy's reserved range
    weird = next(e for e in events if e["ph"] == "X")
    assert weird["tid"] >= 16 * (len(STAGES) + 1)


def test_deep_lane_spill_keeps_tracks_disjoint():
    """20 frames in flight on one stage — deeper than the 16 reserved
    lanes.  Spill past lane 16 must allocate UNIQUE tids (folding onto a
    shared tid renders overlapping X events, a malformed track)."""
    frames = [
        {"frame_id": i, "session": "s", "born": 0.0, "terminal": "sent",
         # all 20 ingest spans overlap: [i, 30+i) — 20 lanes required
         "spans": [["ingest", float(i), 30.0 + i]],
         "marks": []}
        for i in range(20)
    ]
    events = _validate_chrome(to_chrome_trace(
        {"session": "s", "reason": "r", "events": [], "frames": frames}
    ))  # the validator itself asserts per-tid disjointness
    tids = [e["tid"] for e in events if e["ph"] == "X"]
    assert len(tids) == 20 and len(set(tids)) == 20
    labels = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "ingest #20" in labels


def test_safe_list_retries_past_concurrent_mutation():
    from ai_rtc_agent_tpu.obs.trace import safe_list

    class _FlakyDeque:
        """iter() raises like a deque mutated mid-copy, twice, then yields."""

        def __init__(self):
            self.attempts = 0

        def __iter__(self):
            self.attempts += 1
            if self.attempts <= 2:
                raise RuntimeError("deque mutated during iteration")
            return iter([1, 2, 3])

    assert safe_list(_FlakyDeque()) == [1, 2, 3]

    class _Hostile:
        def __iter__(self):
            raise RuntimeError("deque mutated during iteration")

    assert safe_list(_Hostile()) == []  # never raises on the incident path


def test_snapshot_survives_concurrent_ring_appends():
    """The review-found race, as a smoke: worker threads hammer both
    rings while snapshots run — no 'deque mutated during iteration'
    escapes (the DEGRADED auto-snapshot path must never raise)."""
    import threading

    flight = FlightRecorder()
    flight.controller.enabled = True
    rec = flight.register("s1")
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            rec.tracer.mint(frame_id=i).finish("sent")
            rec.event("overload_rung", i=i)
            i += 1

    def reader():
        try:
            for _ in range(300):
                snap = rec.snapshot()
                assert isinstance(snap["frames"], list)
                flight.take_snapshot("s1", reason="race")
                flight.index()
        except Exception as e:  # pragma: no cover - failure diagnostics
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(2)]
    r = threading.Thread(target=reader)
    for t in threads:
        t.start()
    r.start()
    r.join()
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors


def test_last_submit_was_skip_is_thread_local():
    """Sessions of the shared-engine plane share ONE engine: a concurrent
    session's submit on another thread must not cross-contaminate this
    thread's similar_skip trace mark."""
    import threading

    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    eng = StreamEngine.__new__(StreamEngine)  # flag mechanics only
    eng._submit_skip_flag = threading.local()
    eng.last_submit_was_skip = True  # this thread's submit skipped

    seen = {}

    def other_session():
        seen["before"] = eng.last_submit_was_skip  # fresh thread: False
        eng.last_submit_was_skip = False  # its own submit, not a skip
        seen["after"] = eng.last_submit_was_skip

    t = threading.Thread(target=other_session)
    t.start()
    t.join()
    assert seen == {"before": False, "after": False}
    assert eng.last_submit_was_skip is True  # ours is untouched


def test_jsonl_roundtrip():
    snap = _synthetic_snapshot()
    lines = to_jsonl(snap).strip().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert recs[0]["record"] == "header" and recs[0]["id"] == "flt-1"
    kinds = [r["record"] for r in recs]
    assert kinds.count("event") == 2 and kinds.count("frame") == 4
    sheds = [r for r in recs if r["record"] == "frame" and r["terminal"] == "shed"]
    assert sheds and sheds[0]["frame_id"] == 3


def test_stop_jax_bridge_without_start_is_noop():
    assert stop_jax_bridge() is None


# ---------------------------------------------------------------------------
# webhook payload (ISSUE 5 satellite: events.py)
# ---------------------------------------------------------------------------

def test_stream_degraded_webhook_carries_flight_fields():
    posted = []

    class _Resp:
        status = 200

    class _Sess:
        async def post(self, url, headers=None, json=None):
            posted.append(json)
            return _Resp()

    async def go():
        h = StreamEventHandler(session_factory=_Sess)
        h.webhook_url, h.token = "http://orch/webhook", "tok"
        emitted = []
        h.on_emit = lambda name, sid: emitted.append((name, sid))
        recent = [{"t": 1.0, "kind": "supervisor", "new": "DEGRADED"}]
        t = h.handle_session_state(
            "s1", "room", "DEGRADED", "step timeout",
            flight_snapshot_id="flt-9", recent_events=recent,
        )
        await t
        # recovery carries no flight fields (nothing broke)
        t2 = h.handle_session_state("s1", "room", "HEALTHY", "recovered")
        await t2
        return emitted

    emitted = asyncio.run(go())
    degraded = next(p for p in posted if p["event"] == "StreamDegraded")
    assert degraded["flight_snapshot_id"] == "flt-9"
    assert degraded["recent_events"][0]["kind"] == "supervisor"
    assert degraded["state"] == "DEGRADED"
    recovered = next(p for p in posted if p["event"] == "StreamRecovered")
    assert "flight_snapshot_id" not in recovered
    # the black box is told what the outside world was told
    assert ("StreamDegraded", "s1") in emitted


# ---------------------------------------------------------------------------
# /debug endpoints + the chaos acceptance
# ---------------------------------------------------------------------------

class ChaosPipeline:
    """Invert-colors pipeline consulting the engine fault scope the way
    StreamEngine.submit does (same stand-in as test_chaos_session)."""

    def __init__(self):
        self._fault_scope = faults.scope("engine")
        self.restarts = 0

    def __call__(self, frame):
        if self._fault_scope is not None:
            self._fault_scope.step()
        arr = frame if isinstance(frame, np.ndarray) else frame.to_ndarray()
        return 255 - arr

    def restart(self):
        self.restarts += 1


def _vframe(fill: int, age_s: float = 0.0) -> VideoFrame:
    f = VideoFrame.from_ndarray(np.full((8, 8, 3), fill, dtype=np.uint8))
    f.wall_ts = time.monotonic() - age_s
    return f


def test_debug_trace_endpoint_start_stop(monkeypatch):
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    monkeypatch.setenv("TRACE_MAX_CAPTURE_S", "60")

    async def go():
        app = build_app(pipeline=ChaosPipeline(), provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/debug/trace")
            assert (await r.json())["enabled"] is False
            r = await client.post(
                "/debug/trace", json={"action": "start", "duration_s": 9000}
            )
            body = await r.json()
            assert body["tracing"] is True
            assert body["duration_s"] == 60.0  # clamped to TRACE_MAX_CAPTURE_S
            assert (await (await client.get("/debug/trace")).json())["enabled"]
            m = await (await client.get("/metrics")).json()
            assert m["trace_enabled"] == 1
            r = await client.post("/debug/trace", json={"action": "stop"})
            assert (await r.json())["tracing"] is False
            r = await client.post("/debug/trace", json={"action": "bogus"})
            assert r.status == 400
            r = await client.post(
                "/debug/trace", json={"action": "start", "duration_s": "abc"}
            )
            assert r.status == 400  # validated, not a 500 from float()
            r = await client.post("/debug/trace", data=b"not json")
            assert r.status == 400
        finally:
            await client.close()

    asyncio.run(go())


def test_flight_recorder_kill_switch_404s_debug_surface(monkeypatch):
    monkeypatch.setenv("FLIGHT_RECORDER", "0")

    async def go():
        app = build_app(pipeline=ChaosPipeline(), provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            assert (await client.get("/debug/flight")).status == 404
            assert (await client.get("/debug/trace")).status == 404
            m = await (await client.get("/metrics")).json()
            assert "trace_enabled" not in m
        finally:
            await client.close()

    asyncio.run(go())


def test_chaos_degrade_autocaptures_flight_snapshot(monkeypatch):
    """The ISSUE's chaos acceptance: a seeded FAULT_PLAN wedges the engine
    mid-stream; the session degrades to passthrough; the flight recorder
    auto-snapshots at the transition with the supervisor event in its log
    and shed/passthrough terminals in its timelines; GET /debug/flight
    serves it in all three formats and the Chrome export validates."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    monkeypatch.setenv("RESILIENCE_STEP_TIMEOUT_S", "0.25")
    monkeypatch.setenv("RESILIENCE_FIRST_STEP_TIMEOUT_S", "0.25")
    monkeypatch.setenv("SUPERVISOR_STALL_AFTER_S", "30")
    monkeypatch.setenv("TRACE_ENABLE", "1")  # timelines from frame one

    # steps 3-4 wedge far past the 0.25 s budget
    faults.activate(
        FaultPlan(
            specs=(
                FaultSpec(
                    target="engine", kind="slow_step",
                    start=3, stop=5, delay_s=4.0,
                ),
            ),
            seed=7,
        )
    )
    pipe = ChaosPipeline()

    async def go():
        app = build_app(pipeline=pipe, provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "chaos-obs",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
            )
            assert r.status == 200
            pc = next(iter(app["pcs"]))
            viewer = pc.out_tracks[0]
            (sup,) = app["supervisors"].values()

            # phase 1: a stale burst — three frames aged 10 s with a fresh
            # one queued behind them.  The ingest hop sheds all three
            # (freshest-frame-wins), terminal-marking their timelines —
            # completed BEFORE the degrade, so the auto-snapshot holds them.
            for fill in (10, 11, 12):
                await pc.in_track.push(_vframe(fill, age_s=10.0))
            await pc.in_track.push(_vframe(40))
            out = await asyncio.wait_for(viewer.recv(), timeout=3.0)
            assert np.array_equal(
                out if isinstance(out, np.ndarray) else out.to_ndarray(),
                255 - np.full((8, 8, 3), 40, dtype=np.uint8),
            )

            # phase 2: pump into the stall window until the supervisor
            # leaves HEALTHY and a passthrough frame is delivered (its
            # timeline seals with terminal:passthrough)
            deadline = time.monotonic() + 20.0
            saw_passthrough = False
            fill = 50
            while time.monotonic() < deadline:
                await pc.in_track.push(_vframe(fill))
                fill += 1
                out = await asyncio.wait_for(viewer.recv(), timeout=3.0)
                if not isinstance(out, np.ndarray):
                    saw_passthrough = True  # VideoFrame passed through raw
                states = {t["to"] for t in sup.snapshot()["transitions"]}
                if saw_passthrough and "DEGRADED" in states:
                    break
            assert saw_passthrough, "no passthrough frame during the stall"
            assert "DEGRADED" in {
                t["to"] for t in sup.snapshot()["transitions"]
            }

            # the auto-captured snapshot: index lists it...
            idx = await (await client.get("/debug/flight")).json()
            assert idx["trace"]["enabled"] is True
            degrades = [
                s for s in idx["snapshots"] if s["reason"].startswith("DEGRADED")
            ]
            assert degrades, idx
            snap_id = degrades[-1]["id"]

            # ...the JSON body holds the supervisor transition + terminals
            r = await client.get("/debug/flight", params={"id": snap_id})
            assert r.status == 200
            snap = await r.json()
            sups = [e for e in snap["events"] if e["kind"] == "supervisor"]
            assert any(e["new"] == "DEGRADED" for e in sups), snap["events"]
            terminals = [f["terminal"] for f in snap["frames"]]
            assert "shed" in terminals, terminals  # the phase-1 burst
            assert all(t is not None for t in terminals)

            # live capture (by now passthrough timelines have completed too)
            r = await client.get(
                "/debug/flight", params={"session": next(iter(idx["sessions"]))}
            )
            live = await r.json()
            assert "passthrough" in {f["terminal"] for f in live["frames"]}

            # ...the Chrome export of the snapshot validates, shed visible
            r = await client.get(
                "/debug/flight", params={"id": snap_id, "format": "chrome"}
            )
            events = _validate_chrome(await r.json())
            assert any(
                e["ph"] == "i" and e["name"] == "terminal:shed" for e in events
            )
            assert any(
                e["ph"] == "i" and e["name"] == "supervisor"
                and e["args"].get("new") == "DEGRADED"
                for e in events
            )

            # ...and the JSONL export parses line by line
            r = await client.get(
                "/debug/flight", params={"id": snap_id, "format": "jsonl"}
            )
            recs = [json.loads(ln) for ln in (await r.text()).splitlines()]
            assert recs[0]["record"] == "header"

            # error surfaces stay crisp
            assert (
                await client.get("/debug/flight", params={"id": "flt-none"})
            ).status == 404
            assert (
                await client.get("/debug/flight", params={"session": "nope"})
            ).status == 404
            assert (
                await client.get(
                    "/debug/flight", params={"id": snap_id, "format": "bogus"}
                )
            ).status == 400
            # format without a capture selector (a tooling URL whose id
            # variable expanded empty) fails loudly, not index-as-200
            assert (
                await client.get("/debug/flight", params={"format": "chrome"})
            ).status == 400
        finally:
            await client.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# fleet journey correlation (ISSUE 13): header threading, the ?journey=
# fragment selector, JSON error bodies, and the multi-source Chrome merge
# ---------------------------------------------------------------------------

def test_merge_chrome_traces_per_agent_pids_and_stamps():
    """Two agents' captures merge into ONE Perfetto doc: disjoint pids,
    journey/agent/leg stamped into process metadata and span args —
    identically-numbered stage tids no longer collide across agents."""
    from ai_rtc_agent_tpu.obs.export import merge_chrome_traces

    snap_a = _synthetic_snapshot()
    snap_b = _synthetic_snapshot()
    snap_b["session"] = "s2"
    doc = merge_chrome_traces(
        [
            (snap_a, {"journey_id": "j-1", "agent": "agent0", "leg": 1}),
            (snap_b, {"journey_id": "j-1", "agent": "agent1", "leg": 2}),
        ],
        journey="j-1",
    )
    events = _validate_chrome(doc)
    pids = {e["pid"] for e in events}
    assert pids == {1, 2}
    # per-agent disjoint pids: every event of one agent shares one pid
    by_pid_agent = {}
    for e in events:
        if e["ph"] == "M" and e["name"] == "process_name":
            by_pid_agent[e["pid"]] = e["args"]["agent"]
            assert e["args"]["journey_id"] == "j-1"
    assert by_pid_agent == {1: "agent0", 2: "agent1"}
    # span args carry the stamp (Perfetto's "which leg is this" answer)
    spans = [e for e in events if e["ph"] == "X"]
    assert spans
    assert all(e["args"]["journey_id"] == "j-1" for e in spans)
    assert {e["args"]["leg"] for e in spans} == {1, 2}
    assert doc["otherData"]["journey_id"] == "j-1"
    assert len(doc["otherData"]["sources"]) == 2


def test_agent_threads_journey_headers_and_serves_fragment(monkeypatch):
    """The agent half of the tentpole: X-Journey-Id on /offer binds the
    session's recorder/tracer/supervisor context, every snapshot +
    sealed timeline carries it, and GET /debug/flight?journey= serves
    the one-pull fragment the router's bundle fan-out consumes."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    monkeypatch.setenv("TRACE_ENABLE", "1")
    monkeypatch.setenv("WORKER_ID", "agent-frag")

    async def go():
        app = build_app(pipeline=ChaosPipeline(), provider=LoopbackProvider())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "jr",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
                headers={"X-Journey-Id": "j-abc", "X-Journey-Leg": "2"},
            )
            assert r.status == 200
            # the signaling answer echoes the binding
            assert r.headers["X-Journey-Id"] == "j-abc"
            assert r.headers["X-Journey-Leg"] == "2"
            sid = r.headers["X-Stream-Id"]

            # /health session snapshot carries the journey context
            h = await (await client.get("/health")).json()
            ctx = h["sessions"][sid]["context"]["journey"]
            assert ctx["journey_id"] == "j-abc" and ctx["leg"] == 2

            # stream a stale burst so timelines seal (the ingest hop
            # sheds the aged frames, terminal-marking their traces —
            # the loopback tier has no send hop to seal "sent" on)
            pc = next(iter(app["pcs"]))
            viewer = pc.out_tracks[0]
            for fill in (10, 11):
                await pc.in_track.push(_vframe(fill, age_s=10.0))
            await pc.in_track.push(_vframe(20))
            await asyncio.wait_for(viewer.recv(), timeout=3.0)

            # an auto/on-demand snapshot carries the journey binding
            snap_id = app["flight"].take_snapshot(sid, reason="test")
            snap = app["flight"].get_snapshot(snap_id)
            assert snap["journey"]["journey_id"] == "j-abc"
            assert snap["journey"]["agent"] == "agent-frag"
            # sealed timelines carry it too (the merged export's stamp)
            assert snap["frames"]
            assert all(
                f["journey_id"] == "j-abc" and f["leg"] == 2
                for f in snap["frames"]
            )
            # the black box logged the leg start
            assert any(e["kind"] == "journey" for e in snap["events"])
            # the index names the journey per stored snapshot
            idx = await (await client.get("/debug/flight")).json()
            assert any(
                s["id"] == snap_id and s["journey_id"] == "j-abc"
                for s in idx["snapshots"]
            )

            # the fragment: live capture + stored snapshot + devtel
            r = await client.get(
                "/debug/flight", params={"journey": "j-abc"}
            )
            assert r.status == 200
            frag = await r.json()
            assert frag["agent"] == "agent-frag"
            assert sid in frag["sessions"]
            assert [s["id"] for s in frag["snapshots"]] == [snap_id]
            assert "recent_compiles" in frag["devtel"]

            # unknown journey: 404 with a JSON error body (never an
            # empty 200 a jq pipeline reads as success)
            r = await client.get(
                "/debug/flight", params={"journey": "j-none"}
            )
            assert r.status == 404
            assert "error" in await r.json()
            # journey fragments are JSON-only; merge happens router-side
            r = await client.get(
                "/debug/flight",
                params={"journey": "j-abc", "format": "chrome"},
            )
            assert r.status == 400 and "error" in await r.json()
            # unknown query params are rejected, not silently ignored
            r = await client.get(
                "/debug/flight", params={"sessoin": "typo"}
            )
            assert r.status == 400
            assert "sessoin" in (await r.json())["error"]
            # mixed selectors are ambiguous
            r = await client.get(
                "/debug/flight", params={"journey": "j-abc", "id": snap_id}
            )
            assert r.status == 400 and "error" in await r.json()
        finally:
            await client.close()

    asyncio.run(go())
