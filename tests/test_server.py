"""Signaling/server tests: endpoint parity + hermetic loopback end-to-end.

(SURVEY.md section 4 'Integration' + 'End-to-end' tiers — the reference has
zero tests; these encode the behavior its agent.py exhibits.)
"""

import asyncio
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_rtc_agent_tpu.server.agent import build_app
from ai_rtc_agent_tpu.server.signaling import (
    LoopbackProvider,
    make_loopback_offer,
)


class FakePipeline:
    """Pipeline stand-in: invert colors; records control-plane calls."""

    def __init__(self):
        self.prompt = None
        self.t_index_list = None
        self.calls = 0

    def __call__(self, frame):
        self.calls += 1
        arr = frame if isinstance(frame, np.ndarray) else frame.to_ndarray()
        return 255 - arr

    def update_prompt(self, p):
        self.prompt = p

    def update_t_index_list(self, t):
        if len(t) != 4:
            raise ValueError("length must stay 4")
        self.t_index_list = list(t)


def run(coro):
    return asyncio.run(coro)


async def _client(pipeline):
    app = build_app(pipeline=pipeline, provider=LoopbackProvider())
    client = TestClient(TestServer(app))
    await client.start_server()
    return app, client


def test_health_and_cors():
    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.get("/")
            assert r.status == 200 and await r.text() == "OK"
            assert r.headers["Access-Control-Allow-Origin"] == "*"
            r = await client.options("/config")
            assert r.status == 200
        finally:
            await client.close()

    run(go())


def test_config_endpoint_updates_pipeline():
    pipe = FakePipeline()

    async def go():
        app, client = await _client(pipe)
        try:
            r = await client.post(
                "/config", json={"prompt": "hello", "t_index_list": [1, 2, 3, 4]}
            )
            assert r.status == 200
            # invalid length -> 400, not a crash (engine validates)
            r = await client.post("/config", json={"t_index_list": [1]})
            assert r.status == 400
        finally:
            await client.close()

    run(go())
    assert pipe.prompt == "hello"
    assert pipe.t_index_list == [1, 2, 3, 4]


def test_config_guidance_capability_checked_before_mutation():
    """A /config body mixing prompt with guidance against a pipeline that
    cannot do guidance (an injected one without the knob) must apply NOTHING —
    a 400 has to mean 'rejected', never 'half-applied'."""
    import pytest

    from ai_rtc_agent_tpu.server.agent import apply_runtime_config

    pipe = FakePipeline()  # has no update_guidance
    with pytest.raises(ValueError):
        apply_runtime_config(pipe, {"prompt": "late", "guidance_scale": 2.0})
    assert pipe.prompt is None and pipe.t_index_list is None

    class Guided(FakePipeline):
        def update_guidance(self, guidance_scale=None, delta=None):
            self.guidance = guidance_scale
            self.delta = delta

    g = Guided()
    apply_runtime_config(g, {"prompt": "p", "guidance_scale": 2.0, "delta": 0.5})
    assert (g.prompt, g.guidance, g.delta) == ("p", 2.0, 0.5)


def test_config_adapter_presence_keyed_and_capability_checked():
    """ISSUE 20: the "adapter" /config key is PRESENCE-keyed (JSON null
    CLEARS to the base style; an absent key touches nothing), refused
    against a pipeline without the factor-bank surface BEFORE any other
    key applies, and applied FIRST so an unknown style name rejects the
    whole body un-applied."""
    import pytest

    from ai_rtc_agent_tpu.server.agent import apply_runtime_config

    pipe = FakePipeline()  # has no update_adapter
    with pytest.raises(ValueError, match="adapter hot-swap not supported"):
        apply_runtime_config(pipe, {"prompt": "late", "adapter": "ghibli"})
    assert pipe.prompt is None  # nothing half-applied

    class Adapted(FakePipeline):
        def __init__(self):
            super().__init__()
            self.swaps = []

        def update_adapter(self, name):
            if name == "nope":
                raise KeyError("unknown adapter 'nope'")
            self.swaps.append(name)

    a = Adapted()
    apply_runtime_config(a, {"adapter": "ghibli", "prompt": "p"})
    assert a.swaps == ["ghibli"] and a.prompt == "p"
    apply_runtime_config(a, {"adapter": None})  # null = clear, not absent
    assert a.swaps == ["ghibli", None]
    apply_runtime_config(a, {"prompt": "q"})  # absent key: style untouched
    assert a.swaps == ["ghibli", None] and a.prompt == "q"
    with pytest.raises(ValueError, match="string name or null"):
        apply_runtime_config(a, {"adapter": 3})
    # adapter applies FIRST: a registry refusal leaves the prompt alone
    with pytest.raises(KeyError):
        apply_runtime_config(a, {"adapter": "nope", "prompt": "never"})
    assert a.prompt == "q" and a.swaps == ["ghibli", None]


def test_whep_without_source_is_401_and_delete_200():
    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.post(
                "/whep", data="fake", headers={"Content-Type": "application/sdp"}
            )
            assert r.status == 401
            r = await client.delete("/whep")
            assert r.status == 200
            r = await client.post(
                "/whip", data="x", headers={"Content-Type": "text/plain"}
            )
            assert r.status == 400
        finally:
            await client.close()

    run(go())


def test_whip_then_whep_loopback_end_to_end(monkeypatch):
    """Full loop: publish via WHIP, subscribe via WHEP, frames flow through
    the (fake) pipeline with warm-up frames dropped."""
    monkeypatch.setenv("WARMUP_FRAMES", "2")
    pipe = FakePipeline()

    async def go():
        app, client = await _client(pipe)
        try:
            r = await client.post(
                "/whip",
                data=make_loopback_offer(),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201
            assert r.headers["Location"].startswith("/whip/")
            source = app["state"]["source_track"]
            assert source is not None

            r = await client.post(
                "/whep",
                data=make_loopback_offer(video=False, datachannel=False),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201

            # the viewer gets a RELAYED view of the processed stream (the
            # reference's MediaRelay fan-out, agent.py:424-430) — never the
            # raw shared track
            whep_pc = next(pc for pc in app["pcs"] if pc.out_tracks)
            viewer = whep_pc.out_tracks[0]
            assert viewer is not source

            # find the publisher pc and push frames into its inbound track
            pub_pc = next(pc for pc in app["pcs"] if pc.in_track is not None)
            frames = [
                np.full((8, 8, 3), i * 10, dtype=np.uint8) for i in range(4)
            ]
            for f in frames:
                await pub_pc.in_track.push(f)

            out = await viewer.recv()  # 2 warmups dropped by the track
            expected = [255 - f for f in frames[2:]]
            assert any(np.array_equal(out, e) for e in expected)
            assert pipe.calls >= 3  # 2 warmups + >=1 real

            # datachannel config reaches the pipeline
            await pub_pc.datachannel.deliver(json.dumps({"prompt": "via dc"}))
            assert pipe.prompt == "via dc"
        finally:
            await client.close()

    run(go())


def test_offer_full_cycle_with_webhooks(monkeypatch):
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    events = []

    async def go():
        pipe = FakePipeline()
        app, client = await _client(pipe)
        app["stream_event_handler"].webhook_url = None  # default: disabled
        # capture events instead of HTTP
        app["stream_event_handler"].handle_stream_started = (
            lambda s, r, **kw: events.append(("started", r))
        )
        app["stream_event_handler"].handle_stream_ended = (
            lambda s, r, **kw: events.append(("ended", r))
        )
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "room1",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
            )
            assert r.status == 200
            body = await r.json()
            assert body["type"] == "answer"
            pc = next(iter(app["pcs"]))
            assert pc.connectionState == "connected"
            assert pc.out_tracks, "processed track must be sent back"
            await pc.close()
        finally:
            await client.close()

    run(go())
    assert ("started", "room1") in events
    assert ("ended", "room1") in events


def test_metrics_endpoint():
    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.get("/metrics")
            assert r.status == 200
            body = await r.json()
            assert "fps" in body and "frames_total" in body
        finally:
            await client.close()

    run(go())


def test_metrics_exposes_host_plane_sessions():
    """ISSUE 2: /metrics carries per-session packetize/protect/send/recv
    µs histograms when the provider runs the batched host plane."""
    from ai_rtc_agent_tpu.server.rtc_native import NativeRtpProvider
    from ai_rtc_agent_tpu.utils.profiling import FrameStats

    async def go():
        provider = NativeRtpProvider()
        app = build_app(pipeline=FakePipeline(), provider=provider)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            st = FrameStats()
            for us in (3e-6, 5e-6, 8e-6):
                st.record_stage("packetize", us)
                st.record_stage("send", us)
            provider.register_plane_session("pc-test", st)
            body = await (await client.get("/metrics")).json()
            sess = body["host_plane_sessions"]["pc-test"]
            assert sess["packetize_count"] == 3
            assert sess["send_p90_us"] > sess["send_p50_us"] > 0
            provider.unregister_plane_session("pc-test")
            body = await (await client.get("/metrics")).json()
            assert body["host_plane_sessions"] == {}
        finally:
            await client.close()

    run(go())


def test_whep_session_scoped_delete(monkeypatch):
    """DELETE /whep/{session} (the Location we return) closes ONLY that
    subscriber; other viewers keep streaming (VERDICT r1 weak #6)."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")

    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.post(
                "/whip",
                data=make_loopback_offer(),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201

            locs = []
            for _ in range(2):
                r = await client.post(
                    "/whep",
                    data=make_loopback_offer(video=False, datachannel=False),
                    headers={"Content-Type": "application/sdp"},
                )
                assert r.status == 201
                locs.append(r.headers["Location"])
            assert locs[0] != locs[1] and locs[0].startswith("/whep/")
            pcs_by_session = dict(app["state"]["whep_pcs"])
            assert len(pcs_by_session) == 2

            r = await client.delete(locs[0])
            assert r.status == 200
            sid0 = locs[0].rsplit("/", 1)[1]
            sid1 = locs[1].rsplit("/", 1)[1]
            assert pcs_by_session[sid0].connectionState == "closed"
            assert pcs_by_session[sid1].connectionState == "connected"
            assert sid1 in app["state"]["whep_pcs"]

            # unknown session -> 404; bare DELETE closes the rest
            r = await client.delete("/whep/nonexistent")
            assert r.status == 404
            r = await client.delete("/whep")
            assert r.status == 200
            assert pcs_by_session[sid1].connectionState == "closed"

            # WHIP DELETE closes the publisher(s) and drops the source track
            r = await client.delete("/whip")
            assert r.status == 200
            assert app["state"]["source_track"] is None
            assert not app["state"]["whip_pcs"]
        finally:
            await client.close()

    run(go())


def test_whep_two_viewers_both_get_frames(monkeypatch):
    """Relay fan-out: TWO WHEP viewers each receive the processed stream
    (without a relay each frame went to exactly one viewer and concurrent
    recv() corrupted the shared track's state)."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    pipe = FakePipeline()

    async def go():
        app, client = await _client(pipe)
        try:
            r = await client.post(
                "/whip",
                data=make_loopback_offer(),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201
            viewers = []
            for _ in range(2):
                r = await client.post(
                    "/whep",
                    data=make_loopback_offer(video=False, datachannel=False),
                    headers={"Content-Type": "application/sdp"},
                )
                assert r.status == 201
            for pc in app["pcs"]:
                if pc.out_tracks:
                    viewers.append(pc.out_tracks[0])
            assert len(viewers) == 2

            pub_pc = next(pc for pc in app["pcs"] if pc.in_track is not None)
            frames = [
                np.full((8, 8, 3), 30 + i * 40, dtype=np.uint8) for i in range(3)
            ]
            for f in frames:
                await pub_pc.in_track.push(f)

            outs = [await v.recv() for v in viewers]
            expected = [255 - f for f in frames]
            for out in outs:
                assert any(np.array_equal(out, e) for e in expected)
        finally:
            await client.close()

    run(go())


def test_relay_slow_viewer_drops_not_blocks():
    """Latest-wins fan-out: a stalled viewer must not block the pump or the
    healthy viewer, and catches up to a RECENT frame when it resumes."""
    from ai_rtc_agent_tpu.server.relay import TrackRelay

    class Source:
        def __init__(self):
            self.q = asyncio.Queue()

        async def recv(self):
            return await self.q.get()

    async def go():
        src = Source()
        relay = TrackRelay(src)
        fast = relay.subscribe(maxsize=2)
        slow = relay.subscribe(maxsize=2)

        for i in range(8):
            await src.q.put(np.full((4, 4, 3), i, np.uint8))

        fast_frames = [await fast.recv() for _ in range(2)]
        assert all(f.shape == (4, 4, 3) for f in fast_frames)
        # slow viewer never polled while 8 frames flowed: its queue kept only
        # the freshest maxsize frames
        got = await slow.recv()
        assert int(got[0, 0, 0]) >= 4, "stalled viewer should skip stale frames"

        slow.stop()
        await src.q.put(np.full((4, 4, 3), 99, np.uint8))
        out = await fast.recv()
        assert out is not None
        relay.stop()

    run(go())


def test_whip_publisher_failover(monkeypatch):
    """Two publishers: viewers follow the newest; when it leaves, NEW
    viewers land on the previous still-live publisher's relay."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    pipe = FakePipeline()

    async def go():
        app, client = await _client(pipe)
        try:
            locs = []
            for _ in range(2):
                r = await client.post(
                    "/whip",
                    data=make_loopback_offer(),
                    headers={"Content-Type": "application/sdp"},
                )
                assert r.status == 201
                locs.append(r.headers["Location"])
            sids = [loc.rsplit("/", 1)[1] for loc in locs]
            # active source is publisher B (latest wins)
            assert app["state"]["source_relay"] is app["state"]["whip_relays"][sids[1]]

            # B leaves -> A's relay becomes the source again
            r = await client.delete(locs[1])
            assert r.status == 200
            assert app["state"]["source_track"] is app["state"]["whip_tracks"][sids[0]]
            assert app["state"]["source_relay"] is app["state"]["whip_relays"][sids[0]]
            assert sids[1] not in app["state"]["whip_relays"]

            # a new viewer now gets frames from publisher A
            r = await client.post(
                "/whep",
                data=make_loopback_offer(video=False, datachannel=False),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201
            viewer = next(pc for pc in app["pcs"] if pc.out_tracks).out_tracks[0]
            pub_a = app["state"]["whip_pcs"][sids[0]]
            frame = np.full((8, 8, 3), 77, np.uint8)
            await pub_a.in_track.push(frame)
            out = await viewer.recv()
            np.testing.assert_array_equal(out, 255 - frame)
        finally:
            await client.close()

    run(go())


def test_udp_port_pinning_patch():
    """patch_loop_datagram: unbound datagram endpoints land on an
    operator-pinned port (reference agent.py:32-69 — firewall/serverless
    deployments); explicit ports and local_addr=None bypass the patch."""
    from ai_rtc_agent_tpu.server.agent import patch_loop_datagram

    async def go():
        loop = asyncio.get_event_loop()
        patch_loop_datagram(["39551", "39552"])

        tr1, _ = await loop.create_datagram_endpoint(
            asyncio.DatagramProtocol, local_addr=("127.0.0.1", 0)
        )
        port1 = tr1.get_extra_info("sockname")[1]
        assert port1 in (39551, 39552)

        tr2, _ = await loop.create_datagram_endpoint(
            asyncio.DatagramProtocol, local_addr=("127.0.0.1", 0)
        )
        port2 = tr2.get_extra_info("sockname")[1]
        assert port2 in (39551, 39552) and port2 != port1

        # both pinned ports busy -> OSError, not an ephemeral fallback
        with pytest.raises(OSError):
            await loop.create_datagram_endpoint(
                asyncio.DatagramProtocol, local_addr=("127.0.0.1", 0)
            )

        # explicit port bypasses the pin list
        tr3, _ = await loop.create_datagram_endpoint(
            asyncio.DatagramProtocol, local_addr=("127.0.0.1", 39600)
        )
        assert tr3.get_extra_info("sockname")[1] == 39600
        for tr in (tr1, tr2, tr3):
            tr.close()

    run(go())


def test_whip_publisher_churn_sweeps_old_dead_sessions(monkeypatch):
    """An OLDER publisher leaving while a newer one stays live must have its
    track/relay swept immediately (ADVICE r2: the pre-fix code stopped at
    the first live session, leaking entries forever under churn)."""
    monkeypatch.setenv("WARMUP_FRAMES", "0")
    pipe = FakePipeline()

    async def go():
        app, client = await _client(pipe)
        try:
            locs = []
            for _ in range(2):
                r = await client.post(
                    "/whip",
                    data=make_loopback_offer(),
                    headers={"Content-Type": "application/sdp"},
                )
                assert r.status == 201
                locs.append(r.headers["Location"])
            sids = [loc.rsplit("/", 1)[1] for loc in locs]

            # A (older) leaves; B stays live and stays the source
            r = await client.delete(locs[0])
            assert r.status == 200
            assert sids[0] not in app["state"]["whip_tracks"]
            assert sids[0] not in app["state"]["whip_relays"]
            assert app["state"]["source_relay"] is app["state"]["whip_relays"][sids[1]]
        finally:
            await client.close()

    run(go())


def test_offer_failure_closes_half_built_pc(monkeypatch):
    """A failure after the pc exists (e.g. SDP answer generation) must close
    and discard it — with native-rtp providers a bound UDP socket would
    otherwise linger until shutdown (ADVICE r2)."""
    from ai_rtc_agent_tpu.server.signaling import LoopbackPeerConnection

    async def boom(self):
        raise RuntimeError("synthetic createAnswer failure")

    monkeypatch.setattr(LoopbackPeerConnection, "createAnswer", boom)

    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.post(
                "/offer",
                json={
                    "room_id": "r1",
                    "offer": {"sdp": make_loopback_offer(), "type": "offer"},
                },
            )
            assert r.status == 500
            assert app["pcs"] == set()  # nothing half-built left behind
        finally:
            await client.close()

    run(go())


def test_sp_flag_defaults_attention_to_ring(monkeypatch):
    """--sp N with a non-sp attention impl must not be a silent no-op
    (ADVICE r2 medium): startup defaults the impl to ring so the sequence
    axis actually shards over the allocated mesh."""
    monkeypatch.delenv("ATTN_IMPL", raising=False)

    async def go():
        app = build_app(model_id="tiny-test", provider=LoopbackProvider(), sp=2)
        client = TestClient(TestServer(app))
        await client.start_server()  # runs on_startup: builds the pipeline
        try:
            assert app["pipeline"].config.attn_impl == "ring"
        finally:
            await client.close()

    run(go())


def test_demo_page_served():
    """GET /demo: the in-repo browser client (the reference points at a
    hosted app instead — ref docs/connect.md:3-5)."""
    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.get("/demo")
            assert r.status == 200
            body = await r.text()
            assert "RTCPeerConnection" in body and "/offer" in body
        finally:
            await client.close()

    run(go())


def test_config_structurally_wrong_bodies_are_400():
    """JSON that parses but is the wrong shape (array body, null t_index
    entries) must map to 400, never escape as a 500 (hostile/buggy demo
    clients)."""
    async def go():
        app, client = await _client(FakePipeline())
        try:
            r = await client.post(
                "/config", data="[1,2]",
                headers={"Content-Type": "application/json"},
            )
            assert r.status == 400
            r = await client.post("/config", json={"t_index_list": [18, None]})
            assert r.status == 400
        finally:
            await client.close()

    run(go())


def test_default_provider_without_aiortc_is_native(monkeypatch):
    """r5: a deployment without aiortc serves real browsers (native secure
    tier), not the loopback test shim — loopback only on explicit request."""
    monkeypatch.delenv("WEBRTC_PROVIDER", raising=False)
    import builtins

    real_import = builtins.__import__

    def no_aiortc(name, *a, **kw):
        if name == "aiortc" or name.startswith("aiortc."):
            raise ImportError("aiortc unavailable (test)")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_aiortc)
    import importlib.util

    from ai_rtc_agent_tpu.media import native
    from ai_rtc_agent_tpu.server.rtc_native import NativeRtpProvider
    from ai_rtc_agent_tpu.server.signaling import LoopbackProvider, get_provider

    native_tier_viable = (
        native.load() is not None
        # the native tier also needs the secure stack's crypto backend —
        # without it every browser session would die at setup, so the
        # documented degrade is a WORKING loopback (signaling.py r5)
        and importlib.util.find_spec("cryptography") is not None
    )
    if native_tier_viable:
        assert isinstance(get_provider(), NativeRtpProvider)
    else:
        assert isinstance(get_provider(), LoopbackProvider)
    assert isinstance(get_provider("loopback"), LoopbackProvider)
