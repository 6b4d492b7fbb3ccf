"""SCTP data channels on the native secure tier (VERDICT r4 next-round #4).

The reference's runtime control plane rides WebRTC data channels
(reference agent.py:154-168, 324-337) via aiortc's SCTP stack.  These
tests pin the in-repo subset (server/secure/sctp.py): association setup,
DCEP open/ack, ordered delivery, fragmentation, retransmission, checksum
— and the full live path: a Chrome-shaped offer with m=application over
real UDP, config JSON arriving through the agent's datachannel handler.
"""

import pytest

# the secure tier's crypto backend is optional at the package level
# (signaling degrades to loopback without it) — these tests must SKIP,
# not fail collection, on a box without it (resilience PR satellite)
pytest.importorskip("cryptography", reason="secure tier needs cryptography")

import asyncio
import json

import pytest

from ai_rtc_agent_tpu.server import sdp
from ai_rtc_agent_tpu.server.secure.sctp import (
    MAX_FRAGMENT,
    SctpAssociation,
    crc32c,
)
from tests.secure_client import SecureTestPeer, secure_offer


def _pump(first_packets, a, b, drop=None):
    """Deliver packets between two associations until quiescent.
    `drop`: 0-based indices of deliveries to drop (loss injection)."""
    inflight = [(a, p) for p in first_packets]
    n = 0
    while inflight and n < 200:
        tgt, p = inflight.pop(0)
        other = b if tgt is a else a
        n += 1
        if drop and (n - 1) in drop:
            continue
        inflight.extend((other, r) for r in tgt.handle_packet(p))
    return n


def _handshake():
    server = SctpAssociation("server")
    client = SctpAssociation("client")
    _pump(client.start(), server, client)
    assert server.established and client.established
    return server, client


class TestSctpCore:
    def test_crc32c_check_value(self):
        # the standard CRC32c check value (RFC 3720 appendix / Castagnoli)
        assert crc32c(b"123456789") == 0xE3069283

    def test_corrupted_packet_dropped(self):
        server, client = _handshake()
        ch, pkts = client.open_channel("x")
        bad = bytearray(pkts[0])
        bad[-1] ^= 0xFF  # payload flip without fixing the checksum
        assert server.handle_packet(bytes(bad)) == []

    def test_wrong_vtag_dropped(self):
        server, client = _handshake()
        ch, pkts = client.open_channel("x")
        bad = bytearray(pkts[0])
        bad[4:8] = b"\xde\xad\xbe\xef"
        # refresh checksum so only the vtag is wrong
        import struct

        struct.pack_into("!I", bad, 8, 0)
        struct.pack_into("<I", bad, 8, crc32c(bytes(bad)))
        assert server.handle_packet(bytes(bad)) == []

    def test_dcep_open_ack_and_messages_both_ways(self):
        got = []
        server, client = _handshake()
        server.on_message = lambda ch, m: got.append((ch.label, m))
        ch, pkts = client.open_channel("config")
        _pump(pkts, server, client)
        assert ch.readyState == "open"
        (srv_ch,) = server.channels.values()
        assert srv_ch.label == "config" and srv_ch.readyState == "open"
        _pump(ch.send('{"prompt": "p"}'), server, client)
        assert got == [("config", '{"prompt": "p"}')]
        back = []
        ch.on("message")(lambda m: back.append(m))
        _pump(srv_ch.send("applied"), client, server)
        assert back == ["applied"]
        # everything SACKed — nothing left to retransmit on either side
        assert not server._unacked and not client._unacked

    def test_large_message_fragments_and_reassembles(self):
        got = []
        server, client = _handshake()
        server.on_message = lambda ch, m: got.append(m)
        ch, pkts = client.open_channel("big")
        _pump(pkts, server, client)
        msg = "x" * (MAX_FRAGMENT * 3 + 17)
        frames = ch.send(msg)
        assert len(frames) == 4  # 3 full fragments + tail
        _pump(frames, server, client)
        assert got == [msg]

    def test_lost_data_recovered_by_retransmission(self):
        got = []
        server, client = _handshake()
        server.on_message = lambda ch, m: got.append(m)
        ch, pkts = client.open_channel("lossy")
        _pump(pkts, server, client)
        frames = ch.send("must arrive")
        _pump(frames, server, client, drop={0})  # lose the DATA
        assert got == []
        # timer fires (forced): the unacked chunk retransmits
        for entry in client._unacked.values():
            entry[1] -= 10.0
        _pump(client.retransmit_due(), server, client)
        assert got == ["must arrive"]
        assert not client._unacked

    def test_reordered_fragments_deliver_in_order(self):
        got = []
        server, client = _handshake()
        server.on_message = lambda ch, m: got.append(m)
        ch, pkts = client.open_channel("ooo")
        _pump(pkts, server, client)
        frames = ch.send("A" * (MAX_FRAGMENT + 5))
        assert len(frames) == 2
        for p in reversed(frames):  # deliver tail before head
            for r in server.handle_packet(p):
                client.handle_packet(r)
        assert got == ["A" * (MAX_FRAGMENT + 5)]

    def test_duplicate_data_not_redelivered(self):
        got = []
        server, client = _handshake()
        server.on_message = lambda ch, m: got.append(m)
        ch, pkts = client.open_channel("dup")
        _pump(pkts, server, client)
        frames = ch.send("once")
        _pump(frames, server, client)
        for p in frames:  # replay the same DATA
            server.handle_packet(p)
        assert got == ["once"]

    def test_heartbeat_echoed(self):
        server, client = _handshake()
        hb = client._packet(client._chunk(4, 0, b"\x00\x01\x00\x08beat"))
        (ack,) = server.handle_packet(hb)
        assert ack[12] == 5  # HEARTBEAT-ACK
        assert b"beat" in ack

    def test_abort_closes(self):
        server, client = _handshake()
        abort = client._packet(client._chunk(6, 0, b""))
        server.handle_packet(abort)
        assert server.closed
        assert server.send(0, 51, b"late") == []


class TestSdpDatachannel:
    def test_secure_offer_with_application_accepted(self):
        offer = sdp.parse(secure_offer("AA:" * 31 + "AA", datachannel=True))
        app = offer.application()
        assert app is not None and app.sctp_port() == 5000
        answer = sdp.build_answer(
            offer, host="127.0.0.1", video_port=40000,
            secure={"ice_ufrag": "u", "ice_pwd": "p" * 22, "fingerprint": "X"},
        )
        assert "m=application 40000 UDP/DTLS/SCTP webrtc-datachannel" in answer
        assert "a=sctp-port:5000" in answer
        assert "a=group:BUNDLE 0 1" in answer
        assert "a=max-message-size:" in answer

    def test_plain_offer_application_still_rejected(self):
        """Without DTLS there is no SCTP transport — the plain tier must
        keep rejecting the section (port 0)."""
        text = secure_offer("AA:" * 31 + "AA", datachannel=True)
        offer = sdp.parse(text)
        answer = sdp.build_answer(offer, host="127.0.0.1", video_port=40000)
        assert "m=application 0 UDP/DTLS/SCTP webrtc-datachannel" in answer


@pytest.mark.usefixtures("native_lib")
class TestLiveDatachannel:
    def test_config_json_arrives_over_live_datachannel(self, native_lib):
        """The full reference flow (agent.py:154-168): browser-shaped offer
        with m=application -> accepted answer -> STUN -> DTLS -> SCTP ->
        DCEP open "config" -> config JSON applied to the pipeline."""
        from aiohttp.test_utils import TestClient, TestServer

        from ai_rtc_agent_tpu.media import native
        from ai_rtc_agent_tpu.server.agent import build_app
        from ai_rtc_agent_tpu.server.rtc_native import NativeRtpProvider
        from tests.test_secure_e2e import InvertPipeline

        class RecordingPipeline(InvertPipeline):
            def __init__(self):
                self.prompts = []
                self.t_index_lists = []

            def update_prompt(self, p):
                self.prompts.append(p)

            def update_t_index_list(self, t):
                self.t_index_lists.append(t)

        pipeline = RecordingPipeline()

        async def go():
            provider = NativeRtpProvider(use_h264=native.h264_available())
            app = build_app(pipeline=pipeline, provider=provider)
            client = TestClient(TestServer(app))
            await client.start_server()
            peer = await SecureTestPeer().open_socket()
            try:
                offer = secure_offer(
                    peer.cert.fingerprint, datachannel=True
                )
                r = await client.post(
                    "/offer",
                    json={
                        "room_id": "dc",
                        "offer": {"sdp": offer, "type": "offer"},
                    },
                )
                assert r.status == 200, await r.text()
                answer = (await r.json())["sdp"]
                assert "m=application" in answer
                assert "a=sctp-port:5000" in answer
                await peer.establish(answer)
                ch = await peer.open_datachannel("config")
                assert ch.readyState == "open"
                peer.dc_send(
                    ch,
                    json.dumps(
                        {"prompt": "neon fox", "t_index_list": [10, 20, 30, 40]}
                    ),
                )
                for _ in range(40):
                    await peer.drain_dc(0.1)
                    if pipeline.prompts:
                        break
                assert pipeline.prompts == ["neon fox"]
                assert pipeline.t_index_lists == [[10, 20, 30, 40]]
                snap = await (await client.get("/metrics")).json()
                assert snap.get("datachannels_total", 0) >= 1
                assert snap.get("datachannel_messages_total", 0) >= 1
            finally:
                peer.close()
                await client.close()

        asyncio.run(go())


@pytest.fixture(scope="module")
def native_lib():
    from ai_rtc_agent_tpu.media import native

    lib = native.load()
    if lib is None:
        pytest.skip("native lib unavailable")
    return lib


class TestReviewR5Fixes:
    def test_answer_advertises_our_sctp_port_not_echo(self):
        text = secure_offer("AA:" * 31 + "AA", datachannel=True).replace(
            "a=sctp-port:5000", "a=sctp-port:6000"
        )
        offer = sdp.parse(text)
        assert offer.application().sctp_port() == 6000
        answer = sdp.build_answer(
            offer, host="127.0.0.1", video_port=40000,
            secure={"ice_ufrag": "u", "ice_pwd": "p" * 22, "fingerprint": "X"},
        )
        # the answer's a=sctp-port describes OUR listening port (5000)
        assert "a=sctp-port:5000" in answer

    def test_abort_closes_channels_observably(self):
        closed = []
        server, client = _handshake()
        ch, pkts = client.open_channel("obs")
        _pump(pkts, server, client)
        (srv_ch,) = server.channels.values()
        srv_ch.on("close")(lambda: closed.append(srv_ch.sid))
        abort = client._packet(client._chunk(6, 0, b""))
        server.handle_packet(abort)
        assert server.closed
        assert srv_ch.readyState == "closed"
        assert closed == [srv_ch.sid]

    def test_local_close_sends_abort_peer_tears_down(self):
        server, client = _handshake()
        ch, pkts = client.open_channel("bye")
        _pump(pkts, server, client)
        for pkt in server.close():
            client.handle_packet(pkt)
        assert client.closed
        assert ch.readyState == "closed"

    def test_lost_init_recovered_by_client_timer(self):
        server = SctpAssociation("server")
        client = SctpAssociation("client")
        client.start()  # INIT lost: never delivered
        assert not client.established
        client._hs_flight[1] -= 10.0  # timer fires
        _pump(client.retransmit_due(), server, client)
        assert client.established and server.established

    def test_lost_cookie_echo_recovered_by_client_timer(self):
        server = SctpAssociation("server")
        client = SctpAssociation("client")
        # deliver INIT; deliver INIT-ACK; drop the COOKIE-ECHO
        (init,) = client.start()
        (init_ack,) = server.handle_packet(init)
        client.handle_packet(init_ack)  # produces COOKIE-ECHO (dropped)
        assert not client.established
        client._hs_flight[1] -= 10.0
        _pump(client.retransmit_due(), server, client)
        assert client.established and server.established

    def test_duplicate_init_on_established_does_not_reset(self):
        # RFC 9260 s5.2.2: a retransmitted INIT landing AFTER the
        # association established (the client's timer racing a slow
        # INIT-ACK) must be answered with the EXISTING tag and cookie —
        # pre-fix the server re-derived _peer_tag/_cum_in from it,
        # silently desyncing TSN tracking of the live association
        server = SctpAssociation("server")
        client = SctpAssociation("client")
        (init,) = client.start()
        _pump([init], server, client)
        assert server.established and client.established
        tag, cum, cookie = server._peer_tag, server._cum_in, server._cookie
        (reply,) = server.handle_packet(init)  # replay the original INIT
        assert reply[12] == 2  # INIT-ACK, not silence
        assert cookie is not None and cookie in reply
        assert server._peer_tag == tag and server._cum_in == cum
        # the association the duplicate tried to reset still carries data
        got = []
        server.on_message = lambda ch, m: got.append(m)
        ch, pkts = client.open_channel("post-dup")
        _pump(pkts, server, client)
        _pump(ch.send("still alive"), server, client)
        assert got == ["still alive"]


def test_per_session_prompts_over_native_datachannels(native_lib):
    """The batch scheduler on the NATIVE secure tier: each peer's
    datachannel config lands on ITS OWN scheduler session (the per-peer
    prompt isolation the reference cannot give: its datachannel handler
    mutates one global pipeline, reference agent.py:154-168)."""
    from aiohttp.test_utils import TestClient, TestServer

    from ai_rtc_agent_tpu.media import native
    from ai_rtc_agent_tpu.server.agent import build_app
    from ai_rtc_agent_tpu.server.rtc_native import NativeRtpProvider
    from tests.test_batch_scheduler import _FakeScheduler, _StubPipeline

    # the ONE scheduler fake (tests/test_batch_scheduler.py) so a
    # claim/release contract change breaks every consumer loudly
    sched = _FakeScheduler(max_sessions=2)

    async def go():
        provider = NativeRtpProvider(use_h264=native.h264_available())
        app = build_app(
            pipeline=_StubPipeline(), provider=provider,
            batch_scheduler=sched,
        )
        client = TestClient(TestServer(app))
        await client.start_server()
        peers = []
        try:
            for i, prompt in enumerate(["neon fox", "pale moon"]):
                peer = await SecureTestPeer(f"mp-{i}").open_socket()
                peers.append(peer)
                r = await client.post(
                    "/offer",
                    json={
                        "room_id": f"mp-{i}",
                        "offer": {
                            "sdp": secure_offer(
                                peer.cert.fingerprint, datachannel=True
                            ),
                            "type": "offer",
                        },
                    },
                )
                assert r.status == 200, await r.text()
                await peer.establish((await r.json())["sdp"])
                ch = await peer.open_datachannel("config")
                peer.dc_send(ch, json.dumps({"prompt": prompt}))
            for _ in range(40):
                await asyncio.sleep(0.1)
                for peer in peers:
                    await peer.drain_dc(0.05)
                if all(s.prompt for s in sched.claimed):
                    break
            assert [s.prompt for s in sched.claimed] == ["neon fox", "pale moon"]
            assert sched.prompt is None  # never the global plane
        finally:
            for peer in peers:
                peer.close()
            await client.close()

    asyncio.run(go())


class TestChromeShapedSctp:
    """usrsctp/dcsctp wire shapes Chrome actually emits — tolerance pins."""

    def test_init_with_optional_params_tolerated(self):
        import struct as _s

        server = SctpAssociation("server")
        client = SctpAssociation("client")
        (init_pkt,) = client.start()
        # splice usrsctp-style optional params onto the INIT chunk:
        # FORWARD-TSN supported (49152), supported extensions (32776)
        params = _s.pack("!HH", 49152, 4) + _s.pack("!HHBB", 32776, 6, 130, 193) + b"\x00\x00"
        body = bytearray(init_pkt)
        chunk_len = _s.unpack_from("!H", body, 14)[0]
        _s.pack_into("!H", body, 14, chunk_len + len(params))
        body = bytes(body) + params
        body = bytearray(body)
        _s.pack_into("!I", body, 8, 0)
        from ai_rtc_agent_tpu.server.secure.sctp import crc32c

        _s.pack_into("<I", body, 8, crc32c(bytes(body)))
        out = server.handle_packet(bytes(body))
        assert out and out[0][12] == 2  # INIT-ACK

    def test_cookie_echo_bundled_with_dcep_open(self):
        """Chrome bundles COOKIE-ECHO and the first DATA (DCEP OPEN) in one
        SCTP packet — both chunks must process in order."""
        import struct as _s

        opened = []
        server = SctpAssociation("server", on_channel=opened.append)
        client = SctpAssociation("client")
        (init_pkt,) = client.start()
        (init_ack,) = server.handle_packet(init_pkt)
        (cookie_echo,) = client.handle_packet(init_ack)
        # client side: fabricate the bundled packet = COOKIE-ECHO chunk +
        # DCEP OPEN DATA chunk in one SCTP packet
        ce_chunk = cookie_echo[12:]
        ch, open_pkts = client.open_channel("config")
        data_chunk = open_pkts[0][12:]
        bundled = client._packet(ce_chunk + data_chunk)
        outs = server.handle_packet(bundled)
        assert server.established
        assert opened and opened[0].label == "config"
        # replies include COOKIE-ACK and a SACK covering the DATA
        types = [o[12] for o in outs]
        assert 11 in types and 3 in types
