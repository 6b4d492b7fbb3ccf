"""Slot-leak regression suite (ISSUE 4 satellite): the invariant at
server/agent.py — "a leaked slot is permanent 503s" — held only by
convention.  These tests pin it: EVERY failure path of /offer, /whip and
/whep releases the engine slot (and /whep, which never claims one, must
not touch the count), proven by a follow-up /offer succeeding after each
failure."""

import asyncio
import json
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_rtc_agent_tpu.resilience.overload import DeadlineQueue
from ai_rtc_agent_tpu.server.agent import build_app
from ai_rtc_agent_tpu.server.signaling import (
    LoopbackPeerConnection,
    LoopbackProvider,
    SessionDescription,
    make_loopback_offer,
)
from ai_rtc_agent_tpu.stream.scheduler import CapacityError


class FakeSession:
    def __init__(self, owner, session_key):
        self._owner = owner
        self.session_key = session_key
        self.window_queue = DeadlineQueue(2)
        self._released = False

    def release(self):
        # double-release must be harmless (failed -> closed fires both)
        if not self._released:
            self._released = True
            with self._owner._lock:
                self._owner.free += 1

    def __call__(self, frame):
        return frame


class FakeScheduler:
    """Claim/release ledger standing in for BatchScheduler."""

    def __init__(self, slots=1):
        self.slots = slots
        self.free = slots
        self.claims = 0
        self.on_step = None
        self._lock = threading.Lock()

    def claim(self, session_key=None):
        with self._lock:
            if self.free == 0:
                raise CapacityError("full")
            self.free -= 1
            self.claims += 1
        return FakeSession(self, session_key)

    @property
    def free_slots(self):
        return self.free

    def close(self):
        pass


def _app(provider=None, slots=1):
    fake = FakeScheduler(slots)
    # a stub pipeline so startup builds no model; with a scheduler present
    # the claim path never touches it
    app = build_app(
        pipeline=lambda frame: frame,
        provider=provider or LoopbackProvider(), batch_scheduler=fake,
    )
    return app, fake


def _offer_body():
    return {"room_id": "r", "offer": {"sdp": make_loopback_offer(), "type": "offer"}}


async def _assert_slot_free_and_claimable(client, fake):
    """The invariant: after any failure the slot count is fully restored
    and the slot is claimable again (no permanent 503) — checked on the
    ledger directly, since several scenarios leave the provider itself
    deliberately broken."""
    assert fake.free == fake.slots, "slot leaked"
    sess = fake.claim()  # would raise CapacityError on a leak
    sess.release()


def _run(provider, drive):
    async def go():
        app, fake = _app(provider)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await drive(client, fake, app)
            # releases are scheduled via ensure_future(to_thread(...)) —
            # let them land before auditing the ledger
            for _ in range(20):
                if fake.free == fake.slots:
                    break
                await asyncio.sleep(0.05)
            await _assert_slot_free_and_claimable(client, fake)
        finally:
            await client.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# /offer failure paths
# ---------------------------------------------------------------------------

class SdpParseErrorProvider(LoopbackProvider):
    """session_description raises AFTER the slot claim (the parse happens
    inside the guarded region of offer())."""

    def session_description(self, sdp, type):
        raise ValueError("unparseable SDP")


def test_offer_sdp_parse_error_releases_slot():
    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 400
        assert fake.claims == 1  # the claim actually happened

    _run(SdpParseErrorProvider(), drive)


class RemoteDescriptionFailsProvider(LoopbackProvider):
    """setRemoteDescription raises — the negotiation-failure shape (bad
    m= sections, ICE setup failure in the native tier)."""

    class _PC(LoopbackPeerConnection):
        async def setRemoteDescription(self, desc):
            raise ValueError("no video m-section")

    def peer_connection(self, ice_servers=None):
        return self._PC(configuration=ice_servers)


def test_offer_set_remote_description_failure_releases_slot():
    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 400
        assert fake.claims == 1
        assert not app["pcs"], "half-built pc leaked"

    _run(RemoteDescriptionFailsProvider(), drive)


class OnTrackExplodesProvider(LoopbackProvider):
    """The on_track handler itself raises (supervisor/track wiring bug) —
    a non-client error: 500 to the caller, slot still released."""

    class _PC(LoopbackPeerConnection):
        async def setRemoteDescription(self, desc):
            self.remoteDescription = desc
            raise RuntimeError("on_track wiring exploded")

    def peer_connection(self, ice_servers=None):
        return self._PC(configuration=ice_servers)


def test_offer_unexpected_exception_releases_slot():
    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 500
        assert fake.claims == 1
        assert not app["pcs"]

    _run(OnTrackExplodesProvider(), drive)


class AnswerFailsProvider(LoopbackProvider):
    class _PC(LoopbackPeerConnection):
        async def createAnswer(self):
            raise ValueError("answer construction failed")

    def peer_connection(self, ice_servers=None):
        return self._PC(configuration=ice_servers)


def test_offer_create_answer_failure_releases_slot():
    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 400
        assert fake.claims == 1

    _run(AnswerFailsProvider(), drive)


def test_offer_failure_after_on_track_ends_supervision():
    """on_track fires during setRemoteDescription and registers a
    supervisor + overload ladder; a later failure (createAnswer) must end
    them — a leaked watchdog task polls forever and a leaked ladder can
    hold an admission freeze."""

    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 400
        assert fake.claims == 1
        assert app["supervisors"] == {}, "supervisor leaked on failed offer"
        assert app["overload"].ladders == {}, "overload ladder leaked"

    _run(AnswerFailsProvider(), drive)


def test_whip_failure_after_on_track_ends_supervision():
    async def drive(client, fake, app):
        r = await client.post(
            "/whip", data=make_loopback_offer(),
            headers={"Content-Type": "application/sdp"},
        )
        assert r.status == 400
        assert app["supervisors"] == {}, "supervisor leaked on failed whip"
        assert app["overload"].ladders == {}
        assert not app["state"]["whip_tracks"], "publisher track leaked"

    _run(AnswerFailsProvider(), drive)


def test_offer_teardown_race_failed_then_closed_releases_once():
    """connectionstatechange fires release on BOTH 'failed' and 'closed';
    the release must be idempotent — the slot comes back exactly once."""

    async def drive(client, fake, app):
        r = await client.post("/offer", json=_offer_body())
        assert r.status == 200
        assert fake.free == 0
        pc = next(iter(app["pcs"]))
        pc.connectionState = "failed"
        await pc._emit("connectionstatechange")
        pc.connectionState = "closed"
        await pc._emit("connectionstatechange")
        for _ in range(20):
            if fake.free == fake.slots:
                break
            await asyncio.sleep(0.05)
        assert fake.free == fake.slots, "double release corrupted the ledger"

    _run(LoopbackProvider(), drive)


def test_offer_capacity_exhausted_is_503_not_claim():
    """At zero free slots /offer answers 503 + Retry-After and the ledger
    is untouched (no claim to leak)."""

    async def go():
        app, fake = _app(slots=1)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 200
            assert fake.free == 0
            r = await client.post("/offer", json=_offer_body())
            assert r.status == 503
            assert "Retry-After" in r.headers
            assert fake.claims == 1
        finally:
            await client.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# /whip failure paths
# ---------------------------------------------------------------------------

def _whip(client, body="x", ct="application/sdp"):
    return client.post(body and "/whip" or "/whip", data=body,
                       headers={"Content-Type": ct})


def test_whip_bad_content_type_never_claims():
    async def drive(client, fake, app):
        r = await client.post("/whip", data="x",
                              headers={"Content-Type": "text/plain"})
        assert r.status == 400
        assert fake.claims == 0  # refused BEFORE the claim

    _run(LoopbackProvider(), drive)


def test_whip_sdp_parse_error_releases_slot():
    async def drive(client, fake, app):
        r = await client.post("/whip", data="junk",
                              headers={"Content-Type": "application/sdp"})
        assert r.status == 400
        assert fake.claims == 1
        assert not app["state"]["whip_pcs"], "session entry leaked"

    _run(SdpParseErrorProvider(), drive)


def test_whip_negotiation_failure_releases_slot_and_session_entries():
    async def drive(client, fake, app):
        r = await client.post(
            "/whip", data=make_loopback_offer(),
            headers={"Content-Type": "application/sdp"},
        )
        assert r.status == 400
        assert fake.claims == 1
        assert not app["state"]["whip_pcs"]
        assert app["state"]["source_track"] is None

    _run(RemoteDescriptionFailsProvider(), drive)


def test_whip_unexpected_exception_releases_slot():
    async def drive(client, fake, app):
        r = await client.post(
            "/whip", data=make_loopback_offer(),
            headers={"Content-Type": "application/sdp"},
        )
        assert r.status == 500
        assert fake.claims == 1
        assert not app["state"]["whip_pcs"]

    _run(OnTrackExplodesProvider(), drive)


def test_whip_teardown_failed_state_releases_slot():
    async def drive(client, fake, app):
        r = await client.post(
            "/whip", data=make_loopback_offer(),
            headers={"Content-Type": "application/sdp"},
        )
        assert r.status == 201
        assert fake.free == 0
        pc = next(iter(app["pcs"]))
        pc.connectionState = "failed"
        await pc._emit("connectionstatechange")

    _run(LoopbackProvider(), drive)


# ---------------------------------------------------------------------------
# /whep failure paths (claims NO slot — and must not corrupt the ledger)
# ---------------------------------------------------------------------------

def test_whep_paths_do_not_touch_the_slot_ledger():
    async def go():
        app, fake = _app()
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # no publisher yet -> 401; bad content type -> 400
            r = await client.post("/whep", data="x",
                                  headers={"Content-Type": "application/sdp"})
            assert r.status == 401
            r = await client.post("/whep", data="x",
                                  headers={"Content-Type": "text/plain"})
            assert r.status == 400
            assert fake.claims == 0 and fake.free == fake.slots

            # publish, then make the viewer's answer fail: the whep pc and
            # session entry must clean up, the publisher's slot untouched
            r = await client.post(
                "/whip", data=make_loopback_offer(),
                headers={"Content-Type": "application/sdp"},
            )
            assert r.status == 201
            assert fake.free == fake.slots - 1
            n_pcs = len(app["pcs"])

            real_pc = LoopbackProvider.peer_connection

            class _FailingWhepPC(LoopbackPeerConnection):
                async def createAnswer(self):
                    raise ValueError("viewer answer failed")

            app["provider"].peer_connection = (
                lambda ice_servers=None: _FailingWhepPC()
            )
            r = await client.post("/whep", data=make_loopback_offer(),
                                  headers={"Content-Type": "application/sdp"})
            assert r.status == 400
            assert len(app["pcs"]) == n_pcs, "whep pc leaked"
            assert not app["state"]["whep_pcs"], "whep session entry leaked"
            assert fake.free == fake.slots - 1  # publisher keeps its slot
            app["provider"].peer_connection = real_pc.__get__(app["provider"])
        finally:
            await client.close()

    asyncio.run(go())
