"""Broadcast fan-out amortization: dedicated per-viewer chains vs the
encode-once/packetize-once broadcast TX plane (ISSUE 17).

Measures the per-viewer cost of serving one stylized stream to N WHEP
viewers, stage-for-stage against what ``BROADCAST_FANOUT=0`` pays:

  dedicated: every viewer owns the FULL private chain — encode (native
             H.264 when available, else the NullCodec framing this tier
             really runs) + BatchedRtpPacketizer + SRTP protect_frame +
             BatchSender (one sendmmsg per viewer).
  broadcast: encode ONCE, packetize ONCE; each viewer pays only an
             RtpHeaderRewriter pass (bulk copy + vectorized SSRC/seq/ts
             patch) + per-viewer SRTP + a slot in ONE whole-audience
             ``send_grouped`` sendmmsg burst.

Banks TWO contract lines (scripts/perf_compare.py fences both):

  broadcast_viewers_per_core_30fps   how many viewers one core sustains
                                     at 30 fps: floor((frame budget -
                                     shared encode+packetize) / per-
                                     viewer rewrite+protect+send). higher
                                     is better.
  broadcast_single_viewer_overhead_ratio
                                     broadcast N=1 frame cost / dedicated
                                     frame cost — the price a lone viewer
                                     pays for riding the group (the extra
                                     rewrite pass). lower is better.

The amortization ratio at N viewers (broadcast per-viewer cost /
dedicated per-viewer cost) rides the first line as ``vs_baseline``.

Prints one JSON line per metric (bank-and-commit contract) and appends
them to PERF_LOG.jsonl (PERF_LOG_PATH overrides; empty value disables).
Host-only measurement: no jax backend is probed (fingerprint
probe_jax=False), matching host_plane_bench.  Without ``cryptography``
the protect legs are skipped on BOTH sides and the lines say so
(secure:false).

Env knobs: BROADCAST_BENCH_FRAMES (default 20), BROADCAST_BENCH_VIEWERS
(default 32), BROADCAST_BENCH_DIM (default 512), BROADCAST_BENCH_MTU
(default 1200), BROADCAST_BENCH_PAIRS (default 5).
"""

import json
import os
import socket
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from ai_rtc_agent_tpu.media import native  # noqa: E402
from ai_rtc_agent_tpu.media.codec import H264Encoder, NullCodec  # noqa: E402
from ai_rtc_agent_tpu.media.rtp import (  # noqa: E402
    BatchedRtpPacketizer,
    RtpHeaderRewriter,
)
from ai_rtc_agent_tpu.media.sockio import BatchSender  # noqa: E402
from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception  # noqa: E402
from ai_rtc_agent_tpu.utils.hwfp import fingerprint  # noqa: E402
from ai_rtc_agent_tpu.utils.perfbank import bank as _bank  # noqa: E402

FRAMES = int(os.getenv("BROADCAST_BENCH_FRAMES") or 20)
VIEWERS = int(os.getenv("BROADCAST_BENCH_VIEWERS") or 32)
DIM = int(os.getenv("BROADCAST_BENCH_DIM") or 512)
MTU = int(os.getenv("BROADCAST_BENCH_MTU") or 1200)
PAIRS = int(os.getenv("BROADCAST_BENCH_PAIRS") or 5)

# --probe-backend: import jax and stamp the REAL backend instead of the
# "cpu" default (the measurement itself stays host-side either way; what a
# TPU host changes is the codec tier: libavcodec H.264 vs NullCodec).
# --metric=<name>: emit only that contract line.
PROBE_BACKEND = "--probe-backend" in sys.argv
ONLY_METRIC = next(
    (a.split("=", 1)[1] for a in sys.argv if a.startswith("--metric=")),
    None,
)

_TS_STEP = 3000  # 90 kHz / 30 fps


def _frames(n: int):
    """n distinct RGB frames (content varies so an H.264 encoder can't
    collapse the stream into skip frames)."""
    base = np.arange(DIM * DIM * 3, dtype=np.uint32)
    out = []
    for i in range(n):
        arr = ((base * (2654435761 + i) >> 7) & 0xFF).astype(np.uint8)
        out.append(np.ascontiguousarray(arr.reshape(DIM, DIM, 3)))
    return out


def _srtp_contexts(n: int):
    """n independent TX contexts (one per viewer) or None without the
    cryptography package — the tier this box actually serves."""
    try:
        from ai_rtc_agent_tpu.server.secure.srtp import derive_srtp_contexts
    except ImportError:
        return None
    out = []
    for i in range(n):
        km = bytes(((i * 131) + j) & 0xFF for j in range(60))
        tx, _ = derive_srtp_contexts(km, is_server=True)
        out.append(tx)
    return out


def _backend() -> str:
    if not PROBE_BACKEND:
        return "cpu"
    import jax

    return jax.default_backend()


def _make_encoder():
    if native.h264_available():
        enc = H264Encoder(DIM, DIM, 30)
        return lambda arr, pts: enc.encode(arr, pts=pts), "h264"
    return lambda arr, pts: NullCodec.encode(arr, pts=pts), "null"


class _Sink:
    """Loopback UDP sinks, one per viewer (distinct destinations so
    send_grouped exercises its multi-address path)."""

    def __init__(self, n: int):
        self.socks, self.addrs = [], []
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            try:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 22)
            except OSError:
                pass
            self.socks.append(s)
            self.addrs.append(s.getsockname())

    def close(self):
        for s in self.socks:
            s.close()


def _dedicated_leg(frames, encode, sender, out, addr, srtp, stages):
    """ONE representative dedicated viewer chain, per-frame stage times
    accumulated into ``stages`` — the dedicated plane costs this times N
    (each viewer's chain is private and identical)."""
    pkt = BatchedRtpPacketizer(ssrc=0x5EED, payload_type=96, mtu=MTU)
    t0 = time.perf_counter()
    for i, arr in enumerate(frames):
        au = encode(arr, i * _TS_STEP)
        t1 = time.perf_counter()
        pkts = pkt.packetize(au, i * _TS_STEP)
        t2 = time.perf_counter()
        wires = srtp[0].protect_frame(pkts) if srtp else pkts
        t3 = time.perf_counter()
        sender.send(out, wires, addr)
        t4 = time.perf_counter()
        stages["encode"] += t1 - t0
        stages["packetize"] += t2 - t1
        stages["protect"] += t3 - t2
        stages["send"] += t4 - t3
        t0 = t4
    return sum(stages.values())


def _broadcast_leg(frames, encode, sender, out, sinks, srtp, n, stages,
                   desynced=True):
    """The group's whole-audience frame: encode+packetize once, then per
    viewer rewrite (+SRTP) into ONE grouped sendmmsg burst.

    ``desynced=True`` is the worst case — every viewer's seq space has
    diverged (post-GOP-replay frame mode), so each pays the full copying
    rewrite off one shared per-frame plan.  ``desynced=False`` is the
    steady state BroadcastGroup actually sustains (shared OUT_SSRC,
    aligned cursors): rewrite's identity fast path serves the source
    views with zero copying — what a lone production viewer pays."""
    pkt = BatchedRtpPacketizer(ssrc=0x5EED, payload_type=96, mtu=MTU)
    if desynced:
        rws = [
            RtpHeaderRewriter(ssrc=0x1000 + v, seq0=v * 7, ts_offset=v * 1013)
            for v in range(n)
        ]
    else:
        rws = [RtpHeaderRewriter(ssrc=0x5EED, seq0=pkt.seq)
               for _ in range(n)]
    batches = [None] * n
    t0 = time.perf_counter()
    for i, arr in enumerate(frames):
        au = encode(arr, i * _TS_STEP)
        t1 = time.perf_counter()
        pkts = pkt.packetize(au, i * _TS_STEP)
        t2 = time.perf_counter()
        tr = tp = 0.0
        plan = None  # shared gather, exactly as BroadcastGroup.fan_out
        for v in range(n):
            ta = time.perf_counter()
            rw = rws[v]
            if plan is None and not rw.aligned(pkts):
                plan = rw.plan(pkts)
            views = rw.rewrite(pkts, plan)
            tb = time.perf_counter()
            wires = srtp[v].protect_frame(views) if srtp else views
            tc = time.perf_counter()
            batches[v] = (wires, sinks.addrs[v])
            tr += tb - ta
            tp += tc - tb
        t3 = time.perf_counter()
        sender.send_grouped(out, batches)
        t4 = time.perf_counter()
        stages["encode"] += t1 - t0
        stages["packetize"] += t2 - t1
        stages["rewrite"] += tr
        stages["protect"] += tp
        stages["send"] += t4 - t3
        t0 = t4
    return sum(stages.values())


def _pli_storm_probe() -> dict:
    """The acceptance pin, measured in-harness: 16 viewers storm PLIs at
    an AU-mode group inside one coalesce window — the whole audience
    re-syncs from ONE GopCache replay, with ZERO encoder/engine IDRs
    (tests/test_broadcast.py pins the same numbers hermetically)."""
    import asyncio

    from ai_rtc_agent_tpu.server.broadcast import BroadcastGroup

    async def go():
        group = BroadcastGroup("bench", width=8, height=8, coalesce_s=60.0)
        await group.start()
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.setblocking(False)
        try:
            group.feed_au(
                b"\x00\x00\x00\x01" + NullCodec.MAGIC + b"\x00" * 32, 0
            )
            group.feed_au(
                b"\x00\x00\x00\x01" + bytes([0x61]) + b"\x00" * 32, _TS_STEP
            )
            for v in range(16):
                group.add_viewer(f"v{v}", addr=rx.getsockname())
            # join replays are per-viewer and counted too — delta from here
            c0 = group.stats.stage_snapshot_us()
            for v in range(16):
                group.on_viewer_pli(viewer_id=f"v{v}")
            c1 = group.stats.stage_snapshot_us()
            return {
                "replays": int(
                    c1.get("broadcast_gop_replays_total", 0)
                    - c0.get("broadcast_gop_replays_total", 0)
                ),
                "encoder_idrs": int(c1.get("broadcast_encoder_idr_total", 0)),
            }
        finally:
            rx.close()
            await group.close()

    return asyncio.run(go())


def run() -> list:
    frames = _frames(FRAMES)
    encode, codec = _make_encoder()
    srtp = _srtp_contexts(VIEWERS)
    secure = srtp is not None
    sinks = _Sink(VIEWERS)
    out = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    # one sender per leg, as production: every dedicated chain owns its
    # CoalescedFlush; the group owns one grouped sender for the audience
    ded_sender, bcN_sender, bcW_sender, bc1_sender = (
        BatchSender(), BatchSender(), BatchSender(), BatchSender()
    )

    ded_stages = ("encode", "packetize", "protect", "send")
    bc_stages = ("encode", "packetize", "rewrite", "protect", "send")

    # warmup: pool/scratch growth, numpy import, sendmmsg header arrays
    _dedicated_leg(frames[:2], encode, ded_sender, out, sinks.addrs[0],
                   srtp, dict.fromkeys(ded_stages, 0.0))
    for s_, n_, de_ in ((bcN_sender, VIEWERS, False),
                        (bcW_sender, VIEWERS, True), (bc1_sender, 1, False)):
        _broadcast_leg(frames[:2], encode, s_, out, sinks, srtp, n_,
                       dict.fromkeys(bc_stages, 0.0), desynced=de_)

    # interleaved best-of (the perfbank measurement discipline): all legs
    # run adjacently so throttle bursts hit each; every LEG keeps its min
    # across pairs, ratios use per-pair values' medians
    ded_min = dict.fromkeys(ded_stages, float("inf"))
    bcN_min = dict.fromkeys(bc_stages, float("inf"))
    bcW_min = dict.fromkeys(bc_stages, float("inf"))
    bc1_min = dict.fromkeys(bc_stages, float("inf"))
    for _ in range(PAIRS):
        d = dict.fromkeys(ded_stages, 0.0)
        _dedicated_leg(frames, encode, ded_sender, out,
                       sinks.addrs[0], srtp, d)
        bN = dict.fromkeys(bc_stages, 0.0)
        _broadcast_leg(frames, encode, bcN_sender, out, sinks, srtp,
                       VIEWERS, bN, desynced=False)
        bW = dict.fromkeys(bc_stages, 0.0)
        _broadcast_leg(frames, encode, bcW_sender, out, sinks, srtp,
                       VIEWERS, bW, desynced=True)
        b1 = dict.fromkeys(bc_stages, 0.0)
        _broadcast_leg(frames, encode, bc1_sender, out, sinks, srtp,
                       1, b1, desynced=False)
        for k in ded_stages:
            ded_min[k] = min(ded_min[k], d[k])
        for k in bc_stages:
            bcN_min[k] = min(bcN_min[k], bN[k])
            bcW_min[k] = min(bcW_min[k], bW[k])
            bc1_min[k] = min(bc1_min[k], b1[k])

    sinks.close()
    out.close()

    us = lambda t: 1e6 * t / FRAMES  # noqa: E731
    ded_us = {k: round(us(v), 1) for k, v in ded_min.items()}
    bcN_us = {k: round(us(v), 1) for k, v in bcN_min.items()}
    bcW_us = {k: round(us(v), 1) for k, v in bcW_min.items()}
    ded_frame_us = us(sum(ded_min.values()))
    shared_us = us(bcN_min["encode"] + bcN_min["packetize"])
    per_viewer_us = us(
        bcN_min["rewrite"] + bcN_min["protect"] + bcN_min["send"]
    ) / VIEWERS
    # ratios from per-LEG per-stage mins (host_plane_bench discipline):
    # the legs run adjacently, so each stage's min across pairs sees the
    # box's best state and the throttle bursts cancel out of the ratio
    amortization = (
        sum(bcN_min.values()) / VIEWERS / sum(ded_min.values())
        if ded_frame_us > 0 else 0.0
    )
    amortization_desynced = (
        sum(bcW_min.values()) / VIEWERS / sum(ded_min.values())
        if ded_frame_us > 0 else 0.0
    )
    overhead = (
        sum(bc1_min.values()) / sum(ded_min.values())
        if ded_frame_us > 0 else 0.0
    )

    budget_us = 1e6 / 30.0
    viewers_per_core = (
        int((budget_us - shared_us) / per_viewer_us)
        if per_viewer_us > 0 and shared_us < budget_us else 0
    )

    base = {
        "check": "broadcast_bench",
        "secure": secure,
        "codec": codec,
        "dim": DIM,
        "mtu": MTU,
        "frames": FRAMES,
        "viewers": VIEWERS,
        "dedicated_leg_us": ded_us,
        "broadcast_leg_us": bcN_us,
        "broadcast_desynced_leg_us": bcW_us,
        "dedicated_frame_us": round(ded_frame_us, 1),
        "broadcast_shared_us": round(shared_us, 1),
        "broadcast_per_viewer_us": round(per_viewer_us, 1),
        # steady state (aligned seq spaces — what the group sustains) and
        # the worst case (every viewer desynced post-replay, full copying
        # rewrite each): both per-viewer cost over the dedicated chain
        "amortization_ratio": round(amortization, 3),
        "amortization_ratio_desynced": round(amortization_desynced, 3),
        "stages": ("encode+packetize+rewrite+protect+send" if secure
                   else "encode+packetize+rewrite+send"),
        # acceptance pin riding the contract line: 16-viewer PLI storm →
        # exactly one GOP replay, zero encoder/engine IDRs
        "pli_storm": _pli_storm_probe(),
        "backend": _backend(),
        "live": True,
        "label": (
            f"broadcast_{codec}_{'full' if secure else 'nosrtp'}"
            f"_n{VIEWERS}_{DIM}px"
        ),
        "recorded_at": datetime.now(timezone.utc).isoformat(),
        # host-only microbench: probing a jax backend here would cost
        # more than the measurement (host_plane_bench precedent)
        "fingerprint": fingerprint(probe_jax=False),
    }
    line1 = dict(base)
    line1.update({
        "metric": "broadcast_viewers_per_core_30fps",
        "value": viewers_per_core,
        "unit": "viewers",
        # the amortization claim rides the capacity line: broadcast
        # per-viewer cost as a fraction of the dedicated chain at N
        "vs_baseline": round(amortization, 3),
    })
    line2 = dict(base)
    line2.update({
        "metric": "broadcast_single_viewer_overhead_ratio",
        "value": round(overhead, 3),
        "unit": "x",
        "vs_baseline": round(overhead, 3),
    })
    return [line1, line2]


def main():
    sigterm_to_exception("broadcast_bench timeout")
    entries = [{
        "check": "broadcast_bench",
        "metric": "broadcast_viewers_per_core_30fps",
        "value": 0,
        "unit": "viewers",
        "vs_baseline": 0.0,
    }]
    try:
        entries = run()
        for e in entries:
            _bank(e)
    except Exception as e:  # contract: JSON lines on EVERY exit path
        entries[0]["error"] = f"{type(e).__name__}: {e}"
        if ONLY_METRIC is not None:  # the selected row still gets ITS line
            entries[0]["metric"] = ONLY_METRIC
    for e in entries:
        if ONLY_METRIC is None or e.get("metric") == ONLY_METRIC:
            print(json.dumps(e))


if __name__ == "__main__":
    main()
