#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the agent's default serving path once, through the entry points a user
would call, at the full width of SD-Turbo (SD2.1 widths 320/640/1280/1280,
512x512, bf16, random weights from a seed), and fails unless what came out
is right.  Run it through the chip tool from the root of a checkout:

    python3 chip_smoke.py

This process never imports JAX: a chip belongs to one process at a time, so
every phase that needs it is a child process, one at a time, each gone before
the next starts.  The parent is the real client (H.264 over RTP/UDP).

Phases (the run stops at the first that fails):

  device      a child asks JAX for its devices; anything but a TPU ends the
              run here, before a frame is served.
  serve-cold  start ``python -m ai_rtc_agent_tpu.server.agent`` with its
              defaults (provider, BatchScheduler plane, dtype, kernels), the
              scheduler capped at two slots so the boot compiles three
              executables.  One session streams alone (the inline k=1 path),
              a second joins (coalesced k=2 bucket steps), one prompt and
              one t-index update land mid-stream.  Then /health, /metrics
              and the decoded return streams are checked, and SIGTERM must
              end the agent promptly with exit code 0.
  serve-warm  boot the agent again: XLA's persistent compile cache must hit
              and one short session must still be served.
  kernels     ``scripts/tpu_numerics_check.py``: each Pallas kernel, compiled,
              against a plain-XLA reference at the served shapes.

On success the last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
and the exit code is 0.  Any failed check, a child that dies, or the time
budget running out gives a non-zero exit code and no such line.

``--tiny`` is a plumbing run for the CPU (``JAX_PLATFORMS=cpu``): the
``tiny-test`` model and small kernel shapes, every phase runs whatever
fails, and it can never pass — it has no chip to prove anything about.
Logs of the agents it starts go to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

from ai_rtc_agent_tpu.media import native
from ai_rtc_agent_tpu.media.rtp_client import NativeRtpClient

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included
FPS = 30
PTS_TICK = 90_000 // FPS  # NativeRtpClient.send stamps frame i with i * this
SOLO_S, DUO_S, WARM_S = 2.0, 4.0, 2.0  # seconds streamed per stage
# Share of the frames sent that must come back stylized.  The agent drops
# frames it cannot keep up with (latest-wins), so this is a floor that says
# "a stream, not a trickle" — what the chip sustains is a benchmark's
# business, not this script's.
MIN_RETURN_SHARE = 0.10
SIGTERM_GRACE_S = 30.0
# uint8 levels: a returned frame must differ from the one sent by more than
# two H.264 passes do (about 3 on this content), and must not be flat
MIN_STYLIZED_DIFF, MIN_FRAME_STD = 8.0, 2.0
# ... and two sessions fed different patterns must not get the same frames
MIN_SESSIONS_APART = 1.0
# (How much ONE session's output moves when its input does is printed, not
# asserted: SD-Turbo's single step runs at t=999, where the input latents
# weigh 7 % against the noise, so even a negative of the input moves the
# output by about one level — too close to H.264's own frame-to-frame
# refinement to tell a live output from a frozen one.  That the engine
# stepped on these frames is asserted from the scheduler's and the
# supervisors' counters instead.)
# the TPU defaults of the serving graph (models/registry, utils/env)
TPU_DEFAULTS = {"dtype": "bfloat16", "attn_impl": "pallas", "fused_epilogue": True}
KERNELS = ("flash_attention", "fused_stream_epilogue")


class Run:
    """What the phases share: the arguments, the clock and what was found."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        self.model_id = "tiny-test" if tiny else "stabilityai/sd-turbo"
        self.deadline = time.monotonic() + BUDGET_S
        self.device: dict = {}
        self.cold_boot: dict = {}  # /metrics after the cold boot, for the warm check

    def remaining(self, reserve: float = 0.0) -> float:
        left = self.deadline - time.monotonic() - reserve
        if left <= 0:
            raise TimeoutError(f"the {BUDGET_S:.0f} s budget ran out")
        return left


# -- the agent as a child process ---------------------------------------------


class Agent:
    def __init__(self, run: Run, label: str):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(OUT_DIR, f"agent_{label}.log")
        env = dict(os.environ)
        # two scheduler slots: the boot compiles the pipeline's warm-up step
        # and buckets k=1,2 instead of k=1,2,4,8 — the model stays full width
        env["BATCHSCHED_MAX_SESSIONS"] = "2"
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ai_rtc_agent_tpu.server.agent",
             "--model-id", run.model_id, "--port", str(self.port)],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.t_start = time.monotonic()

    def request(self, path: str, body=None, timeout: float = 60.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            text = resp.read().decode()
        return text if text == "OK" else json.loads(text)

    async def wait_ready(self, timeout: float) -> float:
        """Seconds until ``GET /`` answers OK; raises if the agent exits or
        the time runs out (the boot is where a refused kernel shows up)."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"agent exited with code {self.proc.returncode} during "
                    f"boot\n{self.log_tail()}"
                )
            try:
                if self.request("/", timeout=2.0) == "OK":
                    return time.monotonic() - self.t_start
            except OSError:
                pass
            await asyncio.sleep(1.0)
        raise TimeoutError(f"agent not ready after {timeout:.0f} s\n{self.log_tail()}")

    def log_tail(self, lines: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])

    def terminate(self) -> list:
        """SIGTERM, and require a prompt exit with code 0."""
        problems = []
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=SIGTERM_GRACE_S)
        except subprocess.TimeoutExpired:
            problems.append(f"agent still running {SIGTERM_GRACE_S:.0f} s after SIGTERM")
        else:
            print(f"  SIGTERM -> exit code {rc} in {time.monotonic() - t0:.1f} s")
            if rc != 0:
                problems.append(f"agent exit code {rc} after SIGTERM, expected 0")
        return problems

    def kill(self):
        """Whatever happened: nothing this script started outlives it."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()


# -- one client session --------------------------------------------------------


def _mean_abs_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.mean(np.abs(a.astype(np.int16) - b.astype(np.int16))))


class Session:
    """One /offer session: paces moving frames in, keeps what comes back."""

    def __init__(self, name: str, height: int, width: int, vertical: bool):
        self.name, self.vertical = name, vertical
        y, x = np.mgrid[0:height, 0:width].astype(np.float32)
        u = (y if vertical else x) / (height if vertical else width)
        v = (x if vertical else y) / (width if vertical else height)
        # a smooth full-frame pattern: rolling it changes every pixel, and
        # the two sessions' patterns differ everywhere
        self._base = np.stack(
            [127 + 120 * np.sin(2 * np.pi * (2 * u + k / 3.0)) * np.cos(np.pi * v)
             for k in range(3)], axis=-1,
        ).astype(np.uint8)
        self.rtp = NativeRtpClient(width, height, fps=FPS)
        self.sent = 0
        self.sent_by_pts: dict = {}
        # per returned frame: its std, and mean |returned - sent| where the
        # frame sent with the same pts is still kept
        self.stds: list = []
        self.diffs: list = []
        self.last = None  # newest returned frame
        self.max_change = 0.0  # largest mean |d| between consecutive returns
        self.max_late_ms = 0.0

    def frame(self, i: int, negative: bool) -> np.ndarray:
        frame = np.roll(self._base, 8 * i, axis=0 if self.vertical else 1)
        return 255 - frame if negative else frame

    async def open(self, agent: Agent):
        await self.rtp.open()
        answer = await asyncio.to_thread(
            agent.request, "/offer",
            {"room_id": f"smoke-{self.name}",
             "offer": {"sdp": self.rtp.offer_envelope(), "type": "offer"}},
        )
        await self.rtp.connect(json.loads(answer["sdp"])["server_port"])

    def _on_frame(self, rgb: np.ndarray, pts: int):
        self.stds.append(float(rgb.std()))
        src = self.sent_by_pts.get(int(pts))
        if src is not None and src.shape == rgb.shape:
            self.diffs.append(_mean_abs_diff(rgb, src))
        if self.last is not None and self.last.shape == rgb.shape:
            self.max_change = max(self.max_change, _mean_abs_diff(rgb, self.last))
        self.last = rgb

    async def run(self, seconds: float):
        """Stream for ``seconds`` at FPS — the pattern moves every frame and
        turns into its negative halfway — then collect what is in flight."""
        tick = 1.0 / FPS
        t0 = time.monotonic()
        n = int(seconds * FPS)
        for k in range(n):
            i = self.sent
            frame = self.frame(i, negative=k >= n // 2)
            self.sent_by_pts[i * PTS_TICK] = frame
            self.sent_by_pts.pop((i - 90) * PTS_TICK, None)
            self.rtp.send(frame, i)
            self.sent += 1
            self.rtp.drain(self._on_frame)
            late = time.monotonic() - (t0 + (k + 1) * tick)
            self.max_late_ms = max(self.max_late_ms, 1e3 * late)
            await asyncio.sleep(max(0.0, -late))
        end = time.monotonic() + 2.0
        while time.monotonic() < end:
            await asyncio.sleep(0.05)
            self.rtp.drain(self._on_frame)

    def check(self) -> list:
        problems = []
        n, stds, diffs = len(self.stds), self.stds, self.diffs
        floor = max(3, int(MIN_RETURN_SHARE * self.sent))
        print(
            f"  session {self.name}: sent {self.sent}, returned {n} "
            f"(floor {floor}), generator at most {self.max_late_ms:.0f} ms "
            f"late; returned frames: std >= {min(stds, default=0):.1f}, "
            f"mean |returned - sent| >= {min(diffs, default=0):.1f}, largest "
            f"change between consecutive returns {self.max_change:.1f} levels"
        )
        if n < floor:
            return [f"session {self.name}: {n} frames returned of {self.sent} "
                    f"sent, fewer than {floor}"]
        if min(stds) < MIN_FRAME_STD:
            problems.append(
                f"session {self.name}: a returned frame is flat (std "
                f"{min(stds):.2f}); if random bf16 weights saturate at this "
                "width, seed them otherwise"
            )
        if not diffs:
            problems.append(f"session {self.name}: no returned frame carries a sent pts")
        elif min(diffs) < MIN_STYLIZED_DIFF:
            problems.append(
                f"session {self.name}: a returned frame equals what was sent "
                f"(mean |d| {min(diffs):.2f} levels) — passthrough, not the engine"
            )
        return problems


# -- phases ---------------------------------------------------------------------


def phase_device(run: Run) -> list:
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=run.remaining(),
    )
    if r.returncode != 0:
        return [f"JAX found no device (exit {r.returncode}): {r.stderr[-800:]}"]
    run.device = json.loads(r.stdout.strip().splitlines()[-1])
    print(f"  device: {json.dumps(run.device)}")
    if run.device["platform"] != "tpu":
        return [f"platform is {run.device['platform']!r}, not 'tpu': no accelerator"]
    return []


def check_serving(run: Run, health: dict) -> list:
    """The graph that served, from /health: the device the smoke saw, the
    default plane, the TPU defaults, and the kernels in the compiled steps."""
    problems = []
    serving = health.get("serving", {})
    print(f"  serving: {json.dumps(serving, sort_keys=True)}")
    seen = {"platform": serving.get("platform"),
            "kind": serving.get("device_kind"),
            "count": serving.get("device_count")}
    if seen != run.device:
        problems.append(f"agent serves on {seen}, the device phase saw {run.device}")
    if serving.get("platform") != "tpu":
        problems.append(f"agent platform is {serving.get('platform')!r}, not 'tpu'")
    if serving.get("plane") != "batchsched":
        problems.append(f"serving plane is {serving.get('plane')!r}, not the default BatchScheduler")
    for key, want in TPU_DEFAULTS.items():
        if serving.get(key) != want:
            problems.append(f"served {key} is {serving.get(key)!r}, the TPU default is {want!r}")
    kernels = serving.get("mosaic_kernels", {})
    for bucket in ("sbucket-1:full", "sbucket-2:full"):
        found = kernels.get(bucket, {})
        missing = [k for k in KERNELS if found.get(k, 0) < 1]
        if missing:
            problems.append(
                f"compiled step {bucket} holds no Mosaic call for {missing} "
                f"(found {found})"
            )
    return problems


def check_sessions(health: dict, metrics: dict, expect: int) -> list:
    problems = []
    sessions = health.get("sessions", {})
    if len(sessions) != expect:
        problems.append(f"/health lists {len(sessions)} sessions, expected {expect}")
    for key, snap in sessions.items():
        if snap.get("state") != "HEALTHY":
            problems.append(f"session {key} is {snap.get('state')} ({snap.get('reason')})")
        if snap.get("passthrough_frames"):
            problems.append(f"session {key}: {snap['passthrough_frames']} passthrough frames")
        if not snap.get("processed_frames"):
            problems.append(f"session {key}: no processed frames")
    engine = health.get("engine", {})
    if engine.get("state") != "ARMED" or engine.get("trips"):
        problems.append(f"engine guard: {engine}")
    # Serving compiles NOTHING: the bucket steps were prewarmed and the
    # small eager per-slot programs around them rehearsed at boot
    # (BatchScheduler.rehearse), so two claims, a prompt update and a
    # t-index update later the compile counter of the serving phase is
    # still 0 — and the watchdog saw no breach.
    for key in ("devtel_serving_compiles_total", "retrace_breaches_total"):
        if metrics.get(key, 0) != 0:
            problems.append(
                f"{key} = {metrics[key]} after serving, expected 0 "
                f"({health.get('devtel', {}).get('recent_compiles')})"
            )
    return problems


def boot_facts(label: str, ready_s: float, boot: dict) -> None:
    print(
        f"  {label} boot: ready in {ready_s:.0f} s; compile "
        f"{boot.get('devtel_compile_ms_total', 0) / 1e3:.1f} s over "
        f"{boot.get('devtel_compiles_total')} executables (persistent "
        f"cache: {boot.get('compile_cache_misses_total')} written, "
        f"{boot.get('compile_cache_hits_total')} found)"
    )


async def serve(run: Run, label: str) -> list:
    cold = label == "cold"
    agent = Agent(run, label)
    sessions: list = []
    try:
        ready_s = await agent.wait_ready(run.remaining(reserve=120.0))
        boot = await asyncio.to_thread(agent.request, "/metrics")
        boot_facts(label, ready_s, boot)
        health = await asyncio.to_thread(agent.request, "/health")
        serving = health["serving"]
        h, w = serving["height"], serving["width"]

        a = Session("a", h, w, vertical=False)
        sessions.append(a)
        await a.open(agent)
        if not cold:
            await a.run(WARM_S)
        else:
            task_a = asyncio.ensure_future(a.run(SOLO_S + DUO_S))
            await asyncio.sleep(SOLO_S)
            b = Session("b", h, w, vertical=True)
            sessions.append(b)
            await b.open(agent)
            task_b = asyncio.ensure_future(b.run(DUO_S))
            # control plane mid-stream: neither update may compile
            await asyncio.sleep(1.0)
            await asyncio.to_thread(
                agent.request, "/config", {"prompt": "a watercolor of the sea"})
            await asyncio.sleep(1.0)
            last = serving["num_inference_steps"] - 1
            await asyncio.to_thread(
                agent.request, "/config",
                {"t_index_list": [min(t + 1, last)
                                  for t in serving["t_index_list"]]})
            await asyncio.gather(task_a, task_b)

        # sessions still connected: /health lists only live ones
        health = await asyncio.to_thread(agent.request, "/health")
        metrics = await asyncio.to_thread(agent.request, "/metrics")

        problems = check_serving(run, health)
        problems += check_sessions(health, metrics, expect=len(sessions))
        for s in sessions:
            problems += s.check()
        print(
            f"  scheduler: {metrics.get('batchsched_steps_total')} steps, "
            f"occupancy {metrics.get('batchsched_occupancy_hist')}"
        )
        if not metrics.get("batchsched_steps_total"):
            problems.append("batchsched_steps_total did not advance")
        if cold:
            hist = metrics.get("batchsched_occupancy_hist", {})
            if not hist.get("1") or metrics.get("batchsched_occupancy_max", 0) < 2:
                problems.append(
                    f"both the k=1 and the k=2 step must have run: occupancy {hist}")
            if a.last is not None and b.last is not None:
                apart = _mean_abs_diff(a.last, b.last)
                print(f"  sessions a and b: last returned frames {apart:.1f} levels apart")
                if apart < MIN_SESSIONS_APART:
                    problems.append(
                        f"two sessions with different inputs returned the same "
                        f"frame (mean |d| {apart:.2f})")
            peak = metrics.get("device_mem_peak_bytes_in_use", 0)
            print(f"  peak HBM: {peak / 2**30:.2f} GiB" if peak else "  peak HBM: not reported")
            if not peak:
                problems.append("device_mem_peak_bytes_in_use missing or 0")
            run.cold_boot = boot
        else:
            # everything the cold boot compiled and wrote, this boot finds
            hits = boot.get("compile_cache_hits_total", 0)
            written = run.cold_boot.get("compile_cache_misses_total", 0)
            if hits < max(1, written):
                problems.append(
                    f"warm boot found {hits} executables in the persistent "
                    f"cache; the cold boot wrote {written}")
            # ... and spends a small fraction of the cold boot's compile
            # time (checked when the cold boot really was cold)
            cold_s = run.cold_boot.get("devtel_compile_ms_total", 0.0)
            warm_s = boot.get("devtel_compile_ms_total", 0.0)
            if written and warm_s > 0.25 * cold_s:
                problems.append(
                    f"warm boot compiled for {warm_s / 1e3:.1f} s, more than a "
                    f"quarter of the cold boot's {cold_s / 1e3:.1f} s")
        problems += agent.terminate()
        if problems:
            print(f"  agent log: {agent.log_path}")
        return problems
    finally:
        for s in sessions:
            s.rtp.close()
        agent.kill()


def phase_serve_cold(run: Run) -> list:
    return asyncio.run(serve(run, "cold"))


def phase_serve_warm(run: Run) -> list:
    return asyncio.run(serve(run, "warm"))


def phase_kernels(run: Run) -> list:
    cmd = [sys.executable, os.path.join("scripts", "tpu_numerics_check.py")]
    r = subprocess.run(
        cmd + (["--tiny"] if run.tiny else []), cwd=ROOT, text=True,
        stdout=subprocess.PIPE, timeout=run.remaining(),
    )
    lines = r.stdout.strip().splitlines()
    print("\n".join("  " + ln for ln in lines[:-1]))
    if r.returncode != 0:
        return [f"kernel parity exited {r.returncode}: {lines[-1] if lines else ''}"]
    return []


PHASES = (
    ("device", phase_device),
    ("serve-cold", phase_serve_cold),
    ("serve-warm", phase_serve_warm),
    ("kernels", phase_kernels),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--tiny", action="store_true",
        help="CPU plumbing run (tiny-test, small kernels, every phase runs); "
             "never passes",
    )
    args = ap.parse_args(argv)
    os.makedirs(OUT_DIR, exist_ok=True)
    if not native.h264_available():
        raise SystemExit("no H.264 codec (native/libtpurtc.so + libavcodec): "
                         "the smoke streams real H.264")
    run = Run(args.tiny)
    failed = 0
    for name, phase in PHASES:
        t0 = time.monotonic()
        print(f"== {name}", flush=True)
        problems = phase(run)
        for p in problems:
            print(f"FAIL [{name}] {p}", flush=True)
        print(f"== {name}: {'FAILED' if problems else 'ok'} "
              f"({time.monotonic() - t0:.0f} s)", flush=True)
        failed += len(problems)
        if problems and not run.tiny:
            break
    if run.tiny:
        print("--tiny is a plumbing run; it proves nothing about a chip",
              file=sys.stderr)
        return 1
    if failed:
        print(f"chip_smoke: {failed} failure(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": run.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
