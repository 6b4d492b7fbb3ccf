"""Typed environment-variable configuration tier.

The reference reads env vars ad hoc (docs/environment.md:3-25) and has a
latent TypeError: ``WARMUP_FRAMES`` is used unconverted (str when set) while
``DROP_FRAMES`` gets ``int(...)`` (reference lib/tracks.py:17-18).  Here every
env read goes through typed accessors so that class of bug cannot exist.

Recognised variables (superset of reference docs/environment.md):
  AUTH_TOKEN, WEBHOOK_URL            webhook eventing (lib/events.py parity)
  TWILIO_ACCOUNT_SID/_AUTH_TOKEN     ephemeral TURN credentials
  WARMUP_FRAMES, DROP_FRAMES         track warm-up / OBS stutter workaround
  XLA_ENGINES_CACHE                  AOT engine (jax.export) cache dir (the
                                     reference's TRT_ENGINES_CACHE role)
  CIVITAI_CACHE, HF_HUB_CACHE        weight caches (lib/utils.py:6-10)
  HW_ENCODE, HW_DECODE               native codec toggles (was NVENC/NVDEC,
                                     Dockerfile:53-56); on TPU these select
                                     the libavcodec native path vs null codec
  ENC_PRESET, ENC_TUNING_INFO,       encoder tuning (was NVENC_*,
  ENC_DEFAULT/MIN/MAX_BITRATE        docs/environment.md:17-25)
"""

from __future__ import annotations

import os

# the checkout: default home of everything the program builds at run time
# (AOT engines, the XLA compile cache) — anchored here, not to the working
# directory, so two launches from different directories share one cache
REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
)


def get_str(name: str, default: str | None = None) -> str | None:
    v = os.getenv(name)
    return v if v is not None and v != "" else default


def get_int(name: str, default: int) -> int:
    v = os.getenv(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError as e:
        raise ValueError(f"env var {name}={v!r} is not an integer") from e


def get_float(name: str, default: float) -> float:
    v = os.getenv(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError as e:
        raise ValueError(f"env var {name}={v!r} is not a float") from e


def get_bool(name: str, default: bool = False) -> bool:
    """Truthy iff set to a non-empty value that is not 0/false/no/off.

    The reference treats any non-empty NVENC/NVDEC as true
    (lib/pipeline.py:83); we keep that but allow explicit falsy spellings.
    """
    v = os.getenv(name)
    if v is None or v == "":
        return default
    return v.strip().lower() not in ("0", "false", "no", "off")


def get_str_aliased(name: str, alias: str, default: str | None = None):
    """get_str with a legacy alias consulted ONLY when ``name`` is unset —
    lazy, so a stale/invalid alias can't shadow a valid primary value
    (ENC_* vars accept the reference's NVENC_* spellings this way)."""
    v = os.getenv(name)
    if v not in (None, ""):
        return v
    return get_str(alias, default)


def get_int_aliased(name: str, alias: str, default: int) -> int:
    """get_int with a lazy legacy alias (see get_str_aliased)."""
    if os.getenv(name) not in (None, ""):
        return get_int(name, default)
    return get_int(alias, default)


# Graph-variant resolvers (jax-free) ----------------------------------------
# THE single definitions of the serving-graph variant defaults, parameterized
# on the backend name; stream/engine.current_attn_impl /
# current_fused_epilogue bind them to jax.default_backend().


def attn_impl_default(backend: str) -> str:
    """Resolved ATTN_IMPL (xla | pallas | ring | ulysses); empty env counts
    as unset; pallas is the default only on real TPUs."""
    return os.getenv("ATTN_IMPL") or ("pallas" if backend == "tpu" else "xla")


def fused_epilogue_default(backend: str) -> bool:
    """Resolved FUSED_EPILOGUE (operator kill-switch; on for real TPUs)."""
    return get_bool("FUSED_EPILOGUE", backend == "tpu")


# Canonical accessors -------------------------------------------------------

def warmup_frames() -> int:
    return get_int("WARMUP_FRAMES", 10)


def drop_frames() -> int:
    return get_int("DROP_FRAMES", 0)


def engines_cache() -> str:
    return get_str("XLA_ENGINES_CACHE") or os.path.join(
        REPO_ROOT, "models", "engines"
    )


def civitai_cache() -> str:
    return get_str("CIVITAI_CACHE") or "./models/civitai"


def hw_encode() -> bool:
    return get_bool("HW_ENCODE", get_bool("NVENC", False))


def hw_decode() -> bool:
    return get_bool("HW_DECODE", get_bool("NVDEC", False))


def slo_enabled() -> bool:
    """Stage-latency SLO plane (obs/slo.py) — always-on per-hop budget
    aggregation fed by the tracer mint path.  SLO_ENABLE=0 restores the
    bare tracing hot path (one fewer attribute read per frame); the
    plane also requires FLIGHT_RECORDER on, since its feed rides the
    session tracers."""
    return get_bool("SLO_ENABLE", True)


def devtel_enabled() -> bool:
    """Device telemetry plane (obs/devtel.py) — the compile watchdog +
    AOT/transfer accounting.  DEVTEL_ENABLE=0 removes it: the jax
    monitoring listener is never registered and the note_* hooks on the
    staging/readback hot paths reduce to one module-global read."""
    return get_bool("DEVTEL_ENABLE", True)


def journey_enabled() -> bool:
    """Fleet journey plane (fleet/journey.py) — cross-process trace
    correlation: the router mints an ``X-Journey-Id`` per placed
    session, keeps a bounded per-journey event ring, and serves
    one-GET incident bundles at ``/fleet/debug/journey/<id>``.
    ``JOURNEY_ENABLE=0`` removes the plane: no ids are minted or
    forwarded, the debug endpoints 404, and the remaining JOURNEY_*
    knobs are never read."""
    return get_bool("JOURNEY_ENABLE", True)


def migrate_enabled() -> bool:
    """Live session migration (docs/fleet.md "Drain runbook"):
    snapshot/restore of stream state between agents — the agent's
    /migrate/export//migrate/import endpoints and the router's
    ``POST /fleet/drain?mode=migrate`` + crash-restore paths.
    ``MIGRATE_ENABLE=0`` kills the whole surface: the agent endpoints
    404, the router refuses mode=migrate (409) and the crash path falls
    back to the plain AGENT_DEAD re-point."""
    return get_bool("MIGRATE_ENABLE", True)


def broadcast_fanout_enabled() -> bool:
    """Broadcast TX plane (server/broadcast.py): WHEP viewers of a
    native-provider stream share ONE encode/packetize pass and pay only a
    header rewrite + (SRTP) + sendmmsg slot each.  ``BROADCAST_FANOUT=0``
    restores the dedicated per-viewer chain (one private H264Sink and
    pump per viewer); the remaining BROADCAST_* knobs are read by the
    group and GOP cache themselves."""
    return get_bool("BROADCAST_FANOUT", True)


def broadcast_max_viewers() -> int:
    """Viewer admission cap per agent (BROADCAST_MAX_VIEWERS): /whep
    answers 503 + Retry-After past it.  Viewers don't charge engine
    slots, so this bounds TX fan-out cost (rewrite + send per viewer),
    not compute.  0 = uncapped."""
    return max(0, get_int("BROADCAST_MAX_VIEWERS", 256))


def broadcast_edge_pull_enabled() -> bool:
    """Two-level fan-out at the fleet tier (fleet/router.py): subscriber
    legs placed on non-owner agents trigger ONE pulled copy of the
    publisher's stream to that edge (POST /broadcast/pull), so audience
    size stops being a single-box property.  ``BROADCAST_EDGE_PULL=0``
    pins every viewer onto the owning agent instead."""
    return get_bool("BROADCAST_EDGE_PULL", True)


def batchsched_enabled() -> bool:
    """Continuous cross-session batch scheduler (stream/scheduler.py) —
    the default single-device serving path.  BATCHSCHED=0 restores the
    shared single-engine pipeline (sessions serialize through one
    submit lock); the remaining BATCHSCHED_* knobs are read by the
    scheduler itself."""
    return get_bool("BATCHSCHED", True)


def batchsched_dp() -> int:
    """dp shard count for the batch scheduler's session axis
    (BATCHSCHED_DP): the stacked [S, ...] session pytree shards its
    leading axis over a dp mesh of this many devices, so one agent
    process serves the whole chip complement it sits on.  0/1 (default)
    keeps the single-device scheduler.  Derived from MESH_SHAPE's dp
    component ONLY when BATCHSCHED_DP is unset: an explicit 0/1 is the
    per-box kill-switch back to the single-device scheduler even under
    a fleet-wide MESH_SHAPE."""
    if get_str("BATCHSCHED_DP") is not None:
        return max(1, get_int("BATCHSCHED_DP", 0))
    return max(1, mesh_shape()[0])


def adapter_dir() -> str | None:
    """Boot-time style-adapter catalog (ADAPTER_DIR): a directory of
    ``*.safetensors`` LoRA banks (adapter name = file stem) loaded into
    the AdapterRegistry and served as per-session factor banks through
    the batch scheduler (adapters/).  Unset (default) keeps the factors
    path OFF — the stacked state carries no bank, and AOT keys are
    unchanged from an adapterless build."""
    return get_str("ADAPTER_DIR")


def adapter_rank_buckets() -> tuple:
    """Blessed LoRA rank buckets (ADAPTER_RANK_BUCKETS, e.g. "4,8,16"):
    every adapter is zero-padded to the smallest bucket that holds its
    rank, and the scheduler sizes its stacked factor bank at the largest
    bucket in use — the closed set is what keeps hot-swaps same-shaped
    (never a retrace) and the (k, variant, rank, dp) AOT key space
    enumerable for prewarm.  An adapter above the largest bucket is
    REFUSED, never truncated."""
    v = get_str("ADAPTER_RANK_BUCKETS")
    if not v:
        return (4, 8, 16)
    try:
        buckets = tuple(sorted(int(p) for p in v.split(",") if p.strip()))
    except ValueError as e:
        raise ValueError(
            f"ADAPTER_RANK_BUCKETS={v!r} is not comma-separated ints"
        ) from e
    if not buckets or any(b < 1 for b in buckets):
        raise ValueError(f"ADAPTER_RANK_BUCKETS={v!r}: buckets must be >= 1")
    return buckets


def mesh_shape() -> tuple:
    """(dp, tp, sp) serving-mesh axis sizes from MESH_SHAPE ("8,1,1" or
    "8x1x1"; trailing axes default to 1) — the declarative alternative to
    the --tp/--sp CLI flags that also carries the scheduler's dp axis.
    Unset -> (1, 1, 1)."""
    v = get_str("MESH_SHAPE")
    if not v:
        return (1, 1, 1)
    parts = [p.strip() for p in v.replace("x", ",").split(",") if p.strip()]
    if len(parts) > 3:
        raise ValueError(
            f"MESH_SHAPE={v!r}: at most 3 axis sizes (dp,tp,sp)"
        )
    try:
        sizes = [int(p) for p in parts]
    except ValueError as e:
        raise ValueError(f"MESH_SHAPE={v!r} is not integer axis sizes") from e
    if any(s < 1 for s in sizes):
        raise ValueError(f"MESH_SHAPE={v!r}: axis sizes must be >= 1")
    return tuple(sizes + [1] * (3 - len(sizes)))


def perf_log_path(default: str) -> str:
    """PERF_LOG_PATH with the bench-banking semantics: unset -> the
    caller's default (the repo log); an EMPTY value -> ``""`` (banking
    disabled).  Plain :func:`get_str` would collapse empty to the default and
    silently re-enable self-banking."""
    v = os.getenv("PERF_LOG_PATH")
    return default if v is None else v


def pipeline_depth() -> int:
    """Frames kept in flight on the device per track (PIPELINE_DEPTH).

    1 = fully synchronous (reference behavior).  >1 overlaps dispatch,
    device compute and device->host copy across consecutive frames:
    throughput rises, and a frame is still one step and a few
    milliseconds from pull to pixels, because the track holds its pull
    until the running step is about to end (server/tracks.py)."""
    return max(1, get_int("PIPELINE_DEPTH", 2))
