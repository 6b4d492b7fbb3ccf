"""Multi-peer batching: N concurrent WebRTC streams on one chip or a mesh.

The reference serves multiple peers by sharing ONE pipeline with globally-
mutable state (reference agent.py:144-176, 423-430) — every peer sees every
prompt update, and frames are processed serially per track.  Here each peer
gets its OWN stream state (prompt, ring buffer, t-indices), all states are
stacked on a leading peer axis, and one vmapped+sharded step advances every
peer per wall-clock tick:

    states: pytree with leading axis [P, ...]   sharded over mesh axis `dp`
    frames: [P, H, W, 3]                        sharded over `dp`
    step_all = jit(vmap(step))                  one launch, P peers

This is BASELINE.json configs[4] ("Multi-peer WebRTC: N concurrent streams
batched on one TPU chip") and the honest replacement for DataParallel
(reference lib/wrapper.py:187-190).
"""

from __future__ import annotations

import logging
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import devtel
from ..stream.engine import (
    StreamConfig,
    StreamEngine,
    StreamModels,
    _coeff_state,
    make_step_fn,
    stage_frame,
)

logger = logging.getLogger(__name__)


class CapacityError(RuntimeError):
    """All peer slots are claimed (maps to HTTP 503 in the agent)."""


def make_bucket_step(vstep, capacity: int, scatter_output: bool = True):
    """Pure gather -> vmapped-step -> scatter over a stacked slot pytree.

    ``vstep(params, states_k, frames_k) -> (new_states_k, out_k)`` is the
    vmapped single-stream step; ``idx`` [k] selects which of ``capacity``
    slot rows participate.  Duplicate indices (bucket padding) are sound:
    the duplicated rows compute identical values, so the duplicate scatter
    writes land identical data.  The whole thing runs in ONE jitted call so
    the gather/scatter fuses with the step — shared by MultiPeerEngine's
    active-count buckets and the continuous batch scheduler
    (stream/scheduler.py), which is exactly the "slot/bucket design" reuse
    ROADMAP open item 1 calls for.

    ``scatter_output``: True returns a full-capacity output (callers index
    by slot id — the multipeer contract); False returns the k-shaped
    output aligned with ``idx`` (the scheduler resolves waiters by batch
    position, saving the zeros+scatter pass that measurably taxes small
    buckets)."""

    def bucket(params, states, frames_k, idx):
        # the jitted function keeps the name ``bucket``: the trace readers
        # find the step's program by it (``jit_bucket``)
        with jax.named_scope("gather"):
            sub = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), states)
        new_sub, out = vstep(params, sub, frames_k)
        with jax.named_scope("scatter"):
            new_states = jax.tree.map(
                lambda full, ns: full.at[idx].set(ns), states, new_sub
            )
            if not scatter_output:
                return new_states, out
            # scatter into a full-capacity output so callers keep indexing
            # by slot id (rows not in idx are zeros, discarded)
            full_out = jnp.zeros(
                (capacity,) + out.shape[1:], out.dtype
            ).at[idx].set(out)
        return new_states, full_out

    return bucket


class MultiPeerEngine:
    """Fixed-capacity peer-slot engine.

    Slots are pre-allocated (static shapes for AOT); connect/disconnect are
    slot claims/releases with per-slot state resets.  Below-capacity
    occupancy steps through power-of-two active-count buckets (gather
    active rows -> step -> scatter), so a --multipeer 8 agent with one
    peer pays ~1 peer of FLOPs, not 8 (MULTIPEER_BUCKETS=0 restores the
    always-full-batch behavior; dp-mesh engines always run full batch).
    """

    def __init__(
        self,
        models: StreamModels,
        params,
        cfg: StreamConfig,
        encode_prompt: Callable,
        max_peers: int,
        mesh: Mesh | None = None,
    ):
        self.cfg = cfg
        self.max_peers = max_peers
        self.mesh = mesh
        self.encode_prompt = encode_prompt
        self.models = models
        self.params = params
        # template engine used to build per-slot states (with DeepCache on,
        # its prepare() pre-sizes the per-slot unet_cache ring too)
        self._template = StreamEngine(
            models, params, cfg, encode_prompt, jit_compile=False
        )
        self._cache_interval = (
            cfg.unet_cache_interval if cfg.unet_cache_interval >= 2 else 0
        )
        self._tick = 0

        def _vjit(vfn):
            if mesh is not None and mesh.shape.get("dp", 1) > 1:
                # the session-axis rules (parallel/sharding.py) — ONE
                # recipe shared with the dp-sharded batch scheduler, so
                # the two serving tiers cannot drift on what shards
                from .sharding import session_shardings

                repl, row_sh = session_shardings(mesh)
                return jax.jit(
                    vfn,
                    in_shardings=(repl, row_sh, row_sh),
                    out_shardings=(row_sh, row_sh),
                    donate_argnums=(1,),
                )
            return jax.jit(vfn, donate_argnums=(1,))

        if self._cache_interval:
            # GLOBAL cadence: every slot captures on the same tick (one
            # vmapped graph per variant — per-peer phases are unnecessary
            # since the vmapped step applies one graph to all slots anyway;
            # install() resets the cadence so a fresh slot's zeroed cache
            # is never consumed before its first capture)
            vstep = jax.vmap(
                make_step_fn(models, cfg, unet_variant="capture"),
                in_axes=(None, 0, 0),
            )
            self._vstep_cached = jax.vmap(
                make_step_fn(models, cfg, unet_variant="cached"),
                in_axes=(None, 0, 0),
            )
            self._step_cached = _vjit(self._vstep_cached)
        else:
            vstep = jax.vmap(make_step_fn(models, cfg), in_axes=(None, 0, 0))
            self._vstep_cached = None
            self._step_cached = None
        self._step = _vjit(vstep)
        self.states = None  # stacked pytree [P, ...]
        self.active = [False] * max_peers
        # guards the shared template engine during heavy state builds
        # (text-encode + prepare) so concurrent connects don't race it;
        # deliberately separate from any caller-level step lock
        self._heavy_lock = threading.Lock()
        # Active-count buckets (VERDICT r2 weak #5): a --multipeer 8 agent
        # with 1 connected peer must not pay 8 peers of UNet FLOPs.  For
        # active counts below capacity, a bucket executable gathers the
        # active slots' state rows, steps ONLY those, and scatters back —
        # in one jitted call so the gather/scatter fuses with the step.
        # Power-of-two sizes bound the variant count (log2(P) compiles,
        # each lazily on the first tick at that occupancy).  Single-device
        # only: the full-capacity step keeps dp-mesh sharding semantics.
        self._vstep = vstep
        self._bucket_steps: dict = {}
        self._bucket_sizes = []
        b = 1
        while b < max_peers:
            self._bucket_sizes.append(b)
            b *= 2
        single_device = mesh is None or all(
            v == 1 for v in mesh.shape.values()
        )
        from ..utils import env as _env

        self._use_buckets = single_device and _env.get_bool(
            "MULTIPEER_BUCKETS", True
        )
        # buckets COMPOSE with DeepCache (VERDICT r3 item 7): bucket steps
        # are keyed (size, variant) so the count is bounded at
        # log2(P) x 2 — each still compiles lazily on first use at that
        # occupancy (or eagerly via prewarm_buckets)
        self._aot_adopted = False
        self._prewarmed = False

    def _fresh_state(self, prompt: str, seed: int):
        with self._heavy_lock:
            self._template.prepare(prompt, seed=seed)
            return self._template.state

    def start(self, default_prompt: str = ""):
        per_slot = [self._fresh_state(default_prompt, seed=i) for i in range(self.max_peers)]
        self.states = jax.tree.map(lambda *xs: jnp.stack(xs), *per_slot)
        return self

    # -- slot management ----------------------------------------------------

    @property
    def free_slots(self) -> int:
        return self.active.count(False)

    def reserve(self) -> int:
        """Cheap slot claim (no model work — safe under a serving lock)."""
        try:
            slot = self.active.index(False)
        except ValueError:
            raise CapacityError(
                f"all {self.max_peers} peer slots in use"
            ) from None
        self.active[slot] = True
        return slot

    def build_state(self, prompt: str, seed: int):
        """The HEAVY half of connect (text-encode + prepare) — run it
        outside any lock that gates the vmapped step."""
        return self._fresh_state(prompt, seed=seed)

    def install(self, slot: int, state):
        """Cheap slot-state write (device .at[slot].set)."""
        self._set_slot_state(slot, state)
        if self._cache_interval:
            # the fresh slot's unet_cache is zeros — make the NEXT step a
            # global capture so it is never consumed
            self._tick = 0
        logger.info("peer connected -> slot %d", slot)

    def connect(self, prompt: str, seed: int | None = None) -> int:
        slot = self.reserve()
        try:
            self.install(
                slot, self.build_state(prompt, seed=slot if seed is None else seed)
            )
        except Exception:
            self.active[slot] = False
            raise
        return slot

    def disconnect(self, slot: int):
        """Release a slot.  No state reset here: connect() always installs a
        fresh state before the slot is reused, and inactive slots' outputs
        are discarded — a reset would cost a full prepare() per disconnect
        and stall every live peer."""
        if not (0 <= slot < self.max_peers):
            raise ValueError(f"slot {slot} out of range [0, {self.max_peers})")
        self.active[slot] = False
        logger.info("peer disconnected <- slot %d", slot)

    def encode(self, prompt: str):
        """Heavy half of a prompt update (text-encoder forward) — call it
        OUTSIDE any lock that gates the step."""
        with self._heavy_lock:
            return self._template_encode(prompt)

    def apply_prompt(self, slot: int, cond, uncond, extras):
        """Cheap half: write the pre-encoded embeddings into the slot."""
        self._set_slot_leaf(("cond",), slot, cond)
        self._set_slot_leaf(("uncond",), slot, uncond)
        # SDXL-style conditioning extras must swap with the prompt too
        # (round-1 defect: pooled embeds silently kept the old prompt's)
        if self.cfg.use_added_cond and "pooled" in extras:
            self._set_slot_leaf(("added_text",), slot, extras["pooled"])
        if self._cache_interval:
            # DeepCache: stale deep cross-attention features must not serve
            # under the NEW prompt — recapture globally (same contract as
            # StreamEngine.update_prompt)
            self._tick = 0

    def update_prompt(self, slot: int, prompt: str):
        """Per-peer prompt update (an upgrade over the reference's global
        prompt mutation, agent.py:154-168)."""
        self.apply_prompt(slot, *self.encode(prompt))

    def update_t_index(self, slot: int, t_index_list):
        """Per-peer t_index update: a coefficient swap into this slot's
        state rows, zero recompile (same-length rule as
        StreamEngine.update_t_index_list)."""
        t_index_list = tuple(int(t) for t in t_index_list)
        if len(t_index_list) != self.cfg.n_stages:
            raise ValueError(
                f"t_index_list length must stay {self.cfg.n_stages} "
                "(compiled batch size)"
            )
        coeffs = _coeff_state(self.cfg, self._template.schedule, t_index_list)
        for k, v in coeffs.items():
            self.states["coeffs"][k] = self.states["coeffs"][k].at[slot].set(v)
        if self._cache_interval:
            self._tick = 0  # DeepCache: new timesteps -> global recapture

    def _template_encode(self, prompt):
        res = self.encode_prompt(prompt)
        return res if len(res) == 3 else (*res, {})

    def _set_slot_state(self, slot: int, state):
        self.states = jax.tree.map(
            lambda stacked, fresh: stacked.at[slot].set(fresh), self.states, state
        )

    def _set_slot_leaf(self, path: tuple, slot: int, value):
        node = self.states
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = node[path[-1]].at[slot].set(jnp.asarray(value, self.cfg.jdtype))

    # -- AOT engine adoption ------------------------------------------------

    def use_aot_cache(
        self, model_id: str, cache_dir: str | None = None,
        build_on_miss: bool = True,
    ) -> bool:
        """Swap the jitted all-peers step for a serialized AOT executable —
        the multipeer analog of StreamEngine.use_aot_cache (same key
        discipline with a ``peers-N`` attribute; reference engine-cache
        contract: lib/wrapper.py:732-746, :409-512).  Mesh-sharded engines
        are not exported (serialization is per-topology); returns False.
        """
        if self.mesh is not None and np.prod(list(self.mesh.shape.values())) > 1:
            return False
        if self.states is None:
            raise RuntimeError("call start() first (states define the signature)")
        from ..aot.cache import EngineCache
        from ..stream.engine import params_variant_extra, stream_engine_key

        # the single-peer key recipe (incl. cnet/fused/attn graph flags)
        # plus the peer dimension — one recipe, no drift between the two
        # serving modes' cache slots.  With DeepCache: BOTH variants
        # serialized per peer count, adopted atomically (a half-adopted
        # pair would mix an AOT step with a cold jit step mid-cadence —
        # same policy as StreamEngine.use_aot_cache).
        cache = EngineCache(cache_dir)
        frame_spec = jax.ShapeDtypeStruct(
            (self.max_peers, self.cfg.height, self.cfg.width, 3), jnp.uint8
        )
        args = (self.params, self.states, frame_spec)
        if self._cache_interval:
            plan = [
                (self._vstep, {"variant": "capture"}, "_step"),
                (self._vstep_cached, {"variant": "cached"}, "_step_cached"),
            ]
        else:
            plan = [(self._vstep, {}, "_step")]
        qextra = params_variant_extra(self.params)  # w8 never aliases dense
        keys = [
            stream_engine_key(
                model_id, self.cfg, peers=self.max_peers, **extra, **qextra
            )
            for _, extra, _ in plan
        ]
        if not build_on_miss and not all(cache.has(k, args) for k in keys):
            return False
        calls = []
        for (vfn, _, _), k in zip(plan, keys):
            call = cache.load_or_build(
                k, vfn, args, donate_argnums=(1,), build=build_on_miss
            )
            if call is None:
                return False
            calls.append(call)
        for (_, _, attr), call in zip(plan, calls):
            setattr(self, attr, call)
        self._aot_adopted = True  # full-batch cold-start path wins buckets
        return True

    # -- active-count buckets ------------------------------------------------

    def _bucket_for(self, n_active: int):
        """Smallest bucket covering ``n_active``, or None for the full step.

        Once an AOT executable is adopted, buckets only run if they were
        PREWARMED (prewarm_buckets, MULTIPEER_PREWARM_BUCKETS=1): the
        serialized full-batch step is the cold-start guarantee, and a lazy
        bucket compile at serve time would stall it — but prewarmed
        variants keep the idle-slot FLOPs saving on the AOT path too
        (code-review r3).
        """
        if not self._use_buckets or n_active == 0:
            return None
        if self._aot_adopted and not self._prewarmed:
            return None
        for b in self._bucket_sizes:
            if b >= n_active:
                return b
        return None  # at/above the largest bucket: full-capacity step

    def _bucket_step(self, k: int, variant: str = "full"):
        """Jitted step for ``k`` active slots.  ``variant``: "full" (the
        plain/capture graph) or "cached" (DeepCache outermost-tier graph) —
        keyed separately so buckets and UNET_CACHE compose (bounded:
        log2(P) sizes x 2 variants)."""
        step = self._bucket_steps.get((k, variant))
        if step is None:
            vstep = self._vstep if variant == "full" else self._vstep_cached
            step = jax.jit(
                make_bucket_step(vstep, self.max_peers), donate_argnums=(1,)
            )
            self._bucket_steps[(k, variant)] = step
            logger.info(
                "multipeer bucket step for %d/%d active slots (%s) "
                "registered (compiles on first use unless prewarmed)",
                k, self.max_peers, variant,
            )
        return step

    def prewarm_buckets(self):
        """ACTUALLY compile every bucket variant now (jax.jit alone is lazy
        — code-review r3): lower against the live state/param specs and swap
        the compiled executables in.  Trades a longer cold start for zero
        lazy-compile stalls when occupancy first reaches each bucket size;
        also re-enables buckets on the AOT-adopted path."""
        if not self._use_buckets:
            return
        if self.states is None:
            raise RuntimeError("call start() first (states define the specs)")
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        params_s = jax.tree.map(spec, self.params)
        states_s = jax.tree.map(spec, self.states)
        variants = ["full"] + (["cached"] if self._cache_interval else [])
        for k in self._bucket_sizes:
            frames_s = jax.ShapeDtypeStruct(
                (k, self.cfg.height, self.cfg.width, 3), jnp.uint8
            )
            idx_s = jax.ShapeDtypeStruct((k,), jnp.int32)
            for variant in variants:
                # devtel attribution (the scheduler's prewarm contract)
                with devtel.compile_scope(f"peers-{k}:{variant}"):
                    compiled = (
                        self._bucket_step(k, variant)
                        .lower(params_s, states_s, frames_s, idx_s)
                        .compile()
                    )
                self._bucket_steps[(k, variant)] = compiled
                logger.info(
                    "prewarmed bucket step %d/%d (%s)",
                    k, self.max_peers, variant,
                )
        self._prewarmed = True

    # -- hot path -----------------------------------------------------------

    def step_all(self, frames: np.ndarray) -> np.ndarray:
        """frames [P, H, W, 3] uint8 -> [P, H, W, 3] uint8 (all slots)."""
        return self.fetch(self.submit(frames))

    def submit(self, frames: np.ndarray):
        """Dispatch one all-peers step without waiting (see engine.submit)."""
        if self.states is None:
            raise RuntimeError("call start() first")
        if frames.shape[0] != self.max_peers:
            raise ValueError(f"expected {self.max_peers} frame slots, got {frames.shape[0]}")
        active_idx = [i for i, a in enumerate(self.active) if a]
        k = self._bucket_for(len(active_idx))
        if k is not None and isinstance(frames, np.ndarray):
            # pad with a repeat of the last active slot: identical compute,
            # duplicate scatter writes land identical values
            idx = (active_idx + [active_idx[-1]] * k)[:k]
            # through the ONE blessed H2D path (stage_frame): same async
            # staging, plus the devtel transfer meter sees every byte
            frames_k = stage_frame(np.ascontiguousarray(frames[idx]))
            variant = "full"
            if self._cache_interval:
                # same global cadence as the full-batch path: captures
                # refresh only the stepped (active) rows, which are exactly
                # the rows whose caches the cached variant will consume;
                # install() forces a capture tick on every new connect
                if self._tick % self._cache_interval != 0:
                    variant = "cached"
                self._tick += 1
            self.states, out = self._bucket_step(k, variant)(
                self.params, self.states, frames_k,
                jnp.asarray(idx, jnp.int32),
            )
            try:
                out.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
            return out
        if isinstance(frames, np.ndarray):
            # async upload before dispatch (same rationale as engine.submit);
            # on a dp mesh, land the batch PRE-SHARDED so the jitted step
            # never gathers the whole batch onto device 0
            if self.mesh is not None and self.mesh.shape.get("dp", 1) > 1:
                frames = jax.device_put(frames, NamedSharding(self.mesh, P("dp")))
            else:
                frames = stage_frame(frames)
        fn = self._step
        if self._cache_interval:
            if self._tick % self._cache_interval != 0:
                fn = self._step_cached
            self._tick += 1
        self.states, out = fn(self.params, self.states, frames)
        try:
            out.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        return out

    def fetch(self, pending) -> np.ndarray:
        out = np.asarray(pending)
        if out is not pending:  # a real device->host resolve
            devtel.note_d2h(out.nbytes)
        if out.ndim == 5 and out.shape[1] == 1:  # [P, fbs=1, H, W, 3]
            out = out[:, 0]
        return out
