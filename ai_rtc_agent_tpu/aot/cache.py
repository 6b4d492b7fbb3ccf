"""AOT export + serialized-program cache.

TPU-native replacement for the reference's TensorRT engine layer: the
ONNX->TRT compile pipeline (reference lib/wrapper.py:712-915), the engine
cache key discipline (:732-746), the on-disk layout
``engines--<model>/{unet,vae_encoder,vae_decoder}.engine`` (:593-597,
896-910) and the "load engines without base weights" fast path (:409-512).

Here an "engine" is a serialized ``jax.export`` artifact: StableHLO plus the
calling convention, NOT a compiled executable.  Adopting one skips python
tracing and lowering of the model code; the XLA compile of that StableHLO
still runs in every process that loads it, and it is the minutes-long part
at real geometry.  What makes a second boot fast is XLA's persistent compile
cache (``utils/device.configure_compile_cache``), which holds the compiled
executable for the AOT and the plain-jit path alike.

Key discipline mirrors the reference exactly:
    model x mode x min/max batch x resolution x dtype x code-version
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from dataclasses import dataclass

import jax
from jax import export as jax_export

from .. import __version__
from ..obs import devtel
from ..utils import env

logger = logging.getLogger(__name__)


def _donating_call(exp, donate_argnums):
    """Wrap a (de)serialized export's ``call`` so buffer donation survives.

    ``jax.export`` records the donation aliasing in the StableHLO module
    (``tf.aliasing_output`` on the donated args) but ``Exported.call``
    re-enters jit WITHOUT donate_argnums, so the outer executable keeps a
    defensive copy of every "donated" arg alive — an AOT-adopted stream
    engine silently paid a full state-pytree copy (latent ring + noise +
    embeddings) per step.  Re-declaring the donation on the outer jit
    restores in-place aliasing end to end (audited by
    tests/test_aot_cache.py::test_aot_call_donates_state)."""
    if not donate_argnums:
        return exp.call
    return jax.jit(exp.call, donate_argnums=tuple(donate_argnums))


def engine_key(model_id: str, mode: str, **attrs) -> str:
    """Human-readable cache key (reference lib/wrapper.py:732-746 analog)."""
    safe_model = model_id.replace("/", "--")
    parts = [f"engines--{safe_model}", f"mode-{mode}"]
    for k in sorted(attrs):
        parts.append(f"{k}-{attrs[k]}")
    parts.append(f"v-{__version__}")
    return "--".join(parts)


def mesh_key_extra(mesh) -> dict:
    """Engine-key extras for a serving mesh — THE single recipe every key
    producer splices in (BatchScheduler.bucket_keys, prewarm labels, the
    build CLI), mirroring :func:`~..stream.engine.params_variant_extra`:
    empty for a trivial/absent mesh so every pre-existing single-device
    key stays valid, and a ``dp-N`` component otherwise so a dp-sharded
    executable can never collide with — or stand in for — the
    single-device one (a sharded program is per-topology; adopting it on
    the wrong mesh would fail at call time at best)."""
    if mesh is None:
        return {}
    dp = mesh.shape.get("dp", 1)
    return {"dp": dp} if dp > 1 else {}


def adapter_key_extra(rank: int) -> dict:
    """Engine-key extras for the per-session LoRA factor bank (adapters/):
    same empty-when-disabled discipline as :func:`mesh_key_extra` — an
    adapterless scheduler (bank rank 0) keeps every pre-existing key
    valid, while a bank-carrying executable keys on its padded rank so
    the AOT space is ``(k, variant, rank, dp)``.  Rank is the ONLY shape
    axis the bank adds: target set and adapter names live in the stacked
    state, so swaps never touch the key."""
    rank = int(rank or 0)
    return {"lrank": rank} if rank > 0 else {}


def _digest(key: str, args_spec: str, platform: str) -> str:
    h = hashlib.sha256(f"{key}|{args_spec}|{platform}|{jax.__version__}".encode())
    return h.hexdigest()[:16]


@dataclass
class EngineCache:
    """Directory-backed cache of serialized ``jax.export`` programs."""

    cache_dir: str | None = None

    def __post_init__(self):
        self.cache_dir = self.cache_dir or env.engines_cache()

    def _paths(self, key: str, digest: str):
        d = os.path.join(self.cache_dir, key)
        return d, os.path.join(d, f"{digest}.jaxexport"), os.path.join(
            d, f"{digest}.json"
        )

    def _signature(self, key: str, example_args):
        """(specs, args_spec, digest) for a key + example-arg signature —
        the single source of truth shared by has() and load_or_build()."""
        specs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tuple(example_args)
        )
        args_spec = ";".join(f"{s.shape}:{s.dtype}" for s in jax.tree.leaves(specs))
        return specs, args_spec, _digest(key, args_spec, jax.default_backend())

    def has(self, key: str, example_args) -> bool:
        """True when a serialized engine exists for this key + signature."""
        _, _, digest = self._signature(key, example_args)
        _, blob_path, _ = self._paths(key, digest)
        return os.path.exists(blob_path)

    def load_or_build(self, key: str, fn, example_args, donate_argnums=(),
                      build: bool = True):
        """Return a callable backed by a cached executable when possible.

        ``fn`` must be a pure function; ``example_args`` a tuple of arrays /
        ShapeDtypeStructs defining the static signature.  With
        ``build=False``, a miss (including an unreadable blob) returns None
        instead of compiling — the caller keeps its plain jit path.
        """
        platform = jax.default_backend()
        specs, args_spec, digest = self._signature(key, example_args)
        d, blob_path, meta_path = self._paths(key, digest)

        if os.path.exists(blob_path):
            with open(blob_path, "rb") as f:
                blob = f.read()
            try:
                exp = jax_export.deserialize(blob)
            except Exception as e:
                # a truncated or foreign blob: the flatbuffer reader raises
                # whatever it trips over (struct.error, AttributeError,
                # ValueError ...), so this one call is caught broadly — a
                # logged miss, and the caller compiles as if it were absent
                logger.warning(
                    "engine cache entry %s unreadable (%s: %s)",
                    blob_path, type(e).__name__, e,
                )
            else:
                logger.info("engine cache HIT %s (%s)", key, digest)
                # device telemetry (obs/devtel.py): hit counter + the
                # on-disk inventory gauges refresh at this (rare) touch
                devtel.note_aot("hit", cache=self)
                return _donating_call(exp, donate_argnums)
        devtel.note_aot("miss", cache=self)
        if not build:
            return None

        logger.info("engine cache MISS %s — compiling (first run is slow)", key)
        t0 = time.time()
        # the compile watchdog attributes the build's XLA compile to the
        # engine key
        with devtel.compile_scope(key):
            jitted = jax.jit(fn, donate_argnums=donate_argnums)
            exp = jax_export.export(jitted)(*specs)
            blob = exp.serialize()
        os.makedirs(d, exist_ok=True)
        tmp = blob_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, blob_path)
        with open(meta_path, "w") as f:
            json.dump(
                {
                    "key": key,
                    "digest": digest,
                    "platform": platform,
                    "jax": jax.__version__,
                    "args": args_spec,
                    "built_at": time.time(),
                    "build_seconds": time.time() - t0,
                },
                f,
                indent=2,
            )
        logger.info("engine built in %.1fs -> %s", time.time() - t0, blob_path)
        devtel.note_aot("build", seconds=time.time() - t0, cache=self)
        return _donating_call(exp, donate_argnums)

    def stats(self) -> tuple:
        """(entry count, total bytes) of serialized blobs on disk — the
        ``aot_cache_entries``/``aot_cache_bytes`` gauges.  Called by the
        devtel plane at cache touches (hit/miss/build), never per
        scrape, so /metrics stays disk-free."""
        entries = 0
        total = 0
        if os.path.isdir(self.cache_dir):
            for key in os.listdir(self.cache_dir):
                kd = os.path.join(self.cache_dir, key)
                if not os.path.isdir(kd):
                    continue
                for f in os.listdir(kd):
                    if f.endswith(".jaxexport"):
                        entries += 1
                        try:
                            total += os.path.getsize(os.path.join(kd, f))
                        except OSError:
                            pass  # racing delete — the gauge self-heals
        return entries, total

    def entries(self):
        """Metadata of every cached engine.  One corrupt/truncated meta
        JSON (a crashed build, a partial copy) must not crash the whole
        listing — such entries are skipped with a warning; the blobs they
        describe are still served by load_or_build (which reads the blob,
        not the meta)."""
        if not os.path.isdir(self.cache_dir):
            return []
        out = []
        for key in sorted(os.listdir(self.cache_dir)):
            kd = os.path.join(self.cache_dir, key)
            if os.path.isdir(kd):
                for f in sorted(os.listdir(kd)):
                    if f.endswith(".json"):
                        path = os.path.join(kd, f)
                        try:
                            with open(path) as fh:
                                out.append(json.load(fh))
                        except (OSError, ValueError) as e:
                            logger.warning(
                                "skipping unreadable engine meta %s (%s)",
                                path, e,
                            )
        return out


