"""Sharding rules: how model params and activations map onto the mesh.

Tensor-parallel (tp) rules for the UNet/CLIP pytrees — the TPU-native
replacement for the reference's (unused) DataParallel option
(lib/wrapper.py:187-190), except real: Megatron-style column/row splits on
the attention and MLP matmuls, channel splits on convs, replicated norms.
Applied as pjit in_shardings so XLA GSPMD inserts the ICI collectives.

Path-pattern based: rules are (predicate on path leaf names) -> PartitionSpec,
resolved per leaf over the whole pytree.
"""

from __future__ import annotations

import re
from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# column-parallel: shard OUTPUT dim (last axis of our [in,out] kernels and
# HWIO convs); row-parallel: shard INPUT dim (second-to-last axis)
_COLUMN_PAT = re.compile(
    r"(to_q|to_k|to_v|q|k|v|fc1|proj|linear_1|conv1|conv_in|downsample)/kernel$"
)
_ROW_PAT = re.compile(r"(to_out|out|fc2|linear_2|conv2|conv_out|upsample)/kernel$")


def unet_tp_rules(path_s: str, ndim: int):
    if _COLUMN_PAT.search(path_s):
        return P(*([None] * (ndim - 1) + ["tp"]))
    if _ROW_PAT.search(path_s):
        if ndim >= 2:
            return P(*([None] * (ndim - 2) + ["tp", None]))
    # biases feeding column-parallel outputs
    if _COLUMN_PAT.search(path_s.replace("/bias", "/kernel")) and path_s.endswith("bias"):
        return P("tp")
    return P()  # replicate (norms, embeddings, everything else)


def param_shardings(mesh: Mesh, params, rules: Callable = unet_tp_rules):
    """Pytree of NamedShardings for pjit in_shardings."""

    def leaf_sharding(path, leaf):
        spec = rules(_path_str(path), getattr(leaf, "ndim", 0))
        # drop axes that don't divide evenly -> replicate that axis
        dims = []
        for i, ax in enumerate(spec):
            if ax is None:
                dims.append(None)
                continue
            size = mesh.shape[ax]
            if leaf.shape[i] % size == 0 and leaf.shape[i] >= size:
                dims.append(ax)
            else:
                dims.append(None)
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def activation_spec(mesh: Mesh, batch_axis: str = "dp", seq_axis: str | None = "sp"):
    """[B, H, W, C] activation sharding: batch over dp, height over sp
    (spatial sharding IS sequence parallelism for image tokens; XLA inserts
    halo exchanges for convs and gathers for attention)."""
    axes = [batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None]
    axes.append(seq_axis if seq_axis and mesh.shape.get(seq_axis, 1) > 1 else None)
    return P(*axes, None, None)


def shard_params(mesh: Mesh, params, rules: Callable = unet_tp_rules):
    """device_put the pytree according to the rules (materializes shards)."""
    sh = param_shardings(mesh, params, rules)
    return jax.device_put(params, sh)


# -- session-axis (dp) sharding: the serving-tier rules ----------------------
# The batch scheduler's stacked [S, ...] session pytree shards its LEADING
# axis over dp; params replicate (or follow the tp rules above when a tp
# axis is present).  These helpers are the single recipe the scheduler
# derives its pjit in/out specs from.


def session_axis_spec(mesh: Mesh, axis: str = "dp"):
    """PartitionSpec for a leading session/peer axis: ``activation_spec``'s
    batch rule generalized to any-rank stacked state leaves (only the
    leading axis shards; everything trailing replicates with it)."""
    if mesh.shape.get(axis, 1) <= 1:
        return P()
    return P(axis)


def session_shardings(mesh: Mesh, axis: str = "dp"):
    """(replicated, session-axis) NamedSharding pair for a sharded serving
    step: params ride the first (single sharding broadcast over the whole
    pytree — pjit prefix semantics), the stacked states/frames/outputs ride
    the second on their leading [S]/[k] axis."""
    return (
        NamedSharding(mesh, P()),
        NamedSharding(mesh, session_axis_spec(mesh, axis)),
    )


def dp_devices(mesh: Mesh, axis: str = "dp"):
    """The dp axis's device list in axis order — shard d of a leading-axis
    sharded array lives on ``dp_devices(mesh)[d]`` (the staging side of the
    session-axis rules: a session's H2D copy lands on its OWN shard)."""
    import numpy as np

    axes = list(mesh.axis_names)
    arr = np.moveaxis(mesh.devices, axes.index(axis), 0)
    return [arr[d].flat[0] for d in range(mesh.shape.get(axis, 1))]
