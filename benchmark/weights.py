"""Weights from ``--seed``: made on the device in one jitted call, in the
dtype they are served in, from the shapes in ``reference/layout.py``.

Every leaf gets its own key (the run's key folded with the leaf's index in
the flattened tree) and a scale by its role: kernels ``gain / sqrt(fan_in)``
so activations stay of order one through the depth, norm scales near one,
biases and embeddings small.  ``rules`` (the configuration file's
``weights.rules``: ``[path regex, gain, shift]``, first match wins) adjust
single leaves, so that a configuration can keep its decoded image inside
the range where a difference shows (see PERF.md on the comparison).
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp

_DEFAULTS = [
    (r"/kernel$", 1.0, 0.0),
    (r"/scale$", 0.05, 1.0),
    (r"/bias$", 0.02, 0.0),
    (r"token_embedding$", 0.02, 0.0),
    (r"position_embedding$", 0.01, 0.0),
]


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def run_key(seed: int):
    """A key from any whole number (``--seed`` may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def make_weights(shapes, seed: int, dtype, rules=()):
    """shapes: tree with shape-tuple leaves -> same tree of device arrays."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    plan = []
    for path, shape in leaves:
        name = _path_str(path)
        for pattern, gain, shift in list(rules) + _DEFAULTS:
            if re.search(pattern, name):
                break
        else:
            raise ValueError(f"no weight rule matches leaf {name!r}")
        if name.endswith("/kernel"):
            gain = gain / math.sqrt(max(1, math.prod(shape[:-1])))
        plan.append((shape, float(gain), float(shift)))

    # leaves of one shape are drawn by one vmapped call (one key each, from
    # the leaf's index): some sixty random ops instead of twelve hundred,
    # which is the difference between 40 s and 340 s of XLA compile
    groups: dict = {}
    for i, (shape, _, _) in enumerate(plan):
        groups.setdefault(shape, []).append(i)

    def build(key):
        out = [None] * len(plan)
        for shape, idxs in groups.items():
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.asarray(idxs, jnp.uint32)
            )
            x = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(keys)
            col = (len(idxs),) + (1,) * len(shape)
            gains = jnp.asarray([plan[i][1] for i in idxs], jnp.float32).reshape(col)
            shifts = jnp.asarray([plan[i][2] for i in idxs], jnp.float32).reshape(col)
            x = (x * gains + shifts).astype(dtype)
            for j, i in enumerate(idxs):
                out[i] = x[j]
        return out

    arrays = jax.jit(build)(run_key(seed))
    return jax.tree_util.tree_unflatten(treedef, arrays)


def same_layout(ours, theirs) -> str | None:
    """None when two trees have the same structure and leaf shapes, else
    the first difference in words.  ``ours``: shape tuples; ``theirs``:
    anything with ``.shape``."""
    a = jax.tree_util.tree_flatten_with_path(ours, is_leaf=_is_shape)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    names_a = {_path_str(p): tuple(s) for p, s in a}
    names_b = {_path_str(p): tuple(x.shape) for p, x in b}
    for name in sorted(set(names_a) | set(names_b)):
        if names_a.get(name) != names_b.get(name):
            return (
                f"leaf {name}: benchmark layout {names_a.get(name)}, "
                f"program {names_b.get(name)}"
            )
    return None
