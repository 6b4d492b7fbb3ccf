"""Checker: device transfers flow through the blessed helpers.

The device-resident frame path (PR 9) has exactly three transfer
disciplines, each held by ONE helper: H2D staging is
``stream/engine.stage_frame`` (async ``device_put`` before any dispatch
lock), D2H readback is per-slot and memoized
(``BatchScheduler._resolve_row``; the engine's ``fetch`` for the
shared-engine plane), and async D2H kicks (``copy_to_host_async``) live
where the dispatch happens.  A stray transfer anywhere else is exactly
the bug class PR 9 removed — the scheduler's old dispatcher drained the
ENTIRE stacked ``[S, ...]`` batch output with one host copy, so every
session's fetch billed all the others — and it also blinds the
device-telemetry meters (obs/devtel.py counts bytes at the blessed
sites only).  Four rules:

* **stray-h2d** — ``jax.device_put(x)`` with a single argument (the
  implicit default-device frame-staging form) outside the blessed
  scopes.  Explicit placements (``device_put(tree, sharding)``) are
  param/mesh layout, not frame staging, and stay clean.
* **stray-d2h** — ``jax.device_get(...)`` outside the blessed scopes
  (any argument: the call has no host-side reading).
* **stray-async-d2h** — ``.copy_to_host_async()`` outside the blessed
  scopes.
* **batch-drain** — ``np.asarray``/``np.array`` applied to a value
  tainted as a device step output: a name assigned (same function,
  statement order) from calling a step callable (``self._step`` /
  ``self._step_cached`` / a ``self._bucket_step(...)`` factory result /
  a name bound to one), from ``stage_frame(...)``, or from
  ``jax.make_array_from_single_device_arrays(...)`` (a mesh-sharded
  global array — ``np.asarray`` of one is a CROSS-SHARD gather + host
  drain, the sharded spelling of the same every-fetch-bills-everyone
  bug; ISSUE 12's per-shard row readback exists so it never happens).
  Subscripts of tainted names taint too — ``np.asarray(out)[i]`` and
  ``np.asarray(out[i])`` are the same whole-batch host copy.  Host-data
  ``np.asarray`` (the similarity filter, codec planes) is untouched:
  only device-tainted arguments fire.

Blessed scopes (file → enclosing qualname): the helpers above, plus the
scheduler's sharded staging/readback sites by name (ISSUE 12 —
``BatchScheduler._assemble_frames`` owns the per-shard D2D placement
hops of the zero-copy global-batch assembly, ``BatchScheduler.
_rows_from_sharded`` owns slicing each session's row from its OWN
shard): named sites under the real rule, never a file-level exemption.
Export and parameter-placement tiers are exempt wholesale —
``aot/cache.py`` (serialize/deserialize), ``parallel/sharding.py`` /
``parallel/trainer.py`` / ``parallel/checkpoint.py`` (mesh layout +
training, not the serving frame path) — as are ``scripts/``,
``examples/`` and ``bench.py`` (operator tooling, the bounded-queue
carve-out).
"""

from __future__ import annotations

import ast

from .core import Finding, ScopedVisitor, dotted, terminal_name

CHECKER = "device-transfer"

_EXEMPT_PREFIXES = ("scripts/", "examples/")
_EXEMPT_FILES = (
    "bench.py",
    "__graft_entry__.py",
    "ai_rtc_agent_tpu/aot/cache.py",
    "ai_rtc_agent_tpu/parallel/sharding.py",
    "ai_rtc_agent_tpu/parallel/trainer.py",
    "ai_rtc_agent_tpu/parallel/checkpoint.py",
)

# file -> enclosing function qualnames where transfers are THE job
_BLESSED = {
    "ai_rtc_agent_tpu/stream/engine.py": {
        "stage_frame", "StreamEngine.submit", "StreamEngine.fetch",
    },
    "ai_rtc_agent_tpu/stream/scheduler.py": {
        "BatchScheduler._step_batch_locked", "BatchScheduler._resolve_row",
        "BatchScheduler._assemble_frames", "BatchScheduler._rows_from_sharded",
    },
}

# terminal names of attributes that hold a jitted step callable; calling
# one produces device values (the engine/scheduler idiom)
_STEP_ATTRS = {"_step", "_step_cached", "_raw_capture_step"}
# factories whose CALL returns a step callable: self._bucket_step(k, v)(...)
_STEP_FACTORIES = {"_bucket_step"}
# direct producers of device values: the blessed staging helper and the
# zero-copy sharded-batch assembly (np.asarray of the latter is a
# cross-shard gather drain)
_PRODUCER_CALLS = {"stage_frame", "make_array_from_single_device_arrays"}

_HOST_CAST = {
    "np.asarray", "numpy.asarray", "np.array", "numpy.array", "asarray",
}


class _Visitor(ScopedVisitor):
    def __init__(self, mod, blessed):
        super().__init__()
        self.mod = mod
        self.blessed = blessed
        self.findings = []
        # per-function taint: name -> line of the tainting assignment
        self._taint_stack = [{}]

    # fresh taint scope per function (statement-order within it)
    def _in_function(self, node):
        self._taint_stack.append({})
        self._in_named(node)
        self._taint_stack.pop()

    visit_FunctionDef = _in_function
    visit_AsyncFunctionDef = _in_function

    @property
    def _taint(self):
        return self._taint_stack[-1]

    def _flag(self, node, name, message):
        self.findings.append(
            Finding(CHECKER, self.mod.rel, node.lineno, name, message,
                    self.scope)
        )

    def _is_blessed(self) -> bool:
        return self.scope in self.blessed

    # -- taint machinery -------------------------------------------------------

    def _is_step_callable(self, expr) -> bool:
        if isinstance(expr, (ast.Attribute, ast.Name)):
            if terminal_name(expr) in _STEP_ATTRS:
                return True
            return (
                isinstance(expr, ast.Name) and expr.id in self._taint
                and self._taint[expr.id] == "callable"
            )
        return False

    def _is_producer_call(self, node) -> bool:
        """A call whose result is a device value: a step callable, a
        bucket-step factory result, or stage_frame."""
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if self._is_step_callable(f):
            return True
        if isinstance(f, ast.Call) and terminal_name(f.func) in _STEP_FACTORIES:
            return True
        return terminal_name(f) in _PRODUCER_CALLS

    @staticmethod
    def _target_names(targets):
        """Directly-bound names only: ``a``, ``a, b = ...`` — never the
        base of an attribute/subscript target (``p.frame_dev = ...``
        must not taint ``p``)."""
        out = []
        for t in targets:
            if isinstance(t, ast.Name):
                out.append(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    if isinstance(e, ast.Name):
                        out.append(e.id)
        return out

    def visit_Assign(self, node):
        self.generic_visit(node)
        if self._is_producer_call(node.value):
            kind = "device"
        elif isinstance(
            node.value, (ast.Attribute, ast.Name)
        ) and terminal_name(node.value) in _STEP_ATTRS:
            kind = "callable"  # fn = self._step; fn(...) produces device
        else:
            # plain reassignment clears taint (statement order)
            for n in self._target_names(node.targets):
                self._taint.pop(n, None)
            return
        for n in self._target_names(node.targets):
            self._taint[n] = kind

    def _tainted_device(self, expr) -> bool:
        if isinstance(expr, ast.Subscript):
            expr = expr.value
        return (
            isinstance(expr, ast.Name)
            and self._taint.get(expr.id) == "device"
        )

    # -- the four rules --------------------------------------------------------

    def visit_Call(self, node):
        name = dotted(node.func)
        tail = terminal_name(node.func)
        if tail == "device_put" and not self._is_blessed():
            # single-argument = implicit default-device staging; an
            # explicit sharding/device argument is parameter placement
            if len(node.args) + len(node.keywords) == 1:
                self._flag(
                    node, name or "device_put",
                    "stray H2D: bare device_put outside the blessed "
                    "staging path — route frame uploads through "
                    "stream/engine.stage_frame (async, metered, "
                    "lock-free)",
                )
        elif tail == "device_get" and not self._is_blessed():
            self._flag(
                node, name or "device_get",
                "stray D2H: device_get outside the blessed readback "
                "paths — resolve device outputs through the per-slot "
                "row readback / engine fetch",
            )
        elif tail == "copy_to_host_async" and not self._is_blessed():
            self._flag(
                node, name or "copy_to_host_async",
                "stray async D2H: copy_to_host_async outside the "
                "blessed dispatch sites — readback kicks belong where "
                "the dispatch happens (per-slot, never whole-batch)",
            )
        elif name in _HOST_CAST and node.args and self._tainted_device(
            node.args[0]
        ) and not self._is_blessed():
            self._flag(
                node, name,
                "whole-batch host drain: np.asarray of a device step "
                "output outside the blessed readback paths — this is "
                "the every-fetch-bills-all-sessions copy PR 9 removed; "
                "resolve per-slot rows instead",
            )
        self.generic_visit(node)


def check(project) -> list:
    findings = []
    for mod in project.modules:
        if mod.rel.startswith(_EXEMPT_PREFIXES) or mod.rel in _EXEMPT_FILES:
            continue
        v = _Visitor(mod, _BLESSED.get(mod.rel, frozenset()))
        v.visit(mod.tree)
        findings.extend(v.findings)
    return findings
