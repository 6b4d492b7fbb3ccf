"""Checker: functions handed to jax.jit / pjit / pallas_call must be pure.

A traced function runs ONCE per compilation geometry; anything read from
host state (env vars, clocks, numpy RNG, files) is frozen into the
compiled executable and silently goes stale — the worst kind of serving
bug, invisible until a knob flip "does nothing" because its value was
baked at trace time.

Seeds — a function is considered traced when it is:
* passed to ``jax.jit`` / ``jit`` / ``pjit`` / ``pl.pallas_call`` /
  ``pallas_call`` / ``jax.vmap`` / ``vmap`` / ``shard_map`` (also through
  ``partial(fn, ...)``),
* decorated with any of those (bare or via ``@partial(jax.jit, ...)``),
* passed to a local jit-wrapper: a same-module function whose own body
  calls one of the jit entry points (the ``_jit`` idiom in stream/engine.py),
* defined inside a factory whose call result is passed to a jit entry
  point (``jax.jit(make_step_fn(...))`` taints every def nested in
  ``make_step_fn``).

The closure is then walked transitively through same-module calls
(``helper(x)`` / ``self.helper(x)``) — impurities are reported where
they lexically occur.  Documented limits: cross-module calls are not
followed (the hot-path step functions live in one module each) and
impure modules are matched by their canonical names (``time.*``,
``np.random.*`` — an ``import time as _t`` alias evades the match, an
idiom the scanned code does not use inside traced functions).

Impure operations flagged: ``os.environ`` / ``os.getenv`` / typed
``env.get_*`` accessors, ``time.*`` clocks/sleeps, ``np.random.*`` and
``random.*`` host RNG, ``open()``, ``print()`` and socket/subprocess
calls.  ``jax.random`` is explicitly pure and allowed.
"""

from __future__ import annotations

import ast

from .core import Finding, dotted

CHECKER = "trace-purity"

_JIT_ENTRY_TAILS = {"jit", "pjit", "pallas_call", "vmap", "shard_map"}

_TIME_FNS = {
    "time", "monotonic", "perf_counter", "process_time", "time_ns",
    "monotonic_ns", "perf_counter_ns", "sleep",
}


def _is_jit_entry(func_expr) -> bool:
    name = dotted(func_expr)
    if not name:
        return False
    tail = name.split(".")[-1]
    return tail in _JIT_ENTRY_TAILS


def _impurity(call: ast.Call, env_modules) -> str | None:
    """Why this call is impure at trace time, or None."""
    name = dotted(call.func)
    if not name:
        return None
    parts = name.split(".")
    if name in ("os.getenv", "os.environ.get"):
        return "env read is frozen at trace time"
    if len(parts) >= 2 and parts[-2] in env_modules and parts[-1].startswith(
        "get_"
    ):
        return "typed env accessor read is frozen at trace time"
    if parts[0] == "time" and len(parts) == 2 and parts[1] in _TIME_FNS:
        return "host clock is frozen at trace time"
    if (
        len(parts) >= 3
        and parts[0] in ("np", "numpy")
        and parts[1] == "random"
    ):
        return "host RNG draws once at trace time — use jax.random"
    if parts[0] == "random" and len(parts) == 2:
        return "host RNG draws once at trace time — use jax.random"
    if name == "open":
        return "host file I/O inside a traced function"
    if name == "print":
        return "host print runs at trace time only — use jax.debug.print"
    if parts[0] == "subprocess":
        return "host subprocess inside a traced function"
    return None


def _impure_subscript(node, env_modules) -> str | None:
    """os.environ[...] subscript reads."""
    if isinstance(node, ast.Subscript) and dotted(node.value) == "os.environ":
        return "env read is frozen at trace time"
    return None


class _ModuleFuncs:
    def __init__(self, tree):
        self.defs = {}  # name -> node (module funcs + methods, last wins
        self.factories = {}  # kept separately for nested-def tainting
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self.defs.setdefault(item.name, item)
        # nested defs are resolvable too (closures inside methods)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for inner in ast.walk(node):
                    if (
                        inner is not node
                        and isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef))
                    ):
                        self.defs.setdefault(inner.name, inner)

    def resolve(self, expr):
        if isinstance(expr, ast.Name):
            return self.defs.get(expr.id)
        if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name):
            if expr.value.id in ("self", "cls"):
                return self.defs.get(expr.attr)
        return None


def _fn_args_of_call(call: ast.Call):
    """Expressions that name the traced callable in a jit-entry call:
    first positional arg, unwrapping partial(fn, ...)."""
    if not call.args:
        return []
    a = call.args[0]
    if (
        isinstance(a, ast.Call)
        and dotted(a.func).split(".")[-1] == "partial"
        and a.args
    ):
        return [a.args[0]]
    return [a]


def _local_jit_wrappers(tree, funcs) -> set:
    """Names of same-module functions whose body calls a jit entry point
    on one of their own parameters (the `_jit(fn)` idiom)."""
    wrappers = set()
    for name, node in funcs.defs.items():
        params = {p.arg for p in node.args.args + node.args.posonlyargs}
        for call in [
            n for n in ast.walk(node) if isinstance(n, ast.Call)
        ]:
            if not _is_jit_entry(call.func):
                continue
            for fa in _fn_args_of_call(call):
                roots = [
                    n.id for n in ast.walk(fa) if isinstance(n, ast.Name)
                ]
                if set(roots) & params:
                    wrappers.add(name)
    return wrappers


def _seed_traced(mod, funcs):
    """-> set of def nodes considered traced."""
    seeds = []
    wrappers = _local_jit_wrappers(mod.tree, funcs)

    def add_from_expr(expr, depth=0):
        if depth > 4:
            return
        node = funcs.resolve(expr)
        if node is not None:
            seeds.append(node)
            return
        # factory call: jax.jit(make_step_fn(...)) -> every nested def;
        # recurse into the arguments too, so composed wrappers
        # (_jit(_wrap_sp(make_step_fn(...)))) seed the innermost factory
        if isinstance(expr, ast.Call):
            factory = funcs.resolve(expr.func)
            if factory is not None:
                for inner in ast.walk(factory):
                    if (
                        inner is not factory
                        and isinstance(inner, (ast.FunctionDef, ast.AsyncFunctionDef))
                    ):
                        seeds.append(inner)
            for a in expr.args:
                add_from_expr(a, depth + 1)

    for call in [n for n in ast.walk(mod.tree) if isinstance(n, ast.Call)]:
        is_entry = _is_jit_entry(call.func)
        is_wrapper = (
            isinstance(call.func, ast.Name) and call.func.id in wrappers
        )
        if not (is_entry or is_wrapper):
            continue
        for fa in _fn_args_of_call(call):
            add_from_expr(fa)
    # decorators
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if _is_jit_entry(target):
                seeds.append(node)
            elif (
                isinstance(dec, ast.Call)
                and dotted(dec.func).split(".")[-1] == "partial"
                and dec.args
                and _is_jit_entry(dec.args[0])
            ):
                seeds.append(node)
    return seeds


def _env_module_aliases(tree) -> set:
    """Local names under which utils.env is imported ('env', 'env_util')."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.endswith("utils") or node.module.endswith("utils.env")
        ):
            for a in node.names:
                if a.name == "env" or node.module.endswith(".env"):
                    out.add(a.asname or a.name)
    out.add("env")  # conventional name, belt-and-braces
    return out


def check(project) -> list:
    findings = []
    for mod in project.modules:
        funcs = _ModuleFuncs(mod.tree)
        env_modules = _env_module_aliases(mod.tree)
        seeds = _seed_traced(mod, funcs)
        if not seeds:
            continue
        seen = set()
        queue = list(seeds)
        while queue:
            fn = queue.pop()
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    why = _impurity(node, env_modules)
                    if why:
                        findings.append(Finding(
                            CHECKER, mod.rel, node.lineno, dotted(node.func),
                            f"{dotted(node.func)} inside a traced function: "
                            f"{why}", fn.name,
                        ))
                    else:
                        callee = funcs.resolve(node.func)
                        if callee is not None:
                            queue.append(callee)
                why = _impure_subscript(node, env_modules)
                if why:
                    findings.append(Finding(
                        CHECKER, mod.rel, node.lineno, "os.environ",
                        f"os.environ read inside a traced function: {why}",
                        fn.name,
                    ))
    # dedupe (a function can be seeded several ways)
    uniq = {}
    for f in findings:
        uniq[(f.path, f.line, f.name)] = f
    return list(uniq.values())
