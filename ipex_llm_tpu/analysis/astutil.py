"""Shared AST machinery for jaxlint rules.

Everything here is heuristic *local* analysis — no cross-module type
inference.  Rules buy precision by scoping themselves to the modules
where a hazard class is load-bearing (see ``config.py``) and by keeping
the per-module reasoning simple enough to audit: import-alias
resolution, "which functions run under trace", and a small
device-value dataflow.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterator


# --------------------------------------------------------------------------
# import-alias resolution
# --------------------------------------------------------------------------

def import_aliases(tree: ast.Module) -> dict[str, str]:
    """Map local names to the canonical dotted path they import.

    ``import jax.numpy as jnp`` -> {"jnp": "jax.numpy"};
    ``from jax import lax`` -> {"lax": "jax.lax"};
    ``from functools import partial`` -> {"partial": "functools.partial"}.
    """
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    # the conventional roots, in case a file uses them without importing
    # (fixture snippets); real modules override via their own imports
    out.setdefault("jnp", "jax.numpy")
    out.setdefault("np", "numpy")
    out.setdefault("jax", "jax")
    return out


def dotted_name(node: ast.AST) -> str | None:
    """``a.b.c`` attribute/name chain -> "a.b.c" (None for anything else)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def resolve(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """Canonical dotted path of a name/attribute chain, alias-expanded.

    With ``import jax.numpy as jnp``: ``jnp.asarray`` -> "jax.numpy.asarray".
    """
    dn = dotted_name(node)
    if dn is None:
        return None
    head, _, rest = dn.partition(".")
    root = aliases.get(head, head)
    return f"{root}.{rest}" if rest else root


def call_target(call: ast.Call, aliases: dict[str, str]) -> str | None:
    return resolve(call.func, aliases)


# --------------------------------------------------------------------------
# jit detection
# --------------------------------------------------------------------------

_JIT_PATHS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}

# callables whose function-valued arguments run under trace
_TRACING_CALLERS = {
    "jax.lax.while_loop", "jax.lax.fori_loop", "jax.lax.scan",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.vmap", "jax.pmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.custom_jvp", "jax.custom_vjp",
} | _JIT_PATHS


def is_jit_expr(node: ast.AST, aliases: dict[str, str]) -> bool:
    """True if ``node`` evaluates to a jit-wrapped callable.

    Covers ``jax.jit``, ``jax.jit(f, ...)`` and the two partial spellings
    ``partial(jax.jit, ...)`` / ``functools.partial(jax.jit, ...)``.
    """
    if resolve(node, aliases) in _JIT_PATHS:
        return True
    if isinstance(node, ast.Call):
        tgt = call_target(node, aliases)
        if tgt in _JIT_PATHS:
            return True
        if tgt == "functools.partial" and node.args:
            return is_jit_expr(node.args[0], aliases)
    return False


def jit_decorated(fn: ast.FunctionDef | ast.AsyncFunctionDef,
                  aliases: dict[str, str]) -> bool:
    return any(is_jit_expr(d, aliases) for d in fn.decorator_list)


def module_jit_names(tree: ast.Module, aliases: dict[str, str]) -> set[str]:
    """Module-level names bound to jit-wrapped callables.

    ``@partial(jax.jit, ...) def f(...)`` and ``g = jax.jit(impl)``.
    """
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if jit_decorated(node, aliases):
                names.add(node.name)
        elif isinstance(node, ast.Assign) and is_jit_expr(node.value, aliases):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


@dataclass
class TracedScope:
    """A function body that runs under jax tracing."""
    node: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
    reason: str        # "jit-decorated" | "passed to jax.lax.while_loop" | ...
    name: str          # display name ("<lambda>" for lambdas)


def traced_scopes(tree: ast.Module, aliases: dict[str, str]) -> list[TracedScope]:
    """Every function/lambda in the module whose body is traced.

    Two ways in: a jit decorator, or being passed (by local name or
    inline) to a tracing caller like ``lax.while_loop``.  Nested defs
    inside a traced function are traced too.
    """
    scopes: list[TracedScope] = []
    local_defs: dict[int, dict[str, ast.AST]] = {}

    # defs by enclosing scope so "passed by name" resolves locally
    def collect_defs(body: list[ast.stmt], bag: dict[str, ast.AST]) -> None:
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                bag[st.name] = st
            elif isinstance(st, ast.Assign) and isinstance(st.value, ast.Lambda):
                for t in st.targets:
                    if isinstance(t, ast.Name):
                        bag[t.id] = st.value

    all_defs: dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef)):
            collect_defs(node.body, all_defs)

    seen: set[int] = set()

    def add(fn: ast.AST, reason: str) -> None:
        if id(fn) in seen or not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        seen.add(id(fn))
        name = getattr(fn, "name", "<lambda>")
        scopes.append(TracedScope(fn, reason, name))
        # nested defs/lambdas inherit the trace
        for sub in ast.walk(fn):
            if sub is fn:
                continue
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                add(sub, reason)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if jit_decorated(node, aliases):
                add(node, "jit-decorated")
        elif isinstance(node, ast.Call):
            tgt = call_target(node, aliases)
            if tgt in _TRACING_CALLERS:
                short = tgt.rsplit(".", 1)[-1]
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        add(arg, f"passed to {short}")
                    elif isinstance(arg, ast.Name) and arg.id in all_defs:
                        add(all_defs[arg.id], f"passed to {short}")
            elif is_jit_expr(node, aliases) and node.args:
                f = node.args[0]
                if isinstance(f, ast.Lambda):
                    add(f, "jit of lambda")
                elif isinstance(f, ast.Name) and f.id in all_defs:
                    add(all_defs[f.id], "jit-wrapped")
    return scopes


# --------------------------------------------------------------------------
# device-value dataflow (local, per-function)
# --------------------------------------------------------------------------

_HOST_ROOTS = ("numpy.",)


@dataclass
class DeviceFlow:
    """Names in one function that (heuristically) hold device arrays.

    A name becomes "device" when assigned from a ``jnp.*``/``jax.*`` call
    or from a call to a known jit-bound callable; it reverts to host when
    reassigned from anything else (``np.asarray(x)`` launders on purpose:
    the *conversion itself* is the sync JL002 reports, the result is a
    host array).
    """
    aliases: dict[str, str]
    jit_names: set[str] = field(default_factory=set)
    device: set[str] = field(default_factory=set)

    def _is_device_call(self, call: ast.Call) -> bool:
        tgt = call_target(call, self.aliases)
        if tgt is None:
            # self._decode_fn(...) style: attribute call on self with a
            # name we were told is jit-bound
            dn = dotted_name(call.func)
            return bool(dn and dn.startswith("self.")
                        and dn.split(".", 1)[1] in self.jit_names)
        if tgt.startswith(_HOST_ROOTS):
            return False
        if tgt.startswith(("jax.numpy.", "jax.lax.", "jax.random.",
                           "jax.nn.")) or tgt in {"jax.device_put"}:
            return True
        head = tgt.split(".")[0]
        return head in self.jit_names or tgt in self.jit_names

    def _expr_is_device(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                if self._is_device_call(sub):
                    return True
                tgt = call_target(sub, self.aliases)
                if tgt and (tgt.startswith(_HOST_ROOTS)
                            or tgt in ("int", "float", "bool")
                            or tgt.rsplit(".", 1)[-1] == "d2h"):
                    # np.asarray/int()/float()/bool()/hostutil.d2h launder
                    # to host — the conversion site was the sync (JL002
                    # reports it); the result is host data
                    return False
            elif isinstance(sub, ast.Name) and sub.id in self.device:
                return True
        return False

    def assign(self, targets: list[ast.expr], value: ast.AST) -> None:
        names: list[str] = []
        for t in targets:
            if isinstance(t, ast.Name):
                names.append(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                names.extend(e.id for e in t.elts if isinstance(e, ast.Name))
        if not names:
            return
        # function-valued alias: `tick_fn = _ragged_tick` (a jit-bound
        # name, bare or self.-qualified) makes calls through the alias
        # device-producing too — otherwise a sync on the aliased call's
        # result escapes JL002 via one level of indirection
        dn = dotted_name(value)
        if dn is not None:
            ref = dn.split(".", 1)[1] if dn.startswith("self.") else dn
            if ref in self.jit_names or dn in self.jit_names:
                self.jit_names.update(names)
                self.device.difference_update(names)
                return
        is_dev = self._expr_is_device(value)
        for n in names:
            (self.device.add if is_dev else self.device.discard)(n)
