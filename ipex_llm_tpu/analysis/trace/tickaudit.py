"""JP106 groundwork: static dispatch-count audit of an engine tick.

One engine tick's device-dispatch count is THE quantity the ragged-paged-
attention superkernel roadmap item must drive to one — so it is locked
here, statically.  We cannot count dispatches of an abstract trace (no
execution), but we can enumerate which module-level jitted entries a
tick's scheduler functions can possibly call: the tick functions are
plain host Python, so every device dispatch they issue is a call to a
module-level jit-bound name, which plain AST walking finds exactly.

Kept jax-free: a caller reads the audited count without paying a tracer
import.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from importlib import import_module

from ipex_llm_tpu.analysis import astutil


@dataclass(frozen=True)
class TickSpec:
    """What one engine tick is allowed to dispatch.

    ``entries`` are the scheduler functions that make up the tick (host
    Python, searched by name anywhere in the module — methods included);
    ``programs`` the jitted callees that ARE the tick's dispatch chain.
    """
    name: str
    module: str                       # import path of the engine module
    entries: tuple[str, ...]
    programs: tuple[str, ...]
    max_dispatches: int = 2
    suppress: tuple[tuple[str, str], ...] = ()   # (code, reason)


def mixed_tick_spec() -> TickSpec:
    """The mixed prefill+decode tick: ONE fused program
    (``_ragged_tick`` — ragged prefill, on-device first-token merge,
    and the decode horizon in a single jitted entry; the steady-state
    tick is the same entry with no prefill block).  The gate is EXACTLY
    1 dispatch per tick — the ragged paged-attention superkernel
    invariant."""
    return TickSpec(
        name="mixed",
        module="ipex_llm_tpu.serving.engine",
        # a steady tick's dispatch lives in ``_launch``, reached from
        # ``_horizon_step`` for the tick's own program and for the ONE
        # queued behind it (``_queue_next``): the same jitted entry
        entries=("_mixed_step", "_horizon_step", "_launch_planned",
                 "_launch", "_queue_next"),
        programs=("_ragged_tick",),
        max_dispatches=1,
    )


def _module_source(module: str) -> str:
    import inspect

    return inspect.getsource(import_module(module))


def discover_tick_dispatches(spec: TickSpec,
                             source: str | None = None) -> set[str]:
    """Module-level jit-bound names callable from the tick's entry
    functions."""
    src = source if source is not None else _module_source(spec.module)
    tree = ast.parse(src)
    aliases = astutil.import_aliases(tree)
    jit_names = astutil.module_jit_names(tree, aliases)
    found: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in spec.entries:
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            f = call.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if name in jit_names:
                found.add(name)
    return found


def mixed_tick_dispatch_count(source: str | None = None) -> int:
    """Dispatches one mixed tick issues — the number the JP106 gate
    holds at one."""
    return len(discover_tick_dispatches(mixed_tick_spec(), source))
