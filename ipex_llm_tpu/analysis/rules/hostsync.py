"""JL002 hidden-host-sync: blocking device syncs in hot paths.

The serving tick loop's whole performance model is "one designed sync
per horizon" (see ``_decode_horizon_loop``); an accidental ``int(x)``,
``.item()``, ``np.asarray(device_value)`` or ``.block_until_ready()``
anywhere in the tick/decode/mixed-step path collapses pipelining and is
invisible in review — the code *works*, just 10x slower under load.

Detection is a small per-function dataflow: names assigned from
``jnp.*``/``jax.*`` calls (or calls to jit-bound names in the module)
are device values; converting one to host (``int``/``float``/``bool``/
``np.asarray``/``np.array``/``.item()``) is a blocking sync and gets
flagged.  ``.block_until_ready()`` / ``jax.block_until_ready`` is flagged
unconditionally — syncing is its only purpose.  Reassignment from a host
expression launders the name (the conversion site was the sync; the
result is host data).

Designed syncs stay, with a suppression naming WHY the block is the
intended one (e.g. "THE per-horizon sync").  Benches and tests are
relaxed to warn via config — they legitimately block on results.
"""

from __future__ import annotations

import ast

from ipex_llm_tpu.analysis import astutil
from ipex_llm_tpu.analysis.core import ERROR, register

_CONVERTERS = {"int", "float", "bool"}
_NP_CONVERTERS = {"numpy.asarray", "numpy.array"}


def _jit_bound_names(tree, aliases) -> set[str]:
    names = astutil.module_jit_names(tree, aliases)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if astutil.jit_decorated(node, aliases):
                names.add(node.name)
        elif isinstance(node, ast.Assign) and astutil.is_jit_expr(
                node.value, aliases):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    names.add(t.attr)
    return names


def _sync_findings(ctx, expr, flow):
    """Findings for sync patterns inside one expression."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        # x.block_until_ready() / jax.block_until_ready(x)
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "block_until_ready":
            yield ctx.finding("JL002", ERROR, node,
                              "block_until_ready() in a hot path — a "
                              "deliberate full sync; hoist out of the tick "
                              "loop or suppress with the reason it is the "
                              "designed sync point")
            continue
        tgt = astutil.call_target(node, ctx.aliases)
        if tgt == "jax.block_until_ready":
            yield ctx.finding("JL002", ERROR, node,
                              "jax.block_until_ready() in a hot path")
            continue
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
                and not node.args and flow._expr_is_device(node.func.value):
            yield ctx.finding("JL002", ERROR, node,
                              ".item() on a device value blocks until the "
                              "dispatched program finishes")
            continue
        if tgt and tgt.rsplit(".", 1)[-1] == "d2h" and node.args and \
                flow._expr_is_device(node.args[0]):
            yield ctx.finding(
                "JL002", ERROR, node,
                "d2h() is a designed blocking sync — keep it, with a "
                "suppression naming why this is the intended sync point")
            continue
        if tgt in _NP_CONVERTERS and node.args and \
                flow._expr_is_device(node.args[0]):
            yield ctx.finding(
                "JL002", ERROR, node,
                f"{tgt.rsplit('.', 1)[-1]}() materialises a device value on "
                "host (blocking sync) in a hot path")
            continue
        if isinstance(node.func, ast.Name) and node.func.id in _CONVERTERS \
                and len(node.args) == 1 and flow._expr_is_device(node.args[0]):
            yield ctx.finding(
                "JL002", ERROR, node,
                f"{node.func.id}() on a device value is a hidden blocking "
                "sync in a hot path")


def _walk_function(ctx, fn, jit_names):
    flow = astutil.DeviceFlow(ctx.aliases, jit_names)

    def visit(stmts):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue  # nested scopes analysed separately
            if isinstance(st, ast.Assign):
                yield from _sync_findings(ctx, st.value, flow)
                flow.assign(st.targets, st.value)
            elif isinstance(st, ast.AnnAssign) and st.value is not None:
                yield from _sync_findings(ctx, st.value, flow)
                flow.assign([st.target], st.value)
            elif isinstance(st, ast.AugAssign):
                yield from _sync_findings(ctx, st.value, flow)
            elif isinstance(st, (ast.If, ast.While)):
                yield from _sync_findings(ctx, st.test, flow)
                yield from visit(st.body)
                yield from visit(st.orelse)
            elif isinstance(st, ast.For):
                yield from _sync_findings(ctx, st.iter, flow)
                yield from visit(st.body)
                yield from visit(st.orelse)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    yield from _sync_findings(ctx, item.context_expr, flow)
                yield from visit(st.body)
            elif isinstance(st, ast.Try):
                yield from visit(st.body)
                for h in st.handlers:
                    yield from visit(h.body)
                yield from visit(st.orelse)
                yield from visit(st.finalbody)
            else:
                for child in ast.iter_child_nodes(st):
                    if isinstance(child, ast.expr):
                        yield from _sync_findings(ctx, child, flow)

    yield from visit(fn.body)


@register("JL002", "hidden-host-sync", ERROR,
          "blocking device->host sync (.item/int/float/np.asarray/"
          "block_until_ready) in a hot code path")
def check(ctx, config):
    if not config.in_hot(ctx.key):
        return
    jit_names = _jit_bound_names(ctx.tree, ctx.aliases)
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from _walk_function(ctx, node, jit_names)
