"""Trace-rule catalog: codes, default severities, one-line docs.

Kept jax-free so ``--list-rules`` and the suppression validator can name
JP codes without importing the (jax-heavy) tracer.  The long-form catalog
with before/after examples lives in docs/quickstart/static_analysis.md.
"""

from __future__ import annotations

from ipex_llm_tpu.analysis.core import ERROR, WARN

# code -> (name, severity, doc)
TRACE_RULES: dict[str, tuple[str, str, str]] = {
    "JP100": (
        "audit-integrity", ERROR,
        "program failed to trace, manifest missing/drifted, or a registry "
        "suppression has no written reason"),
    "JP101": (
        "donation-coverage", ERROR,
        "large dead-after-call input aval absent from the lowered "
        "input_output_aliases (re-uploaded rather than donated), or a "
        "host-held buffer donated"),
    "JP102": (
        "fp8-pool-integrity", ERROR,
        "an e5m2 pool-resident aval is upcast wholesale inside the lowered "
        "program (breaks the dequant-at-read contract)"),
    "JP103": (
        "host-callback", ERROR,
        "pure_callback/io_callback/debug_print/infeed-outfeed primitive "
        "inside a lowered hot-path program"),
    "JP104": (
        "recompile-surface", ERROR,
        "distinct lowerings over the enumerated bucket grid exceed the "
        "spec bound or disagree with the locked manifest"),
    "JP105": (
        "constant-bloat", WARN,
        "closure-captured constant above the byte threshold baked into "
        "the jaxpr"),
    "JP106": (
        "tick-dispatch-count", ERROR,
        "the mixed prefill+decode tick issues more device dispatches than "
        "the gate allows, or its program set drifted from the registry"),
    "JP107": (
        "packed-weight-integrity", ERROR,
        "a stacked packed-weight plane (the 4/5/8-bit block serving "
        "formats) is dequantized wholesale inside the lowered program "
        "instead of per-layer next to its matmul (a 4x HBM regression)"),
    "JP108": (
        "paged-pool-by-value", ERROR,
        "the paged KV pool moves through the program by value: a scan "
        "takes or returns it layer by layer as xs/ys, or a whole layer of "
        "it is dynamically sliced out or written back (the pool is "
        "addressed in place by (layer, page))"),
}


def severity_of(code: str) -> str:
    return TRACE_RULES[code][1]
