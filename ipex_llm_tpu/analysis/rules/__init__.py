"""jaxlint rule modules — importing this package registers every rule.

Rule catalog (docs/quickstart/static_analysis.md has the long form):

- JL000 bad-suppression   suppression comment malformed / reasonless
- JL001 aliasing-upload   zero-copy upload of a mutable host buffer in an
                          async-dispatch module (the PR 2 race class)
- JL002 hidden-host-sync  blocking device sync in a hot path
- JL003 recompile-hazard  fresh jit per call / unbucketed dynamic dim
- JL004 tracer-leak       side effects escaping traced code
- JL005 nondeterminism    wall-clock / host RNG / set-order in traced code
- JL006 prng-key-reuse    one PRNG key consumed twice without split/fold_in
- JL007 missing-donation  hot-path jit wrapper with cache/pool args and no
                          donate_argnums (the AST companion to JP101)

The trace tier's rules (JP100-JP108, ``analysis/trace/``) are registered
here as catalog stubs so ``--list-rules`` shows the full inventory and
suppression comments naming JP codes validate; their checks run in the
jaxprcheck runner, not per source file.
"""

from ipex_llm_tpu.analysis.core import register
from ipex_llm_tpu.analysis.trace.catalog import TRACE_RULES

from ipex_llm_tpu.analysis.rules import (  # noqa: F401  (register on import)
    aliasing,
    donation,
    hostsync,
    nondeterminism,
    prng,
    recompile,
    tracer,
)


@register("JL000", "bad-suppression", "error",
          "jaxlint suppression comment is malformed, reasonless, or names "
          "an unknown rule")
def _jl000(ctx, config):
    # emitted by core.parse_suppressions, never by a rule body; registered
    # so the code renders in --list-rules and "disable=JL000" resolves
    return iter(())


def _register_trace_stubs():
    for code, (name, severity, doc) in TRACE_RULES.items():
        @register(code, name, severity, doc)
        def _stub(ctx, config):
            # trace rules audit lowered programs, not source files: the
            # jaxprcheck runner (analysis/trace/runner.py) executes them
            return iter(())


_register_trace_stubs()
