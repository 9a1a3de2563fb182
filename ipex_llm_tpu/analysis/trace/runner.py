"""jaxprcheck runner: trace the registry, run JP rules, gate the manifest.

Backend pinning: manifests must be reproducible, so the audit always runs
against the CPU backend with the test suite's 8 virtual devices —
mirroring tests/conftest.py — whatever platform the environment asks
for.  An environment where that cannot be arranged raises (CLI exit 3:
the analyzer is broken, the tree is not).
"""

from __future__ import annotations

import os
import sys
from dataclasses import asdict
from pathlib import Path

# everything imported at module level here must stay jax-free: the CLI
# imports this module BEFORE jax so ensure_cpu_backend can still set
# XLA_FLAGS (the 8-virtual-device pin must precede backend init); the
# jax-heavy tracer/rules/registry modules are imported inside audit()
from ipex_llm_tpu.analysis.core import ERROR, Finding
from ipex_llm_tpu.analysis.trace import manifest as manifest_mod
from ipex_llm_tpu.analysis.trace.tickaudit import (TickSpec,
                                                   discover_tick_dispatches,
                                                   mixed_tick_spec)


def ensure_cpu_backend():
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    # the package was imported before this pin and may have pointed the
    # persistent compile cache into the checkout for a TPU run: a
    # CPU-pinned process must not write XLA:CPU entries there
    # (ipex_llm_tpu.compile_cache_dir)
    jax.config.update("jax_compilation_cache_dir", None)
    if jax.default_backend() != "cpu":   # pragma: no cover - env guard
        raise RuntimeError(
            "jaxprcheck needs the CPU backend for a reproducible manifest; "
            f"got {jax.default_backend()!r} (jax was initialized before "
            "the audit could pin it)")


def _jp100(path: str, line: int, message: str) -> Finding:
    return Finding(rule="JP100", severity=ERROR, path=path, line=line,
                   col=1, message=message, tier="trace")


def _apply_spec_suppressions(spec, findings: list[Finding]) -> list[Finding]:
    """Registry-level suppressions, under the jaxlint policy: every one
    needs a written reason; a reasonless entry is itself a JP100 error."""
    table: dict[str, str] = {}
    out: list[Finding] = []
    for code, reason in spec.suppress:
        if not (reason or "").strip():
            out.append(_jp100(
                spec.source, getattr(spec, "lineno", 1),
                f"[{spec.name}] suppression of {code} has no reason — "
                "give ProgramSpec.suppress a written 'why this is safe'"))
        else:
            table[code] = reason
    for f in findings:
        if f.rule in table:
            out.append(Finding(**{**asdict(f), "suppressed": True,
                                  "reason": table[f.rule]}))
        else:
            out.append(f)
    return out


def audit(specs=None, ticks=None, manifest_path=None, update: bool = False,
          tick_source: str | None = None) -> list[Finding]:
    """Run the full trace-tier audit.  Returns findings (suppressed ones
    included, marked); the caller derives the exit code.

    ``update=True`` rewrites the manifest from the built inventory
    instead of diffing against it (rule findings still report, so an
    --update on a tree with real JP101/JP102 bugs still fails)."""
    ensure_cpu_backend()
    from ipex_llm_tpu.analysis.trace import rules as trace_rules
    from ipex_llm_tpu.analysis.trace.registry import (real_registry,
                                                      requirement_met)
    from ipex_llm_tpu.analysis.trace.tracer import signature, trace_entry

    specs = real_registry() if specs is None else specs
    ticks = (mixed_tick_spec(),) if ticks is None else ticks
    path = Path(manifest_path) if manifest_path else manifest_mod.DEFAULT_PATH
    locked = None if update else manifest_mod.load(path)

    findings: list[Finding] = []
    program_results = []
    for spec in specs:
        if not requirement_met(spec.requires):
            program_results.append(
                (spec, None, f"requires {spec.requires} (unavailable in "
                             "this jax)"))
            continue
        entries, seen = [], set()
        spec_findings: list[Finding] = []
        for point in spec.grid:
            try:
                args, kwargs = spec.build(dict(point))
                sig = signature(args, kwargs)
            except Exception as exc:
                spec_findings.append(_jp100(
                    spec.source, spec.lineno,
                    f"[{spec.name}] input builder failed at {point}: "
                    f"{type(exc).__name__}: {exc}"))
                continue
            if sig in seen:   # two grid points sharing one compiled program
                continue
            seen.add(sig)
            try:
                entry = trace_entry(spec, point, prebuilt=(args, kwargs))
            except Exception as exc:
                spec_findings.append(_jp100(
                    spec.source, spec.lineno,
                    f"[{spec.name}] failed to trace/lower at {point}: "
                    f"{type(exc).__name__}: {exc}"))
                continue
            entries.append(entry)
            spec_findings.extend(trace_rules.check_donation(spec, entry))
            spec_findings.extend(
                trace_rules.check_fp8_integrity(spec, entry))
            spec_findings.extend(
                trace_rules.check_weight_integrity(spec, entry))
            spec_findings.extend(
                trace_rules.check_pool_in_place(spec, entry))
            spec_findings.extend(trace_rules.check_callbacks(spec, entry))
            spec_findings.extend(
                trace_rules.check_constant_bloat(spec, entry))
        locked_count = None
        if locked is not None:
            locked_count = (locked.get("programs", {})
                            .get(spec.name, {}).get("lowerings"))
        # lowering-count drift is JP104's alone; the generic manifest
        # diff below skips the "lowerings" key so one drifted count
        # yields one finding, not a JP104+JP100 pair
        spec_findings.extend(trace_rules.check_recompile_surface(
            spec, len(entries), locked_count))
        findings.extend(_apply_spec_suppressions(spec, spec_findings))
        program_results.append((spec, entries, None))

    tick_results = []
    for tick in ticks:
        discovered = discover_tick_dispatches(tick, tick_source)
        tick_findings = list(
            trace_rules.check_tick_dispatches(tick, discovered))
        findings.extend(_apply_spec_suppressions(
            _TickShim(tick), tick_findings))
        tick_results.append((tick, discovered))

    built = manifest_mod.build(program_results, tick_results)
    if update:
        manifest_mod.save(built, path)
    elif locked is None:
        findings.append(_jp100(
            manifest_mod_relkey(path), 1,
            "manifest missing — run `scripts/jaxprcheck --update` and "
            "commit analysis/programs.lock.json"))
    else:
        for line in manifest_mod.diff(locked, built,
                                      ignore_keys=("lowerings",)):
            findings.append(_jp100(
                manifest_mod_relkey(path), 1,
                f"manifest drift: {line} — review, then "
                "`scripts/jaxprcheck --update`"))

    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings


class _TickShim:
    """Adapts a TickSpec to _apply_spec_suppressions' spec interface."""

    def __init__(self, tick: TickSpec):
        self.suppress = tick.suppress
        self.source = tick.module.replace(".", "/") + ".py"
        self.lineno = 1
        self.name = f"tick:{tick.name}"


def manifest_mod_relkey(path: Path) -> str:
    from ipex_llm_tpu.analysis.config import relkey

    return relkey(str(path))


def list_programs(out=sys.stdout):
    ensure_cpu_backend()
    from ipex_llm_tpu.analysis.trace.registry import (real_registry,
                                                      requirement_met)

    for spec in real_registry():
        status = ("" if requirement_met(spec.requires)
                  else f"  [skipped: requires {spec.requires}]")
        print(f"{spec.name:<32} {len(spec.grid):>2} grid point(s)  "
              f"{spec.source}:{spec.lineno}{status}", file=out)
    tick = mixed_tick_spec()
    print(f"tick:{tick.name:<27} <= {tick.max_dispatches} dispatches  "
          f"{tick.module}", file=out)
