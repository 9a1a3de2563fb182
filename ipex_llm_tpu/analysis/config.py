"""jaxlint configuration: where each hazard class is load-bearing.

Rules deliberately do NOT run everywhere.  JL001 (aliasing uploads) only
matters in modules that dispatch asynchronously against host buffers the
caller or engine keeps mutating; JL002 (hidden host syncs) only matters
in the serving hot path, and is *relaxed to warn* in benches and tests,
which legitimately sync.  Paths are matched as glob patterns against a
repo-anchored posix key (see :func:`relkey`), so the analyzer behaves
identically whether invoked on ``ipex_llm_tpu/`` from the repo root or
on absolute paths.
"""

from __future__ import annotations

import fnmatch
from dataclasses import dataclass, field

# path components we anchor relative keys to — the repo's top-level
# source roots.  An unanchored file keeps its given path.
_ANCHORS = ("ipex_llm_tpu", "tests", "benchmark", "examples", "scripts")


def relkey(path: str) -> str:
    # anchor on the LAST matching component: a checkout that happens to
    # live under a directory named "tests"/"benchmark"/... must not have
    # its package files keyed (and rule-scoped) as that outer tree
    parts = path.replace("\\", "/").strip("/").split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in _ANCHORS:
            return "/".join(parts[i:])
    return "/".join(parts)


def match(key: str, patterns: tuple[str, ...]) -> bool:
    return any(fnmatch.fnmatch(key, pat) for pat in patterns)


@dataclass(frozen=True)
class Config:
    # JL001: modules where dispatch is asynchronous against mutable host
    # state — every numpy->device upload must copy (hostutil.h2d)
    async_modules: tuple[str, ...] = (
        "ipex_llm_tpu/serving/*",
        "ipex_llm_tpu/transformers/multimodal.py",
        "ipex_llm_tpu/speculative.py",
        "ipex_llm_tpu/offload.py",
    )
    # JL002/JL003: hot paths where a hidden blocking sync or a retrace is
    # a tail-latency cliff, plus benches/tests (relaxed below)
    hot_modules: tuple[str, ...] = (
        "ipex_llm_tpu/serving/*",
        "ipex_llm_tpu/speculative.py",
        "benchmark/*",
        "tests/*",
    )
    # (path-glob, rule, severity) — first match wins.  Benches and tests
    # legitimately block on device results; keep the findings visible but
    # non-fatal there.
    severity_overrides: tuple[tuple[str, str, str], ...] = (
        ("benchmark/*", "JL002", "warn"),
        ("tests/*", "JL002", "warn"),
        ("benchmark/*", "JL003", "warn"),
        ("tests/*", "JL003", "warn"),
    )
    # blessed copying-upload helpers (JL001 passes these through)
    upload_helpers: frozenset = frozenset({
        "h2d", "_h2d", "hostutil.h2d",
        "ipex_llm_tpu.hostutil.h2d",
    })
    # blessed shape-bucketing helpers (JL003 accepts dims wrapped in these)
    bucket_helpers: frozenset = frozenset({
        "_round_up", "round_up", "_bucket", "bucket", "next_pow2",
        "pad_batch", "pad_to",
    })
    # JL007: modules whose jitted entries carry persistent device buffers
    # across calls — a wrapper there that donates nothing doubles peak HBM
    # for its cache/pool args (the trace tier, JP101, checks the actual
    # lowered aliases; this is the cheap AST companion)
    donation_modules: tuple[str, ...] = (
        "ipex_llm_tpu/serving/*",
        "ipex_llm_tpu/generation.py",
        "ipex_llm_tpu/speculative.py",
        "ipex_llm_tpu/structured.py",
        "ipex_llm_tpu/transformers/multimodal.py",
    )
    # parameter names that mark a large persistent device buffer (JL007)
    donation_hint_params: frozenset = frozenset({
        "cache", "draft_cache", "row_cache", "kv", "kv_cache", "pool",
        "prev_ring", "prev", "ring", "carry",
    })

    def severity_for(self, key: str, rule: str, default: str) -> str:
        for pat, r, sev in self.severity_overrides:
            if r == rule and fnmatch.fnmatch(key, pat):
                return sev
        return default

    def in_async(self, key: str) -> bool:
        return match(key, self.async_modules)

    def in_hot(self, key: str) -> bool:
        return match(key, self.hot_modules)

    def in_donation(self, key: str) -> bool:
        return match(key, self.donation_modules)


DEFAULT_CONFIG = Config()
