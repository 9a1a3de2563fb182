"""jaxlint: per-rule fixtures, suppression policy, JSON schema, repo gate.

Three layers:

1. fixture tests — every rule fires on a known-bad snippet and stays
   quiet on the known-good rewrite (the before/after pairs in
   docs/quickstart/static_analysis.md);
2. policy tests — suppressions need reasons (JL000), severity overrides
   relax JL002/JL003 to warn in benches/tests, the JSON schema is stable;
3. the tier-1 gate — zero unsuppressed error-tier findings over the real
   ``ipex_llm_tpu/`` tree, and un-migrating one upload call site in the
   real engine source re-triggers JL001 (so the helper cannot silently
   rot away).
"""

from pathlib import Path

import numpy as np

from ipex_llm_tpu.analysis import analyze_paths, analyze_source, to_json

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "ipex_llm_tpu"

# paths that put a snippet inside / outside the configured hazard scopes
ASYNC = "ipex_llm_tpu/serving/snippet.py"     # JL001 + JL002 + JL003 scope
COLD = "ipex_llm_tpu/models/snippet.py"       # neither async nor hot
BENCH = "benchmark/snippet.py"                # JL002/JL003 relaxed to warn


def codes(findings, suppressed=False):
    return [f.rule for f in findings if f.suppressed == suppressed]


def errors(findings):
    return [f for f in findings
            if not f.suppressed and f.severity == "error"]


# --------------------------------------------------------------------------
# JL001 aliasing-upload
# --------------------------------------------------------------------------

JL001_BAD = """
import jax.numpy as jnp
import numpy as np

def upload(buf):
    return jnp.asarray(buf)
"""

JL001_GOOD = """
import jax.numpy as jnp
from ipex_llm_tpu.hostutil import h2d

def upload(buf):
    return h2d(buf)

def constants():
    return jnp.asarray(0.5), jnp.asarray([1, 2, 3])

def already_device(x):
    return jnp.asarray(jnp.zeros_like(x))
"""


def test_jl001_fires_on_raw_asarray_in_async_module():
    assert "JL001" in codes(analyze_source(JL001_BAD, ASYNC))


def test_jl001_fires_on_device_put():
    src = JL001_BAD.replace("jnp.asarray(buf)", "__import__('jax')") \
        .replace("import numpy as np", "import jax") + \
        "\ndef up2(buf):\n    return jax.device_put(buf)\n"
    assert "JL001" in codes(analyze_source(src, ASYNC))


def test_jl001_quiet_on_h2d_literals_and_device_values():
    assert codes(analyze_source(JL001_GOOD, ASYNC)) == []


def test_jl001_quiet_outside_async_modules():
    assert codes(analyze_source(JL001_BAD, COLD)) == []


# --------------------------------------------------------------------------
# JL002 hidden-host-sync
# --------------------------------------------------------------------------

JL002_BAD = """
import jax
import jax.numpy as jnp
import numpy as np

def tick():
    logits = jnp.zeros((4, 8))
    tok = int(logits[0, 0])
    host = np.asarray(logits)
    jax.block_until_ready(logits)
    return tok, host, logits.block_until_ready()
"""

JL002_GOOD = """
import jax.numpy as jnp
import numpy as np

def tick(n_rows):
    logits = jnp.zeros((4, 8))
    count = int(n_rows)          # host value: not a sync
    arr = np.asarray([1, 2, 3])  # host literal: not a sync
    return logits, count, arr
"""


def test_jl002_fires_on_every_sync_shape():
    found = codes(analyze_source(JL002_BAD, ASYNC))
    assert found.count("JL002") >= 4   # int, np.asarray, 2x block_until_ready


def test_jl002_quiet_on_host_values():
    assert codes(analyze_source(JL002_GOOD, ASYNC)) == []


def test_jl002_relaxed_to_warn_in_benches():
    fs = [f for f in analyze_source(JL002_BAD, BENCH) if f.rule == "JL002"]
    assert fs and all(f.severity == "warn" for f in fs)


def test_jl002_flags_named_d2h_sync():
    src = """
import jax.numpy as jnp
from ipex_llm_tpu.hostutil import d2h

def tick():
    x = jnp.zeros((4,))
    return d2h(x)
"""
    assert "JL002" in codes(analyze_source(src, ASYNC))


def test_jl002_sees_through_function_valued_alias():
    # `fn = jitted_name; y = fn(...)` must keep y device-valued — a sync
    # on the aliased call's result cannot escape via one indirection
    src = """
import jax
import numpy as np

@jax.jit
def _step(x):
    return x

def tick(x):
    fn = _step
    y = fn(x)
    return np.asarray(y)
"""
    assert "JL002" in codes(analyze_source(src, ASYNC))


def test_trailing_suppression_covers_multiline_statement():
    # the finding anchors to the line the call STARTS on; the comment
    # trails the line the statement ENDS on — coverage spans the stmt
    src = """
import jax.numpy as jnp
import numpy as np

def tick():
    logits = jnp.zeros((4, 8))
    host = np.asarray(
        logits)  # jaxlint: disable=JL002 -- fixture: designed sync
    return host
"""
    fs = analyze_source(src, ASYNC)
    assert codes(fs) == [] and codes(fs, suppressed=True) == ["JL002"]


def test_trailing_suppression_on_if_header_spares_the_body():
    # a suppression trailing `if cond:` must not blanket the body
    src = """
import jax.numpy as jnp
import numpy as np

def tick(flag):
    logits = jnp.zeros((4, 8))
    if flag:  # jaxlint: disable=JL002 -- fixture: header only
        host = np.asarray(logits)
    return logits
"""
    assert "JL002" in codes(analyze_source(src, ASYNC))


def test_jl002_conversion_launders_to_host():
    # the int() itself is the (one) flagged sync; downstream uses of the
    # converted name are host data, not fresh findings
    src = """
import jax.numpy as jnp
import numpy as np

def tick():
    x = jnp.zeros((4,))
    n = int(x[0])
    return np.asarray([n], np.int32)
"""
    assert codes(analyze_source(src, ASYNC)).count("JL002") == 1


# --------------------------------------------------------------------------
# JL003 recompile-hazard
# --------------------------------------------------------------------------

JL003_BAD = """
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def _decode(x, width):
    return x[:width]

def fresh_wrapper(f, x):
    return jax.jit(f)(x)

def per_call_lambda(x):
    return jax.jit(lambda v: v * 2)(x)

def unbucketed(x, toks):
    return _decode(x, len(toks))
"""

JL003_GOOD = """
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def _decode(x, width):
    return x[:width]

def _round_up(n, m=64):
    return (n + m - 1) // m * m

def bucketed(x, toks):
    return _decode(x, _round_up(len(toks)))
"""


def test_jl003_fires_on_fresh_jit_and_unbucketed_dim():
    found = codes(analyze_source(JL003_BAD, ASYNC))
    assert found.count("JL003") >= 3


def test_jl003_quiet_when_bucketed():
    assert codes(analyze_source(JL003_GOOD, ASYNC)) == []


# --------------------------------------------------------------------------
# JL004 tracer-leak
# --------------------------------------------------------------------------

JL004_BAD = """
import jax
import jax.numpy as jnp

seen = []

class Engine:
    def step(self, x):
        def body(carry):
            self.last = carry          # attr write under trace
            seen.append(carry)         # closure mutation under trace
            return carry + 1
        return jax.lax.while_loop(lambda c: c < 10, body, x)
"""

JL004_GOOD = """
import jax
import jax.numpy as jnp

class Engine:
    def step(self, x):
        def body(carry):
            staged = []                # local staging: fine
            staged.append(carry)
            total = carry + 1
            return total
        out = jax.lax.while_loop(lambda c: c < 10, body, x)
        self.last = out                # host code: fine
        return out
"""


def test_jl004_fires_on_self_and_closure_writes_under_trace():
    found = codes(analyze_source(JL004_BAD, ASYNC))
    assert found.count("JL004") >= 2


def test_jl004_quiet_on_locals_and_host_writes():
    assert codes(analyze_source(JL004_GOOD, ASYNC)) == []


# --------------------------------------------------------------------------
# JL005 nondeterminism-in-jit
# --------------------------------------------------------------------------

JL005_BAD = """
import time
import random
import numpy as np
import jax

@jax.jit
def step(x):
    t = time.time()
    r = np.random.rand()
    jitter = random.random()
    acc = 0
    for name in {"a", "b", "c"}:
        acc = acc + x
    return x * t + r + jitter + acc
"""

JL005_GOOD = """
import jax
import jax.numpy as jnp

@jax.jit
def step(x, key, t):
    r = jax.random.uniform(key)
    acc = 0
    for name in ("a", "b", "c"):
        acc = acc + x
    return x * t + r + acc
"""


def test_jl005_fires_on_entropy_and_set_iteration():
    found = codes(analyze_source(JL005_BAD, ASYNC))
    assert found.count("JL005") >= 4


def test_jl005_quiet_on_explicit_keys_and_ordered_iteration():
    assert codes(analyze_source(JL005_GOOD, ASYNC)) == []


# --------------------------------------------------------------------------
# JL006 prng-key-reuse
# --------------------------------------------------------------------------

JL006_BAD = """
import jax

def sample_twice(key):
    a = jax.random.uniform(key)
    b = jax.random.normal(key)        # same key: correlated
    return a + b

def loop_invariant(key, xs):
    out = []
    for x in xs:
        out.append(jax.random.uniform(key))   # same draw every iter
    return out
"""

JL006_GOOD = """
import jax

def sample_twice(key):
    ka, kb = jax.random.split(key)
    return jax.random.uniform(ka) + jax.random.normal(kb)

def per_iter(key, xs):
    out = []
    for i, x in enumerate(xs):
        key, sub = jax.random.split(key)
        out.append(jax.random.uniform(sub))
    return out

def branches(key, flag):
    # mutually exclusive arms may both consume the incoming key
    if flag:
        return jax.random.uniform(key)
    else:
        return jax.random.normal(key)
"""


def test_jl006_fires_on_reuse_and_loop_invariant_key():
    found = codes(analyze_source(JL006_BAD, ASYNC))
    assert found.count("JL006") >= 2


def test_jl006_quiet_on_split_chain_and_exclusive_branches():
    assert codes(analyze_source(JL006_GOOD, ASYNC)) == []


# --------------------------------------------------------------------------
# suppressions (JL000) + severity + JSON schema
# --------------------------------------------------------------------------

def test_suppression_with_reason_is_honored():
    src = JL001_BAD.replace(
        "return jnp.asarray(buf)",
        "return jnp.asarray(buf)  # jaxlint: disable=JL001 -- buf is "
        "immutable in this fixture")
    fs = analyze_source(src, ASYNC)
    assert codes(fs) == [] and codes(fs, suppressed=True) == ["JL001"]
    assert errors(fs) == []


def test_standalone_suppression_covers_next_line():
    src = JL001_BAD.replace(
        "    return jnp.asarray(buf)",
        "    # jaxlint: disable=JL001 -- fixture: buffer outlives dispatch\n"
        "    return jnp.asarray(buf)")
    fs = analyze_source(src, ASYNC)
    assert codes(fs) == [] and codes(fs, suppressed=True) == ["JL001"]


def test_suppression_without_reason_is_rejected():
    src = JL001_BAD.replace("return jnp.asarray(buf)",
                            "return jnp.asarray(buf)  "
                            "# jaxlint: disable=JL001")
    fs = analyze_source(src, ASYNC)
    assert "JL000" in codes(fs)          # reasonless suppression is an error
    assert "JL001" in codes(fs)          # and does NOT suppress the finding


def test_suppression_of_unknown_rule_is_rejected():
    src = "x = 1  # jaxlint: disable=JL999 -- no such rule\n"
    assert "JL000" in codes(analyze_source(src, COLD))


def test_marker_inside_string_literal_is_inert():
    # a "jaxlint: disable" that is DATA, not a comment, must neither
    # suppress a real finding on its line nor fail the gate as JL000
    src = JL001_BAD.replace(
        "return jnp.asarray(buf)",
        'return jnp.asarray(buf), "# jaxlint: disable=JL001 -- just text"')
    fs = analyze_source(src, ASYNC)
    assert "JL001" in codes(fs)           # the real finding survives
    assert "JL000" not in codes(fs)
    assert codes(fs, suppressed=True) == []


def test_marker_inside_docstring_is_inert():
    src = ('def f():\n'
           '    """Mentions # jaxlint: disable=JL001 in prose."""\n'
           '    return 1\n')
    assert codes(analyze_source(src, ASYNC)) == []


def test_json_schema_stable():
    import json
    fs = analyze_source(JL001_BAD, ASYNC)
    doc = json.loads(to_json(fs))
    assert doc["version"] == 1
    assert set(doc["counts"]) == {"errors", "warnings", "suppressed"}
    assert doc["counts"]["errors"] >= 1
    f = doc["findings"][0]
    # schema v1 is additive: every original field stays, and the trace
    # tier's "tier" discriminator joins without bumping the version
    assert {"rule", "severity", "path", "line", "col", "message",
            "suppressed", "reason"} <= set(f)
    assert f["tier"] == "ast"


# --------------------------------------------------------------------------
# the tier-1 gate over the real tree
# --------------------------------------------------------------------------

def test_repo_is_clean_of_unsuppressed_errors():
    fs = analyze_paths([str(PKG)])
    offenders = errors(fs)
    assert not offenders, "\n".join(f.render() for f in offenders)
    # policy: every surviving suppression documents why it is safe
    assert all(f.reason for f in fs if f.suppressed)


def test_unmigrating_an_upload_call_site_fails_jl001():
    """Deleting the shared copying-upload helper from a migrated call site
    must re-trigger JL001 (acceptance criterion: the helper cannot rot)."""
    engine = (PKG / "serving" / "engine.py").read_text()
    assert "h2d(spec_ks)" in engine
    regressed = engine.replace("h2d(spec_ks)", "jnp.asarray(spec_ks)", 1)
    fs = analyze_source(regressed, "ipex_llm_tpu/serving/engine.py")
    assert any(f.rule == "JL001" and not f.suppressed and
               f.severity == "error" for f in fs)


def test_benches_and_tests_have_no_error_tier_findings():
    fs = analyze_paths([str(REPO / "tests"), str(REPO / "benchmark")])
    offenders = errors(fs)
    assert not offenders, "\n".join(f.render() for f in offenders)


# --------------------------------------------------------------------------
# hostutil: the helper JL001 points everyone at
# --------------------------------------------------------------------------

def test_h2d_copies_mutation_after_upload_is_invisible():
    """The PR 2 race, as a regression test: mutating the host buffer right
    after upload must not change the device value (jnp.asarray may alias;
    h2d must not)."""
    from ipex_llm_tpu.hostutil import h2d
    buf = np.ones(128, np.int32)
    dev = h2d(buf)
    buf[:] = -1                      # engine bookkeeping advances...
    np.testing.assert_array_equal(np.asarray(dev), np.ones(128, np.int32))


def test_h2d_dtype_and_reexport():
    from ipex_llm_tpu.hostutil import d2h, h2d
    from ipex_llm_tpu.serving.engine import _h2d   # compat re-export
    assert _h2d is h2d
    out = h2d([1, 2], np.float32)
    assert out.dtype == np.float32
    assert isinstance(d2h(out), np.ndarray)
