"""The benchmark's weight generator draws the PARENT'S BYTES, in tier-1.

perfbench/tests/test_families.py pins a digest of every leaf of a seed's
tiny tree over (dtype, shape, bytes), recorded when a ``QTensor`` held its
scales as float16.  It now holds the same bytes as uint16 bit patterns
(quantize/core.py), so those digests, which hash the dtype's NAME, no longer
match as they stand; the benchmark's files are not this PR's to edit (ROADMAP
D11n re-pins them).  Here the same digests are taken over each scale plane's
fp16 view: every byte of every leaf is the one the pinned commit drew."""

from __future__ import annotations

import hashlib
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for path in (BENCH, ROOT):                # as the other wrappers here do
    if path not in sys.path:
        sys.path.insert(0, path)

# the pinned digests, from the benchmark's own test module: it imports its
# sibling ``test_perfbench``, so its directory is on the path while it loads
# and not after
_spec = importlib.util.spec_from_file_location(
    "perfbench_families", os.path.join(BENCH, "tests", "test_families.py"))
_cases = importlib.util.module_from_spec(_spec)
_before = list(sys.path)
sys.path.insert(0, os.path.join(BENCH, "tests"))
try:
    _spec.loader.exec_module(_cases)
finally:
    sys.path[:] = _before


@pytest.mark.parametrize("seed", sorted(_cases.DIGESTS))
def test_the_generator_draws_the_pinned_bytes(seed):
    import jax

    from ipex_llm_tpu.models.families import FAMILIES

    hf = _cases.run.hf_config(_cases.TINY)
    cfg = FAMILIES[hf["model_type"]].to_config(hf)
    params = _cases.weights.generator("mistral").param_tree(
        cfg, hf, "sym_int4", seed)
    assert str(jax.tree_util.tree_structure(params)) == _cases.TREEDEF
    got, bits = {}, 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        if a.dtype == np.uint16:          # a scale plane's bit patterns
            a, bits = a.view(np.float16), bits + 1
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
        got[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    assert bits == 5                      # four stacked linears and the head
    assert got == _cases.DIGESTS[seed]
