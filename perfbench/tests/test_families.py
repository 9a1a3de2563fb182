"""What finds a model family's files (PR 27): fast, no server

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_families.py -q

The generator of a run is ``generators/<model_type>.py``, as the plain
reference is ``references/<model_type>.py``.  DIGESTS were recorded on the
parent commit, where ``weights.param_tree`` drew the one family's tree
itself: the move changed no bit of a seed's weights.
"""

from __future__ import annotations

import hashlib
import inspect
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run  # noqa: E402
import weights  # noqa: E402
from test_perfbench import TINY  # noqa: E402

TREEDEF = (
    "PyTreeDef({'embed': *, 'final_norm': *, 'inv_freq': *, 'layers': "
    "{'attn_norm': *, 'down': CustomNode(QTensor[('sym_int4', (256, 128), "
    "32, None)], [*, *, None]), 'gate_up': CustomNode(QTensor[('sym_int4', "
    "(128, 512), 32, None)], [*, *, None]), 'mlp_norm': *, 'o': "
    "CustomNode(QTensor[('sym_int4', (128, 128), 32, None)], [*, *, None]), "
    "'qkv': CustomNode(QTensor[('sym_int4', (128, 256), 32, None)], "
    "[*, *, None])}, 'lm_head': CustomNode(QTensor[('sym_int4', (128, 512), "
    "32, None)], [*, *, None]), 'rope_mscale': *})")
# sha256 (first 16 hex digits) over dtype, shape and bytes of every leaf of
# weights.param_tree(cfg of TINY, "sym_int4", seed) at commit 155cc25, on
# the CPU
DIGESTS = {
    7: {
        "['embed']": "dfc1b52cdb8427ee",
        "['final_norm']": "8cabf8f21ac9bcb9",
        "['inv_freq']": "ad51ecc89c114d24",
        "['layers']['attn_norm']": "ae194c3877bce3d3",
        "['layers']['down'][<flat index 0>]": "546e3ec00b28367e",
        "['layers']['down'][<flat index 1>]": "dc47a23c7fba1014",
        "['layers']['gate_up'][<flat index 0>]": "db24ac8a5a421c4e",
        "['layers']['gate_up'][<flat index 1>]": "341c49d059c4b7b2",
        "['layers']['mlp_norm']": "4fe14d9ea0452eff",
        "['layers']['o'][<flat index 0>]": "403b34c3ff64d8c4",
        "['layers']['o'][<flat index 1>]": "7547b590a5f28816",
        "['layers']['qkv'][<flat index 0>]": "5ec02dbeb13baf6a",
        "['layers']['qkv'][<flat index 1>]": "b1ebf3ec0398297d",
        "['lm_head'][<flat index 0>]": "75d7a00fda189aa2",
        "['lm_head'][<flat index 1>]": "7ed5e331d99f603c",
        "['rope_mscale']": "69c9d9430529a4dd",
    },
    2 ** 31 + 5: {
        "['embed']": "029b25b49602b2b4",
        "['final_norm']": "a6e47fa8cb9a3deb",
        "['inv_freq']": "ad51ecc89c114d24",
        "['layers']['attn_norm']": "ccc0104a6de94ffd",
        "['layers']['down'][<flat index 0>]": "81346a7132e32af1",
        "['layers']['down'][<flat index 1>]": "0d4694cfa0b1ed57",
        "['layers']['gate_up'][<flat index 0>]": "00747c96baa053cb",
        "['layers']['gate_up'][<flat index 1>]": "86bd1998a72e2aeb",
        "['layers']['mlp_norm']": "d0471d3fbeda0466",
        "['layers']['o'][<flat index 0>]": "821babb91bad489a",
        "['layers']['o'][<flat index 1>]": "a2f223d40f44de96",
        "['layers']['qkv'][<flat index 0>]": "5a76d97cdc4fc0e2",
        "['layers']['qkv'][<flat index 1>]": "e922be1ba78d48c8",
        "['lm_head'][<flat index 0>]": "c6ded878e3ea9240",
        "['lm_head'][<flat index 1>]": "69084c159fbe10af",
        "['rope_mscale']": "69c9d9430529a4dd",
    },
}


def leaf_digests(params) -> dict:
    import jax
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    return out


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_the_moved_generator_draws_the_same_bits(seed):
    import jax

    from ipex_llm_tpu.models.families import FAMILIES

    hf = run.hf_config(TINY)
    cfg = FAMILIES[hf["model_type"]].to_config(hf)
    params = weights.generator("mistral").param_tree(cfg, hf, "sym_int4",
                                                     seed)
    assert str(jax.tree_util.tree_structure(params)) == TREEDEF
    assert leaf_digests(params) == DIGESTS[seed]


def test_a_generator_refuses_a_weight_type_it_does_not_draw():
    from ipex_llm_tpu.models.families import FAMILIES

    hf = run.hf_config(TINY)
    cfg = FAMILIES[hf["model_type"]].to_config(hf)
    with pytest.raises(ValueError, match="generators/mistral.py"):
        weights.generator("mistral").param_tree(cfg, hf, "fp8_e4m3", 0)


@pytest.mark.parametrize("name", sorted(os.listdir(
    os.path.join(BENCH, "configs"))))
def test_every_configuration_finds_its_family_by_model_type(name):
    import importlib

    config = run.load_json(os.path.join(BENCH, "configs", name))
    hf = run.hf_config(config)
    gen = weights.generator(hf["model_type"])
    assert list(inspect.signature(gen.param_tree).parameters) == [
        "cfg", "hf", "qtype", "seed"]
    small = gen.tiny(hf)
    assert small["model_type"] == hf["model_type"]
    assert small["hidden_size"] < hf["hidden_size"]
    ref = importlib.import_module(f"references.{hf['model_type']}")
    assert callable(ref.logprobs) and callable(ref.compare)
    # the benchmark's keys, `deployment` among them, are not the program's
    assert not set(run.BENCH_KEYS) & set(hf)


def test_a_family_without_a_generator_names_the_file_to_add():
    family = "a_family_nobody_drew"      # no file of that name, ever
    with pytest.raises(run.BenchFailure,
                       match=rf"perfbench/generators/{family}\.py"):
        weights.generator(family)
    with pytest.raises(run.BenchFailure, match=family):
        weights.build_model({"model_type": family}, "sym_int4", 0)


def test_deployment_is_the_benchmarks_and_withheld_from_the_program():
    config = dict(TINY, reduced=["num_hidden_layers"], deployment={
        "chips_sharing_a_layer": 2, "how": "experts and vocabulary",
        "published": {"num_hidden_layers": 52}})
    hf = run.hf_config(config)
    assert "deployment" not in hf and "reduced" not in hf
    assert hf["num_hidden_layers"] == TINY["num_hidden_layers"]


def test_the_shared_helpers_name_no_family():
    """weights.py, run.py and rehearse_memory.py hold no model key, plane
    name or rotary table: those are the generator's."""
    import re

    for name in ("weights.py", "run.py", "rehearse_memory.py"):
        with open(os.path.join(BENCH, name)) as f:
            hits = [line for line in f if re.search(
                "qkv|gate_up|intermediate_size|rope", line)]
        assert not hits, (name, hits)


def test_stack_draws_an_entry_at_a_time_from_its_own_key():
    import jax
    import numpy as np

    def plane(k):
        return weights.int4_plane(k, 64, 8)

    key = weights.seed_key(2 ** 31 + 5)
    data, scales = jax.jit(lambda k: weights.stack(plane, k, 3, 2))(key)
    assert data.shape == (3, 2, 32, 8) and scales.shape == (3, 2, 2, 8)
    one = jax.jit(plane)(jax.random.split(jax.random.split(key, 3)[1], 2)[0])
    assert np.array_equal(data[1, 0], one[0])
    assert np.array_equal(scales[1, 0], one[1])
    assert weights.seed_key(5) != key
    qt = weights.qtensor((data, scales), 64, 8)
    assert qt.shape == (64, 8) and qt.data.shape[:2] == (3, 2)
