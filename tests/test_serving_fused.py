"""The STRUCTURE of a dense model's mixed tick since PR 52 (the bits are
``tests/test_serving_mixed.py``'s): the chunk's tokens and the decode rows
ride one forward, so the traced program holds ``4 * layers + 1`` dequant-
matmul calls where the staged tick holds twice that, and it is still ONE
dispatch (JP106).  Which tick takes the fused form is decided from the
model's configuration and the tick's static shape alone: a hybrid model, a
latent-attention one, speculation and a fused horizon keep the staged tick,
and ``mixed_fused_steps`` says so beside ``mixed_steps``.  Nothing runs in
the traced half; the served half drives tiny models on the CPU."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import run  # noqa: E402
import weights  # noqa: E402

from ipex_llm_tpu.analysis.trace import registry, tickaudit  # noqa: E402
from ipex_llm_tpu.models import decoder  # noqa: E402
from ipex_llm_tpu.models.families import FAMILIES  # noqa: E402
from ipex_llm_tpu.ops import dispatch  # noqa: E402
from ipex_llm_tpu.serving import engine as engine_mod  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)
from tests.test_decoder import rand_params, tiny_cfg  # noqa: E402


def _kernel_calls(jaxpr, name: str) -> int:
    """``pallas_call``s named ``name`` a run of the program makes: a
    scan's body counts once a step, a loop's or a branch's once."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            info = eqn.params.get("name_and_src_info")
            n += (getattr(info, "name", None) or eqn.params.get("name")) == name
        times = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += times * _kernel_calls(sub, name)
    return n


@pytest.fixture
def kernels_forced(monkeypatch):
    """The program as a TPU traces it (every op's kernel), for counting."""
    monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
    dispatch.clear_cache()
    engine_mod._ragged_tick.clear_cache()
    yield
    monkeypatch.undo()
    dispatch.clear_cache()
    engine_mod._ragged_tick.clear_cache()


# the audit model (analysis/trace/registry.py): a dense decoder of 2 layers
TICKS = {
    # grid point of ``serving.ragged_tick`` -> passes over the weights
    "mixed": (dict(width=8, horizon=1), 1),
    "mixed-wide": (dict(width=128, horizon=1), 1),
    "mixed-fp8-pool": (dict(width=8, horizon=1, kv="fp8"), 1),
    "steady": (dict(width=0, horizon=1), 1),
    "pure-chunk": (dict(width=8, horizon=1, wd=False), 1),
    # what keeps the staged tick: speculation, a fused horizon
    "mixed-spec": (dict(width=8, horizon=1, spec=4), 2),
    "mixed-horizon": (dict(width=8, horizon=8), 2),
}


@pytest.mark.parametrize("tick", list(TICKS))
def test_a_dense_mixed_tick_calls_every_linear_once(tick, kernels_forced):
    point, passes = TICKS[tick]
    pt = {"rows": 4, "kv": "bf16", "wq": "sym_int4", **point}
    cfg, _ = registry.audit_model("sym_int4")
    base, kw = registry._build_ragged_tick(pt)
    jaxpr = engine_mod._ragged_tick.trace(*base, **kw).jaxpr.jaxpr
    assert _kernel_calls(jaxpr, "qmatmul") == passes * (
        4 * cfg.num_layers + 1)
    assert engine_mod.fuses_mixed_tick(
        cfg, pt["horizon"], bool(pt["width"]) and pt.get("wd", True),
        pt.get("spec", 0), None) == (tick.startswith("mixed")
                                     and passes == 1)


def test_a_mixed_tick_is_still_one_dispatch():
    """JP106: the tick's scheduler functions reach one jitted entry."""
    assert tickaudit.mixed_tick_dispatch_count() == 1


def _tiny_of(config: str, seed: int = 11):
    hf = run.hf_config(run.load_json(os.path.join(
        BENCH, "configs", config + ".json")))
    gen = weights.generator(hf["model_type"])
    tiny = gen.tiny(hf)
    cfg = FAMILIES[hf["model_type"]].to_config(tiny)
    return cfg, gen.param_tree(cfg, tiny, "sym_int4", seed)


def _dense():
    cfg = tiny_cfg(vocab_size=131, hidden_size=48, intermediate_size=96,
                   num_heads=4, num_kv_heads=2, head_dim=12,
                   max_position_embeddings=512)
    return cfg, rand_params(cfg, qtype="bf16")


MODELS = {
    # model -> (how it is made, engine options, whether its ticks fuse)
    "dense": (_dense, {}, True),
    "dense-spec": (_dense, dict(spec_k=2), False),
    "dense-horizon": (_dense, dict(decode_horizon=4), True),
    "mistral": (lambda: _tiny_of("mistral-7b-v0.3.int4"), {}, True),
    "hybrid": (lambda: _tiny_of("falcon-h1-34b-instruct.int4.l24"), {},
               False),
    "latent": (lambda: _tiny_of("a.x-k1.int4.ep16"), {}, False),
}


@pytest.mark.parametrize("model", list(MODELS))
def test_the_counter_says_which_mixed_ticks_made_one_pass(model):
    """``mixed_fused_steps == mixed_steps`` for a dense model (a mixed tick
    has ``horizon`` 1 whatever the engine's steady horizon) and 0 for a
    hybrid one, a latent-attention one and a speculating engine; every
    mixed tick is ONE dispatch either way."""
    make, opts, fuses = MODELS[model]
    cfg, params = make()
    assert decoder.fuses_blocks(cfg) == (model not in ("hybrid", "latent"))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=4, max_seq_len=128, page_size=16, prefill_bucket=32,
        **opts))
    rng = np.random.default_rng(52)
    reqs = [Request(prompt_ids=[int(t) for t in rng.integers(
        3, min(cfg.vocab_size, 250), n)], max_new_tokens=6)
        for n in (40, 21, 70)]
    for t in range(400):
        if t < len(reqs):
            eng.submit(reqs[t])
        d0, m0 = eng._tick_dispatches, eng.metrics["mixed_steps"]
        eng._step_once()
        if eng.metrics["mixed_steps"] > m0:
            assert eng._tick_dispatches == d0 + 1
        if t >= len(reqs) and all(r.finish_reason for r in reqs):
            break
    assert [len(list(stream_tokens(r, timeout=10))) for r in reqs] == [6] * 3
    m = eng.metrics
    assert m["mixed_steps"] >= 5
    assert m["mixed_fused_steps"] == (m["mixed_steps"] if fuses else 0)
