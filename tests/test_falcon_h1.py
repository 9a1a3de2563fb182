"""falcon_h1 on the CPU at a tiny size: the PARALLEL block (attention and a
Mamba-2 mixer reading one normed input, summed, then a gated MLP) through
``decoder_forward`` (dense cache) and ``ServingEngine`` (ragged tick, pool AND
state a block), held to the benchmark's plain reference
(perfbench/references/falcon_h1.py, float32, importing nothing of the
program); the muP multipliers each where the equations put it; the state
kernel at N = 256 with part of a group a grid step; a GQA group of five
through the ragged walk.  Seeded random weights drawn by the benchmark's
generator, which draws the tree ``models/build.py`` builds."""

from __future__ import annotations

import importlib
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(ROOT, "perfbench"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import run  # noqa: E402
from ipex_llm_tpu import kv  # noqa: E402
from ipex_llm_tpu.models import decoder  # noqa: E402
from ipex_llm_tpu.models.families import FAMILIES  # noqa: E402
from ipex_llm_tpu.ops import ssm  # noqa: E402
from ipex_llm_tpu.quantize.core import QTensor  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault  # noqa: E402
from tests.test_glm_dsa import _programs_of_a_wave  # noqa: E402
from tests.test_nemotron_h import _ssd_inputs  # noqa: E402
from tests.test_pallas import (_assert_rows_match, _ragged_fixture,  # noqa: E402
                               _ragged_oracle)

gen = importlib.import_module("generators.falcon_h1")
ref = importlib.import_module("references.falcon_h1")

CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "falcon-h1-34b-instruct.int4.l24.json")
# bf16 activations against the float32 reference at this size: the largest
# log-probability error seen over these tests' seeds is 0.03
TOL = 0.1


def tiny_hf(**over) -> dict:
    """The published configuration at the generator's tiny widths, with an
    ``attention_in_multiplier`` that is not 1 (the published one is), so
    that leaving it out shows."""
    return dict(gen.tiny(run.hf_config(run.load_json(CONFIG))),
                attention_in_multiplier=0.5, **over)


@pytest.fixture(scope="module")
def model():
    hf = tiny_hf()
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    return hf, cfg, gen.param_tree(cfg, hf, "sym_int4", 7)


def prompt(seed: int, n: int, vocab: int = 256) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(3, vocab, n)]


# -- the family's configuration -------------------------------------------------

def test_the_family_reads_its_keys():
    hf = run.hf_config(run.load_json(CONFIG))
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    assert cfg.hybrid_pattern == "P" * 24
    # a KV layer AND a state slot a block
    assert (cfg.kv_layers, cfg.ssm_layers, cfg.num_layers) == (24, 24, 24)
    assert decoder.hybrid_schedule(cfg.hybrid_pattern) == ("parallel", 24)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_n_groups,
            cfg.ssm_state_size, cfg.ssm_conv_kernel) == (32, 128, 2, 256, 4)
    assert cfg.ssm_conv_dim == 4096 + 2 * 2 * 256
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 4, 128)
    assert cfg.rope is not None and cfg.rope.base == 1e11
    assert cfg.embedding_multiplier == pytest.approx(5.656854249492381)
    assert cfg.logit_scale == 0.0078125
    assert cfg.key_multiplier == pytest.approx(0.011048543456039804)
    assert cfg.ssm_multipliers == tuple(hf["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(hf["mlp_multipliers"])
    assert (cfg.attention_in_multiplier, cfg.attention_out_multiplier,
            cfg.ssm_in_multiplier) == (1.0, 0.0375, 0.25)
    state = jax.eval_shape(lambda: kv.recurrent_state(cfg, 16))
    assert state["h"].shape == (24, 16, 32, 128, 256)
    assert state["h"].dtype == jnp.float32
    assert state["conv"].shape == (24, 16, 3, 5120)
    assert state["conv"].dtype == jnp.bfloat16
    # the multipliers are 1 / absent everywhere else
    other = FAMILIES["mistral"].to_config(
        {"model_type": "mistral", "vocab_size": 64, "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 1,
         "num_attention_heads": 2})
    assert (other.key_multiplier, other.attention_out_multiplier,
            other.ssm_multipliers, other.mlp_multipliers) == (1, 1, None,
                                                              None)
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        FAMILIES["falcon_h1"].to_config(dict(hf, mamba_d_ssm=8192))
    with pytest.raises(ValueError, match="gated group RMSNorm"):
        FAMILIES["falcon_h1"].to_config(dict(hf, mamba_norm_before_gate=True))


def test_what_a_parallel_block_cannot_do_is_refused_by_its_configuration(
        model):
    from ipex_llm_tpu.parallel import manual, pipeline

    _, cfg, params = model
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    assert "recurrent state" in manual.ineligible_reason(cfg, params, mesh)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        pipeline.pipeline_forward(
            cfg, params, jnp.zeros((2, 4), jnp.int32),
            jax.sharding.Mesh(np.array(jax.devices()[:2]), ("pp",)), 2)


# -- the model through the dense cache -------------------------------------------

def _dense_logprobs(cfg, params, ids, split):
    """Prefill ``ids[:split]`` then decode the rest one token at a time."""
    cache = kv.with_recurrent_state(
        kv.make_cache("normal", cfg.kv_layers, 1, 64, cfg.num_kv_heads,
                      cfg.head_dim), cfg, 1)
    fwd = jax.jit(decoder.decoder_forward, static_argnums=0)
    toks, pos = jnp.asarray([ids]), jnp.arange(len(ids))[None]
    logits, cache = fwd(cfg, params, toks[:, :split], cache, pos[:, :split])
    outs = [logits[0]]
    for t in range(split, len(ids)):
        logits, cache = fwd(cfg, params, toks[:, t: t + 1], cache,
                            pos[:, t: t + 1])
        outs.append(logits[0])
    return np.asarray(jax.nn.log_softmax(jnp.concatenate(outs, 0), -1))


def test_prefill_then_decode_is_the_reference_forward(model):
    hf, cfg, params = model
    ids = prompt(1, 21)
    want = ref.logprobs(hf, params, ids)
    got = _dense_logprobs(cfg, params, ids, split=16)
    assert want.shape == got.shape == (21, 256)
    assert np.abs(got - want).max() < TOL
    only = ref.logprobs(hf, params, ids, rows=[3, 20])
    assert np.array_equal(only, want[[3, 20]])


def _with_plane_columns_scaled(params, name, lo, hi, factor):
    """``params`` with columns [lo, hi) of every block's plane ``name``
    scaled: what applying a multiplier to that range of the plane's OUTPUT
    would compute."""
    tree = dict(params["hybrid"]["parallel"])
    qt = tree[name]
    scales = np.asarray(qt.scale_bits).view(np.float16)
    col = np.ones((scales.shape[-1],), np.float32)
    col[lo:hi] = factor
    tree[name] = QTensor(
        qt.data, jnp.asarray((scales.astype(np.float32) * col).astype(np.float16)),
        qt.zeros, qt.qtype, qt.shape, qt.block_size)
    return dict(params, hybrid={"parallel": tree})


# every multiplier LEFT OUT, and the three that have a wrong side: the
# program computing each of these is not the reference
def _faults(cfg):
    g, d = cfg.mlp_multipliers
    mup = cfg.ssm_multipliers
    return {
        "embedding_multiplier left out": dict(embedding_multiplier=1.0),
        "lm_head_multiplier left out": dict(logit_scale=1.0),
        "attention_in_multiplier left out": dict(attention_in_multiplier=1.0),
        "attention_out_multiplier left out": dict(
            attention_out_multiplier=1.0),
        "key_multiplier left out": dict(key_multiplier=1.0),
        "ssm_in_multiplier left out": dict(ssm_in_multiplier=1.0),
        "ssm_out_multiplier left out": dict(ssm_out_multiplier=1.0),
        "ssm_multipliers left out": dict(ssm_multipliers=None),
        "mlp_multipliers left out": dict(mlp_multipliers=None),
        "mup: z's entry over every column": dict(
            ssm_multipliers=(mup[0],) * 5),
        "mup: the ranges one to the right": dict(
            ssm_multipliers=(mup[4],) + mup[:4]),
        "the gate's multiplier OUTSIDE silu": dict(
            mlp_multipliers=(1.0, g * d)),
        "the down multiplier on the gate too": dict(
            mlp_multipliers=(g * d, d)),
    }


FAULTS = sorted(_faults(FAMILIES["falcon_h1"].to_config(tiny_hf())))


@pytest.mark.parametrize("fault", FAULTS)
def test_each_multiplier_sits_where_the_equations_put_it(model, fault):
    """The served types against the reference agree (the test above); with
    one multiplier left out, or on the wrong side, they do not."""
    hf, cfg, params = model
    ids = prompt(1, 21)
    want = ref.logprobs(hf, params, ids)
    broken = replace(cfg, **_faults(cfg)[fault])
    got = _dense_logprobs(broken, params, ids, split=21)
    assert np.abs(got - want).max() > 2 * TOL, fault


def test_the_key_multiplier_is_on_k_alone(model):
    """On the k columns of the merged projection, not on v beside them (q
    and k are symmetric in a score; v is not in it)."""
    hf, cfg, params = model
    ids = prompt(1, 21)
    want = ref.logprobs(hf, params, ids)
    lo = cfg.q_dim + cfg.kv_dim
    on_v_too = _with_plane_columns_scaled(params, "qkv", lo, lo + cfg.kv_dim,
                                          cfg.key_multiplier)
    got = _dense_logprobs(cfg, on_v_too, ids, split=21)
    assert np.abs(got - want).max() > 2 * TOL
    # and the reference reads the planes the program reads: scaling the k
    # columns of the plane by hand IS a larger key_multiplier
    twice = _with_plane_columns_scaled(params, "qkv", cfg.q_dim, lo, 2.0)
    want2 = ref.logprobs(dict(hf, key_multiplier=2 * hf["key_multiplier"]),
                         params, ids)
    got2 = _dense_logprobs(cfg, twice, ids, split=21)
    assert np.abs(got2 - want2).max() < TOL


def test_multipliers_stay_out_of_the_planes(model):
    """They are applied to activations: the generator's tree and the tree
    ``build.py`` builds hold the same leaves whatever the multipliers."""
    hf, cfg, params = model
    other = FAMILIES["falcon_h1"].to_config(dict(
        hf, key_multiplier=1.0, ssm_multipliers=[1.0] * 5,
        mlp_multipliers=[1.0, 1.0], ssm_out_multiplier=1.0))
    assert other != cfg
    a = jax.eval_shape(lambda: gen.param_tree(cfg, hf, "sym_int4", 0))
    b = jax.eval_shape(lambda: gen.param_tree(other, hf, "sym_int4", 0))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)


def test_build_reads_the_published_weight_names():
    """models/build.py builds the tree the generator draws, from the names
    the published checkpoint uses."""
    from ipex_llm_tpu.models.build import build_params

    hf = tiny_hf()
    cfg = FAMILIES["falcon_h1"].to_config(hf)
    rng = np.random.RandomState(0)
    h, d_in, c, ffn = cfg.hidden_size, 64, cfg.ssm_conv_dim, 256
    sd = {"model.embed_tokens.weight": rng.randn(256, h) * 0.1,
          "model.final_layernorm.weight": np.ones(h),
          "lm_head.weight": rng.randn(256, h) * 0.1}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        m, a, f = pre + "mamba.", pre + "self_attn.", pre + "feed_forward."
        sd.update({
            pre + "input_layernorm.weight": np.ones(h),
            pre + "pre_ff_layernorm.weight": np.ones(h),
            m + "in_proj.weight": rng.randn(d_in + c + 4, h) * 0.1,
            m + "conv1d.weight": rng.randn(c, 1, 4) * 0.3,
            m + "conv1d.bias": rng.randn(c) * 0.1,
            m + "dt_bias": rng.randn(4) - 4.0,
            m + "A_log": np.log(rng.uniform(1, 16, 4)),
            m + "D": np.ones(4), m + "norm.weight": np.ones(d_in),
            m + "out_proj.weight": rng.randn(h, d_in) * 0.1,
            a + "q_proj.weight": rng.randn(320, h) * 0.1,
            a + "k_proj.weight": rng.randn(64, h) * 0.1,
            a + "v_proj.weight": rng.randn(64, h) * 0.1,
            a + "o_proj.weight": rng.randn(h, 320) * 0.1,
            f + "gate_proj.weight": rng.randn(ffn, h) * 0.1,
            f + "up_proj.weight": rng.randn(ffn, h) * 0.1,
            f + "down_proj.weight": rng.randn(h, ffn) * 0.1})
    sd = {k_: np.asarray(v, np.float32) for k_, v in sd.items()}
    params = build_params(cfg, FAMILIES["falcon_h1"].scheme, sd.__getitem__,
                          sd.__contains__, qtype="sym_int4")
    drawn = gen.param_tree(cfg, hf, "sym_int4", 0)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(drawn))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(drawn)):
        if hasattr(want, "shape"):          # (``rope_mscale`` is a number)
            assert got.shape == want.shape and got.dtype == want.dtype
    ids = prompt(4, 12)
    want = ref.logprobs(hf, params, ids)
    got = _dense_logprobs(cfg, params, ids, split=12)
    assert np.abs(got - want).max() < TOL


# -- the state kernel at N = 256 ---------------------------------------------------

def test_a_steps_heads_are_bounded_by_bytes():
    """Nemotron's shapes get the grid they had (two whole groups a step:
    16 heads of [64, 128], 0.5 MB); Falcon-H1's take HALF a group (8 heads
    of [128, 256], 1 MB: a group is 2 MB and would stand in VMEM four
    times)."""
    from ipex_llm_tpu.ops.pallas import ssm_scan

    assert ssm_scan._heads_per_step(64, 8, 64, 128) == 16
    assert ssm_scan._heads_per_step(4, 2, 64, 128) == 4        # tests' shape
    assert ssm_scan._heads_per_step(24, 8, 64, 128) == 8       # 3 groups: one
    assert ssm_scan._heads_per_step(32, 16, 128, 256) == 8
    assert 8 * 128 * 256 * 4 == ssm_scan.STEP_BYTES
    nemotron = FAMILIES["nemotron_h"].to_config(run.hf_config(run.load_json(
        os.path.join(ROOT, "perfbench", "configs",
                     "nemotron3-nano-30b-a3b.int4.ep2.json"))))
    falcon = FAMILIES["falcon_h1"].to_config(
        run.hf_config(run.load_json(CONFIG)))
    assert ssm_scan.supported(nemotron) and ssm_scan.supported(falcon)
    # the grids, as the traced calls carry them: (rows, heads / heads a step)
    for heads, p, groups, n, grid in ((64, 64, 8, 128, "(3, 4)"),
                                      (32, 128, 2, 256, "(3, 4)")):
        h0, x, dt, a, bm, cm = jax.eval_shape(
            lambda: _ssd_inputs(b=3, t=1, heads=heads, p=p, groups=groups,
                                n=n))
        text = str(jax.make_jaxpr(ssm_scan.scan_rows)(
            h0, x, dt, a, bm, cm, jnp.ones((3,), jnp.int32)))
        assert f"grid={grid}" in text, text[:2000]


def test_the_kernel_at_the_published_state_is_sequential_whatever_the_chunking():
    """N = 256, P = 128, 16 heads a group (interpreted here): the XLA
    recurrence's results, and the same BITS for a row's tokens whether they
    arrive in one block or cut in two; a step is part of a group, so each
    half must read ITS group's B and C."""
    from ipex_llm_tpu.ops.pallas import ssm_scan

    h0, x, dt, a, bm, cm = _ssd_inputs(b=2, t=6, heads=32, p=128, groups=2,
                                       n=256)
    lens = jnp.array([6, 4])
    dt = jnp.where((jnp.arange(6)[None, :] < lens[:, None])[..., None], dt, 0)
    bh, ch = (jnp.repeat(v, 16, axis=2) for v in (bm, cm))
    y_seq, h_seq = ssm.ssd_sequential(h0, x, dt, a, bh, ch)
    y, h = ssm_scan.scan_rows(h0, x, dt, a, bm, cm, lens)
    np.testing.assert_allclose(h, h_seq, atol=1e-5)
    np.testing.assert_allclose(y[0], y_seq[0], atol=2e-4)
    np.testing.assert_allclose(y[1, :4], y_seq[1, :4], atol=2e-4)
    hh, ys = h0, []
    for lo, hi in ((0, 1), (1, 6)):
        yy, hh = ssm_scan.scan_rows(
            hh, x[:, lo:hi], dt[:, lo:hi], a, bm[:, lo:hi], cm[:, lo:hi],
            jnp.clip(lens - lo, 0, hi - lo))
        ys.append(yy)
    assert np.array_equal(hh, h)
    assert np.array_equal(jnp.concatenate(ys, 1)[0], y[0])
    # the state's precision, where it can be seen: the same inputs through
    # the reference's recurrence agree to 1e-5 of the state in float32 and
    # miss by a hundred times that with the state and decay in bfloat16
    _, from_zero = ssm_scan.scan_rows(jnp.zeros_like(h0), x, dt, a, bm, cm,
                                      lens)
    for state, close in ((None, True), ("bf16", False)):
        _, want = ref.recurrence(x[0], bm[0], cm[0], dt[0], a, state)
        err = float(jnp.max(jnp.abs(from_zero[0] - want))
                    / jnp.max(jnp.abs(want)))
        assert (err < 1e-5) == close, (state, err)
        assert close or err > 1e-3


# -- a GQA group of five through the ragged walk -------------------------------------

@pytest.mark.parametrize("c", [1, 8, 128])
def test_a_group_of_five_walks_the_pages(c):
    """20 query heads on 4 kv heads: the [G * C, D] query tile of a decode
    row is 5 rows, the first group in the benchmark that is no power of
    two (interpreted here; compiled for the chip in
    tests/test_ragged_kernel_compiles_for_chip.py)."""
    from ipex_llm_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_sdpa)

    q, k, v, tables, kv_len, chunk_len = _ragged_fixture(4, c, "bf16", g=5)
    assert q.shape[2] == 20
    want = _ragged_oracle(q, k[1], v[1], tables, kv_len, chunk_len)
    got = ragged_paged_sdpa(q, k, v, jnp.asarray(tables), jnp.asarray(kv_len),
                            jnp.asarray(chunk_len), layer=jnp.asarray(1))
    _assert_rows_match(got, want, chunk_len)


# -- the model through the serving engine --------------------------------------------

def _engine(cfg, params, **over):
    ec = dict(max_rows=2, max_seq_len=256, page_size=16, prefill_bucket=16)
    return ServingEngine(cfg, params, EngineConfig(**dict(ec, **over)))


def _serve(eng, prompts, new=6):
    reqs = [eng.submit(Request(prompt_ids=p, max_new_tokens=new,
                               temperature=0.0)) for p in prompts]
    outs = [list(stream_tokens(r, timeout=300)) for r in reqs]
    return outs, [list(r.logprobs) for r in reqs]


def _held_to_reference(hf, params, ids, tokens, logprobs):
    want = ref.logprobs(hf, params, ids + tokens[:-1])
    res = ref.compare(want, len(ids), tokens, logprobs)
    assert res["ok"], res
    assert res["logprob_err"] < TOL and res["argmax_margin"] < TOL


@pytest.mark.parametrize("bucket", [64, 16])
def test_serving_engine_is_the_reference(model, bucket):
    """A prompt in ONE chunk and in THREE (prefill_bucket 64 and 16: a
    chunk's K/V to its pages and its state advanced token by token, the next
    chunk from the state the last one left), then decode through pool and
    state; three prompts on two rows, so a row is REUSED from a zero state.
    The served log-probabilities are the reference's full forward, and the
    chunking changes no bit."""
    hf, cfg, params = model
    prompts = [prompt(10, 37), prompt(11, 9), prompt(12, 22)]
    eng = _engine(cfg, params, prefill_bucket=bucket,
                  step_token_budget=bucket).start()
    try:
        outs, lps = _serve(eng, prompts)
        health, metrics = eng.kv_stats(), dict(eng.metrics)
        state = eng.cache.state
    finally:
        eng.stop()
    for ids, toks, lp in zip(prompts, outs, lps):
        assert len(toks) == 6
        _held_to_reference(hf, params, ids, toks, lp)
    assert metrics["mixed_steps"] > 0
    assert metrics["ssm_state_resets_total"] == 3
    assert health["prefix_cache"] == "off: recurrent state"
    # two state buffers, float32 state, a slot a block a row
    assert health["state_cache_bytes"] == 2 * kv.state_bytes(
        kv.recurrent_state(cfg, 2))
    assert state["h"].dtype == jnp.float32
    assert state["h"].shape[:2] == (cfg.num_layers, 2)
    if bucket == 16:
        one, _ = _serve_fresh(cfg, params, prompts[:1], prefill_bucket=64,
                              step_token_budget=64)
        assert one[0] == outs[0]


def _serve_fresh(cfg, params, prompts, **over):
    eng = _engine(cfg, params, **over).start()
    try:
        return _serve(eng, prompts)
    finally:
        eng.stop()


def test_a_probe_gets_the_same_bits_alone_and_beside_others(model):
    _, cfg, params = model
    probe, new = prompt(99, 41), 12
    eng = _engine(cfg, params, max_rows=4).start()
    try:
        (alone,), (alone_lp,) = _serve(eng, [probe], new=new)
        before = [eng.submit(Request(prompt_ids=prompt(200 + i, 30 + 17 * i),
                                     max_new_tokens=10 + 3 * i,
                                     temperature=0.0)) for i in range(2)]
        req = eng.submit(Request(prompt_ids=probe, max_new_tokens=new,
                                 temperature=0.0))
        got = list(stream_tokens(req, timeout=300))
        for other in before:
            list(stream_tokens(other, timeout=300))
    finally:
        eng.stop()
    assert got == alone
    assert (np.asarray(req.logprobs, np.float32).tobytes()
            == np.asarray(alone_lp, np.float32).tobytes())


def test_rollback_restores_state_and_pages(model):
    """A tick that faults AFTER its program ran (the 'sample' site) is rolled
    back and retried: the state it started from was kept by reference and
    the pages it allocated are returned, so the retried run gives the
    unfaulted run's tokens, counters and pool."""
    hf, cfg, params = model
    prompts = [prompt(20, 19), prompt(21, 33)]
    clean = _engine(cfg, params).start()
    try:
        want, _ = _serve(clean, prompts)
        want_metrics, want_kv = dict(clean.metrics), clean.kv_stats()
    finally:
        clean.stop()
    inj = (FaultInjector().inject("sample", TransientFault, nth=1)
           .inject("decode-dispatch", TransientFault, nth=4))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=256, page_size=16, prefill_bucket=16,
        retry_backoff_s=0.0), fault_injector=inj).start()
    try:
        got, lps = _serve(eng, prompts)
        metrics, stats = dict(eng.metrics), eng.kv_stats()
    finally:
        eng.stop()
    assert inj.fired == 2 and metrics["retries"] == 2
    assert got == want
    assert metrics["ssm_state_resets_total"] == want_metrics[
        "ssm_state_resets_total"]
    assert stats["pages_in_use"] == want_kv["pages_in_use"]
    assert stats["pages_free"] == want_kv["pages_free"]
    _held_to_reference(hf, params, prompts[1], got[1], lps[1])


@pytest.mark.parametrize("field,value", [
    ("kv_spill_bytes", 1 << 20), ("spec_k", 2), ("mesh", 2)])
def test_what_recurrent_state_cannot_do_is_refused_by_name(model, field,
                                                            value):
    """The engine's three refusals hold for the family from
    ``cfg.ssm_layers > 0`` alone."""
    _, cfg, params = model
    if field == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:value]), ("tp",))
        with pytest.raises(ValueError, match="recurrent state.*mesh"):
            ServingEngine(cfg, params, EngineConfig(
                max_rows=2, max_seq_len=256, page_size=16), mesh=mesh)
        return
    with pytest.raises(ValueError, match=f"recurrent state.*{field}"):
        _engine(cfg, params, **{field: value})


# -- one scanned body, and nobody else's programs change ------------------------------

def test_the_parallel_block_is_one_scanned_body_and_adds_no_program(model):
    """The family compiles the tick programs nemotron_h compiles for the
    scenario tests/test_glm_dsa.py counts (three prompts joining at once, a
    fourth beside decoding rows, completions), by name and number: 4, which
    is what nemotron_h compiled for it on the parent commit (counted there,
    PR 46), so that family's programs are the ones it had; and the blocks
    are ONE body under a scan, whatever the depth."""
    _, cfg, params = model
    n_gen = importlib.import_module("generators.nemotron_h")
    n_hf = n_gen.tiny(run.hf_config(run.load_json(os.path.join(
        ROOT, "perfbench", "configs",
        "nemotron3-nano-30b-a3b.int4.ep2.json"))))
    n_cfg = FAMILIES["nemotron_h"].to_config(n_hf)
    for names in (_programs_of_a_wave(cfg, params, 256), _programs_of_a_wave(
            n_cfg, n_gen.param_tree(n_cfg, n_hf, "sym_int4", 3), 256)):
        assert set(names) <= {"_ragged_tick", "unpack_rows"}
        assert names.count("_ragged_tick") == 4
    deep = FAMILIES["falcon_h1"].to_config(tiny_hf(num_hidden_layers=6))
    shapes = jax.eval_shape(
        lambda: gen.param_tree(deep, tiny_hf(num_hidden_layers=6),
                               "sym_int4", 0))
    cache = jax.eval_shape(lambda: kv.with_recurrent_state(
        kv.make_cache("normal", 6, 1, 64, deep.num_kv_heads, deep.head_dim),
        deep, 1))
    text = str(jax.make_jaxpr(
        lambda p, c: decoder.decoder_forward(
            deep, p, jnp.zeros((1, 4), jnp.int32), c,
            jnp.arange(4)[None]))(shapes, cache))
    # one block traced, whatever the depth: its three silus (the conv's,
    # the gate z's, the MLP's) appear once each
    assert "length=6" in text
    assert text.count("logistic") == 3
