"""Tensor/data-parallel correctness on the virtual 8-CPU mesh.

The invariant (reference AutoTP contract, deepspeed_autotp.py:83-110 +
low_bit_linear.py:715-722): a model sharded over a ``tp`` (and/or ``dp``)
mesh axis must produce the same logits and the same greedy generation as the
unsharded model.  The reference has no unit-level multi-device test at all
(SURVEY.md §4) — these run on every CI pass via the 8-device CPU mesh from
conftest.py.
"""

import jax
import numpy as np
import pytest

from ipex_llm_tpu.generation import GenerationConfig, generate
from ipex_llm_tpu.parallel import MeshSpec, make_mesh, shard_params
from tests.test_decoder import rand_params, tiny_cfg

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def cfg_params():
    # dims chosen so every sharded axis (heads, ffn blocks, vocab) divides by 8
    cfg = tiny_cfg(
        vocab_size=128, hidden_size=64, intermediate_size=512,
        num_heads=8, num_kv_heads=8, head_dim=8,
    )
    return cfg, rand_params(cfg, qtype="sym_int4")


def _logits(cfg, params, tokens, mesh=None):
    from ipex_llm_tpu.kv import KVCache
    from ipex_llm_tpu.models.decoder import decoder_forward
    from ipex_llm_tpu.ops import dispatch
    import jax.numpy as jnp

    b, t = tokens.shape
    cache = KVCache.init(cfg.num_layers, b, t, cfg.num_kv_heads, cfg.head_dim)
    tok = jnp.asarray(tokens)
    if mesh is not None:
        from ipex_llm_tpu.parallel import shard_batch, shard_cache

        cache = shard_cache(cache, mesh)
        (tok,) = shard_batch(mesh, b, tok)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    with dispatch.spmd(mesh if mesh is not None else None):
        # jitted like every production path: the shard_map-wrapped kernels
        # require tracing (eager partial-auto shard_map is unsupported)
        from functools import partial as _partial

        logits, _ = jax.jit(_partial(decoder_forward, cfg))(
            params, tok, cache, pos
        )
    return np.asarray(logits)


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_tp_logits_match_single_device(cfg_params, tp):
    cfg, params = cfg_params
    tokens = RNG.integers(0, cfg.vocab_size, (2, 9)).astype(np.int32)
    want = _logits(cfg, params, tokens)

    mesh = make_mesh(MeshSpec(tp=tp))
    sharded = shard_params(params, mesh)
    got = _logits(cfg, sharded, tokens, mesh)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("spec", [MeshSpec(dp=4, tp=2),
                                  MeshSpec(dp=2, tp=4)])
def test_dp_tp_combined_logits(cfg_params, spec):
    """dp x tp composition over the full 8-device mesh, at both tp
    degrees."""
    cfg, params = cfg_params
    tokens = RNG.integers(0, cfg.vocab_size, (4, 7)).astype(np.int32)
    want = _logits(cfg, params, tokens)

    mesh = make_mesh(spec)
    sharded = shard_params(params, mesh)
    got = _logits(cfg, sharded, tokens, mesh)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("spec", [MeshSpec(tp=4), MeshSpec(dp=2, tp=2)])
def test_sharded_generate_matches_unsharded(cfg_params, spec):
    cfg, params = cfg_params
    gen = GenerationConfig(max_new_tokens=8, do_sample=False)
    prompts = [list(RNG.integers(0, cfg.vocab_size, 12)),
               list(RNG.integers(0, cfg.vocab_size, 5))]
    want = generate(cfg, params, prompts, gen)

    mesh = make_mesh(spec)
    sharded = shard_params(params, mesh)
    got = generate(cfg, sharded, prompts, gen, mesh=mesh)
    np.testing.assert_array_equal(got.sequences, want.sequences)


@pytest.mark.parametrize("spec", [MeshSpec(pp=2), MeshSpec(pp=2, tp=2),
                                  MeshSpec(dp=2, pp=2, tp=2)])
def test_pipeline_parallel_logits(cfg_params, spec):
    """Layer-stack sharded over pp (stage-sequential pipeline): logits must
    match single-device (reference pipeline_parallel.py:300 equivalence)."""
    cfg, params = cfg_params
    tokens = RNG.integers(0, cfg.vocab_size, (2, 9)).astype(np.int32)
    want = _logits(cfg, params, tokens)

    mesh = make_mesh(spec)
    sharded = shard_params(params, mesh)
    qkv = sharded["layers"]["qkv"]
    # the layer axis is really split across stages
    assert qkv.data.sharding.shard_shape(qkv.data.shape)[0] == cfg.num_layers // 2
    got = _logits(cfg, sharded, tokens, mesh)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_pp_generate_matches(cfg_params):
    """3-axis dp x pp x tp generate over all 8 devices: all three
    parallel axes at once."""
    cfg, params = cfg_params
    gen = GenerationConfig(max_new_tokens=8, do_sample=False)
    prompts = [list(RNG.integers(0, cfg.vocab_size, 11)),
               list(RNG.integers(0, cfg.vocab_size, 9))]
    want = generate(cfg, params, prompts, gen)
    mesh = make_mesh(MeshSpec(dp=2, pp=2, tp=2))
    sharded = shard_params(params, mesh)
    got = generate(cfg, sharded, prompts, gen, mesh=mesh)
    np.testing.assert_array_equal(got.sequences, want.sequences)


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_pallas_kernel_path(cfg_params, monkeypatch, tp):
    """The VERDICT r2 gap: TP must run the fused Pallas kernels, not the jnp
    fallback.  Asserts the shard_map-wrapped kernel is actually invoked on a
    tp>1 mesh AND produces logits matching the single-device model."""
    from ipex_llm_tpu.ops import dispatch
    from ipex_llm_tpu.ops.pallas import qmatmul as pq

    cfg, params = cfg_params
    tokens = RNG.integers(0, cfg.vocab_size, (2, 9)).astype(np.int32)
    want = _logits(cfg, params, tokens)  # plain jnp reference, no kernels

    monkeypatch.setenv("IPEX_LLM_TPU_FORCE_PALLAS", "1")
    dispatch.clear_cache()
    calls = {"n": 0}
    orig = pq.qmatmul_pallas_sharded

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(pq, "qmatmul_pallas_sharded", counting)
    try:
        # kernel-to-kernel reference (the test_serving_tp GQA precedent):
        # the bare single-device kernels, not the jnp path — interpret-
        # mode Pallas rounds bf16 differently enough from jnp to exceed a
        # tight tolerance on a random tiny model, while the sharded form
        # of the SAME kernel family is bit-exact against its single-device
        # form (head-local attention, col/row splits with f32 combines)
        want_kernel = _logits(cfg, params, tokens)
        mesh = make_mesh(MeshSpec(tp=tp))
        sharded = shard_params(params, mesh)
        assert sharded["layers"]["qkv"].tp_mode == "col"
        assert sharded["layers"]["down"].tp_mode == "row"
        got = _logits(cfg, sharded, tokens, mesh)
    finally:
        monkeypatch.delenv("IPEX_LLM_TPU_FORCE_PALLAS")
        dispatch.clear_cache()
    assert calls["n"] > 0, "sharded Pallas kernel was never dispatched"
    np.testing.assert_allclose(got, want_kernel, atol=1e-3, rtol=1e-3)
    # and the jnp oracle stays in the same neighbourhood (loose: two
    # different bf16 pipelines)
    np.testing.assert_allclose(got, want, atol=1e-1, rtol=1e-1)


def test_param_shardings_shapes(cfg_params):
    """Col weights shard the out axis, row weights the in axis."""
    cfg, params = cfg_params
    mesh = make_mesh(MeshSpec(tp=8))
    sharded = shard_params(params, mesh)
    qkv = sharded["layers"]["qkv"]
    # per-device shard of the out axis is 1/8 of the logical out
    db = qkv.data.sharding.shard_shape(qkv.data.shape)
    assert db[-1] == qkv.data.shape[-1] // 8
    down = sharded["layers"]["down"]
    ddb = down.data.sharding.shard_shape(down.data.shape)
    assert ddb[-2] == down.data.shape[-2] // 8


def test_mla_deepseek_tp_logits_match(tmp_path):
    """DeepSeek MLA (low-rank q/kv, unbalanced head dims) under a tp mesh
    must match the single-device logits — covers the q_a/kv_a col-parallel
    rules plus replicated q_b/kv_b."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    cfg = DeepseekV2Config(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=None,
        first_k_dense_replace=99, max_position_embeddings=256,
        attention_bias=False, tie_word_embeddings=False,
    )
    torch.manual_seed(21)
    path = str(tmp_path / "dsv2")
    DeepseekV2ForCausalLM(cfg).eval().save_pretrained(
        path, safe_serialization=True)

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    tokens = np.random.default_rng(2).integers(0, 160, (2, 9)).astype(np.int32)
    m0 = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="bf16")
    want = np.asarray(m0(tokens))

    mesh = make_mesh(MeshSpec(tp=2))
    m1 = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="bf16",
                                              mesh=mesh)
    got = np.asarray(m1(tokens))
    assert np.abs(got - want).max() / np.abs(want).max() < 0.02
