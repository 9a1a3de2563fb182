"""MoE decoder correctness: tiny Mixtral / Qwen2-MoE logits vs HF torch.

Mirrors the reference's layer-equivalence strategy (SURVEY.md §4) for the
MoE families the reference optimizes via fused kernels
(models/deepseek.py:274-343, qwen3_moe.py:70).
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _roundtrip(hf_model, tmp_path, name):
    path = str(tmp_path / name)
    hf_model.save_pretrained(path, safe_serialization=True)
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    return AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="bf16")


def _check_logits(model, hf_model, vocab, tol=0.06, agree_min=0.85):
    tokens = np.random.default_rng(0).integers(0, vocab, (2, 10)).astype(np.int32)
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens).long()).logits.float().numpy()
    got = np.asarray(model(tokens))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() / scale < tol, (
        np.abs(got - want).max() / scale
    )
    agree = (got.argmax(-1) == want.argmax(-1)).mean()
    assert agree > agree_min, agree


def test_mixtral_logits(tmp_path):
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        tie_word_embeddings=False, max_position_embeddings=256,
    )
    torch.manual_seed(0)
    hf = MixtralForCausalLM(cfg).eval()
    model = _roundtrip(hf, tmp_path, "mixtral")
    assert model.config.num_experts == 4
    _check_logits(model, hf, 160)


def test_qwen2_moe_logits(tmp_path):
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM

    cfg = Qwen2MoeConfig(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, shared_expert_intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        tie_word_embeddings=False, max_position_embeddings=256,
    )
    torch.manual_seed(0)
    hf = Qwen2MoeForCausalLM(cfg).eval()
    model = _roundtrip(hf, tmp_path, "qwen2moe")
    _check_logits(model, hf, 160)


def test_qwen3_moe_logits(tmp_path):
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    cfg = Qwen3MoeConfig(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        tie_word_embeddings=False, max_position_embeddings=256,
    )
    torch.manual_seed(0)
    hf = Qwen3MoeForCausalLM(cfg).eval()
    model = _roundtrip(hf, tmp_path, "qwen3moe")
    _check_logits(model, hf, 160)


def test_moe_generate_and_int4(tmp_path):
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=120, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2, tie_word_embeddings=False,
    )
    torch.manual_seed(1)
    path = str(tmp_path / "m4")
    MixtralForCausalLM(cfg).save_pretrained(path, safe_serialization=True)
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(path, load_in_4bit=True)
    out = model.generate(np.arange(3, 12, dtype=np.int32), max_new_tokens=6)
    assert out.shape == (1, 9 + 6)


def test_sparse_matches_dense_oracle(tmp_path, monkeypatch):
    """Sparse dispatch (gather + capacity modes) must reproduce the dense
    all-experts scan exactly when no capacity drops occur."""
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=120, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2, tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    path = str(tmp_path / "msp")
    MixtralForCausalLM(cfg).save_pretrained(path, safe_serialization=True)
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="sym_int4")
    rng = np.random.default_rng(2)
    long_tok = rng.integers(0, 120, (2, 24)).astype(np.int32)   # capacity mode
    short_tok = rng.integers(0, 120, (1, 2)).astype(np.int32)   # gather mode

    monkeypatch.setenv("IPEX_LLM_TPU_DENSE_MOE", "1")
    want_long = np.asarray(model(long_tok))
    want_short = np.asarray(model(short_tok))
    monkeypatch.delenv("IPEX_LLM_TPU_DENSE_MOE")
    got_long = np.asarray(model(long_tok))
    got_short = np.asarray(model(short_tok))
    np.testing.assert_allclose(got_long, want_long, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(got_short, want_short, atol=2e-2, rtol=2e-2)


def test_capacity_drop_semantics():
    """With a tiny forced capacity, overflow pairs are dropped (contribute
    zero) — the standard capacity-factor contract, never NaN/garbage."""
    import jax.numpy as jnp

    from ipex_llm_tpu.ops import moe as moe_ops
    from ipex_llm_tpu.quantize import quantize

    rng = np.random.default_rng(0)
    e, h, f = 4, 16, 32
    gu = quantize(rng.standard_normal((h, 2 * f)).astype(np.float32), "bf16")
    dn = quantize(rng.standard_normal((f, h)).astype(np.float32), "bf16")
    import jax

    gu_s = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * e), gu
    )
    dn_s = jax.tree_util.tree_map(lambda x: jnp.stack([x] * e), dn)
    x = jnp.asarray(rng.standard_normal((1, 12, h)).astype(np.float32))
    # every token picks expert 0 -> massive imbalance
    idx = jnp.zeros((1, 12, 2), jnp.int32)
    w = jnp.full((1, 12, 2), 0.5, jnp.float32)
    out = moe_ops.moe_capacity(x, w, idx, gu_s, dn_s, "silu", e, cf=0.5)
    assert np.isfinite(np.asarray(out)).all()
    # capacity cf=0.5 with N=12,k=2,E=4 -> cap=8: first 8 pairs (4 tokens? no,
    # 8 pairs = 8 of the 24) kept; later tokens got dropped to zero output
    assert float(jnp.abs(out[0, -1]).sum()) == 0.0


def test_expert_offload_matches_resident(tmp_path):
    """FlashMoE-equivalent: host-RAM experts + HBM LRU streaming must
    reproduce the fully-resident model's greedy generation.  The byte
    budget is set below the total expert footprint so evictions and
    re-fetches actually happen."""
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=120, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2, tie_word_embeddings=False,
    )
    torch.manual_seed(5)
    path = str(tmp_path / "moff")
    MixtralForCausalLM(cfg).save_pretrained(path, safe_serialization=True)
    from ipex_llm_tpu.offload import OffloadedMoE
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="sym_int4")
    prompt = np.arange(3, 13, dtype=np.int32)
    want = np.asarray(model.generate(prompt, max_new_tokens=6))

    # ~4 KB budget: holds a single expert entry, so every layer/step evicts
    off = OffloadedMoE(model.config, model.params, hbm_budget_mb=0.004)
    got = off.generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(got, want)
    n_entries = model.config.num_layers * model.config.num_experts
    assert off.store.misses > n_entries, (off.store.misses, off.store.hits)


def test_moe_ep_sharding(tmp_path):
    """MoE logits under an ep×tp mesh == single-device logits."""
    from transformers import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
        num_local_experts=4, num_experts_per_tok=2, tie_word_embeddings=False,
    )
    torch.manual_seed(2)
    path = str(tmp_path / "mep")
    MixtralForCausalLM(cfg).save_pretrained(path, safe_serialization=True)
    from ipex_llm_tpu.parallel import MeshSpec, make_mesh
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    tokens = np.random.default_rng(1).integers(0, 128, (2, 8)).astype(np.int32)
    model = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="sym_int4")
    want = np.asarray(model(tokens))

    mesh = make_mesh(MeshSpec(ep=2, tp=2))
    model.shard(mesh)
    got = np.asarray(model(tokens))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


# ---- the held expert layer's two forms (ops/moe.py ``moe_held``) ----------

HELD = dict(experts=4, hidden=128, inner=64, top_k=2, n_routed=16)


def _held_stacks():
    import jax
    import jax.numpy as jnp

    from ipex_llm_tpu.quantize import core as qcore

    def stack(key, k, n):
        ws = np.asarray(jax.random.normal(key, (HELD["experts"], k, n))) * 0.1
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[
            qcore.pad_planes(qcore.quantize(w, "sym_int4")) for w in ws])

    return (stack(jax.random.key(0), HELD["hidden"], 2 * HELD["inner"]),
            stack(jax.random.key(1), HELD["inner"], HELD["hidden"]))


def _held_gate(case: str):
    """(tokens, gate [N, 4]) of a routing case; the tile is 48 rows for 200
    tokens and 32 for 160 (``row_tile`` at top 2 of 16)."""
    rng = np.random.RandomState(7)
    n = {"ragged": 200, "alone_in_its_tile": 160}.get(case, 192)
    g = np.where(rng.rand(n, 4) < 0.3, rng.rand(n, 4) + 0.1, 0.0)
    if case == "one_expert":            # every pair on expert 2: four tiles
        g[:, [0, 1, 3]] = 0.0
        g[:, 2] = rng.rand(n) + 0.1
    elif case == "no_pair":             # n_hit 0: nothing in the grid
        g[:] = 0.0
    elif case == "alone_in_its_tile":   # expert 1: 33 tokens, the last one
        g[:, 1] = 0.0                   # alone in the second tile of 32;
        g[np.arange(33) * 4 + 3, 1] = 0.5    # expert 3: one token, alone
        g[:, 3] = 0.0
        g[77, 3] = 0.9
    g[5] = 0.0                          # a token routed nowhere held
    return n, g.astype(np.float32)


@pytest.mark.parametrize("case", ["balanced", "one_expert", "no_pair",
                                  "ragged", "alone_in_its_tile"])
def test_held_layer_gives_a_token_the_same_bits_in_every_form(case,
                                                              kernel_forced):
    """Rows grouped by expert (N > 128) == every hit expert over every row
    (the form a decode stage meets) == a plain float32 loop over one expert
    at a time, BIT FOR BIT a token, with the kernel forced through the
    interpreter; and the rows each form says it multiplied."""
    import jax
    import jax.numpy as jnp

    from ipex_llm_tpu.ops import moe as moe_ops

    gu, dn = _held_stacks()
    n, g = _held_gate(case)
    h = jax.random.normal(jax.random.key(2), (n, HELD["hidden"])).astype(
        jnp.bfloat16)
    bm = moe_ops.row_tile(n, HELD["top_k"], HELD["n_routed"])
    assert bm == (32 if n == 160 else 48) and (n % bm > 0) == (
        case == "ragged")

    by_slot = jax.jit(lambda h, gate: moe_ops._held_by_slot(
        h, gate, gu, dn, "silu", True, 0))
    held = jax.jit(lambda h, gate: moe_ops.moe_held(
        h, gate, gu, dn, "silu", True, top_k=HELD["top_k"],
        n_routed=HELD["n_routed"]))
    rows, n_hit, rows_n = held(h, jnp.asarray(g))
    slot, slot_n = by_slot(h, jnp.asarray(g))
    per_expert = (g != 0).sum(0)
    assert int(n_hit) == (per_expert > 0).sum()
    assert int(slot_n) == int(n_hit) * n
    assert int(rows_n) == sum(-(-c // bm) for c in per_expert) * bm
    if case == "one_expert":
        assert int(rows_n) == 4 * bm                 # four tiles, one expert
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(slot))

    plain = np.zeros((n, HELD["hidden"]), np.float32)
    for e in range(HELD["experts"]):                 # ascending, one by one
        alone = np.zeros_like(g)
        alone[:, e] = 1.0
        y_e = np.asarray(by_slot(h, jnp.asarray(alone))[0])
        plain = plain + g[:, e:e + 1] * y_e          # numpy: no fused add
    np.testing.assert_array_equal(np.asarray(rows), plain)
    assert not np.any(np.asarray(rows)[5])

    # a block of the first 64 tokens alone (one row tile: the other form)
    few, _, few_n = held(h[:64], jnp.asarray(g[:64]))
    np.testing.assert_array_equal(np.asarray(few), np.asarray(rows)[:64])
    assert int(few_n) == int(((g[:64] != 0).sum(0) > 0).sum()) * 64


def test_row_tile_follows_the_shapes():
    """The tile is the rows an expert expects plus two standard deviations,
    in sublane tiles of 16, capped at 128: the cells' chunks get 32."""
    from ipex_llm_tpu.ops.moe import row_tile

    assert row_tile(512, 8, 192) == 32        # axk1.long-context's chunk
    assert row_tile(512, 8, 256) == 32        # glm52.long-document's
    assert row_tile(512, 6, 128) == 48
    assert row_tile(512, 4, 4) == 128         # every token everywhere
    assert row_tile(130, 1, 512) == 16
