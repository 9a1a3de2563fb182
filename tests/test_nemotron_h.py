"""nemotron_h on the CPU at a tiny size: Mamba-2 in its two forms, the layer
schedule, the expert layer that holds part of its experts, and the model
through ``decoder_forward`` (dense cache) and ``ServingEngine`` (ragged tick),
held to the benchmark's plain reference (perfbench/references/nemotron_h.py,
float32, importing nothing of the program).  Seeded random weights drawn by
the benchmark's generator, which draws the tree ``models/build.py`` builds."""

from __future__ import annotations

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(ROOT, "perfbench"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from ipex_llm_tpu import kv  # noqa: E402
from ipex_llm_tpu.models import decoder  # noqa: E402
from ipex_llm_tpu.models.families import FAMILIES  # noqa: E402
from ipex_llm_tpu.ops import ssm  # noqa: E402
from ipex_llm_tpu.serving.engine import (EngineConfig, Request,  # noqa: E402
                                         ServingEngine, stream_tokens)
from ipex_llm_tpu.serving.faults import FaultInjector, TransientFault  # noqa: E402

gen = importlib.import_module("generators.nemotron_h")
ref = importlib.import_module("references.nemotron_h")

BASE = {
    "model_type": "nemotron_h", "chunk_size": 128, "conv_kernel": 4,
    "layer_norm_epsilon": 1e-5, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "time_step_floor": 1e-4, "time_step_max": 0.1, "time_step_min": 0.001,
    "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
    "max_position_embeddings": 4096}
# bf16 activations against the float32 reference at this size: the largest
# log-probability error seen over these tests' seeds is 0.08
TOL = 0.25


def tiny_hf(**over) -> dict:
    return dict(gen.tiny(BASE), **over)


@pytest.fixture(scope="module")
def model():
    hf = tiny_hf()
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    return hf, cfg, gen.param_tree(cfg, hf, "sym_int4", 7)


def prompt(seed: int, n: int, vocab: int = 256) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(3, vocab, n)]


# -- Mamba-2 -------------------------------------------------------------------

def _ssd_inputs(b=3, t=9, heads=4, p=8, groups=2, n=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (b, t, heads, p))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(k[1], (b, t, heads)))
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (heads,)))
    bm = jax.random.normal(k[3], (b, t, groups, n))
    cm = jax.random.normal(k[4], (b, t, groups, n))
    h0 = jax.random.normal(k[5], (b, heads, p, n))       # a NON-zero start
    return h0, x, dt, a, bm, cm


def _recurrence_f64(h0, x, dt, a, b, c):
    """The recurrence in numpy float64, a token and a row at a time."""
    h0, x, dt, a, b, c = (np.asarray(v, np.float64)
                          for v in (h0, x, dt, a, b, c))
    h, y = h0.copy(), np.zeros(x.shape)
    for r in range(x.shape[0]):
        for t in range(x.shape[1]):
            h[r] = (np.exp(dt[r, t] * a)[:, None, None] * h[r]
                    + (dt[r, t][:, None] * x[r, t])[..., None]
                    * b[r, t][:, None, :])
            y[r, t] = np.einsum("hpn,hn->hp", h[r], c[r, t])
    return y, h


@pytest.mark.parametrize("cut", [(9,), (4, 5), (1, 3, 5)])
def test_mamba2_sequential_on_a_ragged_block_however_it_is_cut(cut):
    """A ragged block that starts from non-zero state, with rows of
    chunk_lens 9, 4, 1 and 0 (a pad position has dt = 0), is the recurrence
    in float64; cut in pieces that carry the state it gives the same BITS:
    the form the program keeps is the one that does not care about chunks."""
    h0, x, dt, a, bm, cm = _ssd_inputs(b=4)
    lens = jnp.array([9, 4, 1, 0])
    valid = jnp.arange(9)[None, :] < lens[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    bh, ch = (jnp.repeat(v, 2, axis=2) for v in (bm, cm))
    y_seq, h_seq = ssm.ssd_sequential(h0, x, dt, a, bh, ch)
    y64, h64 = _recurrence_f64(h0, x, dt, a, bh, ch)
    np.testing.assert_allclose(y_seq, y64, atol=2e-5)
    np.testing.assert_allclose(h_seq, h64, atol=2e-5)
    # the row with no token keeps its state to the bit
    assert np.array_equal(h_seq[3], h0[3])
    h, ys, lo = h0, [], 0
    for width in cut:
        y, h = ssm.ssd_sequential(h, *(v[:, lo: lo + width]
                                       for v in (x, dt)), a,
                                  *(v[:, lo: lo + width] for v in (bh, ch)))
        ys.append(y)
        lo += width
    assert np.array_equal(h, h_seq)
    assert np.array_equal(jnp.concatenate(ys, 1), y_seq)


def test_mamba2_kernel_is_sequential_whatever_the_chunking():
    """The chip's form (interpreted here): the same bits for a row's tokens
    whether they arrive in one block or cut in two, and in place."""
    from ipex_llm_tpu.ops.pallas import ssm_scan

    h0, x, dt, a, bm, cm = _ssd_inputs(b=2, t=8, heads=4, p=64, groups=2,
                                       n=128)
    lens = jnp.array([8, 5])
    dt = jnp.where((jnp.arange(8)[None, :] < lens[:, None])[..., None], dt, 0)
    bh, ch = (jnp.repeat(v, 2, axis=2) for v in (bm, cm))
    y_seq, h_seq = ssm.ssd_sequential(h0, x, dt, a, bh, ch)
    y, h = ssm_scan.scan_rows(h0, x, dt, a, bm, cm, lens)
    np.testing.assert_allclose(h, h_seq, atol=1e-5)
    np.testing.assert_allclose(y[0], y_seq[0], atol=1e-4)
    np.testing.assert_allclose(y[1, :5], y_seq[1, :5], atol=1e-4)
    cut = [(0, 3), (3, 8)]
    hh, ys = h0, []
    for lo, hi in cut:
        yy, hh = ssm_scan.scan_rows(
            hh, x[:, lo:hi], dt[:, lo:hi], a, bm[:, lo:hi], cm[:, lo:hi],
            jnp.clip(lens - lo, 0, hi - lo))
        ys.append(yy)
    assert np.array_equal(hh, h)
    assert np.array_equal(jnp.concatenate(ys, 1)[0], y[0])
    # the stack form: layer 1 of 3, read from one stack and written to another
    stack = jnp.stack([h0 * 0, h0, h0 * 2])
    y1, out = ssm_scan.scan_stack(stack, jnp.zeros_like(stack), jnp.int32(1),
                                  x[:, :1], dt[:, :1], a, bm[:, :1],
                                  cm[:, :1], jnp.array([1, 0]))
    _, h_one = ssm.ssd_sequential(h0, x[:, :1], dt[:, :1] * jnp.array(
        [1.0, 0.0])[:, None, None], a, bh[:, :1], ch[:, :1])
    np.testing.assert_allclose(out[1], h_one, atol=1e-5)
    assert np.array_equal(out[1, 1], h0[1])          # the dead row: copied
    assert not np.any(out[0]) and not np.any(out[2])


def test_mixer_from_carried_state_is_the_reference(model):
    """The whole mixer over a prompt cut in ragged blocks (the conv tail and
    the state carried between them, a block with no token among them) is
    the reference's mixer over the prompt."""
    hf, cfg, params = model
    lp = jax.tree_util.tree_map(lambda a: a[0], params["hybrid"]["mamba"])
    x0 = jax.random.normal(jax.random.key(3), (11, cfg.hidden_size))
    mixer = ref._mamba_fn(
        cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_n_groups,
        cfg.ssm_state_size, cfg.norm_eps,
        (tuple(lp["in_proj"].shape), tuple(lp["out_proj"].shape)), None)
    ref_lp = {k_: ((v.data, v.scales) if hasattr(v, "scales") else v)
              for k_, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        want = mixer(x0, ref_lp) - x0
    u = ref._rms_norm(x0, lp["norm"], cfg.norm_eps).astype(jnp.bfloat16)[None]
    state = kv.recurrent_state(cfg, 1)
    outs = []
    for lo, hi, width in ((0, 4, 4), (4, 5, 4), (5, 5, 2), (5, 11, 8)):
        blk = jnp.zeros((1, width, cfg.hidden_size), u.dtype)
        blk = blk.at[:, : hi - lo].set(u[:, lo:hi])
        valid = jnp.arange(width)[None, :] < hi - lo
        out, state = ssm.mamba2_mixer(cfg, lp, blk, state, 0, valid)
        outs.append(out[:, : hi - lo])
    got = jnp.concatenate(outs, 1)[0].astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) < 0.05 * float(
        jnp.max(jnp.abs(want)))


# -- the schedule ---------------------------------------------------------------

def test_schedule_parsed_from_the_pattern():
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    kind, units = decoder.hybrid_schedule(pattern)
    assert kind == "units" and len(units) == 23 and sum(units) == 6
    assert [i for i, u in enumerate(units) if u] == [2, 5, 8, 11, 14, 18]
    kind, blocks = decoder.hybrid_schedule("M*EEM")       # not of unit form
    assert kind == "blocks"
    assert blocks == [("M", 0), ("*", 0), ("E", 0), ("E", 1), ("M", 1)]
    assert ref.parse_pattern("M*EEM") == blocks
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        FAMILIES["nemotron_h"].to_config(tiny_hf(
            hybrid_override_pattern="ME-*E"))
    cfg = FAMILIES["nemotron_h"].to_config(tiny_hf())
    assert (cfg.kv_layers, cfg.ssm_layers, cfg.num_layers) == (1, 2, 5)
    assert cfg.num_experts == 8 and cfg.moe_experts_held == 4
    assert cfg.rope is None


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


@pytest.mark.parametrize("pattern", ["MEM*E", "M*EEM"])
def test_prefill_then_decode_is_the_reference_forward(pattern):
    hf = tiny_hf(hybrid_override_pattern=pattern)
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    params = gen.param_tree(cfg, hf, "sym_int4", 11)
    ids = prompt(1, 21)
    want, gap = ref.logprobs(hf, params, ids)
    got = _dense_logprobs(cfg, params, ids, split=16)
    assert want.shape == got.shape == (21, 256) and gap.shape == (21,)
    assert np.abs(got - want).max() < TOL
    # a left-padded prompt (generate's prefill): pads leave the state alone
    pad = 3
    cache = kv.with_recurrent_state(
        kv.make_cache("normal", cfg.kv_layers, 1, 64, cfg.num_kv_heads,
                      cfg.head_dim), cfg, 1)
    toks = jnp.asarray([[0] * pad + ids])
    pos = jnp.maximum(jnp.arange(pad + 21)[None] - pad, 0)
    logits, _ = jax.jit(decoder.decoder_forward, static_argnums=0)(
        cfg, params, toks, cache, pos, kv_start=jnp.array([pad]))
    padded = np.asarray(jax.nn.log_softmax(logits[0, pad:], -1))
    assert np.abs(padded - want).max() < TOL


def test_generate_runs_the_family(model):
    from ipex_llm_tpu.generation import GenerationConfig, generate

    hf, cfg, params = model
    ids = prompt(2, 9)
    out = generate(cfg, params, [ids], GenerationConfig(
        max_new_tokens=4, do_sample=False))
    new = [int(t) for t in out.sequences[0][len(ids):]]
    want, _ = ref.logprobs(hf, params, ids + new[:-1])
    for j, tok in enumerate(new):
        row = want[len(ids) - 1 + j]
        assert row.max() - row[tok] < TOL


# -- the expert layer's share ------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts computed by the holders of experts
    (0, 4) and (4, 4), the shared expert counted once, add up to the layer
    that holds all 8; and no pair is dropped."""
    hf = tiny_hf(n_routed_experts=8)
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    assert cfg.moe_experts_held == cfg.num_experts == 8
    lp = jax.tree_util.tree_map(
        lambda a: a[0], gen.param_tree(cfg, hf, "sym_int4", 5)["hybrid"]["moe"])
    x = jax.random.normal(jax.random.key(1), (2, 6, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    valid = jnp.ones((2, 6), bool).at[1, 4:].set(False)
    whole, stats = decoder._held_moe_block(cfg, lp, x, valid, shared=False)
    assert int(stats[0]) == int(stats[1]) == 10 * 2      # every pair local

    def share(first, shared):
        from dataclasses import replace

        c = replace(cfg, moe_first_expert=first, moe_experts_held=4)
        part = dict(lp)
        for name in ("moe_gate_up", "moe_down"):
            part[name] = jax.tree_util.tree_map(
                lambda a: a[first: first + 4], lp[name])
        return decoder._held_moe_block(c, part, x, valid, shared=shared)

    (a, sa), (b, sb) = share(0, False), share(4, False)
    assert int(sa[1]) + int(sb[1]) == int(stats[1])
    assert int(sa[0]) == int(sb[0]) == int(stats[0])
    both = a.astype(jnp.float32) + b.astype(jnp.float32)
    err = jnp.abs(both - whole.astype(jnp.float32))[np.asarray(valid)]
    assert float(err.max()) <= 0.02 * float(jnp.abs(whole).max())
    with_shared, _ = share(0, True)
    only_shared = with_shared.astype(jnp.float32) - a.astype(jnp.float32)
    assert float(jnp.abs(only_shared).max()) > 0      # counted, once
    # padding routes nowhere
    gone, _ = decoder._held_moe_block(cfg, lp, x, jnp.zeros((2, 6), bool),
                                      shared=False)
    assert not np.any(np.asarray(gone, np.float32))


def test_planes_are_padded_once_at_build():
    """The small repair: a weight whose N or K is no multiple of the
    dequant-matmul kernel's tile is padded when the tree is built, so the
    call pads no weight; Mistral's widths keep their tiles."""
    from ipex_llm_tpu.ops.linear import qmatmul_reference
    from ipex_llm_tpu.ops.pallas import qmatmul as qmm
    from ipex_llm_tpu.quantize import core as qcore

    assert qmm.tile_plan(4096, 6144, 32) == (2048, 4096, 512, 6144)
    assert qmm.tile_plan(14336, 4096, 32) == (2048, 14336, 512, 4096)
    assert qcore.kernel_padded_shape(2688, 10304, 32) == (3072, 10368)
    assert qcore.kernel_padded_shape(1856, 2688, 32) == (2048, 2688)
    assert qmm.tile_plan(3072, 10368, 32) == (1536, 3072, 384, 10368)
    w = np.asarray(jax.random.normal(jax.random.key(0), (160, 200)))
    qt = qcore.quantize(w, "sym_int4")
    padded = qcore.pad_planes(qt)
    assert padded.shape == qt.shape == (160, 200)
    assert padded.data.shape == (256, 256) and padded.scales.shape == (16, 256)
    np.testing.assert_array_equal(qcore.dequantize(padded),
                                  qcore.dequantize(qt))
    x = jax.random.normal(jax.random.key(1), (5, 160)).astype(jnp.bfloat16)
    want = qmatmul_reference(x, qt)
    got = qmm.qmatmul_pallas(x, padded)                    # interpreted
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), atol=0.1)
    # a group of a stack's entries, read in place by their indices: tiles 0
    # and 1 live (entries 1 and 2); the third is not in the grid, and its
    # rows are not written
    stack = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *[
        qcore.pad_planes(qcore.quantize(w * s, "sym_int4"))
        for s in (1.0, -2.0, 0.5)])
    assert qmm.grouped_supported(stack)
    ids = jnp.array([1, 2, 2])
    got = qmm.qmatmul_pallas_grouped(x, stack, ids, jnp.int32(2))
    assert got.shape == (3, 5, 200)
    np.testing.assert_allclose(got[0], -2.0 * want.astype(np.float32),
                               atol=0.25)
    np.testing.assert_allclose(got[1], 0.5 * want.astype(np.float32),
                               atol=0.1)
    own = jnp.stack([x, 2 * x, x])                   # a tile's own input
    got = qmm.qmatmul_pallas_grouped(own, stack, ids, jnp.int32(2))
    np.testing.assert_allclose(got[1], want.astype(np.float32), atol=0.2)
    # the same entries named inside a group that starts at entry 1
    sub = qmm.qmatmul_pallas_grouped(own, stack, jnp.array([0, 1, 1]),
                                     jnp.int32(2), first=jnp.int32(1))
    np.testing.assert_array_equal(sub[:2], got[:2])
    # fp16 scale bits decoded by integer arithmetic, subnormals and zero too
    h = jnp.array([0.0, 6e-8, 1e-5, 0.005, -0.37, 65504.0, -6.1e-5],
                  jnp.float16)
    bits = jax.lax.bitcast_convert_type(h, jnp.uint16).astype(jnp.int32)
    np.testing.assert_array_equal(qmm._f16_bits_to_f32(bits),
                                  h.astype(jnp.float32))


def test_expert_layer_on_the_grouped_kernel_is_the_loop(monkeypatch):
    """The chip's pass over the hit experts (two grouped kernel calls,
    interpreted here) gives what the loop over sliced experts gives, whole
    stacks addressed by (block, expert) included."""
    from ipex_llm_tpu.ops import dispatch, moe as moe_ops

    hf = tiny_hf()
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    tree = gen.param_tree(cfg, hf, "sym_int4", 9)["hybrid"]["moe"]
    h = jax.random.normal(jax.random.key(2), (6, cfg.hidden_size)).astype(
        jnp.bfloat16)
    gate = jnp.zeros((6, 4)).at[0, 2].set(0.7).at[3, 0].set(1.1).at[
        5, 2].set(0.4)
    want, n, rows = moe_ops.moe_held(
        h, gate, tree["moe_gate_up"], tree["moe_down"], cfg.act, gated=False,
        layer=jnp.int32(1))
    monkeypatch.setattr(dispatch, "_use_pallas_env", lambda op=None: True)
    got, n_k, rows_k = moe_ops.moe_held(h, gate, tree["moe_gate_up"],
                                        tree["moe_down"], cfg.act,
                                        gated=False, layer=jnp.int32(1))
    assert int(n) == int(n_k) == 2
    assert int(rows) == int(rows_k) == 2 * 6       # hit experts x the rows
    np.testing.assert_allclose(got, want, atol=0.02 * float(
        jnp.abs(want).max()))
    assert not np.any(np.asarray(got[1]))              # a token routed nowhere


def test_build_reads_the_published_weight_names():
    """models/build.py builds the tree the generator draws, from the names
    the published checkpoint uses."""
    from ipex_llm_tpu.models.build import build_params

    hf = tiny_hf()
    cfg = FAMILIES["nemotron_h"].to_config(hf)
    rng = np.random.RandomState(0)
    h, d_in, c = cfg.hidden_size, 64, cfg.ssm_conv_dim
    sd = {"backbone.embeddings.weight": rng.randn(300, h) * 0.1,
          "backbone.norm_f.weight": np.ones(h),
          "lm_head.weight": rng.randn(300, h) * 0.1}
    for i, kind in enumerate(cfg.hybrid_pattern):
        m = f"backbone.layers.{i}.mixer."
        sd[f"backbone.layers.{i}.norm.weight"] = np.ones(h)
        if kind == "M":
            sd.update({
                m + "in_proj.weight": rng.randn(d_in + c + 4, h) * 0.1,
                m + "conv1d.weight": rng.randn(c, 1, 4) * 0.3,
                m + "conv1d.bias": rng.randn(c) * 0.1,
                m + "dt_bias": rng.randn(4) - 4.0,
                m + "A_log": np.log(rng.uniform(1, 16, 4)),
                m + "D": np.ones(4), m + "norm.weight": np.ones(d_in),
                m + "out_proj.weight": rng.randn(h, d_in) * 0.1})
        elif kind == "E":
            sd[m + "gate.weight"] = rng.randn(8, h) * 0.1
            sd[m + "gate.e_score_correction_bias"] = rng.randn(8) * 0.01
            for e in range(8):
                sd[m + f"experts.{e}.up_proj.weight"] = rng.randn(64, h) * 0.1
                sd[m + f"experts.{e}.down_proj.weight"] = rng.randn(h, 64) * 0.1
            sd[m + "shared_experts.up_proj.weight"] = rng.randn(128, h) * 0.1
            sd[m + "shared_experts.down_proj.weight"] = rng.randn(h, 128) * 0.1
        else:
            for p, rows in (("q", 128), ("k", 64), ("v", 64)):
                sd[m + f"{p}_proj.weight"] = rng.randn(rows, h) * 0.1
            sd[m + "o_proj.weight"] = rng.randn(h, 128) * 0.1
    sd = {k_: np.asarray(v, np.float32) for k_, v in sd.items()}
    params = build_params(cfg, FAMILIES["nemotron_h"].scheme, sd.__getitem__,
                          sd.__contains__, qtype="sym_int4")
    drawn = gen.param_tree(cfg, hf, "sym_int4", 0)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(drawn))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(drawn)):
        assert got.shape == want.shape and got.dtype == want.dtype
    ids = prompt(4, 12)
    want, _ = ref.logprobs(hf, params, ids)
    got = _dense_logprobs(cfg, params, ids, split=12)
    assert np.abs(got - want).max() < TOL


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
    want, _ = ref.logprobs(hf, params, ids + tokens[:-1])
    for j, (tok, lp) in enumerate(zip(tokens, logprobs)):
        row = want[len(ids) - 1 + j]
        assert abs(float(row[tok]) - lp) < TOL, (j, row[tok], lp)
        assert float(row.max() - row[tok]) < TOL


@pytest.mark.parametrize("horizon", [1, 8])
def test_serving_engine_is_the_reference(model, horizon):
    """Mixed ticks (three prompts on two rows: chunked prefill beside decode,
    then a row REUSED by the third prompt, its state zeroed on admission),
    at horizon 1 and 8."""
    hf, cfg, params = model
    prompts = [prompt(10, 37), prompt(11, 9), prompt(12, 22)]
    eng = _engine(cfg, params, decode_horizon=horizon).start()
    try:
        outs, lps = _serve(eng, prompts)
        health = eng.kv_stats()
        metrics = dict(eng.metrics)
    finally:
        eng.stop()
    for ids, toks, lp in zip(prompts, outs, lps):
        assert len(toks) == 6
        _held_to_reference(hf, params, ids, toks, lp)
    assert metrics["mixed_steps"] > 0
    assert metrics["ssm_state_resets_total"] == 3
    routed, local = (metrics["moe_pairs_routed_total"],
                     metrics["moe_pairs_local_total"])
    assert 0 < local < routed and routed % cfg.num_experts_per_tok == 0
    assert 0 < metrics["moe_experts_hit_total"] <= (
        metrics["moe_layer_calls_total"] * cfg.moe_experts_held)
    assert health["prefix_cache"] == "off: recurrent state"
    assert health["state_cache_bytes"] == 2 * kv.state_bytes(
        kv.recurrent_state(cfg, 2))


def test_routing_weights_do_not_depend_on_the_shape_of_the_block(model):
    """A token's routing weights are the same bits whatever block carries it,
    and the k chosen are summed term by term: as a reduction, XLA on the
    chip orders the sum by the block's shape (one ulp between [1, 128, 6]
    and [4, 32, 6], measured in PR 28), which a CPU cannot show, so the
    program is held to having no such reduction at all."""
    _, cfg, params = model
    lp = jax.tree_util.tree_map(lambda a: a[0], params["hybrid"]["moe"])
    logits = jax.random.normal(jax.random.key(5), (128, cfg.num_experts))
    route = jax.jit(lambda lg: decoder._route(cfg, lp, lg))
    w0, i0 = route(logits.reshape(1, 128, -1))
    for b, t in ((4, 32), (32, 4), (128, 1)):
        w, i = route(logits.reshape(b, t, -1))
        assert np.array_equal(w.reshape(w0.shape), w0)
        assert np.array_equal(i.reshape(i0.shape), i0)
    np.testing.assert_allclose(w0.sum(-1), cfg.moe_router_scale, rtol=1e-6)
    text = str(jax.make_jaxpr(lambda lg: decoder._route(cfg, lp, lg))(
        logits.reshape(4, 32, -1)))
    assert "reduce_sum" not in text


def test_a_probe_gets_the_same_bits_alone_and_inside_mixed_ticks(model):
    """The benchmark's demand (and the engine's contract since PR 21): one
    prompt sent alone (it prefills as a block of its own and decodes in
    steady ticks) and again beside others that arrive before, with and
    after it (it shares prefill blocks and decodes inside mixed ticks)
    gives the same tokens and the same log-probability BITS."""
    _, cfg, params = model
    probe, new = prompt(99, 41), 20
    eng = _engine(cfg, params, max_rows=4).start()
    try:
        (alone,), (alone_lp,) = _serve(eng, [probe], new=new)
        mixed_before = eng.metrics["mixed_steps"]
        for trial in range(2):
            before = [eng.submit(Request(
                prompt_ids=prompt(200 + 10 * trial + i, 30 + 17 * i),
                max_new_tokens=10 + 3 * i, temperature=0.0))
                for i in range(2)]
            req = eng.submit(Request(prompt_ids=probe, max_new_tokens=new,
                                     temperature=0.0))
            got, after = [], []
            for tok in stream_tokens(req, timeout=300):
                got.append(tok)
                if len(got) % 3 == 1 and len(after) < 5:
                    # a new prompt while the probe decodes: mixed ticks
                    after.append(eng.submit(Request(
                        prompt_ids=prompt(300 + 10 * trial + len(after),
                                          25 + 9 * len(after)),
                        max_new_tokens=4, temperature=0.0)))
            for other in before + after:
                list(stream_tokens(other, timeout=300))
            assert got == alone
            assert (np.asarray(req.logprobs, np.float32).tobytes()
                    == np.asarray(alone_lp, np.float32).tobytes())
        assert eng.metrics["mixed_steps"] - mixed_before >= 2 * 5
    finally:
        eng.stop()


def test_rollback_restores_the_recurrent_state(model):
    """A tick that faults AFTER its program ran (the 'sample' site) is rolled
    back and retried: the state it started from was kept by reference, so the
    retried tick gives the unfaulted run's tokens and counters."""
    hf, cfg, params = model
    prompts = [prompt(20, 19), prompt(21, 33)]
    clean = _engine(cfg, params).start()
    try:
        want, _ = _serve(clean, prompts)
        want_metrics = dict(clean.metrics)
    finally:
        clean.stop()
    inj = (FaultInjector().inject("sample", TransientFault, nth=1)
           .inject("decode-dispatch", TransientFault, nth=4))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_rows=2, max_seq_len=256, page_size=16, prefill_bucket=16,
        retry_backoff_s=0.0), fault_injector=inj).start()
    try:
        got, lps = _serve(eng, prompts)
        metrics = dict(eng.metrics)
    finally:
        eng.stop()
    assert inj.fired == 2 and metrics["retries"] == 2
    assert got == want
    for name in ("moe_pairs_routed_total", "moe_pairs_local_total",
                 "moe_rows_computed_total", "ssm_state_resets_total"):
        assert metrics[name] == want_metrics[name], name
    _held_to_reference(hf, params, prompts[1], got[1], lps[1])


def test_the_same_prompt_twice_prefills_twice(model):
    """The prefix cache is off for the model: no hit, no registration, the
    same tokens both times (the benchmark sends one probe twice)."""
    hf, cfg, params = model
    ids = prompt(30, 40)                     # two full pages and more
    eng = _engine(cfg, params).start()
    try:
        first, _ = _serve(eng, [ids])
        second, _ = _serve(eng, [ids])
        metrics, stats = dict(eng.metrics), eng.kv_stats()
    finally:
        eng.stop()
    assert first == second
    assert metrics["prefix_hits"] == 0 and stats["prefix_pages_cached"] == 0
    assert metrics["ssm_state_resets_total"] == 2
    assert metrics["mixed_prefill_tokens"] == 2 * len(ids)


@pytest.mark.parametrize("field,value", [
    ("kv_spill_bytes", 1 << 20), ("spec_k", 2)])
def test_what_recurrent_state_cannot_do_is_refused_by_name(model, field,
                                                            value):
    _, cfg, params = model
    with pytest.raises(ValueError, match=f"recurrent state.*{field}"):
        _engine(cfg, params, **{field: value})
