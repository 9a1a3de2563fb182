"""Generation engine: bucketed prefill + fully-jitted decode loop.

Reference counterpart: the patched ``GenerationMixin.generate`` stack
(SURVEY.md §3.2) where Python drives the model token-by-token and every step
is a separate kernel dispatch.  TPU-first design instead:

- **prefill** pads the prompt batch into a length bucket (multiples of
  ``BUCKET``) and runs one jitted forward; left-padding + ``kv_start`` masks
  keep shapes static across ragged prompts (SURVEY.md §7 hard part (b));
- **decode** is ONE jitted ``lax.while_loop`` that samples, appends to the KV
  cache, and early-exits when every sequence hit EOS — zero host round-trips
  until the whole generation finishes;
- a **streaming** variant jits a single step and drives it from Python when
  the caller needs tokens as they arrive (serving), trading a host sync per
  token for latency visibility.

Re-jit happens only when the (prompt bucket, capacity) pair changes, the
moral equivalent of the reference re-allocating KV blocks of
KV_ALLOC_BLOCK_LENGTH=256 (models/utils.py:39-75).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu import kv as kv_mod
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.models.decoder import decoder_forward
from ipex_llm_tpu.ops.sampling import SamplingParams, sample

BUCKET = 128          # prompt-length bucket granularity
DECODE_BLOCK = 256    # KV capacity granularity (reference KV_ALLOC_BLOCK_LENGTH)
REP_WINDOW = 512      # repetition-penalty lookback ring size


@dataclass(frozen=True)
class GenerationConfig:
    """HF-compatible knobs (the subset the reference's benchmarks exercise)."""

    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: tuple[int, ...] = ()
    pad_token_id: int = 0
    seed: int = 0

    def sampling(self) -> SamplingParams:
        return SamplingParams(
            temperature=self.temperature,
            top_k=self.top_k,
            top_p=self.top_p,
            repetition_penalty=self.repetition_penalty,
            do_sample=self.do_sample,
        )

    def with_kwargs(self, kwargs: dict) -> "GenerationConfig":
        """Pop HF-style generate kwargs into a new config (int eos coerced)."""
        from dataclasses import replace as _replace

        fields = {
            k: kwargs.pop(k)
            for k in list(kwargs)
            if k in GenerationConfig.__dataclass_fields__
        }
        if isinstance(fields.get("eos_token_id"), int):
            fields["eos_token_id"] = (fields["eos_token_id"],)
        return _replace(self, **fields) if fields else self


@dataclass
class GenerateResult:
    sequences: np.ndarray          # [B, prompt+new] right-trimmed at pad
    num_prompt_tokens: int
    num_new_tokens: np.ndarray     # [B]
    first_token_s: float = 0.0     # TTFT (prefill + first sample)
    rest_token_s: float = 0.0      # mean per-token latency after the first
    # speculative-decoding acceptance telemetry (reference clear_benchmarks)
    n_rounds: int = 0
    n_drafted: int = 0
    n_matched: int = 0
    th_stop_draft: float = 0.0     # final auto-tuned draft-stop threshold


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def pad_batch(
    input_ids: Any, pad_id: int, bucket: int = BUCKET
) -> tuple[np.ndarray, np.ndarray, int]:
    """Left-pad a ragged (or rectangular) batch into a bucketed array.

    Returns (tokens [B, Tpad], lengths [B], Tpad).
    """
    if isinstance(input_ids, np.ndarray) and input_ids.ndim == 2:
        rows = list(input_ids)
    elif hasattr(input_ids, "tolist") and getattr(input_ids, "ndim", 1) == 2:
        rows = [np.asarray(r) for r in np.asarray(input_ids)]
    else:
        rows = [np.asarray(r).reshape(-1) for r in input_ids]
    lens = np.array([len(r) for r in rows], dtype=np.int32)
    tpad = _round_up(max(int(lens.max()), 1), bucket)
    out = np.full((len(rows), tpad), pad_id, dtype=np.int32)
    for i, r in enumerate(rows):
        out[i, tpad - len(r):] = r
    return out, lens, tpad


# ---------------------------------------------------------------------------
# jitted stages
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill_step(
    cfg: ModelConfig,
    params: dict,
    cache,
    tokens: jnp.ndarray,      # [B, Tpad] left-padded
    lengths: jnp.ndarray,     # [B]
    input_embeds: jnp.ndarray | None = None,  # streamed-embedding path
):
    """Run the prompt through the decoder; returns (last_logits [B,V], cache)."""
    b, tpad = tokens.shape
    kv_start = (tpad - lengths).astype(jnp.int32)
    # logical positions: 0..len-1 right-aligned, clipped at 0 in the pad zone
    pos = jnp.maximum(jnp.arange(tpad)[None, :] - kv_start[:, None], 0)
    pos = _glm2d_positions(cfg, pos, lengths)
    logits, cache = decoder_forward(
        cfg, params, tokens, cache, pos, kv_start=kv_start,
        last_token_only=True, input_embeds=input_embeds,
    )
    return logits, cache


def _glm2d_positions(cfg: ModelConfig, pos: jnp.ndarray,
                     lengths: jnp.ndarray) -> jnp.ndarray:
    """chatglm v1 2D position ids from running indices ``pos`` [B, T].

    The prompt convention is [...tokens, gMASK, sop]: tokens before sop
    (index len-1) take sequence positions 0..len-2 with block 0; sop and
    every generated token stay at the gMASK position (len-2) while the
    block channel counts 1, 2, ... (reference chatglm.py 2D rotary;
    THUDM get_position_ids semantics).  Returns [B, 2, T] (or ``pos``
    unchanged for non-2D models).
    """
    if not cfg.rope_2d:
        return pos
    bnd = jnp.maximum(lengths - 1, 1).astype(jnp.int32)[:, None]  # sop index
    return jnp.stack([jnp.minimum(pos, bnd - 1),
                      jnp.maximum(pos - bnd + 1, 0)], axis=1)


@partial(jax.jit, static_argnames=("cfg", "obs"), donate_argnums=(2,))
def prefill_collect_step(cfg: ModelConfig, params: dict, cache, tokens,
                         lengths, obs: int):
    """Prefill that also returns the SnapKV observation-window queries."""
    b, tpad = tokens.shape
    kv_start = (tpad - lengths).astype(jnp.int32)
    pos = jnp.maximum(jnp.arange(tpad)[None, :] - kv_start[:, None], 0)
    pos = _glm2d_positions(cfg, pos, lengths)
    logits, cache, obs_q = decoder_forward(
        cfg, params, tokens, cache, pos, kv_start=kv_start,
        last_token_only=True, collect_obs=obs,
    )
    return logits, cache, obs_q


@partial(
    jax.jit,
    static_argnames=("cfg", "gen", "max_steps"),
    donate_argnums=(2,),
)
def decode_loop(
    cfg: ModelConfig,
    params: dict,
    cache,
    first_tokens: jnp.ndarray,   # [B] token sampled from prefill
    lengths: jnp.ndarray,        # [B] prompt lengths
    kv_start: jnp.ndarray,       # [B]
    prev_ring: jnp.ndarray,      # [B, REP_WINDOW] int32 (-1 pad) rep-penalty ring
    key: jax.Array,
    gen: GenerationConfig,
    max_steps: int,
):
    """Whole decode loop in one XLA program with EOS early-exit.

    Returns (tokens [B, max_steps], n_done_steps, cache).
    """
    b = first_tokens.shape[0]
    sp = gen.sampling()
    eos = jnp.asarray(gen.eos_token_id, jnp.int32) if gen.eos_token_id else None

    out_buf = jnp.full((b, max_steps), gen.pad_token_id, jnp.int32)
    out_buf = out_buf.at[:, 0].set(first_tokens)
    done0 = jnp.zeros((b,), bool)
    if eos is not None:
        done0 = (first_tokens[:, None] == eos[None, :]).any(axis=1)

    def cond(state):
        step, _, _, _, done, _, _ = state
        return (step < max_steps) & ~done.all()

    def body(state):
        step, tok, cache, key, done, prev, out = state
        pos = lengths + step - 1            # logical position of `tok`
        logits, cache = decoder_forward(
            cfg, params, tok[:, None], cache,
            _glm2d_positions(cfg, pos[:, None], lengths),
            kv_start=kv_start, last_token_only=True,
        )
        key, sub = jax.random.split(key)
        nxt = sample(logits, sub, sp, prev if sp.repetition_penalty != 1.0 else None)
        nxt = jnp.where(done, gen.pad_token_id, nxt)
        if eos is not None:
            done = done | (nxt[:, None] == eos[None, :]).any(axis=1)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, step))
        # per-row ring write (rows have ragged lengths; a shared index would
        # corrupt the ring for every row but the first)
        prev = prev.at[jnp.arange(b), (lengths + step) % REP_WINDOW].set(nxt)
        return step + 1, nxt, cache, key, done, prev, out

    state = (jnp.asarray(1, jnp.int32), first_tokens, cache, key, done0,
             prev_ring, out_buf)
    step, _, cache, _, done, _, out = jax.lax.while_loop(cond, body, state)
    return out, step, cache


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _init_prev_ring(tokens: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Seed the repetition-penalty ring with the prompt tail.

    Slot convention: the token at absolute position ``p`` lives at ring index
    ``p % REP_WINDOW`` — the same convention the decode loops use for writes,
    so generation keeps evicting the *oldest* token even when the prompt is
    longer than the window.
    """
    b, tpad = tokens.shape
    ring = np.full((b, REP_WINDOW), -1, dtype=np.int32)
    for i in range(b):
        length = int(lengths[i])
        row = tokens[i, tpad - length:]
        for p in range(max(0, length - REP_WINDOW), length):
            ring[i, p % REP_WINDOW] = row[p]
    return ring


def generate(
    cfg: ModelConfig,
    params: dict,
    input_ids: Any,
    generation_config: GenerationConfig,
    kv_kind: str = "auto",
    streamer: Callable[[np.ndarray], None] | None = None,
    mesh=None,
    host_embed: np.ndarray | None = None,
) -> GenerateResult:
    """End-to-end generate.  ``input_ids``: list of token lists or [B, T] array.

    When ``streamer`` is given, decode runs step-by-step from Python (one host
    sync per token) and the callback receives each new token row [B].

    When ``mesh`` is given (a ``jax.sharding.Mesh``, params already placed by
    ``parallel.shard.shard_params``), the KV cache and batch arrays are placed
    with matching NamedShardings and the whole loop runs SPMD — XLA inserts
    the TP psums over ICI (the AutoTP ``inference_all_reduce`` equivalent,
    reference low_bit_linear.py:715-722) with no collective in model code.
    """
    gen = generation_config
    tokens, lengths, tpad = pad_batch(input_ids, gen.pad_token_id)
    b = tokens.shape[0]

    if cfg.recurrent_layers and kv_kind in ("auto", "compress"):
        # SnapKV compression renumbers slots; recurrent state has none
        kv_kind = "fp8" if kv_mod.use_quantize_kv_cache() else "normal"
    if host_embed is not None and kv_kind == "auto":
        # SnapKV's prefill_collect path has no input_embeds form; the
        # streamed-embedding user trades that optimization away
        kv_kind = "normal"

    compress = kv_kind == "compress"
    if compress:
        from ipex_llm_tpu import compresskv

        # Compression keeps capacity+window slots per row; a prompt that
        # short would gather masked pad slots into the compressed cache and
        # then attend garbage after renumbering.  Fall back to the normal
        # cache for those rows' batch (mirrors the auto-path gate).
        if int(lengths.min()) <= compresskv.capacity() + compresskv.window():
            import warnings

            warnings.warn(
                "kv_kind='compress' needs every prompt longer than "
                f"capacity+window ({compresskv.capacity()}+{compresskv.window()}); "
                "falling back to the normal KV cache", stacklevel=2,
            )
            compress, kv_kind = False, "normal"
    if kv_kind == "auto":
        from ipex_llm_tpu import compresskv

        if (
            compresskv.use_compress_kv(int(lengths.min()))
            and cfg.sliding_window is None
        ):
            compress, kv_kind = True, "compress"
        else:
            kv_kind = "fp8" if kv_mod.use_quantize_kv_cache() else "normal"
    if compress:
        # prefill-only cache; decode runs in the compressed cache
        capacity = tpad
        cache = kv_mod.make_cache(
            "normal", cfg.num_layers, b, capacity, cfg.num_kv_heads,
            cfg.head_dim, v_head_dim=cfg.v_dim,
        )
    else:
        capacity = tpad + _round_up(gen.max_new_tokens + 1, DECODE_BLOCK)
        cache = kv_mod.make_cache(
            kv_kind, cfg.kv_layers, b, capacity, cfg.num_kv_heads,
            cfg.head_dim, v_head_dim=cfg.v_dim,
        )
        # a hybrid model's recurrent state rides the same cache object
        cache = kv_mod.with_recurrent_state(cache, cfg, b)

    from ipex_llm_tpu.ops import dispatch as _dispatch

    with _dispatch.spmd(mesh if mesh is not None and mesh.size > 1 else None):
        return _generate_inner(
            cfg, params, gen, tokens, lengths, tpad, b, cache, mesh, streamer,
            compress, host_embed,
        )


def _generate_inner(cfg, params, gen, tokens, lengths, tpad, b, cache, mesh,
                    streamer, compress=False, host_embed=None):
    tokens_j = jnp.asarray(tokens)
    lengths_j = jnp.asarray(lengths)
    if mesh is not None:
        from ipex_llm_tpu.parallel import shard as shard_mod

        cache = shard_mod.shard_cache(cache, mesh)
        tokens_j, lengths_j = shard_mod.shard_batch(mesh, b, tokens_j, lengths_j)

    t0 = time.perf_counter()
    if compress:
        from ipex_llm_tpu import compresskv

        w, cap = compresskv.window(), compresskv.capacity()
        logits, cache, obs_q = prefill_collect_step(
            cfg, params, cache, tokens_j, lengths_j, w
        )
        new_total = cap + w + _round_up(gen.max_new_tokens + 1, DECODE_BLOCK)
        cache = compresskv.compress(
            cache, obs_q, jnp.asarray((tpad - lengths).astype(np.int32)),
            cap, w, new_total,
        )
    else:
        pre_emb = None
        if host_embed is not None:
            # host gather of the whole padded prompt (one transfer; the
            # table itself never leaves host RAM)
            pre_emb = jnp.asarray(host_embed[tokens], jnp.float32)
        logits, cache = prefill_step(cfg, params, cache, tokens_j, lengths_j,
                                     input_embeds=pre_emb)
    key = jax.random.PRNGKey(gen.seed)
    key, sub = jax.random.split(key)
    prev_ring = jnp.asarray(_init_prev_ring(tokens, lengths))
    first = sample(
        logits, sub, gen.sampling(),
        prev_ring if gen.repetition_penalty != 1.0 else None,
    )
    first.block_until_ready()
    ttft = time.perf_counter() - t0
    # the first sampled token joins the penalty window immediately
    prev_ring = prev_ring.at[jnp.arange(b), lengths_j % REP_WINDOW].set(first)

    if compress:
        # compression gathers only valid slots and renumbers them from 0
        kv_start = jnp.zeros((b,), jnp.int32)
    else:
        kv_start = jnp.asarray((tpad - lengths).astype(np.int32))
    if mesh is not None:
        from ipex_llm_tpu.parallel import shard as shard_mod

        kv_start, prev_ring, first = shard_mod.shard_batch(
            mesh, b, kv_start, prev_ring, first
        )
    t1 = time.perf_counter()
    if streamer is None and host_embed is None:
        out, steps, cache = decode_loop(
            cfg, params, cache, first, lengths_j, kv_start, prev_ring, key,
            gen, gen.max_new_tokens,
        )
        out = np.asarray(out)
        steps = int(steps)
    else:
        # streaming callback or streamed host embedding: decode runs
        # step-by-step from Python (the host gather cannot live inside a
        # jitted while_loop)
        out, steps = _stream_decode(
            cfg, params, cache, first, lengths_j, kv_start, prev_ring, key,
            gen, streamer, host_embed=host_embed,
        )
    dt = time.perf_counter() - t1

    eos_set = set(gen.eos_token_id)
    new_counts = np.zeros((b,), np.int32)
    for i in range(b):
        n = 0
        for t in out[i, :steps]:
            n += 1
            if int(t) in eos_set:
                break
        new_counts[i] = n
    seqs = np.concatenate([tokens[:, tpad - lengths.max():], out[:, :steps]], axis=1)
    return GenerateResult(
        sequences=seqs,
        num_prompt_tokens=int(lengths.max()),
        num_new_tokens=new_counts,
        first_token_s=ttft,
        rest_token_s=dt / max(steps - 1, 1),
    )


# prev (the repetition-penalty ring) is dead after the call — the caller
# rebinds it to the returned ring every step — and matches the ring output
# aval exactly, so donating it aliases the buffers instead of copying
# [B, REP_WINDOW] per token (trace audit JP101 on generation.decode_one)
@partial(jax.jit, static_argnames=("cfg", "gen"), donate_argnums=(2, 6))
def _decode_one(cfg, params, cache, tok, pos, kv_start, prev, ring_idx, key,
                gen: GenerationConfig, lengths=None, input_embeds=None):
    logits, cache = decoder_forward(
        cfg, params, tok[:, None], cache,
        pos[:, None] if lengths is None
        else _glm2d_positions(cfg, pos[:, None], lengths),
        kv_start=kv_start, last_token_only=True, input_embeds=input_embeds,
    )
    key, sub = jax.random.split(key)
    sp = gen.sampling()
    nxt = sample(logits, sub, sp, prev if sp.repetition_penalty != 1.0 else None)
    prev = prev.at[jnp.arange(nxt.shape[0]), ring_idx].set(nxt)
    return nxt, cache, key, prev


def _stream_decode(cfg, params, cache, first, lengths, kv_start, prev_ring,
                   key, gen: GenerationConfig, streamer, host_embed=None):
    """Python-driven decode loop: one host sync per token.  Used for token
    streaming AND for the streamed >HBM-vocab embedding (reference
    embedding.py:96 DiskEmbedding) — ``host_embed`` [V, H] lives in host
    RAM (or a memmap); each step gathers only the current tokens' rows and
    ships [B, 1, H] to the device, never the table."""
    b = first.shape[0]
    eos_set = set(gen.eos_token_id)
    out = np.full((b, gen.max_new_tokens), gen.pad_token_id, np.int32)
    out[:, 0] = np.asarray(first)
    if streamer is not None:
        streamer(out[:, 0])
    done = np.array([int(t) in eos_set for t in out[:, 0]])
    tok = first
    step = 1
    while step < gen.max_new_tokens and not done.all():
        pos = lengths + step - 1
        emb = None
        if host_embed is not None:
            emb = jnp.asarray(
                host_embed[np.asarray(tok)][:, None, :], jnp.float32)
        tok, cache, key, prev_ring = _decode_one(
            cfg, params, cache, tok, pos, kv_start, prev_ring,
            (lengths + step) % REP_WINDOW, key, gen,
            lengths=lengths if cfg.rope_2d else None,
            input_embeds=emb,
        )
        row = np.asarray(tok)
        row = np.where(done, gen.pad_token_id, row)
        out[:, step] = row
        if streamer is not None:
            streamer(row)
        done |= np.isin(row, list(eos_set)) if eos_set else False
        tok = jnp.asarray(row)
        step += 1
    return out, step
