"""Drop-in ``AutoModelForCausalLM`` (the reference's compatibility contract).

Reference counterpart: transformers/model.py:111 ``from_pretrained`` with
``load_in_low_bit=...`` / ``load_in_4bit=True``, :532 ``load_low_bit``, :59
``save_low_bit``.  The reference wraps+patches a torch HF model; here the HF
checkpoint is only a *weight source* — tensors stream from safetensors shards
straight into quantized JAX arrays (never a full-precision model in memory,
the ``low_memory_init`` behaviour by construction) and run through the shared
scan-based decoder (models/decoder.py).

The returned ``TPUModelForCausalLM`` keeps the HF call shape users script
against: ``model.generate(input_ids, max_new_tokens=...)`` accepts torch /
numpy / list input and returns the same kind, and records
``first_cost`` / ``rest_cost_mean`` like the reference's BenchmarkWrapper
(utils/benchmark_util_*.py) so existing benchmark harnesses read timings the
same way.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.generation import GenerationConfig, generate
from ipex_llm_tpu.kv import make_cache, with_recurrent_state
from ipex_llm_tpu.models import serialize
from ipex_llm_tpu.models.build import build_params
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.models.decoder import decoder_forward
from ipex_llm_tpu.models.families import get_family
from ipex_llm_tpu.models.loader import CheckpointReader, read_config
from ipex_llm_tpu.quantize import qtypes


def _resolve_qtype(kwargs: dict) -> str:
    """Map the reference's loading kwargs to one qtype name (model.py:130-158)."""
    low_bit = kwargs.pop("load_in_low_bit", None)
    load_4bit = kwargs.pop("load_in_4bit", False)
    if low_bit is None:
        low_bit = "sym_int4" if load_4bit else "bf16"
    if not qtypes.is_supported(low_bit):
        raise ValueError(
            f"load_in_low_bit={low_bit!r} is not supported; "
            f"choose from {qtypes.all_qtypes()}"
        )
    return low_bit


class TPUModelForCausalLM:
    """A quantized causal LM bound to (config, param pytree)."""

    def __init__(self, cfg: ModelConfig, params: dict, hf_config: dict, qtype: str):
        self.config = cfg
        self.hf_config = hf_config
        self.params = params
        self.qtype = qtype
        self.mesh = None  # set by .shard(mesh) for SPMD inference
        # host-RAM [V, H] table for the streamed >HBM-vocab embedding
        # (from_pretrained(disk_embedding=True)); None = table in HBM
        self.streamed_embed = None
        # BenchmarkWrapper-compatible timing attributes
        self.first_cost: float | None = None
        self.rest_cost_mean: float | None = None
        self.generation_config = GenerationConfig(
            eos_token_id=self._eos_ids(hf_config),
            pad_token_id=hf_config.get("pad_token_id") or 0,
        )

    # -- loading ------------------------------------------------------------

    @classmethod
    def from_pretrained(cls, pretrained_model_name_or_path: str, *args, **kwargs):
        """Load + quantize an HF checkpoint directory.

        Supported kwargs (reference names): ``load_in_low_bit``,
        ``load_in_4bit``, ``mixed_precision``, ``optimize_model`` (accepted,
        always on — the optimized path is the only path here).
        """
        path = str(pretrained_model_name_or_path)
        if not os.path.isdir(path):
            raise ValueError(
                f"{path!r} is not a local directory; download the checkpoint "
                "first (hub download is not available in this environment)"
            )
        qtype = _resolve_qtype(kwargs)
        mixed_precision = kwargs.pop("mixed_precision", False)
        imatrix_file = kwargs.pop("imatrix", None)
        mesh = kwargs.pop("mesh", None)
        speculative = kwargs.pop("speculative", False)
        embedding_qtype = kwargs.pop("embedding_qtype", None)
        # reference embedding.py:58 CpuEmbedding: the TPU lever is HBM, so
        # cpu_embedding maps to the quantized-in-HBM table (in-jit row
        # dequant, no host sync)
        if kwargs.pop("cpu_embedding", False):
            embedding_qtype = embedding_qtype or "sym_int8"
        # reference embedding.py:96 DiskEmbedding: a vocab table too big
        # even for HBM stays in HOST RAM; generate gathers only the current
        # tokens' rows per step and ships [B,1,H] over PCIe (decode then
        # runs the python-driven loop — see generation._stream_decode)
        disk_embedding = kwargs.pop("disk_embedding", False)
        kwargs.pop("optimize_model", True)
        kwargs.pop("torch_dtype", None)
        kwargs.pop("trust_remote_code", None)
        # reference model.py: model_hub="modelscope" switches the download
        # hub; this environment is zero-egress so only local paths load —
        # the kwarg is accepted for script compatibility
        kwargs.pop("model_hub", None)

        hf_config = read_config(path)
        if hf_config.get("model_type") == "bert":
            # encoder-only embedding family (reference models/bert.py)
            from ipex_llm_tpu.models.bert import TPUBertModel

            if mesh is not None:
                raise NotImplementedError("bert SPMD sharding not supported")
            return TPUBertModel.from_pretrained(path, load_in_low_bit=qtype)
        if hf_config.get("model_type") in ("rwkv", "rwkv5"):
            # recurrent family: state instead of a KV cache (models/rwkv.py)
            from ipex_llm_tpu.models.rwkv import TPURwkvForCausalLM

            if mesh is not None:
                raise NotImplementedError("rwkv SPMD sharding not supported")
            return TPURwkvForCausalLM.from_pretrained(
                path, load_in_low_bit=qtype
            )
        if hf_config.get("model_type") in ("yuan", "baichuan_m1"):
            # conv-augmented attention families with rolling state beyond
            # the KV cache (models/convattn.py; reference models/yuan.py,
            # models/baichuan_m1.py)
            from ipex_llm_tpu.models.convattn import (
                TPUBaichuanM1ForCausalLM,
                TPUYuanForCausalLM,
            )

            if mesh is not None:
                raise NotImplementedError(
                    "yuan/baichuan_m1 SPMD sharding not supported")
            cls2 = (TPUYuanForCausalLM
                    if hf_config["model_type"] == "yuan"
                    else TPUBaichuanM1ForCausalLM)
            return cls2.from_pretrained(path, load_in_low_bit=qtype)
        family = get_family(hf_config.get("model_type", "llama"), hf_config)
        cfg = family.to_config(hf_config)
        reader = CheckpointReader(path)
        qc = hf_config.get("quantization_config")
        if qc and qc.get("quant_method") in ("gptq", "awq"):
            # GPTQ/AWQ interop (reference model.py:251-295): dequantize the
            # packed checkpoint on read, requantize into QTensors
            from ipex_llm_tpu.transformers.quant_import import (
                QuantizedCheckpointAdapter,
            )

            reader = QuantizedCheckpointAdapter(reader, qc)
            if qtype == "bf16":  # keep a 4-bit checkpoint 4-bit by default
                qtype = "asym_int4"
        imatrix_data = None
        if imatrix_file is not None:
            # reference model.py:333: imatrix file from llama.cpp's tool
            from ipex_llm_tpu.quantize.imatrix import load_imatrix

            imatrix_data = (imatrix_file if isinstance(imatrix_file, dict)
                            else load_imatrix(imatrix_file))
        params = build_params(
            cfg, family.scheme, reader.get, reader.has,
            qtype=qtype, mixed_precision=mixed_precision,
            moe_scheme=family.moe, embedding_qtype=embedding_qtype,
            qkv_transform=family.qkv_transform,
            transpose_weights=family.transpose_weights,
            imatrix_data=imatrix_data,
        )
        model = cls(cfg, params, hf_config, qtype)
        if disk_embedding:
            if "lm_head" not in params:
                raise NotImplementedError(
                    "disk_embedding needs an untied lm_head (tied logits "
                    "read the embed table on-device every step)")
            import numpy as np

            from ipex_llm_tpu.quantize.core import QTensor
            from ipex_llm_tpu.quantize import dequantize

            emb = params.pop("embed")
            model.streamed_embed = np.asarray(
                dequantize(emb) if isinstance(emb, QTensor)
                else emb, np.float32)
        if speculative:
            # reference model.py:366-376: draft = sym_int4 copy of the same
            # checkpoint (no separate draft weights)
            canonical = qtypes.resolve(qtype).name
            if canonical in ("sym_int4", "asym_int4", "nf4", "fp4"):
                model.draft_model = model
            else:
                draft_params = build_params(
                    cfg, family.scheme, reader.get, reader.has,
                    qtype="sym_int4", moe_scheme=family.moe,
                    qkv_transform=family.qkv_transform,
                    transpose_weights=family.transpose_weights,
                )
                model.draft_model = cls(cfg, draft_params, hf_config, "sym_int4")
        if mesh is not None:
            model.shard(mesh)
        return model

    def shard(self, mesh) -> "TPUModelForCausalLM":
        """Place the params onto a ``jax.sharding.Mesh`` under the TP rules.

        The AutoTP equivalent (reference convert.py:217-228 +
        low_bit_linear.py:715-722): column/row-parallel NamedShardings per
        projection; XLA inserts the psum over ICI during compilation.
        """
        from ipex_llm_tpu.parallel.shard import shard_params

        self.params = shard_params(self.params, mesh)
        self.mesh = mesh
        draft = getattr(self, "draft_model", None)
        if draft is not None and draft is not self and draft.mesh is not mesh:
            draft.shard(mesh)
        return self

    @classmethod
    def from_gguf(cls, fpath: str, optimize_model: bool = True,
                  cpu_embedding: bool = False, low_bit: str | None = None):
        """Load a .gguf file directly (reference model.py:391, gguf/api.py:31).

        Weights keep their ggml block formats (k-quants decode in-jit); the
        reference instead dequantizes k-quants to fp16/fp32 on CPU.
        """
        from ipex_llm_tpu.gguf import load_gguf_model
        from ipex_llm_tpu.gguf.api import is_yuan_gguf, load_gguf_yuan

        if is_yuan_gguf(fpath):
            # yuan-2 rides arch "llama" but needs the convattn decoder
            # (reference gguf/api.py:54 -> gguf/models/yuan2.py)
            from ipex_llm_tpu.models.convattn import TPUYuanForCausalLM

            ycfg, yparams, yhf = load_gguf_yuan(fpath)
            model = TPUYuanForCausalLM(ycfg, yparams, yhf, "gguf")
        else:
            cfg, params, hf_config = load_gguf_model(fpath)
            model = cls(cfg, params, hf_config, qtype="gguf")
        # the reference returns (model, tokenizer); a GGUF-embedded
        # tokenizer needs no files on disk when transformers has gguf support
        tokenizer = None
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(
                os.path.dirname(fpath) or ".",
                gguf_file=os.path.basename(fpath),
            )
        except Exception:
            pass
        return model, tokenizer

    @classmethod
    def load_low_bit(cls, path: str, *args, mesh=None, **kwargs):
        """Reload a ``save_low_bit`` checkpoint (reference model.py:532).

        ``mesh`` shards the reloaded params under the TP rules, matching the
        ``from_pretrained(..., mesh=...)`` path."""
        params, hf_config, qtype = serialize.load_low_bit(path)
        family = get_family(hf_config.get("model_type", "llama"), hf_config)
        cfg = family.to_config(hf_config)
        model = cls(cfg, params, hf_config, qtype)
        if mesh is not None:
            model.shard(mesh)
        return model

    def save_low_bit(self, path: str) -> None:
        serialize.save_low_bit(path, self.params, self.hf_config, self.qtype)

    # -- inference ----------------------------------------------------------

    def _eos_ids(self, hf_config: dict) -> tuple[int, ...]:
        eos = hf_config.get("eos_token_id")
        if eos is None:
            return ()
        if isinstance(eos, int):
            return (eos,)
        return tuple(eos)

    def __call__(self, input_ids: Any, **kwargs) -> jnp.ndarray:
        """Full-sequence forward, returns logits [B, T, V] (for eval/tests)."""
        tokens = np.asarray(_to_numpy(input_ids), np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        b, t = tokens.shape
        cache = with_recurrent_state(make_cache(
            "normal", self.config.kv_layers, b, max(t, 1),
            self.config.num_kv_heads, self.config.head_dim,
            v_head_dim=self.config.v_dim,
        ), self.config, b)
        pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        tokens_j = jnp.asarray(tokens)
        from ipex_llm_tpu.ops import dispatch as _dispatch

        with _dispatch.spmd(
            self.mesh if self.mesh is not None and self.mesh.size > 1 else None
        ):
            if self.mesh is not None:
                from ipex_llm_tpu.parallel.shard import shard_batch, shard_cache

                cache = shard_cache(cache, self.mesh)
                (tokens_j,) = shard_batch(self.mesh, b, tokens_j)
            emb = None
            if self.streamed_embed is not None:
                emb = jnp.asarray(self.streamed_embed[tokens], jnp.float32)
            logits, _ = decoder_forward(
                self.config, self.params, tokens_j, cache, pos,
                input_embeds=emb,
            )
        return logits

    def generate(
        self,
        input_ids: Any = None,
        attention_mask: Any = None,
        streamer: Any = None,
        generation_config: GenerationConfig | None = None,
        **kwargs,
    ):
        """HF-shaped generate; returns prompt+new tokens, same type as input."""
        was_torch = _is_torch(input_ids)
        tokens = np.asarray(_to_numpy(input_ids), np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        if attention_mask is not None:
            am = np.asarray(_to_numpy(attention_mask))
            rows = [tokens[i][am[i].astype(bool)] for i in range(len(tokens))]
        else:
            rows = list(tokens)

        gcfg = (generation_config or self.generation_config).with_kwargs(kwargs)

        # reference lookup.py:63-83: IPEX_LLM_PERFORMANCE_MODE=1 switches
        # long greedy prompts to prompt-lookup decoding automatically.
        # Pass the MASK-FILTERED row (pad tokens must not enter the ngram
        # table) and the merged generation config (custom eos/penalties
        # survive); _spec_generate re-wraps torch outputs itself.
        if (os.environ.get("IPEX_LLM_PERFORMANCE_MODE") == "1"
                and self.streamed_embed is None
                and len(rows) == 1 and len(rows[0]) >= 512
                and streamer is None and not gcfg.do_sample
                and self.mesh is None):
            row = rows[0]
            if was_torch:
                import torch

                row = torch.from_numpy(np.ascontiguousarray(row)).long()
            return self.lookup_generate(row, generation_config=gcfg)

        stream_cb = None
        if streamer is not None:
            def stream_cb(row):  # HF TextStreamer protocol: put(token_ids)
                streamer.put(np.asarray(row))

        res = generate(
            self.config, self.params, rows, gcfg, streamer=stream_cb,
            mesh=self.mesh, host_embed=self.streamed_embed,
        )
        if streamer is not None and hasattr(streamer, "end"):
            streamer.end()
        self.first_cost = res.first_token_s
        self.rest_cost_mean = res.rest_token_s
        out = res.sequences
        if was_torch:
            import torch

            return torch.from_numpy(np.ascontiguousarray(out)).long()
        return out

    def speculative_generate(
        self,
        input_ids: Any = None,
        draft_model: "TPUModelForCausalLM | None" = None,
        max_step_draft: int = 6,
        **kwargs,
    ):
        """Self-speculative greedy decoding (reference speculative.py:805).

        ``draft_model`` defaults to this model's own weights — load with
        ``from_pretrained(..., speculative=True)`` to attach a sym_int4
        draft of the same checkpoint like the reference (model.py:366-376).
        """
        return self._spec_generate(input_ids, draft_model, max_step_draft,
                                   False, 0, kwargs)

    def lookup_generate(self, input_ids: Any = None, max_matching_ngram_size:
                        int = 2, num_output_tokens: int = 6, **kwargs):
        """Prompt-lookup decoding (reference lookup.py:274)."""
        return self._spec_generate(input_ids, None, num_output_tokens,
                                   True, max_matching_ngram_size, kwargs)

    def _spec_generate(self, input_ids, draft_model, k, lookup, ngram, kwargs):
        from ipex_llm_tpu.speculative import speculative_generate as _spec

        if self.streamed_embed is not None:
            # the speculative driver's jitted draft/verify loops cannot
            # host-gather the streamed table per token
            raise NotImplementedError(
                "disk_embedding models support plain generate() only")

        was_torch = _is_torch(input_ids)
        tokens = np.asarray(_to_numpy(input_ids), np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        gcfg = kwargs.pop("generation_config", None) or self.generation_config
        gcfg = gcfg.with_kwargs(kwargs)
        draft = draft_model if draft_model is not None else getattr(
            self, "draft_model", None
        )
        res = _spec(
            self.config, self.params, list(tokens), gcfg,
            draft_params=None if draft is None else draft.params,
            draft_cfg=None if draft is None else draft.config,
            max_step_draft=k, lookup=lookup,
            ngram_size=ngram or 2,
            mesh=self.mesh,
        )
        self.first_cost = res.first_token_s
        self.rest_cost_mean = res.rest_token_s
        self.n_matched = getattr(res, "n_matched", 0)
        self.n_drafted = getattr(res, "n_drafted", 0)
        self.last_result = res
        out = res.sequences
        if was_torch:
            import torch

            return torch.from_numpy(np.ascontiguousarray(out)).long()
        return out

    # convenience parity helpers
    @property
    def device(self) -> str:
        return str(jax.devices()[0])

    def to(self, *_args, **_kw):  # .to('xpu') in reference scripts — no-op
        return self

    def eval(self):
        return self

    def half(self):
        return self


def _is_torch(x) -> bool:
    return type(x).__module__.startswith("torch")


def _to_numpy(x):
    if x is None:
        raise ValueError("input_ids is required")
    if _is_torch(x):
        return x.detach().cpu().numpy()
    return x


class _NotYetSupported:
    """Loader stub for reference Auto* classes whose decoders haven't landed.

    The reference exposes 10 Auto* classes (model.py:791-827).  Aliasing the
    seq2seq/vision ones to the causal LM would silently mis-load whisper-class
    checkpoints, so they fail loudly instead.
    """

    _kind = "this model class"

    @classmethod
    def from_pretrained(cls, *args, **kwargs):
        raise NotImplementedError(
            f"{cls.__name__} is not supported yet by ipex_llm_tpu; "
            "only decoder-only causal LMs load today"
        )

    load_low_bit = from_pretrained


class AutoModelForSpeechSeq2Seq:
    """Speech seq2seq loader (whisper; reference model.py:803)."""

    @classmethod
    def from_pretrained(cls, path: str, *args, **kwargs):
        from ipex_llm_tpu.models.whisper import (
            TPUWhisperForConditionalGeneration,
        )

        return TPUWhisperForConditionalGeneration.from_pretrained(
            str(path), **kwargs
        )


class AutoModelForSequenceClassification:
    """Encoder classifier / reranker loader (reference model.py Auto list).

    Dispatches bert-style checkpoints to the TPU encoder + classifier head;
    other architectures fail loudly."""

    @classmethod
    def from_pretrained(cls, path: str, **kwargs):
        hf = read_config(str(path))
        if hf.get("model_type") == "bert":
            from ipex_llm_tpu.models.bert import (
                TPUBertForSequenceClassification,
            )

            qtype = _resolve_qtype(kwargs)
            return TPUBertForSequenceClassification.from_pretrained(
                str(path), load_in_low_bit=qtype)
        raise NotImplementedError(
            f"AutoModelForSequenceClassification supports bert-style "
            f"encoders; got {hf.get('model_type')!r}"
        )


class AutoModelForMaskedLM:
    """Encoder MLM loader (reference model.py Auto list)."""

    @classmethod
    def from_pretrained(cls, path: str, **kwargs):
        hf = read_config(str(path))
        if hf.get("model_type") == "bert":
            from ipex_llm_tpu.models.bert import TPUBertForMaskedLM

            qtype = _resolve_qtype(kwargs)
            return TPUBertForMaskedLM.from_pretrained(
                str(path), load_in_low_bit=qtype)
        raise NotImplementedError(
            f"AutoModelForMaskedLM supports bert-style encoders; got "
            f"{hf.get('model_type')!r}"
        )


class AutoModelForSeq2SeqLM:
    """Seq2seq loader: whisper checkpoints route to the encoder-decoder
    module; other seq2seq architectures (t5/bart) fail loudly."""

    @classmethod
    def from_pretrained(cls, path: str, *args, **kwargs):
        hf = read_config(str(path))
        if hf.get("model_type") == "whisper":
            from ipex_llm_tpu.models.whisper import (
                TPUWhisperForConditionalGeneration,
            )

            return TPUWhisperForConditionalGeneration.from_pretrained(
                str(path), **kwargs
            )
        raise NotImplementedError(
            f"AutoModelForSeq2SeqLM supports whisper; got "
            f"{hf.get('model_type')!r} (t5/bart-style encoders-decoders "
            "are not implemented)"
        )


AutoModelForCausalLM = TPUModelForCausalLM
AutoModel = TPUModelForCausalLM
