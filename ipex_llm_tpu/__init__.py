"""ipex_llm_tpu — a TPU-native LLM acceleration framework.

Capability peer of the reference `ipex-llm` (Intel's low-bit LLM library for
XPU/NPU/CPU, see /root/reference/python/llm/src/ipex_llm/__init__.py), rebuilt
from scratch and idiomatically for TPU on JAX/XLA/Pallas:

- block-quantized weights (INT4/INT5/INT8/NF4/NF3/FP4/FP6/FP8, GGUF k-quants)
  stored as packed arrays in a JAX pytree (``QTensor``), instead of the
  reference's ggml C blobs (reference: ggml/quantize.py, low_bit_linear.py);
- a Pallas kernel library for the hot ops (fused dequant-matmul, flash SDPA
  with fp8 KV, fused RoPE, RMS/LayerNorm, MoE routing) replacing the SYCL
  ``xe_linear``/``xe_batch``/``xe_addons`` extensions (reference §2.3);
- native JAX model definitions driven by HF checkpoints as a *weight source*
  rather than monkey-patched torch forwards (reference: transformers/convert.py);
- mesh-based tensor/pipeline/expert/context parallelism over ICI/DCN through
  ``jax.sharding`` (replacing DeepSpeed-AutoTP + oneCCL, reference §2.2).

Public API mirrors the reference's compatibility contract:

    from ipex_llm_tpu import optimize_model
    from ipex_llm_tpu.transformers import AutoModelForCausalLM
    model = AutoModelForCausalLM.from_pretrained(path, load_in_low_bit="sym_int4")
    out = model.generate(input_ids, max_new_tokens=32)
"""

import os

__version__ = "0.1.0"

__all__ = ["optimize_model", "load_low_bit", "low_memory_init",
           "llm_patch", "llm_unpatch", "__version__"]


# The one place jax's persistent compile cache is pointed anywhere: a fixed
# directory of the checkout (listed in .gitignore), so every process of a
# command shares it and its path, which is part of the cache key, never moves.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def compile_cache_dir(environ=os.environ) -> str | None:
    """The directory this package points jax's persistent compile cache at,
    or None where it sets none.

    - ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from outside;
      jax reads the variable itself and code sets nothing.
    - a process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, a CPU
      rehearsal, CPU replicas): no cache.  The checkout is copied to other
      hosts with whatever it holds, and XLA:CPU entries compiled for one
      host's CPU features are not safe to load on another.
    - otherwise: the fixed in-checkout directory.
    """
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        return None
    return _CHECKOUT_CACHE_DIR


def _init_compilation_cache() -> None:
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)


_init_compilation_cache()


def __getattr__(name):
    # lazy: keep `import ipex_llm_tpu` light (no jax trace-time cost) the way
    # the reference keeps its top-level import side-effect free apart from the
    # IPEX auto-import shim (reference: __init__.py:33-47).
    if name in ("optimize_model", "load_low_bit", "low_memory_init"):
        from ipex_llm_tpu import optimize

        return getattr(optimize, name)
    if name in ("llm_patch", "llm_unpatch"):
        from ipex_llm_tpu import llm_patching

        return getattr(llm_patching, name)
    raise AttributeError(name)
