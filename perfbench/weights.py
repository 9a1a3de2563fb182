"""The model of a run: random weights from ``--seed``, made ON THE DEVICE in
jitted calls, already in the type they are served in.

The program's own ``random_params`` draws float32 normals on the host and
quantizes them through the native library: 68 s for Mistral-7B (my chip run,
PR 21), paid by every run of every cell.  A checkpoint's values do not
change what a tick costs, so the benchmark draws the packed int4 planes and
their fp16 scales directly: uniform 4-bit codes, and scales of random sign
sized so that a dequantized weight has the standard deviation the program's
generator gives it (1.2 / sqrt(in_features)).

WHICH leaves a family's tree holds is that family's file,
``generators/<model_type>.py``, found by the configuration's ``model_type``
as the plain reference is (README: "a model family").  Here is only what
every family shares: the key from the seed, the drawing helpers (each to be
called inside the generator's own ``jax.jit``), the ``QTensor`` wrap, and
:func:`build_model`.  The plain reference dequantizes the same planes by its
own code, so a wrong plane layout shows as ``correct: false``.
"""

from __future__ import annotations

import importlib
import math
import os

from failure import BenchFailure

BLOCK = 32                  # quantization block along in_features
# std of a uniform 4-bit code minus 8: sqrt((16^2 - 1) / 12)
CODE_STD = math.sqrt(255.0 / 12.0)
WEIGHT_GAIN = 1.2           # random_init.py: 0.3 / sqrt(in) * 4


def seed_key(seed: int):
    """The key of a run.  ``--seed`` may pass 2**31, more than a key's 32
    signed bits hold: the low 31 bits seed it and the rest are folded in."""
    import jax

    return jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


def check_block(name: str, k: int) -> None:
    if k % BLOCK:
        raise ValueError(f"{name}: in_features {k} is not a multiple of the "
                         f"quantization block {BLOCK}")


def int4_plane(key, k: int, n: int):
    """One sym_int4 weight ``[k, n]`` as the pair (packed codes ``[k/2, n]``
    uint8, scales ``[k/32, n]`` fp16): a dequantized weight has the standard
    deviation WEIGHT_GAIN / sqrt(k)."""
    import jax
    import jax.numpy as jnp

    kd, ks, kg = jax.random.split(key, 3)
    data = jax.random.bits(kd, (k // 2, n), jnp.uint8)
    mag = (WEIGHT_GAIN / math.sqrt(k) / CODE_STD) * (
        1.0 + 0.1 * jax.random.uniform(ks, (k // BLOCK, n), jnp.float32,
                                       -1.0, 1.0))
    sign = jnp.where(jax.random.bernoulli(kg, 0.5, (k // BLOCK, n)),
                     1.0, -1.0)
    return data, (mag * sign).astype(jnp.float16)


def stack(draw, key, *lead: int):
    """``draw(key)`` (a plane, or a whole layer's tree) repeated over the
    leading axes ``lead`` (layers; layers x experts), each from its own key.
    Under ``lax.map``, one entry at a time, so the generator's temporaries
    stay one entry's size however large the stack."""
    import jax

    if not lead:
        return draw(key)
    return jax.lax.map(lambda k: stack(draw, k, *lead[1:]),
                       jax.random.split(key, lead[0]))


def vector(key, shape, mean: float = 1.0, spread: float = 0.05):
    """A float32 vector around ``mean`` (a norm's weight, a bias)."""
    import jax
    import jax.numpy as jnp

    return mean + spread * jax.random.normal(key, shape, jnp.float32)


def dense(key, shape, fan_in: int, dtype):
    """An unquantized matrix (an embedding table, a router) of standard
    deviation WEIGHT_GAIN / sqrt(fan_in), drawn in float32."""
    import jax
    import jax.numpy as jnp

    return ((WEIGHT_GAIN / math.sqrt(fan_in))
            * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def qtensor(pair, k: int, n: int, qtype: str = "sym_int4"):
    """The program's wrap of one plane, or of a stack of them: the logical
    shape is one plane's whatever axes lead."""
    from ipex_llm_tpu.quantize.core import QTensor

    return QTensor(pair[0], pair[1], None, qtype, (k, n), BLOCK)


def generator(model_type: str):
    """``generators/<model_type>.py``: the module that draws that family's
    tree."""
    try:
        return importlib.import_module(f"generators.{model_type}")
    except ModuleNotFoundError as e:
        if e.name != f"generators.{model_type}":
            raise
        raise BenchFailure(
            f"no weight generator for model_type {model_type!r}: add "
            f"perfbench/generators/{model_type}.py with param_tree(cfg, hf, "
            f"qtype, seed) and tiny(hf) (has: "
            f"{_generators()})") from None


def _generators() -> list[str]:
    import generators

    return sorted({f[:-3] for path in generators.__path__
                   for f in os.listdir(path)
                   if f.endswith(".py") and not f.startswith("_")})


def build_model(hf_config: dict, qtype: str, seed: int):
    """The object ``from_pretrained`` returns, around the family's tree."""
    import jax

    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.transformers.model import TPUModelForCausalLM

    gen = generator(hf_config["model_type"])
    cfg = FAMILIES[hf_config["model_type"]].to_config(hf_config)
    params = jax.block_until_ready(gen.param_tree(cfg, hf_config, qtype, seed))
    return TPUModelForCausalLM(cfg, params, hf_config, qtype)


def tree_bytes(params) -> int:
    import jax

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
               if isinstance(leaf, jax.Array))
