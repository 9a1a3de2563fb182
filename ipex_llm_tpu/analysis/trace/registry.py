"""The program registry: every hot-path jitted entry, with its abstract-
input builder over the real bucket grid and its expected contracts.

A ProgramSpec names the jitted callable, how to build abstract inputs for
one grid point (ShapeDtypeStructs over a tiny audit model — lowering cost
is shape-independent-enough that tiny dims keep the audit fast while the
*grid* axes stay the engine's real ones: prefill buckets x horizons x row
counts x kv_storage in {bf16, fp8}), and the donation contract: which
dynamic args are DEAD after the call (the host overwrites its handle —
donation candidates, JP101 demands aliases for the large ones) and which
are HELD (the host re-passes the same buffer next call — donation there
is a use-after-donate bug, also JP101).

Registering a new program (docs/quickstart/static_analysis.md has the
worked example): write a builder returning the exact ``(args, kwargs)``
the real call site passes (statics included), list the dynamic arg names
in signature order, declare dead/held, pick the grid, append the spec in
``real_registry``, then run ``scripts/jaxprcheck --update`` and commit
the manifest diff.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ipex_llm_tpu.analysis.config import relkey


@dataclass(frozen=True)
class ProgramSpec:
    name: str
    fn: Any                                   # the jitted callable
    build: Callable[[dict], tuple[tuple, dict]]
    grid: tuple[dict, ...]
    arg_names: tuple[str, ...]                # dynamic args, in order
    dead: frozenset = frozenset()             # dead-after-call arg names
    held: frozenset = frozenset()             # host-reused arg names
    min_donate_bytes: int = 2048              # JP101 floor at audit shapes
    max_lowerings: int = 8                    # JP104 bound
    const_bytes_limit: int = 1 << 16          # JP105 threshold
    suppress: tuple[tuple[str, str], ...] = ()   # (code, written reason)
    requires: str | None = None               # e.g. "jax.shard_map"
    source: str = field(default="", compare=False)
    lineno: int = field(default=1, compare=False)

    def __post_init__(self):
        if not self.source:
            import inspect

            fn = inspect.unwrap(self.fn)
            wrapped = getattr(fn, "__wrapped__", fn)
            object.__setattr__(self, "source",
                               relkey(inspect.getsourcefile(wrapped)))
            object.__setattr__(self, "lineno",
                               wrapped.__code__.co_firstlineno)


def requirement_met(requires: str | None) -> bool:
    """'jax.shard_map'-style dotted attribute probe."""
    if not requires:
        return True
    obj: Any = __import__(requires.split(".", 1)[0])
    for part in requires.split(".")[1:]:
        obj = getattr(obj, part, None)
        if obj is None:
            return False
    return True


# --------------------------------------------------------------------------
# the audit model: tiny dims, real param-tree structure
# --------------------------------------------------------------------------

def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                   if hasattr(x, "shape") else x), tree)


def audit_model(wq: str = "bf16"):
    """(cfg, abstract params) for a tiny llama through the REAL build
    path, so the param tree the audit lowers against is structurally the
    tree every engine entry actually takes.  ``wq`` is the weight-qtype
    axis (EngineConfig.weight_qtype): "sym_int4" lowers against stacked
    packed-code planes — the tree JP107's packed-weight protection and
    the int4 donation contracts are audited on.  The quantized variant
    widens hidden/ffn (128/256 vs 32/64) AND the query-head count (16 vs
    4, so the o-projection's contraction dim num_heads*head_dim is 128,
    not one lone block) to keep every stacked weight at >= 4 quantization
    blocks per matrix: at <= 2 blocks the per-layer ``[n_blocks, block,
    out]`` dequant view inside the scan body (the DESIGN) shape-collides
    with the full-stack ``[L, in, out]`` form JP107 forbids — a
    toy-model-only ambiguity (real serving weights run thousands of
    contraction rows), kept out of the audit by construction.  KV-head
    and head dims stay equal across variants so both share one
    paged-cache shape.  (Thin wrapper so ``audit_model()`` and
    ``audit_model("bf16")`` normalize to ONE lru_cache key — the real
    quantize work in random_params must not run twice per audit.)"""
    return _audit_model(wq)


def audit_cfg(wq: str = "bf16"):
    """The audit model's ModelConfig ALONE — no param tree, no random
    init.  Split out of :func:`audit_model` so runtime consumers (the
    perfwatch MFU join needs the audit dims to scale the manifest's
    cost_analysis to the serving model) can read the audit shape without
    paying the quantize/random-params build."""
    from ipex_llm_tpu.models.random_init import llama_config

    wide = wq != "bf16"
    return llama_config(hidden_size=128 if wide else 32,
                        intermediate_size=256 if wide else 64, num_layers=2,
                        num_heads=16 if wide else 4, num_kv_heads=2,
                        head_dim=8, vocab_size=97,
                        max_position_embeddings=256)


@lru_cache(maxsize=4)
def _audit_model(wq: str):
    from ipex_llm_tpu.models.random_init import random_params

    cfg = audit_cfg(wq)
    return cfg, _sds(random_params(cfg, qtype=wq, seed=0))


def audit_cfg_tp():
    """The manual-TP audit model's ModelConfig alone (see
    :func:`audit_cfg`)."""
    from ipex_llm_tpu.models.random_init import llama_config

    return llama_config(hidden_size=32, intermediate_size=64, num_layers=2,
                        num_heads=8, num_kv_heads=8, head_dim=8,
                        vocab_size=96, max_position_embeddings=256)


@lru_cache(maxsize=1)
def audit_model_tp():
    """(cfg, abstract params) for the MANUAL-TP tick grid: every sharded
    axis — q/kv heads, the packed qkv/gate_up out widths, the ffn
    contraction, the vocab — divides by 8, so one model lowers the
    sharded tick at tp in {1, 2, 4, 8} on the audit's 8 virtual CPU
    devices."""
    from ipex_llm_tpu.models.random_init import random_params

    cfg = audit_cfg_tp()
    return cfg, _sds(random_params(cfg, qtype="bf16", seed=0))


def _tp_mesh(tp: int):
    from ipex_llm_tpu.parallel import MeshSpec, make_mesh

    return make_mesh(MeshSpec(tp=tp))


_POOL_PAGES = 18      # audit pool: pages, page size, table width
_PAGE = 16
_MAXP = 4


def _tp_paged_cache(tp: int, rows: int, storage: str,
                    max_pages: int = _MAXP):
    """Abstract paged pool WITH the real placement's shardings: the
    engine's cache arrives kv-head-sharded (shard_paged_cache), and the
    donation alias only forms when the lowered input sharding matches the
    output's — an unsharded abstract pool would audit a program the
    engine never dispatches (and falsely flag the pool copy JP101
    protects against)."""
    from dataclasses import replace as _dc_replace

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ipex_llm_tpu.kv import PagedKVCache

    cfg, _ = audit_model_tp()
    cache = _sds(PagedKVCache.init(
        cfg.num_layers, _POOL_PAGES, rows, max_pages, cfg.num_kv_heads,
        _PAGE, cfg.head_dim, v_head_dim=cfg.v_dim, storage=storage))
    mesh = _tp_mesh(tp)
    pool = NamedSharding(mesh, P(None, None, "tp", None, None))
    rep = NamedSharding(mesh, P())

    def sh(x, s):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)

    return _dc_replace(cache, k=sh(cache.k, pool), v=sh(cache.v, pool),
                       tables=sh(cache.tables, rep),
                       length=sh(cache.length, rep))


def _paged_cache(rows: int, storage: str, max_pages: int = _MAXP):
    from ipex_llm_tpu.kv import PagedKVCache

    cfg, _ = audit_model()
    return _sds(PagedKVCache.init(
        cfg.num_layers, _POOL_PAGES, rows, max_pages, cfg.num_kv_heads,
        _PAGE, cfg.head_dim, v_head_dim=cfg.v_dim, storage=storage))


def _dense_cache(batch: int, capacity: int):
    from ipex_llm_tpu.kv import make_cache

    cfg, _ = audit_model()
    return _sds(make_cache("normal", cfg.num_layers, batch, capacity,
                           cfg.num_kv_heads, cfg.head_dim,
                           v_head_dim=cfg.v_dim))


def _key():
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _i32(*s):
    return jax.ShapeDtypeStruct(s, jnp.int32)


def _f32(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def _bool(*s):
    return jax.ShapeDtypeStruct(s, jnp.bool_)


def _grid(**axes) -> tuple[dict, ...]:
    """Cartesian product of named axes, insertion-ordered."""
    points: list[dict] = [{}]
    for name, values in axes.items():
        points = [{**p, name: v} for p in points for v in values]
    return tuple(points)


# --------------------------------------------------------------------------
# builders — one per registered program, mirroring the real call sites
# --------------------------------------------------------------------------

def _tp_stamped_params(tp: int):
    """The abstract audit_model_tp tree with the manual layout's
    ``tp_mode`` stamps (the static aux parallel/manual.py's in_specs are
    derived from) — shapes are unchanged by the relayout permutation, so
    the abstract tree lowers exactly like a placed one."""
    from dataclasses import replace as _dc_replace

    from ipex_llm_tpu.parallel.shard import param_shardings
    from ipex_llm_tpu.quantize.core import QTensor

    cfg, params = audit_model_tp()
    mesh = _tp_mesh(tp)
    sh = param_shardings(params, mesh)

    def stamp(p, s, key):
        if isinstance(p, QTensor) and isinstance(s, QTensor):
            # the manual layout replicates the embed table
            return _dc_replace(p, tp_mode=None if key == "embed"
                               else s.tp_mode)
        return p

    out = {}
    for k, v in params.items():
        if k == "layers":
            out[k] = {kk: stamp(vv, sh[k][kk], kk) for kk, vv in v.items()}
        elif isinstance(v, (int, float)):
            out[k] = v
        else:
            out[k] = stamp(v, sh[k], k)
    return cfg, out, mesh


def _build_ragged_tick(pt):
    tp = pt.get("tp", 0)
    if tp > 1:
        # manual-mesh form: the whole tick inside one fully-manual
        # shard_map region (parallel/manual.py) over a pure-tp mesh
        cfg, params, mesh = _tp_stamped_params(tp)
        cache = _tp_paged_cache(tp, pt["rows"], pt["kv"])
    else:
        # tp=1 IS the single-chip program (the engine routes tp<=1 to
        # the plain path — manual.ineligible_reason): the grid point
        # exists so the tp axis reads {1, 2, 4, 8}, and dedups against
        # the matching single-chip row by signature
        cfg, params = audit_model(pt.get("wq", "bf16"))
        mesh = None
        cache = _paged_cache(pt["rows"], pt["kv"])
    r = pt["rows"]
    w = pt["width"]
    kw = {"prefill": None, "horizon": pt["horizon"],
          "with_decode": pt.get("wd", True), "mesh": mesh}
    if w:
        # admission-wave form: an epoch.  The pow2-padded prefill block,
        # the row state and the block table ride ONE packed int32 buffer
        # (serving/epoch.py) that the program takes apart itself, so no
        # row state is carried in
        from ipex_llm_tpu.serving.epoch import EpochLayout

        layout = EpochLayout(rows=r, max_pages=_MAXP, e_w=2, p_b=2,
                             width=w, p_maxp=2)
        none5 = (None,) * 5
        base = (cfg, params, cache, *none5, _key(), *none5)
        kw.update(prefill=_i32(layout.size), epoch=layout)
    else:   # steady-state form: pure decode horizon over carried state
        base = (cfg, params, cache, _i32(r), _i32(r),
                _bool(r), _f32(r), _f32(r), _key(), _i32(r), _i32(r),
                _i32(r), _i32(r, 2), _i32(r))
    if tp > 1:
        kw.update(tp_manual=True,
                  collective_qtype=pt.get("cq", "bf16"))
    if pt.get("spec"):
        # speculative form: the device token-history ring (donated, the
        # proposer's input) and the per-row traced draft-width caps ride
        # as dynamic kwargs; spec_k/spec_ngram are statics
        kw.update(hist=_i32(r, _MAXP * _PAGE), spec_ks=_i32(r),
                  spec_k=pt["spec"], spec_ngram=3)
    return base, kw


def _build_gen_prefill(pt):
    cfg, params = audit_model()
    b = pt["batch"]
    return (cfg, params, _dense_cache(b, pt["bucket"] + 32),
            _i32(b, pt["bucket"]), _i32(b)), {}


def _gen_config():
    from ipex_llm_tpu.generation import GenerationConfig

    return GenerationConfig(max_new_tokens=32, eos_token_id=(1,))


def _build_decode_loop(pt):
    cfg, params = audit_model()
    b = pt["batch"]
    return (cfg, params, _dense_cache(b, 160), _i32(b), _i32(b), _i32(b),
            _i32(b, 512), _key(), _gen_config(), 32), {}


def _build_decode_one(pt):
    cfg, params = audit_model()
    b = pt["batch"]
    return (cfg, params, _dense_cache(b, 160), _i32(b), _i32(b), _i32(b),
            _i32(b, 512), _i32(b), _key(), _gen_config()), {}


def _build_mm_prefill(pt):
    cfg, params = audit_model()
    t = pt["bucket"]
    return (cfg, params, _dense_cache(1, t + 32), _i32(1, t), _i32(1, t),
            _f32(1, t, cfg.hidden_size)), {}


def _build_mm_decode(pt):
    cfg, params = audit_model()
    return (cfg, params, _dense_cache(1, 96), _i32(1, 1), _i32(1, 1)), {}


def _build_json_decode_step(pt):
    cfg, params = audit_model()
    return (cfg, params, _dense_cache(1, 96), _i32(1, 1), _i32(1, 1),
            _i32(1)), {}


# --------------------------------------------------------------------------
# the registry
# --------------------------------------------------------------------------

@lru_cache(maxsize=1)
def real_registry() -> tuple[ProgramSpec, ...]:
    from ipex_llm_tpu import generation, structured
    from ipex_llm_tpu.serving import engine
    from ipex_llm_tpu.transformers import multimodal

    kv_axis = ("bf16", "fp8")
    return (
        # -- serving/engine.py ------------------------------------------
        ProgramSpec(
            # THE tick program (JP106's one allowed dispatch): the grid
            # covers the steady-state form (width=0: pure decode
            # horizon), the admission-wave form (prefill block at both
            # pow2 chunk widths), the
            # pure-chunk form (wd=False: prefill+merge with the decode
            # stage statically skipped — a distinct jit variant with the
            # same donation contract), AND the speculative forms
            # (spec_k=4: on-device draft+verify+accept inside the horizon
            # loop, steady-state at both horizons plus the admission-wave
            # joiner tick), each over bf16 and fp8 pools
            name="serving.ragged_tick",
            fn=engine._ragged_tick,
            build=_build_ragged_tick,
            grid=(_grid(rows=(4, 8), width=(0,), horizon=(1, 8),
                        kv=kv_axis)
                  + _grid(rows=(4,), width=(8, 128), horizon=(1,),
                          kv=kv_axis)
                  + _grid(rows=(4,), width=(8,), horizon=(1,),
                          wd=(False,), kv=kv_axis)
                  + _grid(rows=(4,), width=(0,), horizon=(1, 8),
                          spec=(4,), kv=kv_axis)
                  # spec admission joiner at BOTH pow2 chunk widths: the
                  # runtime recompile sentinel bounds the engine's pow2
                  # width family by the widest point sampled here, so a
                  # spec engine's wide admission wave must be priced or
                  # it flags out-of-grid on its first burst
                  + _grid(rows=(4,), width=(8, 128), horizon=(1,),
                          spec=(4,), kv=kv_axis)
                  # weight-qtype axis (EngineConfig.weight_qtype): the
                  # tick over stacked int4-packed weight planes — steady
                  # decode at both horizons on bf16+fp8 pools plus the
                  # admission-wave joiner tick; JP107 protects the packed
                  # stacks, JP101 re-verifies the donation map with the
                  # params held (packed planes are never donated)
                  + _grid(rows=(4,), width=(0,), horizon=(1, 8),
                          wq=("sym_int4",), kv=kv_axis)
                  # int4 admission joiner over BOTH pool storages: the
                  # int4+fp8KV pairing is the fixed-HBM serving config,
                  # and the runtime sentinel requires the structural
                  # (wq, kv) form to be locked or every such admission
                  # wave flags out-of-grid.
                  # Width stays at the 8 representative only: on the
                  # widened int4 AUDIT model (hidden=128) a width-128
                  # chunk's [p=2, 128, out] activation shape-collides
                  # with the [L=2, 128, out] packed gate_up stack and
                  # false-fires JP107 (the documented toy-model
                  # ambiguity); the sentinel's width bound spans the wq
                  # axis (perfwatch._mag_group), so real engines' wider
                  # int4 waves are bounded by the bf16 rows' 128
                  + _grid(rows=(4,), width=(8,), horizon=(1,),
                          wq=("sym_int4",), kv=kv_axis)
                  # ...and the int4 pure-chunk form (wd=False): a
                  # distinct jit variant with its own donation map, and
                  # a structural form the sentinel must find locked —
                  # an int4 engine's admission wave with nothing yet
                  # decoding dispatches exactly this program
                  + _grid(rows=(4,), width=(8,), horizon=(1,),
                          wd=(False,), wq=("sym_int4",), kv=kv_axis)
                  # manual-mesh tp axis (parallel/manual.py): the whole
                  # tick inside ONE fully-manual shard_map region over a
                  # pure-tp CPU mesh, per-shard pools, explicit
                  # collectives.  tp=1 is the single-chip program by
                  # construction (dedups by signature); tp in {2, 4, 8}
                  # lower the sharded steady-state tick, tp=2 also the
                  # admission-wave and speculative forms, the quantized
                  # collective families (cq: EQuARX e5m2/int8 wires) and
                  # the fp8 pool — donation aliases verified per point
                  # like every other row
                  + _grid(rows=(4,), width=(0,), horizon=(1,),
                          tp=(1, 2, 4, 8), kv=("bf16",))
                  + _grid(rows=(4,), width=(8,), horizon=(1,),
                          tp=(2,), kv=("bf16",))
                  + _grid(rows=(4,), width=(0,), horizon=(8,),
                          spec=(4,), tp=(2,), kv=("bf16",))
                  + _grid(rows=(4,), width=(0,), horizon=(1,),
                          tp=(2,), cq=("e5m2", "int8"), kv=("bf16",))
                  + _grid(rows=(4,), width=(0,), horizon=(1,),
                          tp=(2,), kv=("fp8",))),
            arg_names=("params", "cache", "toks", "row_lens", "active",
                       "temps", "top_ps", "key", "seeds", "steps",
                       "top_ks", "eos", "remain"),
            # hist (spec forms only) is device-resident dead-after-call
            # state like toks: the host rebinds _dev["hist"] per tick
            dead=frozenset({"cache", "toks", "row_lens", "active",
                            "steps", "remain", "hist"}),
            # key is HELD (checkpoint-by-reference, the PR 6 rule);
            # sampling params/eos are epoch-held; the packed epoch
            # (``prefill``) and spec_ks are fresh per-tick uploads,
            # unlisted on purpose
            held=frozenset({"params", "temps", "top_ps", "seeds",
                            "top_ks", "eos", "key"}),
            max_lowerings=38,
        ),
        # -- generation.py ----------------------------------------------
        ProgramSpec(
            name="generation.prefill_step",
            fn=generation.prefill_step,
            build=_build_gen_prefill,
            grid=_grid(batch=(1, 2), bucket=(128,)),
            arg_names=("params", "cache", "tokens", "lengths"),
            dead=frozenset({"cache"}),
            held=frozenset({"params"}),
            max_lowerings=2,
        ),
        ProgramSpec(
            name="generation.decode_loop",
            fn=generation.decode_loop,
            build=_build_decode_loop,
            grid=_grid(batch=(2,)),
            arg_names=("params", "cache", "first_tokens", "lengths",
                       "kv_start", "prev_ring", "key"),
            dead=frozenset({"cache", "first_tokens", "prev_ring", "key"}),
            held=frozenset({"params"}),
            max_lowerings=1,
        ),
        ProgramSpec(
            name="generation.decode_one",
            fn=generation._decode_one,
            build=_build_decode_one,
            grid=_grid(batch=(2,)),
            arg_names=("params", "cache", "tok", "pos", "kv_start", "prev",
                       "ring_idx", "key"),
            dead=frozenset({"cache", "tok", "prev", "key"}),
            held=frozenset({"params"}),
            max_lowerings=1,
        ),
        # -- transformers/multimodal.py ---------------------------------
        ProgramSpec(
            name="multimodal.mm_prefill",
            fn=multimodal._mm_prefill,
            build=_build_mm_prefill,
            grid=_grid(bucket=(64,)),
            arg_names=("params", "cache", "tokens", "pos", "embeds"),
            dead=frozenset({"cache"}),
            held=frozenset({"params"}),
            max_lowerings=1,
        ),
        ProgramSpec(
            name="multimodal.mm_decode",
            fn=multimodal._mm_decode,
            build=_build_mm_decode,
            grid=_grid(bucket=(1,)),
            arg_names=("params", "cache", "tok", "pos"),
            dead=frozenset({"cache"}),
            held=frozenset({"params"}),
            max_lowerings=1,
        ),
        # -- structured.py ----------------------------------------------
        ProgramSpec(
            name="structured.json_decode_step",
            fn=structured._json_decode_step,
            build=_build_json_decode_step,
            grid=_grid(bucket=(1,)),
            arg_names=("params", "cache", "tok", "pos", "kv_start"),
            dead=frozenset({"cache"}),
            held=frozenset({"params"}),
            max_lowerings=1,
        ),
    )
