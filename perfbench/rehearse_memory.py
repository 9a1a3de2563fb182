#!/usr/bin/env python3
"""The third rehearsal (on-chip-measurement guide, section 2): compile the
steady decode tick of a configuration at its engine sizes for a DESCRIBED
v5e chip (none attached, JAX_PLATFORMS=cpu) and print memory_analysis().
Run by hand before a chip call; nothing runs, so this gives bytes, never
times:

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_memory.py <config name> [pool_pages ...]

It lowers the program the CHIP runs: the program's dispatch asks jax for
the backend, which is the CPU here, and would lower its XLA fallbacks (one
more copy of the KV pool: PERF.md section 4), so the kernel dispatch and
compiled (not interpreted) kernels are forced first; each line says how
many Mosaic kernels the compiled tick holds.

What belongs to the model's family comes from ``generators/<model_type>.py``:
the parameter shapes from its ``param_tree`` under ``eval_shape``, a tiny
configuration from its ``tiny`` (an engine around it on the CPU gives the
tick's row-state arguments, which depend on the engine's sizes only), and
the cache from its ``cache_shapes(cfg, engine_config)`` where it has one
(else the paged KV pool).  A family whose tick takes other ARGUMENTS adds a
rehearsal file of its own beside this one.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import run  # noqa: E402
import weights  # noqa: E402


def force_chip_dispatch() -> str:
    """Make the program trace what it traces on a TPU: every op's kernel,
    compiled by Mosaic.  Steered from here, not by an option of the program
    (the guide's section 2).  The kernel modules are imported first: each
    binds its own name for the interpret-mode probe as it is imported."""
    import importlib
    import pkgutil

    import ipex_llm_tpu.ops.pallas as kernels
    from ipex_llm_tpu.ops import dispatch

    dispatch._use_pallas_env = lambda op=None: True
    forced = []
    for info in pkgutil.iter_modules(kernels.__path__):
        mod = importlib.import_module(f"{kernels.__name__}.{info.name}")
        if hasattr(mod, "_interpret"):
            mod._interpret = lambda: False
            forced.append(info.name)
    if not forced or not hasattr(dispatch, "use_pallas"):
        raise SystemExit("rehearse_memory: the program's kernel dispatch is "
                         "not where this script steers it; it would lower "
                         "the CPU's fallbacks")
    return "kernels forced, compiled: " + ", ".join(forced)


def described(make, place):
    """``make()``'s tree as shapes placed on ``place``.  A leaf that is no
    array (a python number the program reads while it traces) keeps its
    value."""
    import jax

    kept = {}

    def traced():
        tree = make()
        for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
            if not hasattr(leaf, "shape"):
                kept[i] = leaf
        return tree

    leaves, treedef = jax.tree_util.tree_flatten(jax.eval_shape(traced))
    return treedef.unflatten([
        kept[i] if i in kept
        else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place)
        for i, x in enumerate(leaves)])


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ipex_llm_tpu.kv import PagedKVCache
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.serving.engine import (EngineConfig, ServingEngine,
                                             _ragged_tick)

    config = run.load_json(os.path.join(HERE, "configs", argv[0] + ".json"))
    hf, qtype = run.hf_config(config), config["weight_qtype"]
    pools = [int(a) for a in argv[1:]] or [config["engine"]["pool_pages"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    gen = weights.generator(hf["model_type"])
    family = FAMILIES[hf["model_type"]]
    cfg = family.to_config(hf)
    params = described(lambda: gen.param_tree(cfg, hf, qtype, 0), chip)
    # the row state of a tiny model under the SAME engine sizes
    tiny = gen.tiny(hf)
    tcfg = family.to_config(tiny)
    eng = ServingEngine(tcfg, gen.param_tree(tcfg, tiny, qtype, 0),
                        EngineConfig(**dict(config["engine"], pool_pages=0)))
    dev = eng._sync_device_state()
    rows = described(lambda: (
        dev["toks"], dev["row_lens"], dev["active"], dev["temps"],
        dev["top_ps"], eng.key, dev["seeds"], dev["steps"], dev["top_ks"],
        dev["eos"], dev["remain"]), chip)
    dispatch = force_chip_dispatch()
    for pool in pools:
        ec = EngineConfig(**dict(config["engine"], pool_pages=pool))
        if hasattr(gen, "cache_shapes"):
            cache = described(lambda: gen.cache_shapes(cfg, ec), chip)
        else:
            cache = described(lambda: PagedKVCache.init(
                cfg.num_layers, ec.n_pages, ec.max_rows, ec.max_pages,
                cfg.num_kv_heads, ec.page_size, cfg.head_dim), chip)
        # a cache that carries recurrent state is held TWICE by the engine:
        # the tick reads the committed buffer (``state_src``, the same shapes)
        # and writes the spare one, which is the donated cache's
        state_src = cache.state
        out = {"config": argv[0], "pool_pages": pool, "dispatch": dispatch,
               "state_buffers": 0 if state_src is None else 2}
        try:
            compiled = _ragged_tick.lower(
                cfg, params, cache, *rows, prefill=None, horizon=1, mesh=None,
                tp_manual=False, collective_qtype=eng._collective_qtype,
                state_src=state_src).compile()
        except jax.errors.JaxRuntimeError as e:
            # what the chip's compiler would refuse, e.g. out of HBM
            out["refused"] = str(e).split("\n\n")[0][:400]
            print(json.dumps(out), flush=True)
            continue
        ma = compiled.memory_analysis()
        out.update(
            # 0 would mean the fallbacks were lowered after all
            mosaic_kernels=compiled.as_text().count("tpu_custom_call"),
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            generated_code_bytes=ma.generated_code_size_in_bytes)
        out["resident_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                                 - out["alias_bytes"] + out["temp_bytes"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
