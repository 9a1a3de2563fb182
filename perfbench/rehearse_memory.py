#!/usr/bin/env python3
"""The third rehearsal (on-chip-measurement guide, section 2): compile the
steady decode tick of a configuration at its engine sizes for a DESCRIBED
v5e chip (none attached, JAX_PLATFORMS=cpu) and print memory_analysis().
Run by hand before a chip call; nothing runs, so this gives bytes, never
times:

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_memory.py <config name> [pool_pages ...]

A tiny engine on the CPU gives the tick's row-state arguments (they depend on
the engine's sizes only); the parameters and the KV pool are passed as shapes
at the configuration's real sizes.
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


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ipex_llm_tpu.kv import PagedKVCache
    from ipex_llm_tpu.models.families import FAMILIES
    from ipex_llm_tpu.serving.engine import (EngineConfig, ServingEngine,
                                             _ragged_tick_fn)

    config = run.load_json(os.path.join(HERE, "configs", argv[0] + ".json"))
    hf = run.hf_config(config)
    pools = [int(a) for a in argv[1:]] or [config["engine"]["pool_pages"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)
            if hasattr(x, "shape") and hasattr(x, "dtype") else x, tree)

    cfg = FAMILIES[hf["model_type"]].to_config(hf)
    params = sds(jax.eval_shape(
        lambda: weights.param_tree(cfg, config["weight_qtype"], 0)))
    params["rope_mscale"] = float(cfg.rope.mscale(cfg.max_position_embeddings))
    # the row state of a tiny model under the SAME engine sizes
    tiny = dict(hf, hidden_size=128, intermediate_size=256,
                num_hidden_layers=1, num_attention_heads=2,
                num_key_value_heads=1, vocab_size=256)
    tiny.pop("head_dim", None)
    tcfg = FAMILIES[hf["model_type"]].to_config(tiny)
    eng = ServingEngine(tcfg, weights.param_tree(tcfg, "sym_int4", 0),
                        EngineConfig(**dict(config["engine"], pool_pages=0)))
    dev = eng._sync_device_state()
    for pool in pools:
        ec = EngineConfig(**dict(config["engine"], pool_pages=pool))
        cache = sds(jax.eval_shape(lambda: PagedKVCache.init(
            cfg.num_layers, ec.n_pages, ec.max_rows, ec.max_pages,
            cfg.num_kv_heads, ec.page_size, cfg.head_dim)))
        args = (cfg, params, cache, sds(dev["toks"]), sds(dev["row_lens"]),
                sds(dev["active"]), sds(dev["temps"]), sds(dev["top_ps"]),
                sds(eng.key), sds(dev["seeds"]), sds(dev["steps"]),
                sds(dev["top_ks"]), sds(dev["eos"]), sds(dev["remain"]))
        try:
            compiled = _ragged_tick_fn.lower(
                *args, prefill=None, horizon=1, mesh=None, tp_manual=False,
                collective_qtype=eng._collective_qtype).compile()
        except jax.errors.JaxRuntimeError as e:
            # what the chip's compiler would refuse, e.g. out of HBM
            print(json.dumps({"config": argv[0], "pool_pages": pool,
                              "refused": str(e).split("\n\n")[0][:400]}),
                  flush=True)
            continue
        ma = compiled.memory_analysis()
        out = {"config": argv[0], "pool_pages": pool,
               "argument_bytes": ma.argument_size_in_bytes,
               "output_bytes": ma.output_size_in_bytes,
               "alias_bytes": ma.alias_size_in_bytes,
               "temp_bytes": ma.temp_size_in_bytes,
               "generated_code_bytes": ma.generated_code_size_in_bytes}
        out["resident_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                                 - out["alias_bytes"] + out["temp_bytes"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
