"""Tensor-parallel + speculative serving through the paged engine.

Reference counterpart: the vLLM TP serving quickstart
(docs/mddocs/Quickstart/vLLM_quickstart, Ray worker TP) and the FastChat
worker's ``speculative`` load flag (serving/fastchat/ipex_llm_worker.py:57)
— here expressed as ONE SPMD mesh plus in-engine prompt-lookup speculative
steps.

    python examples/tp_serving.py          # tp=4 virtual mesh + spec_k=3

On real hardware drop the XLA_FLAGS override and point --model at a real
checkpoint; the same code serves a v5e pod slice.
"""

import os

os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "")
     + " --xla_force_host_platform_device_count=4").strip(),
)

from _tiny_model import tiny_checkpoint


def main():
    import numpy as np

    from ipex_llm_tpu.parallel import MeshSpec, make_mesh
    from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                             ServingEngine, stream_tokens)
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    path = tiny_checkpoint()
    mesh = make_mesh(MeshSpec(tp=4))
    model = AutoModelForCausalLM.from_pretrained(
        path, load_in_low_bit="sym_int4", mesh=mesh)
    eng = ServingEngine(
        model.config, model.params,
        EngineConfig(max_rows=4, max_seq_len=256, prefill_bucket=32,
                     spec_k=3),
        default_eos=model.generation_config.eos_token_id,
        mesh=mesh,
    ).start()
    try:
        prompts = [list(np.random.default_rng(s).integers(0, 200, 12))
                   for s in range(3)]
        reqs = [eng.submit(Request(prompt_ids=p, max_new_tokens=24))
                for p in prompts]
        for i, r in enumerate(reqs):
            toks = list(stream_tokens(r, timeout=600))
            print(f"request {i}: {len(toks)} tokens, "
                  f"finish={r.finish_reason}")
        print("engine metrics:", {
            k: v for k, v in eng.metrics.items()
            if k in ("requests", "tokens", "steps", "spec_steps",
                     "spec_accept_rate", "pages_in_use")})
    finally:
        eng.stop()


if __name__ == "__main__":
    main()
