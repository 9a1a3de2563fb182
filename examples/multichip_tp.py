"""Tensor-parallel generation over a device mesh.

Reference counterpart: the DeepSpeed-AutoTP examples
(example/GPU/Deepspeed-AutoTP).  On real hardware the mesh spans TPU
chips over ICI; here it runs on 8 virtual CPU devices so the example is
runnable anywhere (the sharding program is identical either way).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/multichip_tp.py [--model PATH]
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

from _tiny_model import model_arg  # noqa: E402


def main():
    args, model_path = model_arg()
    import numpy as np

    from ipex_llm_tpu.parallel.mesh import make_mesh

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    # single-device reference
    ref = AutoModelForCausalLM.from_pretrained(model_path,
                                               load_in_low_bit="sym_int4")
    prompt = np.arange(7, 23, dtype=np.int32)
    want = np.asarray(ref.generate(prompt, max_new_tokens=8))

    # tp=2 sharded: column/row-parallel quantized weights, psum via GSPMD
    mesh = make_mesh(tp=2)
    tp = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit="sym_int4", mesh=mesh
    )
    got = np.asarray(tp.generate(prompt, max_new_tokens=8))
    assert np.array_equal(want, got), "tp=2 must match single-device output"
    print(f"tp=2 over {mesh.devices.size}-device mesh: identical tokens",
          got[0, len(prompt):].tolist())


if __name__ == "__main__":
    main()
