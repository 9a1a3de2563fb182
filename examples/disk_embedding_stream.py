"""Stream a >HBM-vocab embedding table from host RAM (disk_embedding).

Reference counterpart: ``DiskEmbedding`` (reference
transformers/embedding.py:96) — vocabularies too large even for
accelerator memory keep the table out of device memory; each decode step
gathers only the current tokens' rows.

TPU-native form: the table stays a host numpy array, params carry no
``embed`` leaf, prefill ships the gathered prompt rows once, and decode
runs the python-driven loop moving [B, 1, H] per step over PCIe.

    python examples/disk_embedding_stream.py [--model PATH]
"""

import argparse

import numpy as np

from _tiny_model import tiny_checkpoint


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=None)
    args = p.parse_args()
    path = args.model or tiny_checkpoint()

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    m = AutoModelForCausalLM.from_pretrained(
        path, load_in_low_bit="sym_int4", disk_embedding=True)
    assert "embed" not in m.params
    print(f"embed table in HOST RAM: {m.streamed_embed.shape} "
          f"({m.streamed_embed.nbytes / 1e6:.1f} MB never enters HBM)")

    out = m.generate(np.array([[5, 9, 13, 21]], np.int32),
                     max_new_tokens=12, do_sample=False)
    print("generated:", out[0].tolist())


if __name__ == "__main__":
    main()
