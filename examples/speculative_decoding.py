"""Self-speculative decoding: INT4 draft, bf16 verify, identical output.

Reference counterpart: example/CPU/Speculative-Decoding (speculative.py's
``speculative_generate``).  With greedy verification the output is
token-identical to plain decoding; telemetry shows the acceptance rate and
the auto-tuned ``th_stop_draft``.

    python examples/speculative_decoding.py [--model PATH]
"""

from _tiny_model import model_arg


def main():
    args, model_path = model_arg()
    import numpy as np

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    # speculative=True keeps bf16 weights for verification and makes an
    # int4 draft copy (the reference's self-speculative setup)
    model = AutoModelForCausalLM.from_pretrained(
        model_path, load_in_low_bit="bf16", speculative=True
    )
    prompt = np.arange(5, 37, dtype=np.int32)

    plain = model.generate(prompt, max_new_tokens=args.n_predict)
    spec = model.speculative_generate(prompt, max_new_tokens=args.n_predict)
    assert np.array_equal(np.asarray(plain), np.asarray(spec))

    r = model.last_result
    print(f"accepted {r.n_matched}/{r.n_drafted} drafted tokens over "
          f"{r.n_rounds} rounds; final th_stop_draft={r.th_stop_draft:.3f}")
    print("output:", np.asarray(spec)[0, len(prompt):].tolist())


if __name__ == "__main__":
    main()
