"""Low-bit sentence embeddings + cosine retrieval (the RAG building block).

Reference counterpart: langchain/embeddings/transformersembeddings.py used
by example/GPU/LangChain/rag.py.  Uses a BERT-class encoder through
AutoModel + TransformersEmbeddings; synthesizes a tiny random encoder when
no --model is given.

    python examples/embeddings_rag.py [--model BERT_PATH]
"""

import os

from _tiny_model import CACHE_DIR, model_arg


def _tiny_bert(path=os.path.join(CACHE_DIR, "tiny_bert")):
    if os.path.exists(os.path.join(path, "config.json")):
        return path
    import torch
    from transformers import BertConfig, BertModel

    torch.manual_seed(0)
    BertModel(BertConfig(
        vocab_size=224 + 2, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128,
    )).eval().save_pretrained(path, safe_serialization=True)
    from tokenizers import Regex, Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    vocab = {chr(i + 32): i for i in range(0, 224)}
    vocab["<unk>"] = 224
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Split(Regex("."), "isolated")
    PreTrainedTokenizerFast(tokenizer_object=tok,
                            unk_token="<unk>").save_pretrained(path)
    return path


def main():
    import numpy as np

    args, _ = model_arg()
    path = args.model or _tiny_bert()

    from ipex_llm_tpu.langchain import TransformersEmbeddings

    emb = TransformersEmbeddings.from_model_id(
        path, model_kwargs={"load_in_low_bit": "sym_int4"})

    docs = [
        "TPUs multiply matrices with a systolic array.",
        "The capital of France is Paris.",
        "Quantization stores weights in four bits.",
    ]
    doc_vecs = np.asarray(emb.embed_documents(docs))
    q = np.asarray(emb.embed_query("How are weights compressed?"))
    scores = doc_vecs @ q
    best = int(scores.argmax())
    for d, s in zip(docs, scores):
        print(f"  {s:+.3f}  {d}")
    print(f"best match: {docs[best]!r}")


if __name__ == "__main__":
    main()
