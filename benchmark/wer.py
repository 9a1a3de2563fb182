"""Whisper word-error-rate harness.

Reference counterpart: ``dev/benchmark/whisper/`` (librispeech + jiwer WER
for the patched Whisper).  This is the TPU-native peer over
``TPUWhisperForConditionalGeneration``: it pairs ``<name>.wav`` audio files
with ``<name>.txt`` reference transcripts and reports corpus-level WER
(edit-distance substitutions+insertions+deletions over reference words —
the jiwer formula, implemented here so the harness stays dependency-free).

Hermetic mode (no audio on disk): ``--selftest`` runs the model twice on a
synthetic waveform and asserts WER(model, model) == 0, proving the
pipeline end-to-end without a dataset.

Usage:
  python benchmark/wer.py --model /path/whisper --audio-dir /path/wavs
  python benchmark/wer.py --model /path/whisper --selftest
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def edit_ops(ref: list[str], hyp: list[str]) -> tuple[int, int, int]:
    """(substitutions, deletions, insertions) of the minimal edit path."""
    m, n = len(ref), len(hyp)
    # dp over (cost, S, D, I); cost ties broken arbitrarily (standard WER)
    dp = [[(j, 0, 0, j) for j in range(n + 1)]]
    for i in range(1, m + 1):
        row = [(i, 0, i, 0)]
        for j in range(1, n + 1):
            if ref[i - 1] == hyp[j - 1]:
                c, s, d, ins = dp[i - 1][j - 1]
                row.append((c, s, d, ins))
            else:
                sub = dp[i - 1][j - 1]
                dele = dp[i - 1][j]
                insr = row[j - 1]
                best = min(sub, dele, insr, key=lambda t: t[0])
                if best is sub:
                    row.append((best[0] + 1, best[1] + 1, best[2], best[3]))
                elif best is dele:
                    row.append((best[0] + 1, best[1], best[2] + 1, best[3]))
                else:
                    row.append((best[0] + 1, best[1], best[2], best[3] + 1))
        dp.append(row)
    _, s, d, ins = dp[m][n]
    return s, d, ins


def normalize(text: str) -> list[str]:
    """Lowercase, strip punctuation — the usual ASR scoring normalization."""
    out = []
    for w in text.lower().split():
        w = "".join(ch for ch in w if ch.isalnum() or ch == "'")
        if w:
            out.append(w)
    return out


def wer(ref_text: str, hyp_text: str) -> float:
    ref, hyp = normalize(ref_text), normalize(hyp_text)
    if not ref:
        return 0.0 if not hyp else 1.0
    s, d, i = edit_ops(ref, hyp)
    return (s + d + i) / len(ref)


def corpus_wer(pairs: list[tuple[str, str]]) -> dict:
    """pairs of (reference, hypothesis) -> aggregate WER (errors summed over
    the corpus before dividing, the librispeech convention)."""
    tot_err = tot_ref = 0
    per_utt = []
    for ref_text, hyp_text in pairs:
        ref, hyp = normalize(ref_text), normalize(hyp_text)
        s, d, i = edit_ops(ref, hyp) if ref or hyp else (0, 0, 0)
        tot_err += s + d + i
        tot_ref += len(ref)
        per_utt.append(round((s + d + i) / max(len(ref), 1), 4))
    return {
        "wer": round(tot_err / max(tot_ref, 1), 4),
        "utterances": len(pairs),
        "ref_words": tot_ref,
        "per_utt": per_utt,
    }


def _features(audio: np.ndarray, sr: int, fe, model) -> np.ndarray:
    """Mel features via the checkpoint's own extractor (the api_server
    transcription pipeline), clipped to the encoder window."""
    want_sr = getattr(fe, "sampling_rate", 16000)
    if sr != want_sr:  # linear resample (no audio stack in this image)
        n = int(len(audio) * want_sr / sr)
        audio = np.interp(np.linspace(0, len(audio) - 1, n),
                          np.arange(len(audio)), audio).astype(np.float32)
    feats = fe(audio, sampling_rate=want_sr,
               return_tensors="np")["input_features"]
    return feats[:, :, :2 * model.config.max_source_positions]


def run_dir(model_path: str, audio_dir: str, low_bit: str = "sym_int4",
            max_new_tokens: int = 128) -> dict:
    from transformers import AutoTokenizer, WhisperFeatureExtractor

    from ipex_llm_tpu.models.whisper import TPUWhisperForConditionalGeneration
    from ipex_llm_tpu.serving.api_server import _read_wav

    model = TPUWhisperForConditionalGeneration.from_pretrained(
        model_path, load_in_low_bit=low_bit)
    tok = AutoTokenizer.from_pretrained(model_path)
    fe = WhisperFeatureExtractor.from_pretrained(model_path)
    pairs = []
    for name in sorted(os.listdir(audio_dir)):
        if not name.endswith(".wav"):
            continue
        txt = os.path.join(audio_dir, name[:-4] + ".txt")
        if not os.path.exists(txt):
            continue
        with open(os.path.join(audio_dir, name), "rb") as f:
            audio, sr = _read_wav(f.read())
        feats = _features(audio, sr, fe, model)
        ids = model.generate(feats, max_new_tokens=max_new_tokens)
        hyp = tok.decode(list(map(int, np.asarray(ids)[0])),
                         skip_special_tokens=True)
        with open(txt) as f:
            ref = f.read()
        pairs.append((ref, hyp))
    return corpus_wer(pairs)


def selftest(model_path: str, low_bit: str = "sym_int4") -> dict:
    """Hermetic: transcribe a synthetic tone twice; WER(run1, run2) must be
    0 (greedy decode is deterministic) — proves features->encode->decode->
    detokenize end-to-end without any dataset."""
    from transformers import AutoTokenizer, WhisperFeatureExtractor

    from ipex_llm_tpu.models.whisper import TPUWhisperForConditionalGeneration

    model = TPUWhisperForConditionalGeneration.from_pretrained(
        model_path, load_in_low_bit=low_bit)
    tok = AutoTokenizer.from_pretrained(model_path)
    fe = WhisperFeatureExtractor.from_pretrained(model_path)
    t = np.arange(16000 * 2) / 16000.0
    audio = (0.3 * np.sin(2 * np.pi * 440 * t)).astype(np.float32)
    feats = _features(audio, 16000, fe, model)
    outs = []
    for _ in range(2):
        ids = model.generate(feats, max_new_tokens=16)
        outs.append(tok.decode(list(map(int, np.asarray(ids)[0])),
                               skip_special_tokens=True))
    return {"selftest_wer": wer(outs[0], outs[1]), "hyp": outs[0][:80]}


def main(argv=None):
    ap = argparse.ArgumentParser("ipex-llm-tpu whisper WER harness")
    ap.add_argument("--model", required=True)
    ap.add_argument("--audio-dir", default=None)
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--max-wer", type=float, default=None,
                    help="fail (exit 1) if corpus WER exceeds this")
    args = ap.parse_args(argv)

    if args.selftest:
        res = selftest(args.model, args.low_bit)
        print(json.dumps(res))
        return 0 if res["selftest_wer"] == 0.0 else 1
    if not args.audio_dir:
        raise SystemExit("need --audio-dir or --selftest")
    res = run_dir(args.model, args.audio_dir, args.low_bit)
    print(json.dumps(res))
    if args.max_wer is not None and res["wer"] > args.max_wer:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
