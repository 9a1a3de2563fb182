"""Exam-style multiple-choice evaluation harness (the ceval runner peer).

Reference counterpart: ``dev/benchmark/ceval/`` (C-Eval exam accuracy via
per-option scoring over the patched model).  Protocol here is the standard
loglikelihood formulation the harness world converged on: for each question
build the exam prompt, score the continuation " A"/" B"/" C"/" D" with the
model (via the lm-eval adapter's loglikelihood), pick the argmax, report
accuracy per subject and overall.

Data format (hermetic — no dataset download exists in this environment):
a JSON file holding a list of
  {"subject": str, "question": str,
   "choices": {"A": str, "B": str, "C": str, "D": str}, "answer": "A"}

Usage:
  python benchmark/ceval.py --model /path/ckpt --data questions.json
  python benchmark/ceval.py --model /path/ckpt --data questions.json \
      --low-bit sym_int4 --few-shot 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LETTERS = ("A", "B", "C", "D")


def format_question(q: dict, with_answer: bool = False) -> str:
    s = q["question"].rstrip() + "\n"
    for letter in LETTERS:
        if letter in q["choices"]:
            s += f"{letter}. {q['choices'][letter]}\n"
    s += "Answer:"
    if with_answer:
        s += f" {q['answer']}\n\n"
    return s


def build_prompt(q: dict, shots: list[dict]) -> str:
    subject = q.get("subject", "knowledge")
    head = (f"The following are multiple choice questions (with answers) "
            f"about {subject}.\n\n")
    body = "".join(format_question(s, with_answer=True) for s in shots)
    return head + body + format_question(q)


class _Req:
    def __init__(self, args):
        self.args = args


def evaluate(lm, questions: list[dict], few_shot: int = 0) -> dict:
    """lm: anything with the lm-eval ``loglikelihood`` API (lmeval adapter).

    Few-shot exemplars come from OTHER questions of the same subject (the
    ceval dev-split convention, applied within the provided file)."""
    by_subject: dict[str, list[dict]] = defaultdict(list)
    for q in questions:
        by_subject[q.get("subject", "knowledge")].append(q)

    per_subject_hits: dict[str, list[int]] = defaultdict(list)
    for subject, qs in by_subject.items():
        for i, q in enumerate(qs):
            shots = [s for j, s in enumerate(qs) if j != i][:few_shot]
            ctx = build_prompt(q, shots)
            reqs = [_Req((ctx, f" {letter}")) for letter in LETTERS
                    if letter in q["choices"]]
            scores = lm.loglikelihood(reqs)
            letters = [letter for letter in LETTERS if letter in q["choices"]]
            pick = letters[max(range(len(scores)),
                               key=lambda k: scores[k][0])]
            per_subject_hits[subject].append(int(pick == q["answer"]))

    subjects = {
        s: round(sum(h) / len(h), 4) for s, h in per_subject_hits.items()
    }
    all_hits = [h for hs in per_subject_hits.values() for h in hs]
    return {
        "accuracy": round(sum(all_hits) / max(len(all_hits), 1), 4),
        "n_questions": len(all_hits),
        "subjects": subjects,
    }


def main(argv=None):
    ap = argparse.ArgumentParser("ipex-llm-tpu exam (ceval-style) harness")
    ap.add_argument("--model", required=True)
    ap.add_argument("--data", required=True, help="questions JSON file")
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("--few-shot", type=int, default=0)
    ap.add_argument("--min-accuracy", type=float, default=None,
                    help="fail (exit 1) below this overall accuracy")
    args = ap.parse_args(argv)

    from ipex_llm_tpu.lmeval import IpexLLMTPULM

    lm = IpexLLMTPULM(pretrained=args.model, load_in_low_bit=args.low_bit)
    with open(args.data) as f:
        questions = json.load(f)
    res = evaluate(lm, questions, few_shot=args.few_shot)
    print(json.dumps(res))
    if args.min_accuracy is not None and res["accuracy"] < args.min_accuracy:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
