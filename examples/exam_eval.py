"""Exam-style multiple-choice accuracy (the ceval harness).

Reference counterpart: ``dev/benchmark/ceval`` — per-option loglikelihood
scoring through the quantized model, reported per subject.

    python examples/exam_eval.py [--model PATH] [--data questions.json]
"""

import argparse
import json
import tempfile

from _tiny_model import tiny_checkpoint


_DEMO = [
    {"subject": "astronomy", "question": "Which planet is largest?",
     "choices": {"A": "Mars", "B": "Jupiter", "C": "Venus", "D": "Mercury"},
     "answer": "B"},
    {"subject": "astronomy", "question": "What does the sun mostly burn?",
     "choices": {"A": "hydrogen", "B": "iron", "C": "carbon", "D": "gold"},
     "answer": "A"},
]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=None)
    p.add_argument("--data", default=None)
    p.add_argument("--few-shot", type=int, default=1)
    args = p.parse_args()
    path = args.model or tiny_checkpoint()
    data = args.data
    if data is None:
        f = tempfile.NamedTemporaryFile("w", suffix=".json", delete=False)
        json.dump(_DEMO, f)
        f.close()
        data = f.name
        print("(no --data given: scoring a 2-question demo file; a random "
              "tiny model answers at chance)")

    import sys

    sys.path.insert(0, ".")
    from benchmark.ceval import main as ceval_main

    ceval_main(["--model", path, "--data", data, "--low-bit", "sym_int4",
                "--few-shot", str(args.few_shot)])


if __name__ == "__main__":
    main()
