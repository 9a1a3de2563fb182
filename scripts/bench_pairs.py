"""Benchmark runs of two checkouts in turn on ONE machine, for a builder's
parent-against-change pairs through the chip tool:

    chiprun --timeout 3400 -- python3 scripts/bench_pairs.py <call> \
        parent:<cell>:<seed>:0 change:<cell>:<seed>:0 change:<cell>:<seed2>:1 ...

``parent`` and ``change`` are checkouts under the gitignored ``.scratch/``
(``git archive <commit> | tar -x -C .scratch/parent``; lay BENCHMARK.json and
perfbench/ over the parent's where the PR adds metrics).  One line a run on
stdout (the end-to-end values, the per-layer ones of a traced run); under
``chiprun_out/<call>/`` every run's output, ``results.jsonl``, an untraced
run's ``window.json`` and a traced run's ``reduced.json``.  No jax here: each
run is a process of its own that holds the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(REPO, ".scratch")


def main():
    call, jobs = sys.argv[1], sys.argv[2:]
    out = os.path.join(REPO, "chiprun_out", call)
    os.makedirs(out, exist_ok=True)
    for i, job in enumerate(jobs):
        side, cell, seed, trace = job.split(":")
        cwd = os.path.join(HERE, side)
        tag = f"{i:02d}_{side}_{cell}_{seed}_t{trace}"
        t0 = time.time()
        with open(os.path.join(out, tag + ".err"), "w") as err:
            p = subprocess.run(
                ["python3", "perfbench/run.py", "--workload", cell, "--seed",
                 seed, "--seconds", "40", "--trace", trace],
                cwd=cwd, stdout=subprocess.PIPE, stderr=err, text=True)
        lines = [l for l in p.stdout.splitlines() if l.strip()]
        with open(os.path.join(out, tag + ".out"), "w") as f:
            f.write(p.stdout)
        rec = {"job": job, "rc": p.returncode, "s": round(time.time() - t0, 1)}
        try:
            res = json.loads(lines[-1])
            rec["correct"] = res.get("correct")
            rec["failed"] = res.get("failed")
            rec["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
            rec["device"] = res.get("device")
            if "breakdown" in res:
                rec["breakdown"] = res["breakdown"]
            extra = json.loads(lines[-2])
            rec["all_values"] = extra.get("all_values")
            rec["correctness"] = extra.get("correctness")
            rec["setup_parts"] = extra.get("setup_parts")
        except Exception as e:  # noqa: BLE001
            rec["parse_error"] = repr(e)
        tmp = os.path.join(cwd, ".perfbench_tmp", cell)
        for name in ("window.json", "reduced.json"):
            src = os.path.join(tmp, name)
            if os.path.exists(src) and (name == "window.json") != (trace == "1"):
                shutil.copy(src, os.path.join(out, tag + "." + name))
        with open(os.path.join(out, "results.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        brief = {k: rec.get(k) for k in ("job", "rc", "s", "correct", "failed",
                                         "parse_error")}
        m = rec.get("metrics") or {}
        brief["m"] = {k: round(v, 3) for k, v in m.items()}
        av = rec.get("all_values") or {}
        brief["av"] = {k: round(v, 3) for k, v in av.items()
                       if k in ("itl_p50_ms", "itl_p95_ms", "output_tok_s")}
        print(json.dumps(brief), flush=True)
        if rec["rc"] != 0:
            with open(os.path.join(out, tag + ".err")) as f:
                print(f.read()[-3000:], flush=True)


if __name__ == "__main__":
    main()
