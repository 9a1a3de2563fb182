"""Replica-fault-tolerant serving: a router over N engine replicas.

Reference counterpart: the FastChat controller + worker quickstart
(docs/mddocs/Quickstart/fastchat_quickstart) — but with failover
semantics the controller tier lacks: health-driven ejection and
reinstatement, zero-token failover replay, terminal error objects for
mid-stream replica deaths, and rolling drain/restart.

    python examples/replica_fleet.py [--model PATH] [--replicas 3] \
        [--router-port 8080]

then (the surface is the same as a single replica):

    curl http://127.0.0.1:8080/v1/completions -H 'Content-Type: application/json' \
      -d '{"prompt": "hello", "max_tokens": 16}'
    curl http://127.0.0.1:8080/health    # aggregated per-replica view
    curl http://127.0.0.1:8080/metrics   # Prometheus-style fleet scrape
"""

import sys

from _tiny_model import tiny_checkpoint


def main():
    from ipex_llm_tpu.serving.router import main as router_main

    argv = sys.argv[1:]
    joined = " ".join(argv)
    if "--model" not in joined:
        argv = ["--model", tiny_checkpoint()] + argv
    if "--replicas" not in joined:
        argv = ["--replicas", "3"] + argv
    router_main(argv)


if __name__ == "__main__":
    main()
