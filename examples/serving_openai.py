"""OpenAI-compatible server over the paged continuous-batching engine.

Reference counterpart: the FastAPI serving quickstarts
(docs/mddocs/Quickstart/fastapi_quickstart + vllm docker quickstarts).

    python examples/serving_openai.py [--model PATH] [--port 8000]

then:

    curl http://127.0.0.1:8000/v1/chat/completions -H 'Content-Type: application/json' \
      -d '{"model": "local", "messages": [{"role": "user", "content": "hi"}], "max_tokens": 16}'

Streaming (SSE) works with ``"stream": true``; `/metrics` exposes engine
counters including paged-KV ``pages_in_use`` and prefix-cache hits.
"""

import sys

from _tiny_model import tiny_checkpoint


def main():
    from ipex_llm_tpu.serving.api_server import main as serve_main

    argv = sys.argv[1:]
    if "--model" not in " ".join(argv):
        argv = ["--model", tiny_checkpoint()] + argv
    serve_main(argv)


if __name__ == "__main__":
    main()
