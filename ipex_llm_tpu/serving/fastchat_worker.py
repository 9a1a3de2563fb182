"""FastChat model worker speaking the controller protocol.

Reference counterpart: serving/fastchat/ipex_llm_worker.py — a worker that
registers with a FastChat controller, heartbeats its queue length, and
streams NUL-delimited JSON chunks ({"text": cumulative, "error_code": 0,
"usage": {...}, "finish_reason": ...}) from /worker_generate_stream
(reference ipex_llm_worker.py:266-414 protocol).  Here generation runs on
the paged continuous-batching TPU engine instead of a HF generate thread,
so one worker process serves concurrent requests.

Run:  python -m ipex_llm_tpu.serving.fastchat_worker --model-path <ckpt> \
          --controller-address http://localhost:21001
(--no-register for standalone use, e.g. tests.)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import uuid

from aiohttp import web

from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                         ServingEngine, next_stream_item)
from ipex_llm_tpu.serving.faults import EngineOverloaded
from ipex_llm_tpu.serving.observe import Tracer, parse_traceparent

HEARTBEAT_INTERVAL_S = 45.0

# FastChat protocol error codes (fastchat.constants.ErrorCode peers): the
# controller retries another worker on 50301/50302; 50001 is internal.
ERROR_CODE_INTERNAL = 50001
ERROR_CODE_OVERLOADED = 50302
ERROR_CODE_TIMEOUT = 50300


class FastChatWorker:
    def __init__(self, model, tokenizer, model_names: list[str],
                 controller_addr: str | None = None,
                 worker_addr: str = "http://localhost:21002",
                 limit_worker_concurrency: int = 8,
                 engine_config: EngineConfig | None = None,
                 drain_timeout_s: float = 30.0):
        self.tok = tokenizer
        self.drain_timeout_s = drain_timeout_s
        self.model_names = model_names
        self.controller_addr = controller_addr
        self.worker_addr = worker_addr
        self.worker_id = uuid.uuid4().hex[:8]
        self.limit = limit_worker_concurrency
        self.call_ct = 0
        self.in_flight = 0
        eos = model.generation_config.eos_token_id
        self._eos = tuple(eos) if isinstance(eos, (list, tuple)) else (
            (eos,) if eos is not None else ())
        self.engine = ServingEngine(
            model.config, model.params,
            engine_config or EngineConfig(
                max_rows=limit_worker_concurrency),
            default_eos=self._eos,
            mesh=getattr(model, "mesh", None),
        ).start()
        self.app = web.Application()
        self.app.add_routes([
            web.post("/worker_generate_stream", self.api_generate_stream),
            web.post("/worker_generate", self.api_generate),
            web.post("/worker_get_status", self.api_get_status),
            web.post("/count_token", self.api_count_token),
            web.post("/model_details", self.api_model_details),
            web.post("/worker_get_conv_template", self.api_conv_template),
            # observability surface (serving/observe.py): the same
            # /trace + /debug/flight views api_server exposes, so a
            # FastChat fleet is traceable/postmortem-able too
            web.get("/trace/{trace_id}", self.api_trace),
            web.get("/debug/flight", self.api_flight),
            # device-time observatory (serving/perfwatch.py): the perf
            # block api_server serves under /health — a FastChat worker's
            # recompile sentinel and MFU join are inspectable without the
            # OpenAI surface
            web.get("/debug/perf", self.api_perf),
        ])
        # graceful drain on SIGTERM (reference workers restart-on-error;
        # here the replica finishes in-flight requests before exiting)
        self.app.on_shutdown.append(self._on_shutdown)

    async def _on_shutdown(self, app):
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.engine.drain,
                                   self.drain_timeout_s)
        self.engine.stop()

    # -- controller protocol ------------------------------------------------

    def status(self) -> dict:
        # queue_length feeds the controller's least-loaded routing.
        # in_flight counts each stream for its WHOLE lifetime — engine
        # queue wait included — so adding engine.queue_depth would count
        # queued requests twice and make this worker look busier than it
        # is.
        return {"model_names": self.model_names, "speed": 1,
                "queue_length": self.in_flight}

    async def register(self, session) -> None:
        await session.post(
            self.controller_addr + "/register_worker",
            json={"worker_name": self.worker_addr, "check_heart_beat": True,
                  "worker_status": self.status()},
        )

    async def heartbeat_loop(self) -> None:
        import aiohttp

        async with aiohttp.ClientSession() as session:
            try:
                await self.register(session)
            except Exception:
                pass
            while True:
                await asyncio.sleep(HEARTBEAT_INTERVAL_S)
                try:
                    r = await session.post(
                        self.controller_addr + "/receive_heart_beat",
                        json={"worker_name": self.worker_addr,
                              "queue_length": self.in_flight},
                    )
                    if not (await r.json()).get("exist", True):
                        await self.register(session)
                except Exception:
                    pass  # controller down: keep serving, retry next beat

    # -- generation ---------------------------------------------------------

    def _make_request(self, params: dict) -> tuple[Request, int]:
        prompt = params["prompt"]
        ids = self.tok(prompt)["input_ids"]
        stop = params.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        stop_ids = tuple(params.get("stop_token_ids") or ())
        temperature = float(params.get("temperature", 1.0))
        if not bool(params.get("do_sample", temperature > 0)):
            temperature = 0.0
        tk = int(params.get("top_k", -1))
        # W3C trace context rides the worker protocol's JSON params (the
        # protocol is body-shaped; HTTP callers may also put the header
        # value here) — the engine's spans then key to the caller's trace
        tp = parse_traceparent(params.get("traceparent"))
        req = Request(
            prompt_ids=list(map(int, ids)),
            max_new_tokens=int(params.get("max_new_tokens", 256)),
            temperature=temperature,
            top_p=float(params.get("top_p", 1.0)),
            top_k=0 if tk <= 0 else tk,
            eos_token_id=tuple(self._eos) + stop_ids,
            stop_strings=list(stop),
            trace_id=tp[0] if tp else None,
        )
        return req, len(ids)

    async def _next_tok(self, req: Request) -> int | None:
        """Bounded-wait token fetch via the engine's shared dead-engine-
        detecting protocol: fails the request with an error chunk instead
        of hanging the client."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, next_stream_item,
                                          self.engine, req)

    async def _stream_chunks(self, params: dict):
        """Yield the protocol's cumulative-text JSON chunks."""
        self.call_ct += 1
        self.in_flight += 1
        req = None
        try:
            req, n_in = self._make_request(params)
            echo = bool(params.get("echo", True))
            base = params["prompt"] if echo else ""
            try:
                self.engine.submit(req)
            except EngineOverloaded as e:
                # load-shed in the protocol's own shape: a non-zero
                # error_code chunk makes the controller retry elsewhere
                req = None
                yield {"text": f"worker overloaded: {e}",
                       "error_code": ERROR_CODE_OVERLOADED,
                       "finish_reason": "abort"}
                return
            toks: list[int] = []
            while True:
                tok = await self._next_tok(req)
                if tok is None:
                    break
                toks.append(tok)
                yield {
                    "text": base + self.tok.decode(
                        toks, skip_special_tokens=True),
                    "error_code": 0,
                    "usage": {"prompt_tokens": n_in,
                              "completion_tokens": len(toks),
                              "total_tokens": n_in + len(toks)},
                    "finish_reason": None,
                }
            shed = req.finish_reason == "abort" and not req.cancelled
            if req.finish_reason in ("error", "timeout") or shed:
                # drain-deadline shed surfaces as overloaded (non-zero
                # error_code -> the controller retries another worker),
                # never as a 200 with truncated text
                text, code = {
                    "timeout": ("request deadline exceeded",
                                ERROR_CODE_TIMEOUT),
                    "abort": ("worker draining: request aborted",
                              ERROR_CODE_OVERLOADED),
                }.get(req.finish_reason,
                      ("request failed in the engine", ERROR_CODE_INTERNAL))
                yield {"text": text, "error_code": code,
                       "finish_reason": req.finish_reason}
                return
            yield {
                "text": base + self.tok.decode(toks, skip_special_tokens=True),
                "error_code": 0,
                "usage": {"prompt_tokens": n_in,
                          "completion_tokens": len(toks),
                          "total_tokens": n_in + len(toks)},
                "finish_reason": req.finish_reason or "stop",
            }
        finally:
            self.in_flight -= 1
            # consumer vanished mid-stream (client disconnect raised out of
            # the generator): free the engine row instead of decoding the
            # rest of max_new_tokens into an orphaned queue
            if req is not None and req.finish_reason is None:
                self.engine.abort(req)

    # -- HTTP endpoints -----------------------------------------------------

    async def api_generate_stream(self, request: web.Request):
        params = await request.json()
        resp = web.StreamResponse()
        await resp.prepare(request)
        async for chunk in self._stream_chunks(params):
            await resp.write(json.dumps(chunk).encode() + b"\0")
        await resp.write_eof()
        return resp

    async def api_generate(self, request: web.Request):
        params = await request.json()
        last = None
        async for chunk in self._stream_chunks(params):
            last = chunk
        return web.json_response(last)

    async def api_get_status(self, request: web.Request):
        return web.json_response(self.status())

    async def api_count_token(self, request: web.Request):
        params = await request.json()
        n = len(self.tok(params["prompt"])["input_ids"])
        return web.json_response({"count": n, "error_code": 0})

    async def api_model_details(self, request: web.Request):
        ctx = getattr(self.engine.cfg, "max_position_embeddings", 4096)
        return web.json_response({"context_length": ctx})

    async def api_conv_template(self, request: web.Request):
        # templating lives client-side for this worker (one_shot default)
        return web.json_response({"conv": None})

    async def api_trace(self, request: web.Request):
        tid = request.match_info["trace_id"]
        tr = self.engine.trace_view(tid)
        if tr is None:
            return web.json_response(
                {"error": f"unknown trace {tid!r} (tracing disabled, or "
                          "aged out)", "error_code": ERROR_CODE_INTERNAL},
                status=404)
        if request.query.get("format") == "chrome":
            return web.json_response(Tracer.chrome_events([tr]))
        return web.json_response(tr)

    async def api_flight(self, request: web.Request):
        return web.json_response(self.engine.flight.view())

    async def api_perf(self, request: web.Request):
        return web.json_response({"perf": self.engine.perf_view(),
                                  "planner": self.engine.planner_view()})


def build_worker(model_path: str, low_bit: str = "sym_int4",
                 controller_addr: str | None = None,
                 worker_addr: str = "http://localhost:21002",
                 model_names: list[str] | None = None,
                 limit_worker_concurrency: int = 8,
                 drain_timeout_s: float = 30.0,
                 engine_config: EngineConfig | None = None) -> FastChatWorker:
    from transformers import AutoTokenizer

    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    # the serving stack owns the weight-width axis end to end: both
    # halves of the rule live in serving/engine.py — a pinned
    # EngineConfig.weight_qtype outranks low_bit for the LOAD
    # (resolve_load_low_bit), and the loaded width threads back into the
    # config for truthful /health (default_weight_qtype).  The max_rows
    # fallback must be applied BEFORE the defaulting rule so an absent
    # engine_config still sizes the engine to the worker's concurrency
    # limit (FastChatWorker's own `or` fallback never fires once a
    # config object exists).
    from ipex_llm_tpu.serving.engine import (default_weight_qtype,
                                             resolve_load_low_bit)

    load_q = resolve_load_low_bit(engine_config, low_bit)
    model = AutoModelForCausalLM.from_pretrained(model_path,
                                                 load_in_low_bit=load_q)
    tok = AutoTokenizer.from_pretrained(model_path, trust_remote_code=True)
    names = model_names or [model_path.rstrip("/").split("/")[-1]]
    ec = default_weight_qtype(
        engine_config or EngineConfig(max_rows=limit_worker_concurrency),
        load_q)
    return FastChatWorker(model, tok, names, controller_addr, worker_addr,
                          limit_worker_concurrency,
                          engine_config=ec,
                          drain_timeout_s=drain_timeout_s)


def main(argv=None):
    ap = argparse.ArgumentParser("ipex-llm-tpu FastChat model worker")
    ap.add_argument("--model-path", required=True)
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=21002)
    ap.add_argument("--controller-address", default="http://localhost:21001")
    ap.add_argument("--worker-address", default=None)
    ap.add_argument("--model-names", default=None)
    ap.add_argument("--limit-worker-concurrency", type=int, default=8)
    ap.add_argument("--weight-qtype", default=None, metavar="QTYPE",
                    help="serving weight width (default: --low-bit), "
                         "authoritative end to end: the checkpoint loads "
                         "at this width, full-width weights re-pack at "
                         "engine build, and the fused tick reads packed "
                         "codes with dequant fused into the matmul")
    ap.add_argument("--kv-storage", default="bf16",
                    choices=("bf16", "fp8"), metavar="FMT",
                    help="paged KV pool storage: bf16 (default) or fp8 "
                         "e5m2 (half the KV bytes, twice the pages per "
                         "byte budget; slightly lossy)")
    ap.add_argument("--kv-pool-bytes", type=int, default=0, metavar="BYTES",
                    help="KV pool byte budget (page count derived from "
                         "bytes / page size at --kv-storage width; 0 = "
                         "auto page sizing)")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="on-device prompt-lookup speculative serving "
                         "(reference ipex_llm_worker `speculative` load "
                         "flag): draft/verify/accept up to K candidates "
                         "per row per decode step inside the fused tick; "
                         "composes with --decode-horizon")
    ap.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                    help="longest n-gram the speculative lookup proposer "
                         "matches against the row's token history")
    ap.add_argument("--decode-horizon", type=int, default=1, metavar="H",
                    help="fused multi-step decode: H decode steps per "
                         "device program, one host sync per H tokens")
    ap.add_argument("--planner", default="mpc", choices=("mpc", "static"),
                    help="tick planner (serving/planner.py): mpc (default) "
                         "re-picks the tick shape — chunk budget, decode "
                         "horizon, spec widths, admission — per tick for "
                         "deadline goodput, within the locked grid; "
                         "static = the fixed-knob escape hatch "
                         "(bit-identical to the pre-planner engine)")
    ap.add_argument("--trace", action="store_true",
                    help="request-lifecycle tracing (per-request spans "
                         "staged in the transactional tick; /trace/{id} "
                         "and the caller's traceparent honored)")
    ap.add_argument("--no-register", action="store_true")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    metavar="SECONDS",
                    help="graceful-drain window on SIGTERM: stop admission, "
                         "finish in-flight requests, abort stragglers")
    args = ap.parse_args(argv)
    worker_addr = args.worker_address or f"http://localhost:{args.port}"
    names = args.model_names.split(",") if args.model_names else None
    w = build_worker(args.model_path, args.low_bit,
                     None if args.no_register else args.controller_address,
                     worker_addr, names, args.limit_worker_concurrency,
                     drain_timeout_s=args.drain_timeout,
                     engine_config=EngineConfig(
                         max_rows=args.limit_worker_concurrency,
                         weight_qtype=args.weight_qtype,
                         kv_storage=args.kv_storage,
                         kv_pool_bytes=args.kv_pool_bytes,
                         spec_k=args.spec_k, spec_ngram=args.spec_ngram,
                         decode_horizon=args.decode_horizon,
                         trace_requests=args.trace,
                         planner=args.planner))
    if w.controller_addr:
        async def on_start(app):
            app["hb"] = asyncio.create_task(w.heartbeat_loop())

        w.app.on_startup.append(on_start)
    web.run_app(w.app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
