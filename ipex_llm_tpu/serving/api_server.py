"""OpenAI-compatible HTTP server (aiohttp) over the ServingEngine.

Reference counterpart: serving/fastapi/api_server.py:90 (+openai_protocol.py)
— same endpoints (`/v1/chat/completions`, `/v1/completions`, `/v1/models`),
same SSE streaming shape (``data: {chunk}\\n\\n`` … ``data: [DONE]``).
FastAPI isn't available in this image; aiohttp.web provides the async server.

Run: ``python -m ipex_llm_tpu.serving.api_server --model <dir> --port 8000``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time
import uuid
from typing import Any

try:
    from aiohttp import web
except ImportError as _e:  # pragma: no cover
    web = None
    _AIOHTTP_ERR = _e

from jax.profiler import TraceAnnotation

from ipex_llm_tpu.serving.engine import (EngineConfig, Request,
                                         ServingEngine, next_stream_item,
                                         stream_stamp)
from ipex_llm_tpu.serving.faults import EngineOverloaded
from ipex_llm_tpu.serving.kv_transport import TransportError
from ipex_llm_tpu.serving.observe import (LATENCY_BUCKETS_S, Histogram,
                                          Tracer, parse_traceparent)


def _now() -> int:
    return int(time.time())


def _req_failed(req: Request) -> bool:
    """True when the request's terminal state is a server-side failure the
    client must see as an error object: an engine fault, an expired
    deadline, or a server-initiated abort (drain-deadline shed).  A
    client-initiated abort (``req.cancelled`` — disconnect or stop-string)
    is not a failure: the client asked for it."""
    return (req.finish_reason in ("error", "timeout")
            or (req.finish_reason == "abort" and not req.cancelled))


class OpenAIServer:
    def __init__(self, engine: ServingEngine, tokenizer, model_name: str,
                 asr=None, drain_timeout_s: float = 30.0,
                 kv_import_token: str | None = None,
                 profile_dir: str | None = None):
        if web is None:  # pragma: no cover
            raise ImportError(f"aiohttp is required for serving: {_AIOHTTP_ERR}")
        self.engine = engine
        self.tok = tokenizer
        self.model_name = model_name
        self.drain_timeout_s = drain_timeout_s
        # shared-token authn for /kv/import (X-KV-Import-Token): the wire
        # format's checksum proves INTEGRITY, not identity — without a
        # token any caller that can reach the port can scatter
        # checksum-consistent garbage into the shared prefix cache and
        # poison every future prefix hit.  None = open (single-tenant /
        # loopback deployments).
        self.kv_import_token = kv_import_token
        # /debug/profile capture target (a fresh temp dir per capture
        # when None)
        self.profile_dir = profile_dir
        # replica identity for the router tier: a stable uuid for this
        # server's lifetime (a restart mints a new one — that is the
        # point: the router can tell "same process recovered" from
        # "process was replaced"), plus the uptime epoch the /health
        # replica block reports against
        self.replica_id = uuid.uuid4().hex
        self.started_monotonic = time.monotonic()
        # asr = (whisper model, feature extractor, tokenizer) enabling the
        # OpenAI audio surface (the reference serves whisper through its
        # workers; SURVEY L6 lists the audio endpoint)
        self.asr = asr
        # a token's way from the engine's commit to the socket, measured
        # on the event-loop thread from the stamp the commit left beside
        # the token (engine.stream_stamp): per written event, the seconds
        # until this coroutine resumed with it (wake), until its write
        # returned (deliver), and the thread's CPU time from the one to
        # the other (decode + render + write).  Monotonic, and NOT
        # rollback-covered: a write happened.  The CPU clock is a system
        # call (6.2 us on the chip's host; PERF.md section 6, PR 42): it
        # is read for one item in _CPU_SAMPLE and counted that many times
        self._stream_items = 0
        self.stream_counters = {"stream_events_total": 0,
                                "stream_wake_s_total": 0.0,
                                "stream_deliver_s_total": 0.0,
                                "stream_cpu_s_total": 0.0}
        self.stream_deliver_s = Histogram(LATENCY_BUCKETS_S)
        # client_max_size: aiohttp's 1 MiB default would 413 every
        # realistically-sized /kv/import page-set blob (one 7B-shaped
        # bf16 page is ~64 MiB) and silently break the disaggregated
        # handoff in production; 1 GiB bounds a whole long-prompt page
        # set while still refusing pathological bodies
        self.app = web.Application(client_max_size=1 << 30)
        self.app.router.add_post("/v1/chat/completions", self.chat)
        self.app.router.add_post("/v1/completions", self.completions)
        self.app.router.add_get("/v1/models", self.models)
        self.app.router.add_get("/health", self.health)
        self.app.router.add_get("/metrics", self.metrics)
        # TGI-protocol surface (reference serving/fastchat/tgi_api_server.py)
        self.app.router.add_post("/generate", self.tgi_generate)
        self.app.router.add_post("/generate_stream", self.tgi_generate_stream)
        # transportable-KV surface (disaggregated prefill/decode): the
        # router's handoff orchestration drives these two legs
        self.app.router.add_post("/kv/prefill", self.kv_prefill)
        self.app.router.add_post("/kv/import", self.kv_import)
        # observability surface (serving/observe.py): per-request traces
        # (assembled fleet-wide by the router), the tick flight recorder,
        # and an operational jax.profiler capture window
        self.app.router.add_get("/trace/{trace_id}", self.trace_get)
        self.app.router.add_get("/debug/traces", self.traces_export)
        self.app.router.add_get("/debug/flight", self.debug_flight)
        self.app.router.add_get("/debug/profile", self.debug_profile)
        if asr is not None:
            self.app.router.add_post("/v1/audio/transcriptions",
                                     self.transcriptions)
        # graceful drain on SIGTERM/SIGINT: aiohttp's run_app shutdown
        # sequence awaits on_shutdown before tearing connections down, so
        # in-flight requests finish inside the drain window while /health
        # reports "draining"
        self.app.on_shutdown.append(self._on_shutdown)

    async def _on_shutdown(self, app):
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.engine.drain,
                                   self.drain_timeout_s)
        self.engine.stop()

    # -- helpers ------------------------------------------------------------

    def _encode_chat(self, messages: list[dict]) -> list[int]:
        if hasattr(self.tok, "apply_chat_template") and getattr(
            self.tok, "chat_template", None
        ):
            return list(self.tok.apply_chat_template(
                messages, add_generation_prompt=True
            ))
        text = "\n".join(f"{m['role']}: {m['content']}" for m in messages)
        text += "\nassistant:"
        return list(self.tok(text)["input_ids"])

    def _mk_request(self, body: dict, prompt_ids: list[int],
                    headers=None) -> Request:
        def num(key, default, cast):
            v = body.get(key)
            return cast(default if v is None else v)

        # W3C trace context: the real HTTP header wins (curl/OTel
        # clients), the body field is the router's transport-agnostic
        # carrier (HTTPBackend promotes it to the header; scripted
        # backends deliver it in-body) — either way the engine's spans
        # key to the caller's trace id and /trace assembles end to end
        tp = parse_traceparent((headers or {}).get("traceparent")
                               or body.get("traceparent"))

        eos: tuple[int, ...] = ()
        if self.tok.eos_token_id is not None:
            eos = (int(self.tok.eos_token_id),)
        req = Request(
            prompt_ids=prompt_ids,
            max_new_tokens=num("max_tokens", 128, int),
            # OpenAI API defaults: temperature=1.0, top_p=1.0 (clients
            # relying on the documented default expect sampled output)
            temperature=num("temperature", 1.0, float),
            top_p=num("top_p", 1.0, float),
            top_k=int(body.get("top_k") or 0),
            seed=(int(body["seed"]) if body.get("seed") is not None
                  else None),
            eos_token_id=eos,
            request_id=str(uuid.uuid4()),
            # per-request wall-clock budget (queue wait + generation); the
            # router stamps each failover attempt's REMAINING budget here,
            # so a deadline spans attempts instead of resetting per replica
            deadline_s=(float(body["deadline_s"])
                        if body.get("deadline_s") else None),
            trace_id=tp[0] if tp else None,
        )
        stop = body.get("stop")
        req.stop_strings = ([stop] if isinstance(stop, str) else stop) or []
        return req

    @staticmethod
    def _find_stop(text: str, stops: list[str]) -> int:
        """Earliest stop-sequence offset in ``text``, or -1."""
        hits = [text.find(s) for s in stops if s and text.find(s) >= 0]
        return min(hits) if hits else -1

    # Internal finish reasons: engine "stop" (EOS) / "length" / "abort" /
    # "error" (quarantined or engine failure) / "timeout" (deadline), plus
    # server-side "stop_string" for stop-sequence truncation.  The OpenAI
    # surface maps stop_string -> "stop" and surfaces error/timeout — and
    # a server-initiated abort (drain-deadline shed, _req_failed) — as
    # JSON error objects (HTTP 500/408/503, or a terminal SSE error
    # event); the TGI surface maps stop -> "eos_token", stop_string ->
    # "stop_sequence" and failures to its {"error", "error_type"} shape.
    @staticmethod
    def _openai_reason(fr: str | None) -> str | None:
        return "stop" if fr == "stop_string" else fr

    def _retry_after_s(self, e: EngineOverloaded) -> int:
        """Honest Retry-After for a shed submission.  Draining: the rest
        of the drain window (by then the replica has restarted or shed
        everything).  Queue full: the backlog in units of engine waves —
        a queue of depth D in front of an R-row engine clears in about
        D/R admission waves; clamped to [1, 30] so a burst never tells
        clients to go away for minutes."""
        if e.draining:
            return max(1, int(self.engine.drain_seconds_left) + 1)
        rows = max(1, self.engine.ec.max_rows)
        return max(1, min(30, -(-e.queue_depth // rows)))

    def _submit(self, req: Request) -> Request:
        """Engine submit with load-shedding mapped onto HTTP: a full
        bounded queue is 429 (retryable overload), a draining engine is
        503 (this replica is going away) — both as OpenAI-style error
        objects with Retry-After derived from queue depth / the drain
        deadline (the router's backpressure signal)."""
        try:
            return self.engine.submit(req)
        except EngineOverloaded as e:
            body = json.dumps({"error": {
                "message": str(e),
                "type": "overloaded_error",
                "code": "engine_draining" if e.draining else "queue_full",
                "queue_depth": e.queue_depth,
            }})
            cls = (web.HTTPServiceUnavailable if e.draining
                   else web.HTTPTooManyRequests)
            raise cls(text=body, content_type="application/json",
                      headers={"Retry-After": str(self._retry_after_s(e))})

    @staticmethod
    def _error_payload(req: Request) -> dict:
        if req.finish_reason == "timeout":
            return {"error": {"message": "request deadline exceeded "
                                         "(queue wait + generation)",
                              "type": "timeout_error", "code": "timeout"}}
        if req.finish_reason == "abort":
            return {"error": {"message": "request aborted: server "
                                         "draining (retry elsewhere)",
                              "type": "unavailable_error",
                              "code": "server_draining"}}
        return {"error": {"message": "request failed in the engine "
                                     "(isolated fault)",
                          "type": "server_error", "code": "error"}}

    def _error_response(self, req: Request):
        status = {"timeout": 408, "abort": 503}.get(req.finish_reason, 500)
        return web.json_response(self._error_payload(req), status=status)

    async def _next_tok(self, req: Request) -> int | None:
        """One token from the stream queue via the engine's shared
        dead-engine-detecting fetch (replaces the queue.get-with-no-
        timeout hang)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, next_stream_item,
                                          self.engine, req)

    async def _first_tok(self, request, req: Request) -> int | None:
        """The wait for a stream's FIRST token, which a long prompt makes
        long: a client that goes away meanwhile is noticed HERE, and its row
        freed, not at the first write after the whole prompt was prefilled
        for nobody (64 abandoned 3,000-token prompts held every row of the
        engine for a minute: my chip run, PR 37)."""
        pending = asyncio.ensure_future(self._next_tok(req))
        while True:
            done, _ = await asyncio.wait({pending}, timeout=0.25)
            if done:
                return pending.result()
            transport = request.transport
            if transport is None or transport.is_closing():
                # the engine ends the stream with None, which lets the
                # fetch's worker thread go
                self.engine.abort(req)
                raise ConnectionResetError("the client went away while its "
                                           "prompt was prefilling")

    # one stream item in this many has its CPU time read: a prime, so that
    # the sampled place moves through the rows of a tick (16, 32, 64)
    _CPU_SAMPLE = 13

    def _woke(self, req: Request) -> tuple[float | None, float, float | None]:
        """Called where a coroutine resumes with a stream item: the
        commit's stamp for it (None: put outside a commit), the wall
        clock now and, for a sampled item, this thread's CPU clock."""
        self._stream_items += 1
        sampled = self._stream_items % self._CPU_SAMPLE == 0
        return (stream_stamp(req), time.perf_counter(),
                time.thread_time() if sampled else None)

    def _delivered(self, woke: tuple[float | None, float, float | None]):
        """One token event has left for its consumer (``resp.write``
        returned; ``_collect`` has it in its list): count its way."""
        stamp, t_wake, cpu_wake = woke
        if stamp is None:
            return
        deliver = time.perf_counter() - stamp
        c = self.stream_counters
        c["stream_events_total"] += 1
        c["stream_wake_s_total"] += t_wake - stamp
        c["stream_deliver_s_total"] += deliver
        if cpu_wake is not None:
            c["stream_cpu_s_total"] += self._CPU_SAMPLE * (
                time.thread_time() - cpu_wake)
        self.stream_deliver_s.observe(deliver)

    async def _collect(self, req: Request) -> str:
        toks: list[int] = []
        drop = set(req.eos_token_id)
        stops = getattr(req, "stop_strings", [])
        while True:
            tok = await self._next_tok(req)
            if tok is None:
                break
            woke = self._woke(req)
            if tok in drop:
                continue
            toks.append(tok)
            if stops:
                text = self.tok.decode(toks)
                cut = self._find_stop(text, stops)
                if cut >= 0:
                    self.engine.abort(req)
                    req.finish_reason = "stop_string"
                    return text[:cut]
            self._delivered(woke)
        return self.tok.decode(toks)

    async def _stream_sse(self, request, req: Request, chunk_fn,
                          final_fn=None, send_done: bool = True,
                          error_fn=None):
        """Shared SSE streaming loop (OpenAI and TGI surfaces).

        ``chunk_fn(piece, finish, tok)`` renders one incremental event;
        ``final_fn(sent_text, finish_reason)`` (optional) renders the
        terminal event instead of ``chunk_fn("", finish, None)``;
        ``error_fn(req)`` (optional) renders the terminal error event for
        an "error"/"timeout" finish in the surface's own error shape."""
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)
        drop = set(req.eos_token_id)
        stops = getattr(req, "stop_strings", [])
        sent = ""
        toks: list[int] = []
        try:
            while True:
                tok = await (self._next_tok(req) if toks
                             else self._first_tok(request, req))
                if tok is None:
                    break
                woke = self._woke(req)
                if tok in drop:
                    continue
                toks.append(tok)
                text = self.tok.decode(toks)
                cut = self._find_stop(text, stops) if stops else -1
                if cut >= 0:
                    piece, done = text[:cut][len(sent):], True
                else:
                    piece, done = text[len(sent):], False
                if piece:
                    sent += piece
                    # on the host plane of a profiler capture, one clock
                    # with the engine's spans and the device's operations
                    with TraceAnnotation("server/stream_write"):
                        await resp.write(
                            f"data: "
                            f"{json.dumps(chunk_fn(piece, None, tok))}\n\n"
                            .encode()
                        )
                    self._delivered(woke)
                if done:
                    self.engine.abort(req)
                    req.finish_reason = "stop_string"
                    break
            if _req_failed(req):
                # terminal error event (the stream already carries a 200
                # status line; an error object in the stream is the OpenAI
                # streaming convention)
                err = (error_fn or self._error_payload)(req)
                await resp.write(f"data: {json.dumps(err)}\n\n".encode())
            else:
                final = (final_fn(sent, req.finish_reason) if final_fn
                         else chunk_fn("", req.finish_reason, None))
                await resp.write(f"data: {json.dumps(final)}\n\n".encode())
            if send_done:
                await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: free the engine row instead of decoding on
            self.engine.abort(req)
            raise
        return resp

    # -- endpoints ----------------------------------------------------------

    async def chat(self, request):
        body = await request.json()
        ids = self._encode_chat(body.get("messages", []))
        rf = body.get("response_format") or {}
        if rf.get("type") in ("json_object", "json_schema"):
            # constrained decoding runs the offline validator-filtered path
            # (structured.py), bypassing the batch engine
            return await self._chat_json(body, ids)
        req = self._submit(self._mk_request(body, ids,
                                            request.headers))
        rid = f"chatcmpl-{req.request_id[:12]}"

        if body.get("stream"):
            def chunk(piece: str, finish, tok=None):
                delta = {"content": piece} if piece else {}
                return {
                    "id": rid, "object": "chat.completion.chunk",
                    "created": _now(), "model": self.model_name,
                    "choices": [{"index": 0, "delta": delta,
                                 "finish_reason":
                                     self._openai_reason(finish)}],
                }
            return await self._stream_sse(request, req, chunk)

        text = await self._collect(req)
        if _req_failed(req):
            return self._error_response(req)
        return web.json_response({
            "id": rid, "object": "chat.completion", "created": _now(),
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": self._openai_reason(req.finish_reason),
            }],
            "usage": {
                "prompt_tokens": len(req.prompt_ids),
                "completion_tokens": len(req.output_ids),
                "total_tokens": len(req.prompt_ids) + len(req.output_ids),
            },
        })

    async def _chat_json(self, body: dict, ids: list[int]):
        rf = body.get("response_format") or {}
        import asyncio as _asyncio

        from ipex_llm_tpu.structured import generate_json

        loop = _asyncio.get_running_loop()
        text = await loop.run_in_executor(
            None,
            lambda: generate_json(
                self.engine.cfg, self.engine.params, self.tok, ids,
                max_new_tokens=int(body.get("max_tokens") or 256),
                schema=(rf.get("json_schema") or {}).get("schema"),
            ),
        )
        return web.json_response({
            "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
            "object": "chat.completion", "created": _now(),
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": "stop",
            }],
            "usage": {"prompt_tokens": len(ids)},
        })

    async def completions(self, request):
        body = await request.json()
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0]
        ids = list(self.tok(prompt)["input_ids"])
        req = self._submit(self._mk_request(body, ids,
                                            request.headers))
        rid = f"cmpl-{req.request_id[:12]}"

        if body.get("stream"):
            def chunk(piece: str, finish, tok=None):
                return {
                    "id": rid, "object": "text_completion", "created": _now(),
                    "model": self.model_name,
                    "choices": [{"index": 0, "text": piece,
                                 "finish_reason":
                                     self._openai_reason(finish)}],
                }
            return await self._stream_sse(request, req, chunk)

        text = await self._collect(req)
        if _req_failed(req):
            return self._error_response(req)
        choice = {"index": 0, "text": text,
                  "finish_reason": self._openai_reason(req.finish_reason)}
        if body.get("logprobs"):
            # chosen-token logprobs (top-alternatives not tracked)
            choice["logprobs"] = {
                "tokens": [self.tok.decode([t]) for t in req.output_ids],
                "token_logprobs": [round(lp, 6) for lp in req.logprobs],
                "top_logprobs": None,
                "text_offset": [],
            }
        return web.json_response({
            "id": rid, "object": "text_completion", "created": _now(),
            "model": self.model_name,
            "choices": [choice],
            "usage": {
                "prompt_tokens": len(req.prompt_ids),
                "completion_tokens": len(req.output_ids),
                "total_tokens": len(req.prompt_ids) + len(req.output_ids),
            },
        })

    async def models(self, request):
        return web.json_response({
            "object": "list",
            "data": [{"id": self.model_name, "object": "model",
                      "owned_by": "ipex_llm_tpu"}],
        })

    async def health(self, request):
        """Liveness that actually reflects the engine (failure-detection
        surface, SURVEY §5): dead engine thread -> 503; recent step errors
        surface as degraded; a draining engine (SIGTERM received, letting
        in-flight requests finish) reports "draining" so load balancers
        stop routing to this replica."""
        thread = self.engine._thread
        if thread is None or not thread.is_alive():
            return web.json_response(
                {"status": "dead", "error": "engine thread not running"},
                status=503)
        body = {"status": "ok"}
        last = self.engine.metrics.get("last_error")
        if last:
            body = {"status": "degraded", "last_error": str(last)}
        if self.engine.draining:
            body["status"] = "draining"
        # replica identity + liveness for the router tier: a stable uuid
        # (new per server start — distinguishes "recovered" from
        # "replaced"), uptime, and the committed-tick counter — `ticks`
        # frozen while `uptime_s` advances is the wedged-replica signal
        body["replica"] = {
            "replica_id": self.replica_id,
            "uptime_s": round(time.monotonic() - self.started_monotonic, 3),
            "ticks": self.engine.metrics.get("ticks", 0),
        }
        # host-sync economics of the fused decode horizon: tokens emitted
        # per blocking device->host sync, total seconds blocked, and the
        # horizon the last fused step actually ran (page pressure can
        # shorten it below EngineConfig.decode_horizon)
        m = self.engine.metrics
        body["decode"] = {
            "tokens_per_sync": m.get("tokens_per_sync", 0.0),
            "host_sync_s": m.get("host_sync_s", 0.0),
            "decode_horizon_effective": m.get("decode_horizon_effective", 0),
            # admission-wave economics (the mixed prefill+decode step):
            # mixed ticks run, prompt tokens batched per tick, and the
            # rolling TTFT p95 the step is sized against
            "mixed_steps": m.get("mixed_steps", 0),
            "mixed_fused_steps": m.get("mixed_fused_steps", 0),
            "prefill_tokens_per_step": m.get("prefill_tokens_per_step", 0.0),
            "ttft_p95_s": m.get("ttft_p95_s", 0.0),
        }
        # KV-pool economics: storage format and byte footprint, occupancy,
        # and the pressure trace (prefix-cache LRU evictions, allocation-
        # failure clamps) — what the fp8-vs-bf16 fixed-budget story is
        # operated on (capacity planning reads pool_bytes/pages_total,
        # incident triage reads the clamp/eviction counters)
        body["kv"] = self.engine.kv_stats()
        # weight-pool economics, side by side with the kv block: the two
        # byte lines (weights.weight_bytes + kv.pool_bytes) are the one
        # HBM budget an operator provisions — int4 weights hand their
        # saved bytes to the KV pool (more pages, more concurrent rows at
        # the same cap)
        body["weights"] = self.engine.weight_stats()
        # multi-chip routing: which tick serves this mesh (the fully-
        # manual shard_map tick vs the per-op GSPMD fallback, with the
        # recorded reason), the collective wire family, and the per-shard
        # KV split — the first thing to read when a tp engine is slower
        # than expected (a silent GSPMD fallback looks like a perf bug)
        if self.engine.mesh is not None:
            eng = self.engine
            # per-shard bytes off the REAL placement (shard_paged_cache
            # head-shards the pool on the GSPMD path too, when heads
            # divide — dividing by tp only under the manual tick would
            # overreport fallback engines by tp)
            shard_bytes = (eng.cache.k.addressable_shards[0].data.nbytes
                           + eng.cache.v.addressable_shards[0].data.nbytes)
            body["parallel"] = {
                "mesh": dict(eng.mesh.shape),
                "tp_manual": eng._tp_manual,
                "tp_fallback_reason": eng._tp_fallback_reason,
                "collective_qtype": eng._collective_qtype,
                "kv_pool_bytes_per_shard": int(shard_bytes),
            }
        # device-time observatory (serving/perfwatch.py): per-family
        # attribution buckets + MFU/roofline join, and the recompile
        # sentinel — compiles_warm or compiles_out_of_grid advancing
        # mid-serving is the first thing to read when tick latency
        # develops multi-second spikes (a shape-driven recompile is
        # invisible in every other series)
        perf = self.engine.perf_view()
        if perf is not None:
            body["perf"] = perf
        # fault-domain observability: admission backlog vs the bound (what
        # a 429 means), per-request failures isolated by bisection,
        # transient step retries, load-shed and deadline-expired counts
        body["fault_domain"] = {
            "queue_depth": self.engine.queue_depth,
            "max_queue": self.engine.ec.max_queue,
            "errors_isolated": m.get("errors_isolated", 0),
            "retries": m.get("retries", 0),
            "rejected": m.get("rejected", 0),
            "timeouts": m.get("timeouts", 0),
        }
        # speculative-decoding economics (spec_k > 0 engines): draft
        # counts, the rolling accept rate the operator tunes spec_k /
        # spec_ngram against, and tokens emitted per spec-tick dispatch —
        # the on-device draft+verify+accept loop's amortization story
        if self.engine.ec.spec_k > 0:
            body["spec"] = self.engine.spec_stats()
        # tick planner (serving/planner.py): the decide half of the
        # observe->decide loop — last plan, per-reason decision counts,
        # measured per-family step rates, and the deadline-miss rate the
        # planner is optimizing against.  mode "static" = the escape
        # hatch (PR 15 behavior, bit-identical)
        body["planner"] = self.engine.planner_view()
        return web.json_response(body)

    def _metrics_numeric(self) -> dict:
        """Flat numeric counter/gauge map: engine metrics + kv_ pool stats
        + replica liveness — the per-replica series the router aggregates."""
        # dict() first: a single GIL-atomic copy — the engine thread
        # inserts new counter keys at runtime, and iterating the live
        # dict races a "changed size during iteration" 500
        out = {k: v for k, v in dict(self.engine.metrics).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
        for k, v in self.engine.kv_stats().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"kv_{k}"] = v
        for k, v in self.engine.weight_stats().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"weights_{k}"] = v
        # perfwatch counters (perf_ prefix): the recompile-sentinel
        # series (compiles_total/warm/out_of_grid are fleet-summable
        # true counters) + per-family attributed ticks/device seconds
        for k, v in self.engine.perf_numeric().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = v
        for k, v in self.stream_counters.items():
            out[k] = round(v, 6)
        out["uptime_s"] = round(
            time.monotonic() - self.started_monotonic, 3)
        return out

    async def metrics(self, request):
        """Prometheus-style text exposition, every series labelled with
        this replica's stable id so a fleet scrape stays per-replica
        attributable, now including REAL histogram series
        (``_bucket``/``_sum``/``_count`` — TTFT, per-token latency, tick
        sync, swap-in); ``?format=json`` keeps the machine-readable shape
        the router's aggregation fetches and fleet-sums."""
        vals = self._metrics_numeric()
        hists = {**self.engine.histograms(),
                 "stream_deliver_s": self.stream_deliver_s}
        if request.query.get("format") == "json":
            return web.json_response(
                {"replica_id": self.replica_id, "metrics": vals,
                 "histograms": {k: h.to_dict() for k, h in hists.items()}})
        lines = []
        for name in sorted(vals):
            lines.append(f'ipex_llm_tpu_{name}'
                         f'{{replica_id="{self.replica_id}"}} {vals[name]}')
        for name in sorted(hists):
            lines.extend(hists[name].prometheus_lines(
                f"ipex_llm_tpu_{name}",
                labels=f'replica_id="{self.replica_id}"'))
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")

    # -- transportable KV (disaggregated prefill/decode) --------------------

    def _body_prompt_ids(self, body: dict) -> list[int]:
        """Token ids for any surface's prompt shape (chat messages /
        completions prompt / TGI inputs) — the handoff legs must map a
        body to the SAME ids the eventual completion request will, or
        the exported pages' chain hashes won't match at admission."""
        if body.get("messages"):
            return self._encode_chat(body["messages"])
        p = body.get("prompt", body.get("inputs", ""))
        if isinstance(p, list):
            p = p[0] if p else ""
        return list(self.tok(str(p))["input_ids"])

    async def kv_prefill(self, request):
        """Handoff leg 1 (prefill replica): run the prompt through this
        engine — a one-token greedy generation, i.e. the prefill plus a
        throwaway first sample — then export the cached prefix pages as
        a transportable page set (serving/kv_transport.py), returned as
        application/octet-stream.  The decode replica that imports it
        re-derives the first token itself from the uncovered tail.  422
        when nothing is exportable (prompt under one page, or the pages
        already evicted with no spill tier to serve them)."""
        body = await request.json()
        wire = str(body.get("wire", "auto"))
        if wire not in ("auto", "fp8", "bf16"):
            return web.json_response(
                {"error": {"message": f"unknown wire format {wire!r}: "
                                      "one of auto, fp8, bf16",
                           "type": "invalid_request_error",
                           "code": "bad_wire_format"}}, status=400)
        ids = self._body_prompt_ids(body)
        if not ids:
            return web.json_response(
                {"error": {"message": "empty prompt",
                           "type": "invalid_request_error",
                           "code": "empty_prompt"}}, status=400)
        req = self._mk_request(
            dict(body, max_tokens=1, temperature=0.0, stream=False), ids)
        self._submit(req)
        while await self._next_tok(req) is not None:
            pass
        if _req_failed(req):
            return self._error_response(req)
        loop = asyncio.get_running_loop()
        blob = await loop.run_in_executor(
            None, self.engine.export_prefix, ids, wire)
        if blob is None:
            return web.json_response(
                {"error": {"message": "no full prefix page cached for "
                                      "this prompt",
                           "type": "invalid_request_error",
                           "code": "nothing_to_export"}}, status=422)
        return web.Response(body=blob,
                            content_type="application/octet-stream",
                            headers={"X-KV-Tokens": str(len(ids))})

    async def kv_import(self, request):
        """Handoff leg 2 (decode replica): verify + import a page set
        into this engine's pool and prefix cache, so the completion
        routed here next prefills only the uncovered tail.  Malformed
        blobs are 400 (``TransportError`` — unverified bytes are never
        scattered).  With ``--kv-import-token`` set, callers must
        present the shared token (X-KV-Import-Token): the blob checksum
        proves integrity, NOT identity — without authn any reachable
        caller could scatter checksum-consistent pages into the shared
        prefix cache and poison every future prefix hit."""
        if self.kv_import_token is not None:
            import hmac
            presented = request.headers.get("X-KV-Import-Token")
            # constant-time: a short-circuiting != leaks correct token
            # prefixes through 401 latency — exactly the caller this
            # check exists to keep out
            if not hmac.compare_digest(presented or "",
                                       self.kv_import_token):
                return web.json_response(
                    {"error": {"message": "missing or invalid "
                                          "X-KV-Import-Token",
                               "type": "authentication_error",
                               "code": "kv_import_unauthorized"}},
                    status=401)
        blob = await request.read()
        loop = asyncio.get_running_loop()
        try:
            res = await loop.run_in_executor(
                None, self.engine.import_pages, blob)
        except TransportError as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error",
                           "code": "bad_page_set"}}, status=400)
        return web.json_response(res)

    # -- observability (serving/observe.py) ---------------------------------

    async def trace_get(self, request):
        """One request's lifecycle trace (``?format=chrome`` renders the
        Chrome trace-event shape).  404 when tracing is off or the trace
        aged out of the bounded LRU; the router's /trace/{id} merges
        this replica's spans with its own and the other replicas'."""
        tid = request.match_info["trace_id"]
        tr = self.engine.trace_view(tid)
        if tr is None:
            return web.json_response(
                {"error": {"message": f"unknown trace {tid!r} (tracing "
                                      "disabled, or aged out)",
                           "type": "invalid_request_error",
                           "code": "unknown_trace"}}, status=404)
        if request.query.get("format") == "chrome":
            return web.json_response(Tracer.chrome_events([tr]))
        return web.json_response(tr)

    async def traces_export(self, request):
        """Whole-window trace export: every trace still in the LRU, as
        ids (default) or one Perfetto-loadable Chrome trace-event JSON
        (``?format=chrome``) — the grab-everything artifact for a latency
        investigation."""
        tracer = self.engine.tracer
        if tracer is None:
            return web.json_response(
                {"error": {"message": "tracing disabled (--trace / "
                                      "EngineConfig.trace_requests)",
                           "type": "invalid_request_error",
                           "code": "tracing_disabled"}}, status=404)
        if request.query.get("format") == "chrome":
            return web.json_response(tracer.export_chrome())
        return web.json_response({"trace_ids": tracer.trace_ids()})

    async def debug_flight(self, request):
        """The tick flight recorder: the last N working-tick records and
        any frozen postmortem dumps (_fail_all / quarantine capture one
        automatically) — what the SIGKILL and chaos gates previously had
        no artifact for."""
        return web.json_response(self.engine.flight.view())

    async def debug_profile(self, request):
        """Operational jax.profiler capture: trace this replica for
        ``?seconds=N`` (clamped; default 3) into ``?dir=`` (restricted
        to a subdirectory of --profile-dir — an unauthenticated caller
        must not get an arbitrary-filesystem-write primitive out of
        profiler artifacts) / ``--profile-dir`` / a fresh temp dir, via
        profiling.trace — xprof/tensorboard/Perfetto-loadable.  409 when
        a capture is already running (jax allows one at a time)."""
        import os
        import tempfile

        from ipex_llm_tpu import profiling

        try:
            seconds = float(request.query.get("seconds", 3.0))
        except ValueError:
            return web.json_response(
                {"error": {"message": "seconds must be a number",
                           "type": "invalid_request_error",
                           "code": "bad_seconds"}}, status=400)
        base = self.profile_dir
        log_dir = base or tempfile.mkdtemp(prefix="ipex-llm-tpu-profile-")
        sub = request.query.get("dir")
        if sub:
            if base is None:
                return web.json_response(
                    {"error": {"message": "?dir= requires --profile-dir "
                                          "(captures are confined to it)",
                               "type": "invalid_request_error",
                               "code": "no_profile_dir"}}, status=400)
            cand = os.path.realpath(os.path.join(base, sub))
            if cand != os.path.realpath(base) and not cand.startswith(
                    os.path.realpath(base) + os.sep):
                return web.json_response(
                    {"error": {"message": "?dir= must stay inside "
                                          "--profile-dir",
                               "type": "invalid_request_error",
                               "code": "bad_profile_dir"}}, status=400)
            log_dir = cand
        loop = asyncio.get_running_loop()
        try:
            res = await loop.run_in_executor(
                None, profiling.capture, log_dir, seconds)
        except RuntimeError as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "conflict_error",
                           "code": "capture_in_progress"}}, status=409)
        return web.json_response(res)

    # -- TGI protocol -------------------------------------------------------

    def _tgi_request(self, body: dict, headers=None) -> Request:
        """TGI shape: {"inputs": str, "parameters": {...}} (reference
        tgi_api_protocol.py ChatCompletionParam)."""
        p = body.get("parameters") or {}
        mapped = {
            "max_tokens": p.get("max_new_tokens", 64),
            "temperature": (p.get("temperature", 1.0)
                            if p.get("do_sample", False) else 0.0),
            "top_p": p.get("top_p", 1.0),
            "top_k": p.get("top_k", 0),
            "stop": p.get("stop"),
            "seed": p.get("seed"),
            "deadline_s": body.get("deadline_s"),
        }
        if body.get("traceparent"):
            mapped["traceparent"] = body["traceparent"]
        ids = list(self.tok(body.get("inputs", ""))["input_ids"])
        return self._mk_request(mapped, ids, headers)

    @staticmethod
    def _tgi_reason(fr: str | None) -> str:
        return {"stop": "eos_token", "stop_string": "stop_sequence"}.get(
            fr, fr or "length")

    @staticmethod
    def _tgi_error_payload(req: Request) -> dict:
        """TGI error shape: flat {"error", "error_type"}."""
        if req.finish_reason == "timeout":
            return {"error": "request deadline exceeded",
                    "error_type": "timeout"}
        if req.finish_reason == "abort":
            return {"error": "request aborted: server draining",
                    "error_type": "unavailable"}
        return {"error": "request failed in the engine (isolated fault)",
                "error_type": "generation"}

    async def tgi_generate(self, request):
        body = await request.json()
        req = self._submit(self._tgi_request(body, request.headers))
        text = await self._collect(req)
        if _req_failed(req):
            status = {"timeout": 408,
                      "abort": 503}.get(req.finish_reason, 500)
            return web.json_response(self._tgi_error_payload(req),
                                     status=status)
        return web.json_response({
            "generated_text": text,
            "details": {
                "finish_reason": self._tgi_reason(req.finish_reason),
                "generated_tokens": len(req.output_ids),
                "prefill": [],
            },
        })

    async def tgi_generate_stream(self, request):
        body = await request.json()
        req = self._submit(self._tgi_request(body, request.headers))

        def chunk(piece, finish, tok):
            n = len(req.output_ids)
            lp = req.logprobs[n - 1] if 0 < n <= len(req.logprobs) else 0.0
            return {"token": {"id": int(tok), "text": piece,
                              "logprob": round(float(lp), 6),
                              "special": False},
                    "generated_text": None}

        def final(sent, finish):
            return {"token": None, "generated_text": sent,
                    "details": {"finish_reason": self._tgi_reason(finish),
                                "generated_tokens": len(req.output_ids)}}

        return await self._stream_sse(request, req, chunk, final_fn=final,
                                      send_done=False,
                                      error_fn=self._tgi_error_payload)

    # -- audio (whisper) ----------------------------------------------------

    async def transcriptions(self, request):
        """OpenAI /v1/audio/transcriptions: multipart WAV in, text out."""
        import asyncio

        form = await request.post()
        part = form.get("file")
        if part is None:
            return web.json_response(
                {"error": {"message": "missing 'file' form field"}},
                status=400)
        data = part.file.read()
        asr_model, fe, asr_tok = self.asr

        def pipeline():
            """WAV decode + resample + mel features + generate — all off
            the event loop so concurrent SSE streams never stall."""
            import numpy as np

            samples, sr = _read_wav(data)
            want_sr = getattr(fe, "sampling_rate", 16000)
            if sr != want_sr:  # linear resample (no audio stack in image)
                n = int(len(samples) * want_sr / sr)
                samples = np.interp(
                    np.linspace(0, len(samples) - 1, n),
                    np.arange(len(samples)), samples).astype("float32")
            feats = fe(samples, sampling_rate=want_sr,
                       return_tensors="np")["input_features"]
            # the extractor pads to 30 s; clip to the encoder window
            feats = feats[:, :, :2 * asr_model.config.max_source_positions]
            return asr_model.generate(feats, max_new_tokens=224)

        loop = asyncio.get_running_loop()
        try:
            ids = await loop.run_in_executor(None, pipeline)
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"only PCM WAV input is supported "
                                      f"in this build ({e})"}}, status=400)
        text = asr_tok.decode(list(map(int, ids[0])),
                              skip_special_tokens=True)
        return web.json_response({"text": text})


def _read_wav(data: bytes):
    """stdlib PCM WAV decode -> (float32 mono samples, sample_rate)."""
    import io
    import wave

    import numpy as np

    with wave.open(io.BytesIO(data), "rb") as w:
        sw = w.getsampwidth()
        nch = w.getnchannels()
        sr = w.getframerate()
        raw = w.readframes(w.getnframes())
    if sw == 2:
        x = np.frombuffer(raw, np.int16).astype(np.float32) / 32768.0
    elif sw == 4:
        x = np.frombuffer(raw, np.int32).astype(np.float32) / 2147483648.0
    elif sw == 1:
        x = (np.frombuffer(raw, np.uint8).astype(np.float32) - 128.0) / 128.0
    else:
        raise ValueError(f"unsupported sample width {sw}")
    if nch > 1:
        x = x.reshape(-1, nch).mean(axis=1)
    return x, sr


def build_server(model_path: str, low_bit: str = "sym_int4",
                 engine_config: EngineConfig | None = None,
                 model=None, tokenizer=None,
                 asr_model_path: str | None = None,
                 tensor_parallel_size: int = 1,
                 drain_timeout_s: float = 30.0,
                 kv_import_token: str | None = None,
                 profile_dir: str | None = None) -> OpenAIServer:
    """``tensor_parallel_size`` > 1 serves under a tp mesh (SPMD AutoTP, the
    reference's vLLM-TP serving mode); a model already ``.shard(mesh)``-ed
    passes its mesh through implicitly.

    When build_server loads the checkpoint itself, ``low_bit`` threads
    into ``EngineConfig.weight_qtype`` (unless the caller's engine_config
    already pins one), so the SERVING stack owns the weight-width axis
    end to end and /health's ``weights`` block reports it.  A model
    handed in via ``model=`` keeps whatever width it carries — silently
    requantizing a caller's full-width tree would be a lossy surprise;
    such callers opt in via ``EngineConfig(weight_qtype=...)``."""
    from ipex_llm_tpu.transformers import AutoModelForCausalLM

    mesh = None
    if tensor_parallel_size > 1:
        from ipex_llm_tpu.parallel import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(tp=tensor_parallel_size))
    ec = engine_config or EngineConfig()
    if model is None:
        import os

        from ipex_llm_tpu.serving.engine import default_weight_qtype

        if os.path.exists(f"{model_path}/bigdl_config.json"):
            # a save_low_bit checkpoint carries ITS OWN width — thread
            # that, not the CLI --low-bit default, so a bf16 or nf4 save
            # is never silently requantized (and /health never reports a
            # width the tree does not hold)
            model = AutoModelForCausalLM.load_low_bit(model_path, mesh=mesh)
            ec = default_weight_qtype(ec, getattr(model, "qtype", None))
        else:
            # both halves of the width rule live in serving/engine.py: a
            # pinned EngineConfig.weight_qtype outranks --low-bit for the
            # LOAD (resolve_load_low_bit — the flag is authoritative end
            # to end), and the loaded width threads back into the config
            from ipex_llm_tpu.serving.engine import resolve_load_low_bit

            load_q = resolve_load_low_bit(ec, low_bit)
            model = AutoModelForCausalLM.from_pretrained(
                model_path, load_in_low_bit=load_q, mesh=mesh
            )
            ec = default_weight_qtype(ec, load_q)
    if tokenizer is None:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(model_path,
                                                  trust_remote_code=True)
    engine = ServingEngine(
        model.config, model.params, ec,
        default_eos=model.generation_config.eos_token_id,
        mesh=mesh if mesh is not None else getattr(model, "mesh", None),
    ).start()
    asr = None
    if asr_model_path is not None:
        from transformers import AutoFeatureExtractor, AutoTokenizer

        from ipex_llm_tpu.models.whisper import (
            TPUWhisperForConditionalGeneration,
        )

        asr = (
            TPUWhisperForConditionalGeneration.from_pretrained(
                asr_model_path, load_in_low_bit=low_bit),
            AutoFeatureExtractor.from_pretrained(asr_model_path),
            AutoTokenizer.from_pretrained(asr_model_path),
        )
    return OpenAIServer(engine, tokenizer, model_name=model_path, asr=asr,
                        drain_timeout_s=drain_timeout_s,
                        kv_import_token=kv_import_token,
                        profile_dir=profile_dir)


def main(argv=None):
    ap = argparse.ArgumentParser("ipex-llm-tpu OpenAI-compatible server")
    ap.add_argument("--model", required=True)
    ap.add_argument("--low-bit", default="sym_int4")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-rows", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=4096)
    ap.add_argument("--asr-model", default=None,
                    help="whisper checkpoint enabling /v1/audio/transcriptions")
    ap.add_argument("--collective-qtype", default=None,
                    choices=("bf16", "e5m2", "int8"), metavar="FAMILY",
                    help="AllReduce wire family for the manual-mesh tp "
                         "tick (ops/collectives.py): bf16 = exact (f32 "
                         "accumulate, tp2 bit-identical to single-chip); "
                         "e5m2/int8 = EQuARX-style quantized payloads, "
                         "bounded error for less ICI traffic.  Default: "
                         "the IPEX_LLM_TPU_COLLECTIVE_QTYPE env, else "
                         "bf16")
    ap.add_argument("--tensor-parallel-size", type=int, default=1,
                    help="serve under a tp mesh of this many chips")
    ap.add_argument("--spec-k", "--speculative", type=int, default=0,
                    metavar="K", dest="spec_k",
                    help="prompt-lookup speculative serving: draft, "
                         "verify, and accept up to K candidates per row "
                         "per decode step, ON DEVICE inside the fused "
                         "tick (reference ipex_llm_worker `speculative` "
                         "flag); composes with --decode-horizon; "
                         "accept rate in /health's spec block")
    ap.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                    help="longest n-gram the speculative lookup proposer "
                         "matches against the row's token history")
    ap.add_argument("--decode-horizon", type=int, default=1, metavar="H",
                    help="fused multi-step decode: run H decode steps per "
                         "device program (one host sync per H tokens; "
                         "streaming granularity becomes up to H tokens, "
                         "times K+1 with --spec-k)")
    ap.add_argument("--planner", default="mpc", choices=("mpc", "static"),
                    help="tick planner (serving/planner.py): mpc (default) "
                         "re-picks chunk budget, decode horizon, per-row "
                         "spec widths, and admission count once per tick "
                         "to maximize predicted goodput (completed-under-"
                         "deadline tok/s), choosing only among manifest-"
                         "locked grid points; static = the pre-planner "
                         "fixed-knob behavior, bit-identical escape hatch. "
                         "/health's planner block shows the last plan and "
                         "decision counts")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    metavar="B",
                    help="mixed prefill+decode step: per-tick token budget "
                         "during admission waves — prefill chunks for ALL "
                         "joining requests batch with the decode step in "
                         "one device program.  Positive; default: the "
                         "prefill bucket")
    ap.add_argument("--weight-qtype", default=None, metavar="QTYPE",
                    help="serving weight width (default: --low-bit), "
                         "authoritative end to end: the checkpoint loads "
                         "at this width (sym_int4/nf4/sym_int8/...; a "
                         "save_low_bit checkpoint keeps its own recorded "
                         "width), any full-width linear weights re-pack "
                         "at engine build, and the fused tick reads "
                         "packed codes with dequant fused into the "
                         "matmul — ~4.5 bits/weight of HBM traffic "
                         "instead of 16.  /health reports packed bytes + "
                         "bytes saved in its weights block")
    ap.add_argument("--kv-storage", default="bf16",
                    choices=("bf16", "fp8"), metavar="FMT",
                    help="paged KV pool storage format: bf16 (full width, "
                         "default) or fp8 (e5m2 codes — half the KV bytes "
                         "per token, twice the pages per byte budget; "
                         "slightly lossy vs bf16)")
    ap.add_argument("--kv-pool-bytes", type=int, default=0, metavar="BYTES",
                    help="KV pool byte budget: pool page count is derived "
                         "as BYTES / page_bytes(model, --kv-storage), so "
                         "fp8 automatically holds 2x the pages.  0 = size "
                         "in pages (the auto heuristic)")
    ap.add_argument("--kv-spill-bytes", type=int, default=0,
                    metavar="BYTES",
                    help="host-RAM KV spill tier budget: prefix pages "
                         "evicted under pool pressure (and finished "
                         "rows' decode pages) demote to a host LRU and "
                         "swap back on the next prefix hit instead of "
                         "being recomputed.  0 = off")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="bounded admission queue: submissions beyond this "
                         "many waiting requests are load-shed with HTTP "
                         "429 (0 = unbounded)")
    ap.add_argument("--request-deadline", type=float, default=0.0,
                    metavar="SECONDS",
                    help="default per-request wall-clock deadline covering "
                         "queue wait + generation; an expired request "
                         "finishes with HTTP 408 (0 = no deadline)")
    ap.add_argument("--max-step-retries", type=int, default=3,
                    help="bounded retries (exponential backoff) for "
                         "transient device faults before the engine "
                         "bisects and quarantines the culprit request")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    metavar="SECONDS",
                    help="graceful-drain window on SIGTERM: stop admission "
                         "(503), let in-flight requests finish, then "
                         "abort stragglers")
    ap.add_argument("--trace", action="store_true",
                    help="request-lifecycle tracing: per-request spans "
                         "(queue wait, prefill chunks, swap-ins, first "
                         "token, decode horizons, spec rounds, retries, "
                         "finish) staged inside the transactional tick, "
                         "served at /trace/{id} and /debug/traces "
                         "(Chrome trace-event JSON via ?format=chrome); "
                         "honors/propagates W3C traceparent")
    ap.add_argument("--kv-import-token", default=None, metavar="TOKEN",
                    help="require this shared token (X-KV-Import-Token "
                         "header) on /kv/import: blob checksums prove "
                         "integrity, not identity — without a token the "
                         "shared prefix cache is poisonable by any "
                         "reachable caller.  The router forwards its "
                         "--kv-import-token on handoff legs")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="jax.profiler capture target for /debug/profile"
                         "?seconds=N (default: a fresh temp dir per "
                         "capture)")
    args = ap.parse_args(argv)
    srv = build_server(
        args.model, args.low_bit,
        EngineConfig(max_rows=args.max_rows, max_seq_len=args.max_seq_len,
                     spec_k=args.spec_k, spec_ngram=args.spec_ngram,
                     decode_horizon=args.decode_horizon,
                     step_token_budget=args.step_token_budget,
                     kv_storage=args.kv_storage,
                     kv_pool_bytes=args.kv_pool_bytes,
                     kv_spill_bytes=args.kv_spill_bytes,
                     weight_qtype=args.weight_qtype,
                     max_queue=args.max_queue,
                     request_deadline_s=args.request_deadline,
                     max_step_retries=args.max_step_retries,
                     trace_requests=args.trace,
                     collective_qtype=args.collective_qtype,
                     planner=args.planner),
        asr_model_path=args.asr_model,
        tensor_parallel_size=args.tensor_parallel_size,
        drain_timeout_s=args.drain_timeout,
        kv_import_token=args.kv_import_token,
        profile_dir=args.profile_dir,
    )
    web.run_app(srv.app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
