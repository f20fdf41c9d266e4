"""OpenAI-compatible HTTP service (aiohttp).

Role of the reference's axum server (`lib/llm/src/http/service/openai.rs`):
/v1/chat/completions, /v1/completions, /v1/models with SSE streaming,
client-disconnect cancellation (`disconnect.rs` — here: the request
generator is closed when aiohttp detects the peer went away, which
cancels the engine request), request metrics incl. TTFT/ITL histograms
(`metrics.rs`), /metrics exposition, and /health & /live endpoints
(reference `system_status_server.rs`).
"""

from __future__ import annotations

import asyncio
import base64
import logging
import time
import uuid
from typing import Optional

from aiohttp import web

from dynamo_tpu.engine.scheduler import FinishReason
from dynamo_tpu.llm.backend import StreamDetokenizer, wire_finish_reason
from dynamo_tpu.llm.protocols import openai as oai
from dynamo_tpu.llm.service import ModelHandle, ModelManager
from dynamo_tpu.runtime import ledger as ledger_mod
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.metrics import (
    FrontendMetrics, MetricsRegistry, RequestMetrics)

logger = logging.getLogger(__name__)



class HttpService:
    def __init__(
        self,
        models: ModelManager,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[tracing.Tracer] = None,
    ) -> None:
        self.models = models
        self.registry = registry or MetricsRegistry()
        self.metrics = FrontendMetrics(self.registry)
        # Per-request lifecycle histograms (dynamo_request_*): TTFT /
        # TPOT / queue wait, always on (cheap); spans ride the tracer.
        self.request_metrics = RequestMetrics(self.registry)
        # SLO burn-rate monitor (runtime/slo.py), installed by the
        # embedding process (frontend main) when --slo-* flags configure
        # objectives; None → /debug/slo reports enabled=false.
        self.slo_monitor = None
        # Request-ledger fold point (ISSUE 18): completed per-request
        # phase ledgers land here — dynamo_request_phase_seconds{phase=},
        # the goodput counter pair, /debug/requests, and the dominant-
        # phase attribution SloMonitor and `dynamo top` read.  Frontend
        # main sets slo_ttft/slo_tpot from the --slo-* flags.
        self.ledger_sink = ledger_mod.LedgerSink(self.registry)
        self.tracer = tracer or tracing.get_tracer()
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self.chat_completions)
        self.app.router.add_post("/v1/completions", self.completions)
        self.app.router.add_post("/v1/embeddings", self.embeddings)
        self.app.router.add_post("/v1/responses", self.responses)
        self.app.router.add_post("/clear_kv_blocks", self.clear_kv_blocks)
        self.app.router.add_get("/v1/models", self.list_models)
        self.app.router.add_get("/metrics", self.prometheus)
        self.app.router.add_get("/debug/traces", self.debug_traces)
        self.app.router.add_get("/debug/requests", self.debug_requests)
        self.app.router.add_get("/debug/slo", self.debug_slo)
        self.app.router.add_get("/debug/flightrecorder",
                                self.debug_flightrecorder)
        self.app.router.add_get("/debug/deviceprofile",
                                self.debug_deviceprofile)
        self.app.router.add_get("/health", self.health)
        self.app.router.add_get("/live", self.live)
        self._runner: Optional[web.AppRunner] = None
        self.port: Optional[int] = None

    # -- lifecycle --------------------------------------------------------

    async def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Bind and serve; returns the bound port (0 → ephemeral)."""
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        logger.info("HTTP service on %s:%s", host, self.port)
        return self.port

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    # -- helpers ----------------------------------------------------------

    @staticmethod
    def _error(status: int, message: str, type_: str = "invalid_request_error"):
        body = oai.ErrorResponse(
            error=oai.ErrorDetail(message=message, type=type_))
        return web.json_response(body.model_dump(exclude_none=True),
                                 status=status)

    def _lookup(self, model: str) -> Optional[ModelHandle]:
        return self.models.get(model)

    @staticmethod
    def _request_id(request: web.Request, prefix: str) -> str:
        """Trace context: honor a caller-provided X-Request-Id so one id
        is grep-able across frontend and worker logs (reference
        distributed trace ctx over transport headers, logging.rs:73-79).
        A unique suffix is ALWAYS appended — the raw header value is not
        unique (proxy retries, concurrent duplicates) and the engine keys
        request state by this id."""
        header = request.headers.get("x-request-id")
        if header:
            return f"{header[:120]}-{uuid.uuid4().hex[:8]}"
        return oai.request_id(prefix)

    def _start_trace(self, route: str, rid: str, model: str):
        """Root span for one HTTP request, reusing the request id as the
        trace id (one grep-able id across logs, metrics, and the merged
        Perfetto view).  Returns (span, contextvar token); both are
        no-ops when tracing is off."""
        span = self.tracer.start_span(
            f"http.{route}", trace_id=rid,
            attrs={"rid": rid, "model": model})
        token = tracing.use_span(span) if span.ctx is not None else None
        return span, token

    @staticmethod
    def _end_trace(span, token) -> None:
        span.end()
        if token is not None:
            tracing.restore(token)

    def _validate_context(self, handle: ModelHandle, pre):
        """Boundary validation (reference `protocols/openai/validate.rs`):
        a prompt that cannot fit the model context is a client error the
        HTTP layer must surface as a 400 — r2 silently finished such
        requests as zero-token LENGTH stops.  A prompt that fits but whose
        max_tokens would overflow gets max_tokens clamped."""
        ctx = handle.max_context
        n = len(pre.token_ids)
        if n >= ctx:
            return self._error(
                400,
                f"prompt has {n} tokens which exceeds the model's maximum "
                f"context length of {ctx} tokens",
                "invalid_request_error")
        budget = ctx - n
        if pre.sampling.max_tokens > budget:
            import dataclasses

            pre.sampling = dataclasses.replace(pre.sampling,
                                               max_tokens=budget)
        return None

    # -- routes -----------------------------------------------------------

    async def health(self, _req: web.Request) -> web.Response:
        ready = len(self.models) > 0
        return web.json_response(
            {"status": "ready" if ready else "starting",
             "models": self.models.names()},
            status=200 if ready else 503)

    async def live(self, _req: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def prometheus(self, _req: web.Request) -> web.Response:
        return web.Response(text=self.registry.expose(),
                            content_type="text/plain")

    async def debug_traces(self, req: web.Request) -> web.Response:
        """Most recent completed traces (`?n=K`, default 32) — the
        per-process buffer tools/trace_merge.py stitches across the
        deployment."""
        try:
            n = int(req.query.get("n", "32"))
        except ValueError:
            return self._error(400, "n must be an integer")
        return web.json_response(
            tracing.debug_traces_payload(n, self.tracer))

    async def debug_requests(self, req: web.Request) -> web.Response:
        """Slowest-N completed request ledgers (`?n=K`, default 10) with
        full phase stamps, plus the window's dominant phase and the
        goodput ratio — "which hop ate this request's latency", served
        straight from the LedgerSink ring."""
        try:
            n = int(req.query.get("n", "10"))
        except ValueError:
            return self._error(400, "n must be an integer")
        return web.json_response(self.ledger_sink.debug_payload(n))

    async def debug_flightrecorder(self, req: web.Request) -> web.Response:
        """The frontend's flight-recorder ring (`?n=K`, default 256):
        SLO state transitions and slow-request markers — the frontend
        half of a fleet postmortem (worker rings ride their
        StatusServers)."""
        from dynamo_tpu.runtime import flight_recorder

        try:
            n = int(req.query.get("n", "256"))
        except ValueError:
            return self._error(400, "n must be an integer")
        return web.json_response(
            flight_recorder.get_recorder().debug_payload(n))

    async def debug_deviceprofile(self, req: web.Request) -> web.Response:
        """This process's device-truth plane
        (runtime/device_profiler.py): state without `?ms=`, one bounded
        jax.profiler capture with `?ms=N` (`&python=1` for Python
        frames too) — same payload shape as the
        worker StatusServer route, so tooling treats every process
        uniformly.  (Worker captures ride the workers' own status
        ports or the control-plane `profile/<pid>` command; this route
        covers frontend-side device work.)"""
        import asyncio

        from dynamo_tpu.runtime import device_profiler

        prof = device_profiler.get_profiler()
        ms_raw = req.query.get("ms")
        if ms_raw is None:
            return web.json_response(prof.debug_payload())
        try:
            ms = int(ms_raw)
            if ms <= 0:
                raise ValueError
        except ValueError:
            return self._error(400, "ms must be a positive integer")
        python = req.query.get("python", "0") not in ("", "0", "false")
        res = await asyncio.to_thread(prof.capture, ms, python)
        return web.json_response(res, status=200 if res.get("ok") else 503)

    async def debug_slo(self, _req: web.Request) -> web.Response:
        """Current SLO burn-rate evaluation over this frontend's request
        histograms (runtime/slo.py; enabled via the --slo-* flags)."""
        from dynamo_tpu.runtime import slo as slo_mod

        if self.slo_monitor is None:
            return web.json_response(slo_mod.disabled_payload())
        return web.json_response(self.slo_monitor.payload())

    async def list_models(self, _req: web.Request) -> web.Response:
        listing = oai.ModelList(
            data=[oai.ModelInfo(id=n) for n in self.models.names()])
        return web.json_response(listing.model_dump())

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = oai.ChatCompletionRequest.model_validate(await request.json())
        except Exception as e:
            return self._error(400, f"invalid request: {e}")
        handle = self._lookup(body.model)
        if handle is None:
            return self._error(404, f"model {body.model!r} not found",
                               "model_not_found")
        rid = self._request_id(request, "chatcmpl")
        root, tok = self._start_trace("chat", rid, body.model)
        try:
            with self.tracer.start_span("frontend.preprocess"):
                try:
                    pre = handle.preprocessor.preprocess_chat(body, rid)
                except ValueError as e:
                    return self._error(400, str(e))
            mm = handle.multimodal
            if mm is not None and mm.image_refs(body.messages):
                # image_url parts → encode worker → prompt_embeds
                # (llm/multimodal.py; reference multimodal_v1 processor).
                try:
                    with self.tracer.start_span("frontend.encode_images"):
                        pre = await mm.attach(body.messages, pre)
                except Exception as e:
                    return self._error(
                        502, f"image encoding failed: {e}", "encode_error")
            elif mm is None and self._has_image_parts(body.messages):
                return self._error(
                    400, "this model has no multimodal pipeline configured "
                         "(image_url parts unsupported)")
            err = self._validate_context(handle, pre)
            if err is not None:
                return err
            self._attach_priority(request, pre)
            logger.info("request %s: chat model=%s prompt_tokens=%d "
                        "stream=%s", rid, body.model, len(pre.token_ids),
                        body.stream)
            root.set_attr(prompt_tokens=len(pre.token_ids),
                          stream=bool(body.stream))
            if body.stream:
                return await self._stream_chat(request, handle, body, pre,
                                               rid)
            return await self._unary_chat(handle, body, pre, rid)
        finally:
            self._end_trace(root, tok)

    @staticmethod
    def _attach_priority(request: web.Request, pre) -> None:
        """QoS class (ISSUE 15): the x-dynamo-priority header (named
        class or 0..2 integer) rides the preprocessed request's
        annotations to the worker's scheduler.  Absent header = standard;
        the worker side is equally forgiving (service.priority_of)."""
        header = request.headers.get("x-dynamo-priority")
        if header:
            from dynamo_tpu.llm.service import PRIORITY_ANNOTATION

            pre.annotations[PRIORITY_ANNOTATION] = header.strip()

    @staticmethod
    def _has_image_parts(messages) -> bool:
        from dynamo_tpu.llm.multimodal import MultimodalAttach

        return bool(MultimodalAttach.image_refs(messages))

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = oai.CompletionRequest.model_validate(await request.json())
        except Exception as e:
            return self._error(400, f"invalid request: {e}")
        handle = self._lookup(body.model)
        if handle is None:
            return self._error(404, f"model {body.model!r} not found",
                               "model_not_found")
        rid = self._request_id(request, "cmpl")
        root, tok = self._start_trace("completion", rid, body.model)
        try:
            return await self._completions_traced(request, handle, body,
                                                  rid, root)
        finally:
            self._end_trace(root, tok)

    async def _completions_traced(self, request, handle, body, rid, root):
        with self.tracer.start_span("frontend.preprocess"):
            try:
                pre = handle.preprocessor.preprocess_completion(body, rid)
            except ValueError as e:
                return self._error(400, str(e))
        err = self._validate_context(handle, pre)
        if err is not None:
            return err
        self._attach_priority(request, pre)
        logger.info("request %s: completion model=%s prompt_tokens=%d "
                    "stream=%s", rid, body.model, len(pre.token_ids),
                    body.stream)
        root.set_attr(prompt_tokens=len(pre.token_ids),
                      stream=bool(body.stream))
        if body.stream:
            return await self._stream_completion(request, handle, body, pre,
                                                 rid)

        start = time.monotonic()
        self.metrics.requests_total.inc(labels={"model": body.model})
        self.metrics.requests_in_flight.add(1, labels={"model": body.model})
        want_lp = bool(pre.sampling.logprobs)
        try:
            results, total_out = await self._collect_choices(
                handle, pre, body.n, body.model, start, want_lp)
        finally:
            self.metrics.requests_in_flight.add(-1, labels={"model": body.model})
        self._observe_done(body.model, start, len(pre.token_ids), total_out)
        choices = []
        for i, (text, reason, det, lp_sink) in enumerate(results):
            logprobs = None
            if lp_sink:
                logprobs = {
                    "tokens": [handle.tokenizer.decode([t])
                               for t, _ in lp_sink],
                    "token_logprobs": [lp for _, lp in lp_sink],
                }
            choices.append(oai.CompletionChoice(
                index=i, text=text, finish_reason=reason,
                logprobs=logprobs))
        resp = oai.CompletionResponse(
            id=rid, model=body.model, choices=choices,
            usage=oai.Usage(
                prompt_tokens=len(pre.token_ids),
                completion_tokens=total_out,
                total_tokens=len(pre.token_ids) + total_out))
        return web.json_response(resp.model_dump(exclude_none=True))

    async def clear_kv_blocks(self, _req: web.Request) -> web.Response:
        """Admin: flush every model's reusable KV blocks (reference
        `http/service/clear_kv_blocks.rs`)."""
        out = {}
        for name in self.models.names():
            clear = getattr(self.models.get(name).client,
                            "clear_kv_blocks", None)
            if clear is None:
                out[name] = {"status": "unsupported"}
                continue
            try:
                out[name] = {"status": "ok", "cleared": await clear()}
            except Exception as e:
                out[name] = {"status": "error", "error": str(e)}
        return web.json_response(out)

    async def responses(self, request: web.Request) -> web.StreamResponse:
        """/v1/responses (reference `protocols/openai/responses.rs`):
        normalised onto the chat pipeline; unary and SSE streaming."""
        try:
            body = oai.ResponsesRequest.model_validate(await request.json())
        except Exception as e:
            return self._error(400, f"invalid request: {e}")
        handle = self._lookup(body.model)
        if handle is None:
            return self._error(404, f"model {body.model!r} not found",
                               "model_not_found")
        rid = self._request_id(request, "resp")
        root, tok = self._start_trace("responses", rid, body.model)
        try:
            return await self._responses_traced(request, handle, body, rid,
                                                root)
        finally:
            self._end_trace(root, tok)

    async def _responses_traced(self, request, handle, body, rid, root):
        with self.tracer.start_span("frontend.preprocess"):
            try:
                chat = body.as_chat()
                pre = handle.preprocessor.preprocess_chat(chat, rid)
            except Exception as e:
                # as_chat's ChatMessage validation failures are client
                # input errors too (e.g. an unsupported role) — 400, not
                # 500.
                return self._error(400, str(e))
        err = self._validate_context(handle, pre)
        if err is not None:
            return err
        self._attach_priority(request, pre)
        logger.info("request %s: responses model=%s prompt_tokens=%d "
                    "stream=%s", rid, body.model, len(pre.token_ids),
                    body.stream)
        root.set_attr(prompt_tokens=len(pre.token_ids),
                      stream=bool(body.stream))
        if body.stream:
            return await self._stream_responses(request, handle, body, pre,
                                                rid)
        start = time.monotonic()
        self.metrics.requests_total.inc(labels={"model": body.model})
        self.metrics.requests_in_flight.add(1, labels={"model": body.model})
        det = StreamDetokenizer(handle.tokenizer, pre.stop_sequences)
        parts, reason = [], None
        try:
            async for out in self._token_stream(handle, pre, det,
                                                body.model, start):
                parts.append(out.text)
                if out.finished:
                    reason = out.finish_reason
        finally:
            self.metrics.requests_in_flight.add(-1,
                                                labels={"model": body.model})
        self._observe_done(body.model, start, len(pre.token_ids),
                           det.completion_tokens)
        # Responses-API status semantics: stop → completed; truncation
        # (length ceiling) → incomplete; engine error → failed.
        status = {"stop": "completed", "length": "incomplete",
                  "error": "failed"}.get(str(reason or "stop"), "completed")
        resp = oai.ResponsesResponse(
            id=rid, model=body.model, status=status,
            output=[oai.ResponseOutputMessage(
                status=status,
                content=[oai.ResponseOutputText(text="".join(parts))])],
            usage=oai.ResponsesUsage(
                input_tokens=len(pre.token_ids),
                output_tokens=det.completion_tokens,
                total_tokens=len(pre.token_ids) + det.completion_tokens))
        return web.json_response(resp.model_dump(exclude_none=True))

    async def _stream_responses(self, request, handle, body, pre, rid):
        """Responses-API SSE: `response.created` → N ×
        `response.output_text.delta` → `response.completed` (the event
        names OpenAI's Responses stream uses; the reference streams
        internally and folds for unary, `http/service/openai.rs:222-226`)."""
        start = time.monotonic()
        self.metrics.requests_total.inc(labels={"model": body.model})
        self.metrics.requests_in_flight.add(1, labels={"model": body.model})
        response = web.StreamResponse(
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache"})
        await response.prepare(request)
        det = StreamDetokenizer(handle.tokenizer, pre.stop_sequences)
        parts, reason = [], None
        try:
            created = oai.ResponsesResponse(
                id=rid, model=body.model, status="in_progress")
            await response.write(oai.sse_encode_event(
                "response.created",
                {"type": "response.created",
                 "response": created.model_dump(exclude_none=True)}
            ).encode())
            async for out in self._token_stream(handle, pre, det,
                                                body.model, start):
                if out.text:
                    parts.append(out.text)
                    await response.write(oai.sse_encode_event(
                        "response.output_text.delta",
                        {"type": "response.output_text.delta",
                         "delta": out.text}).encode())
                if out.finished:
                    reason = out.finish_reason
                    break
            status = {"stop": "completed", "length": "incomplete",
                      "error": "failed"}.get(str(reason or "stop"),
                                             "completed")
            final = oai.ResponsesResponse(
                id=rid, model=body.model, status=status,
                output=[oai.ResponseOutputMessage(
                    status=status,
                    content=[oai.ResponseOutputText(text="".join(parts))])],
                usage=oai.ResponsesUsage(
                    input_tokens=len(pre.token_ids),
                    output_tokens=det.completion_tokens,
                    total_tokens=len(pre.token_ids)
                    + det.completion_tokens))
            await response.write(oai.sse_encode_event(
                "response.completed",
                {"type": "response.completed",
                 "response": final.model_dump(exclude_none=True)}
            ).encode())
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("client disconnected: %s", rid)
            raise
        finally:
            self.metrics.requests_in_flight.add(-1,
                                                labels={"model": body.model})
            self._observe_done(body.model, start, len(pre.token_ids),
                               det.completion_tokens)
        await response.write_eof()
        return response

    async def embeddings(self, request: web.Request) -> web.Response:
        """/v1/embeddings: last-token hidden-state embeddings (reference
        route `http/service/openai.rs:315`)."""
        try:
            body = oai.EmbeddingRequest.model_validate(await request.json())
        except Exception as e:
            return self._error(400, f"invalid request: {e}")
        handle = self._lookup(body.model)
        if handle is None:
            return self._error(404, f"model {body.model!r} not found",
                               "model_not_found")
        embed = getattr(handle.client, "embed", None)
        if embed is None:
            return self._error(501, "this model's engine does not serve "
                                    "embeddings", "not_implemented")
        inputs = body.inputs()
        if not inputs:
            return self._error(400, "input must be non-empty")
        if len(inputs) > 128:
            # Embeddings run one prefill per input on the engine; an
            # unbounded batch would starve token streaming for seconds.
            return self._error(400, f"too many inputs ({len(inputs)} > "
                                    "128 per request)")
        token_lists = []
        for item in inputs:
            toks = (handle.tokenizer.encode(item)
                    if isinstance(item, str) else list(item))
            if len(toks) >= handle.max_context:
                return self._error(
                    400, f"input of {len(toks)} tokens exceeds the model's "
                         f"maximum context length of {handle.max_context}")
            token_lists.append(toks)
        try:
            vecs = await embed(token_lists)
        except (ValueError, NotImplementedError) as e:
            return self._error(400, str(e))
        except (ConnectionError, OSError) as e:
            return self._error(503, f"embedding worker unavailable: {e}",
                               "service_unavailable")

        def encode_vec(vec):
            if body.encoding_format == "base64":
                import numpy as np

                return base64.b64encode(
                    np.asarray(vec, np.float32).tobytes()).decode("ascii")
            return [float(x) for x in vec]

        n_in = sum(len(t) for t in token_lists)
        resp = oai.EmbeddingResponse(
            model=body.model,
            data=[oai.EmbeddingData(index=i, embedding=encode_vec(vec))
                  for i, vec in enumerate(vecs)],
            usage=oai.Usage(prompt_tokens=n_in, total_tokens=n_in))
        return web.json_response(resp.model_dump(exclude_none=True))

    async def _stream_completion(self, request, handle, body, pre, rid):
        """SSE stream of `text_completion` chunks (ADVICE r1: the unary-only
        handler broke OpenAI streaming clients)."""

        def make_chunk(i, out, lps):
            logprobs = None
            if lps:
                logprobs = {
                    "tokens": [handle.tokenizer.decode([t])
                               for t, _ in lps],
                    "token_logprobs": [lp for _, lp in lps],
                }
            return [oai.CompletionResponse(
                id=rid, model=body.model,
                choices=[oai.CompletionChoice(
                    index=i, text=out.text or "",
                    finish_reason=out.finish_reason,
                    logprobs=logprobs)])]

        def make_usage_chunk(usage):
            return oai.CompletionResponse(
                id=rid, model=body.model, choices=[], usage=usage)

        return await self._stream_sse(request, handle, body, pre, rid,
                                      make_chunk, make_usage_chunk)

    # -- chat serving internals -------------------------------------------

    def _fan_out(self, pre, n: int):
        """n>1 sampling: clone the preprocessed request per choice with a
        distinct engine id; a client-pinned seed folds the choice index in
        (reproducible, but distinct across choices — vLLM convention)."""
        import copy
        import dataclasses

        out = []
        for i in range(n):
            clone = copy.copy(pre)
            clone.request_id = f"{pre.request_id}-c{i}" if i else pre.request_id
            if i and pre.sampling.seed is not None:
                clone.sampling = dataclasses.replace(
                    pre.sampling, seed=pre.sampling.seed + i)
            out.append(clone)
        return out

    async def _collect_one(self, handle, pre, model, start, want_lp,
                           on_first=None, observe_queue_wait=True):
        """Drain one engine stream → (text, finish_reason, det, lp_sink).
        `on_first` fires at the first yielded output (choice-0's prompt
        blocks are sealed by then — the signal siblings gate on)."""
        det = StreamDetokenizer(handle.tokenizer, pre.stop_sequences)
        lp_sink = [] if want_lp else None
        parts, reason = [], None
        async for out in self._token_stream(
                handle, pre, det, model, start, lp_sink=lp_sink,
                observe_queue_wait=observe_queue_wait):
            if on_first is not None:
                on_first()
                on_first = None
            parts.append(out.text)
            if out.finished:
                reason = out.finish_reason
        return "".join(parts), reason, det, lp_sink

    async def _collect_choices(self, handle, pre, n, model, start, want_lp):
        """n-choice unary collection.  Choice 0 starts FIRST; siblings
        launch at its FIRST TOKEN — the shared prompt blocks are sealed
        once prefill completes, so waiting for choice 0's whole stream
        (ADVICE r3) bought nothing but latency.  Siblings still
        prefix-hit instead of paying n× prefill for the same prompt.
        Failures don't leak running generations: everything is gathered
        with return_exceptions and the first error re-raised only after
        every stream has settled."""
        clones = self._fan_out(pre, n)
        if n == 1:
            r = await self._collect_one(handle, clones[0], model, start,
                                        want_lp)
            return [r], r[2].completion_tokens
        sealed = asyncio.Event()

        async def run0():
            try:
                return await self._collect_one(handle, clones[0], model,
                                               start, want_lp,
                                               on_first=sealed.set)
            finally:
                sealed.set()  # error/empty stream: don't strand siblings

        async def run_sib(clone):
            await sealed.wait()
            # Sibling TTFT measures from its own start: folding choice
            # 0's prefill into the histogram would skew it.  Queue wait
            # is choice 0's alone (a sibling's would read ~0).
            return await self._collect_one(handle, clone, model,
                                           time.monotonic(), want_lp,
                                           observe_queue_wait=False)

        results = await asyncio.gather(
            run0(), *(run_sib(c) for c in clones[1:]),
            return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        total_out = sum(det.completion_tokens for _, _, det, _ in results)
        return list(results), total_out

    # TPOT interval spans recorded per trace before they'd crowd out the
    # rest of the timeline (the histogram still sees every interval).
    MAX_TPOT_SPANS = 32

    async def _token_stream(self, handle, pre, det, model, start_ts,
                            lp_sink=None, observe_queue_wait=True):
        """Engine deltas → TextDeltas, with TTFT/ITL observation and the
        request-lifecycle trace spans (queue wait → TTFT → per-token
        TPOT intervals, parented under the request's root span).
        `lp_sink`: list collecting (token_id, logprob) pairs when the
        request asked for logprobs.  `observe_queue_wait`: False for
        n>1 sibling choices — their start_ts is their own launch time
        (post-seal), so a ~0 "queue wait" per sibling would skew the
        histogram low by a factor of n."""
        labels = {"model": model}
        tracer = self.tracer
        parent = tracing.current_span() if tracer.enabled else None
        led = None
        if observe_queue_wait:
            # Request ledger (ISSUE 18): begin BEFORE the client pipeline
            # so route/queue/prefill/kv_transfer stamps land on it; n>1
            # siblings (observe_queue_wait=False) stay ledger-less — one
            # ledger per HTTP request, choice 0's path.
            led = ledger_mod.begin(pre)
            # Queue wait, frontend view: request arrival → the
            # generation stream starting (preprocess, image encode,
            # routing, admission to the client pipeline): the ledger's
            # `receive` stamp (dynamo_request_phase_seconds{phase=
            # "receive"}) and the `frontend.queue_wait` span.  The
            # engine-side engine.queue_wait span covers in-engine wait.
            t_entry = time.monotonic()
            if led is not None:
                led.stamp("receive", dur=t_entry - start_ts, t=t_entry)
            if parent is not None:
                tracer.record_span("frontend.queue_wait", parent,
                                   start_ts, t_entry)
        first = True
        last_t = None
        n_intervals = 0
        ttft_s = None
        itl_sum = 0.0

        def tpot_mean():
            return itl_sum / n_intervals if n_intervals else None

        async for delta in handle.client.generate(pre):
            now = time.monotonic()
            ledger_mod.absorb_delta(pre, delta, where="frontend")
            if (lp_sink is not None and delta.logprobs
                    and len(delta.logprobs) == len(delta.token_ids)):
                lp_sink.extend(zip(delta.token_ids, delta.logprobs))
            if delta.token_ids:
                if first:
                    ttft_s = now - start_ts
                    self.metrics.ttft.observe(now - start_ts,
                                              labels={"model": model})
                    self.request_metrics.ttft.observe(now - start_ts,
                                                      labels=labels)
                    if parent is not None:
                        tracer.record_span("frontend.ttft", parent,
                                           start_ts, now)
                    first = False
                elif last_t is not None:
                    self.metrics.itl.observe(now - last_t,
                                             labels={"model": model})
                    self.request_metrics.tpot.observe(now - last_t,
                                                      labels=labels)
                    n_intervals += 1
                    itl_sum += now - last_t
                    if (parent is not None
                            and n_intervals <= self.MAX_TPOT_SPANS):
                        tracer.record_span(
                            "decode.tpot", parent, last_t, now,
                            attrs={"index": n_intervals,
                                   "tokens": len(delta.token_ids)})
                last_t = now
                out = det.push_tokens(delta.token_ids)
                if out.finished:      # stop string hit mid-stream
                    self.request_metrics.observe_outcome(ok=True)
                    self.ledger_sink.fold(led, ttft_s, tpot_mean(),
                                          det.completion_tokens, ok=True)
                    yield out
                    return
                if out.text:
                    yield out
            if delta.finished:
                # Terminal outcome feeds the SLO error-rate objective:
                # engine ERROR finishes are budget burn, everything else
                # (stop/length/cancel) is a served request.
                ok = delta.finish_reason is not FinishReason.ERROR
                self.request_metrics.observe_outcome(ok=ok)
                self.ledger_sink.fold(led, ttft_s, tpot_mean(),
                                      det.completion_tokens, ok=ok)
                yield det.finish(delta.finish_reason)
                return
        # Engine stream ended without a finished marker (worker died):
        self.request_metrics.observe_outcome(ok=False)
        self.ledger_sink.fold(led, ttft_s, tpot_mean(),
                              det.completion_tokens, ok=False)
        yield det.finish(FinishReason.ERROR)

    async def _unary_chat(self, handle, body, pre, rid):
        start = time.monotonic()
        self.metrics.requests_total.inc(labels={"model": body.model})
        self.metrics.requests_in_flight.add(1, labels={"model": body.model})
        want_lp = bool(pre.sampling.logprobs)
        try:
            results, total_out = await self._collect_choices(
                handle, pre, body.n, body.model, start, want_lp)
        finally:
            self.metrics.requests_in_flight.add(-1, labels={"model": body.model})
        self._observe_done(body.model, start, len(pre.token_ids), total_out)

        choices = []
        for i, (text, reason, det, lp_sink) in enumerate(results):
            tool_calls = None
            if body.tools and body.tool_choice != "none":
                # Tool-call extraction (reference postprocessor/
                # tool_calling): only attempted when the client declared
                # tools; parse failure leaves plain content.  A pinned
                # tool_choice wraps the whole completion as that call's
                # arguments (no marker syntax expected from the model).
                from dynamo_tpu.llm.postprocessor import (
                    force_tool_call,
                    forced_tool_name,
                    parse_tool_calls,
                )

                forced = forced_tool_name(body.tool_choice, body.tools)
                if forced:
                    text, calls = "", force_tool_call(text, forced)
                else:
                    text, calls = parse_tool_calls(text,
                                                   body.tool_call_parser)
                if calls:
                    tool_calls = calls
                    reason = "tool_calls"
            logprobs = None
            if lp_sink:
                logprobs = oai.ChatLogprobs(content=[
                    oai.ChatLogprobEntry(
                        token=handle.tokenizer.decode([t]), logprob=lp)
                    for t, lp in lp_sink])
            choices.append(oai.ChatChoice(
                index=i,
                # OpenAI wire shape: `content` is present (possibly "")
                # unless the message is a tool call — `text or None`
                # under exclude_none silently DROPPED the key whenever
                # the detokenizer produced no text.
                message=oai.ChatMessage(
                    role="assistant",
                    content=(text or None) if tool_calls else text,
                    tool_calls=tool_calls),
                finish_reason=reason,
                logprobs=logprobs))
        resp = oai.ChatCompletionResponse(
            id=rid, model=body.model, choices=choices,
            usage=oai.Usage(
                prompt_tokens=len(pre.token_ids),
                completion_tokens=total_out,
                total_tokens=len(pre.token_ids) + total_out))
        return web.json_response(resp.model_dump(exclude_none=True))

    async def _stream_chat(self, request, handle, body, pre, rid):
        # Streaming tool calls (VERDICT r5 #8 — r5 was unary-only): one
        # incremental parser per choice turns content deltas into
        # OpenAI-spec `delta.tool_calls` fragments; the final chunk's
        # finish_reason flips to "tool_calls" when any call was emitted.
        use_tools = bool(body.tools) and body.tool_choice != "none"
        parsers = {}
        if use_tools:
            from dynamo_tpu.llm.postprocessor import (
                StreamingToolCallParser,
                forced_tool_name,
            )

            forced = forced_tool_name(body.tool_choice, body.tools)
            parsers = {i: StreamingToolCallParser(body.tool_call_parser,
                                                  forced_name=forced)
                       for i in range(body.n)}

        def _logprobs(lps):
            if not lps:
                return None
            return oai.ChatLogprobs(content=[
                oai.ChatLogprobEntry(
                    token=handle.tokenizer.decode([t]), logprob=lp)
                for t, lp in lps])

        def _chunk(i, delta, finish=None, lps=None):
            return oai.ChatCompletionChunk(
                id=rid, model=body.model,
                choices=[oai.ChatStreamChoice(
                    index=i, delta=delta, finish_reason=finish,
                    logprobs=_logprobs(lps))])

        def make_chunk(i, out, lps):
            if not use_tools:
                return [_chunk(
                    i, oai.ChatChoiceDelta(content=out.text or None),
                    out.finish_reason, lps)]
            p = parsers[i]
            content, deltas = p.push(out.text) if out.text else ("", [])
            finish = None
            if out.finished:
                fcontent, fdeltas, any_calls = p.finish()
                content += fcontent
                deltas = deltas + fdeltas
                finish = "tool_calls" if any_calls else out.finish_reason
            chunks = []
            if content:
                chunks.append(_chunk(
                    i, oai.ChatChoiceDelta(content=content)))
            for d in deltas:
                chunks.append(_chunk(
                    i, oai.ChatChoiceDelta(tool_calls=[d])))
            if out.finished:
                chunks.append(_chunk(i, oai.ChatChoiceDelta(), finish))
            # Logprobs ride the first chunk of the batch; while the
            # parser buffers (no chunk emitted) they'd be dropped, so
            # pin them to a bare chunk instead.
            if lps:
                if chunks:
                    chunks[0].choices[0].logprobs = _logprobs(lps)
                else:
                    chunks.append(_chunk(
                        i, oai.ChatChoiceDelta(), lps=lps))
            return chunks

        def make_usage_chunk(usage):
            return oai.ChatCompletionChunk(
                id=rid, model=body.model, choices=[], usage=usage)

        def head_chunk(i):
            # Leading chunk with the assistant role (OpenAI convention),
            # one per choice index.
            return oai.ChatCompletionChunk(
                id=rid, model=body.model,
                choices=[oai.ChatStreamChoice(
                    index=i,
                    delta=oai.ChatChoiceDelta(role="assistant",
                                              content=""))])

        return await self._stream_sse(request, handle, body, pre, rid,
                                      make_chunk, make_usage_chunk,
                                      head_chunk=head_chunk)

    async def _stream_sse(self, request, handle, body, pre, rid,
                          make_chunk, make_usage_chunk, head_chunk=None):
        """Shared SSE scaffolding for chat + text completion streams:
        metrics, disconnect-cancel, optional stream_options.include_usage
        final chunk, and the [DONE] sentinel.

        n > 1 multiplexes n engine streams into the one SSE stream with
        per-choice `index` (the reference streams everything internally
        and folds for unary, `http/service/openai.rs:222-226`; r3
        rejected stream+n>1 with a 400).  `make_chunk(i, out, lps)`
        stamps the choice index and returns the LIST of chunks one
        TextDelta expands to (content, tool-call fragments, finish).
        Choice 0 starts first; siblings launch at its first token so
        they prefix-hit the sealed prompt blocks.
        """
        start = time.monotonic()
        self.metrics.requests_total.inc(labels={"model": body.model})
        self.metrics.requests_in_flight.add(1, labels={"model": body.model})
        response = web.StreamResponse(
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache"})
        await response.prepare(request)

        clones = self._fan_out(pre, body.n)
        dets = [StreamDetokenizer(handle.tokenizer, pre.stop_sequences)
                for _ in clones]
        want_lp = bool(pre.sampling.logprobs)
        queue: asyncio.Queue = asyncio.Queue()
        sealed = asyncio.Event()

        async def pump(i, clone):
            try:
                if i:
                    await sealed.wait()
                st = start if i == 0 else time.monotonic()
                lp_sink = [] if want_lp else None
                sent = 0
                async for out in self._token_stream(
                        handle, clone, dets[i], body.model, st,
                        lp_sink=lp_sink, observe_queue_wait=(i == 0)):
                    sealed.set()
                    lps = []
                    if lp_sink is not None:
                        lps, sent = lp_sink[sent:], len(lp_sink)
                    await queue.put(("chunk", i, out, lps))
                    if out.finished:
                        break
            except BaseException as e:
                await queue.put(("error", i, e, None))
                raise
            finally:
                sealed.set()
                await queue.put(("done", i, None, None))

        tasks = [asyncio.create_task(pump(i, c))
                 for i, c in enumerate(clones)]
        try:
            if head_chunk is not None:
                for i in range(len(clones)):
                    await response.write(
                        oai.sse_encode(head_chunk(i)).encode())
            remaining = len(clones)
            while remaining:
                # Coalesce every READY chunk into one socket write: at
                # high token rates the queue backs up while a write
                # drains, and one syscall per token-delta was a top-2
                # cost in a CPU load test (the reason the reference keeps
                # this loop in Rust, SURVEY §2.4.2).
                batch = [await queue.get()]
                while True:
                    try:
                        batch.append(queue.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                buf = []
                for kind, i, out, lps in batch:
                    if kind == "done":
                        remaining -= 1
                    elif kind == "error":
                        raise out
                    else:
                        # One TextDelta can fan out to several SSE chunks
                        # (content + tool_call fragments + finish).
                        buf.extend(oai.sse_encode(ch).encode()
                                   for ch in make_chunk(i, out, lps))
                if buf:
                    await response.write(b"".join(buf))
            if (body.stream_options or {}).get("include_usage"):
                n_in = len(pre.token_ids)
                total_out = sum(d.completion_tokens for d in dets)
                usage = oai.Usage(
                    prompt_tokens=n_in,
                    completion_tokens=total_out,
                    total_tokens=n_in + total_out)
                await response.write(
                    oai.sse_encode(make_usage_chunk(usage)).encode())
            await response.write(oai.SSE_DONE.encode())
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: closing the generator cancels the engine
            # request (reference disconnect.rs semantics).
            logger.info("client disconnected: %s", rid)
            raise
        finally:
            for t in tasks:
                t.cancel()
            # Retrieve every task's outcome: a second sibling error after
            # the first was raised would otherwise log "Task exception was
            # never retrieved" on every multi-choice failure.
            await asyncio.gather(*tasks, return_exceptions=True)
            self.metrics.requests_in_flight.add(-1, labels={"model": body.model})
            self._observe_done(body.model, start, len(pre.token_ids),
                               sum(d.completion_tokens for d in dets))
        await response.write_eof()
        return response

    def _observe_done(self, model, start_ts, in_tokens, out_tokens):
        labels = {"model": model}
        self.metrics.request_duration.observe(
            time.monotonic() - start_ts, labels=labels)
        self.metrics.input_tokens.observe(in_tokens, labels=labels)
        self.metrics.output_tokens.observe(out_tokens, labels=labels)
