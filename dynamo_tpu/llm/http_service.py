"""OpenAI-compatible HTTP service.

Capability parity with reference HttpService (lib/llm/src/http/service/
service_v2.rs:125-340, routers in openai.rs:1023-1094): ``/v1/chat/completions``,
``/v1/completions``, ``/v1/models``, ``/health``, ``/live``, ``/metrics`` with
SSE streaming, client-disconnect cancellation (disconnect.rs), request
validation errors in OpenAI error format, and per-route Prometheus metrics
including TTFT/ITL observations (http/service/metrics.rs).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
from typing import AsyncIterator

from aiohttp import web
from pydantic import ValidationError

from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.preprocessor import aggregate_chat_stream
from dynamo_tpu.llm.protocols import (
    ChatCompletionRequest,
    CompletionRequest,
    usage_block,
)
from dynamo_tpu.llm.recorder import finish_account, make_account
from dynamo_tpu.runtime import slo as slo_mod
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.errors import (AdapterNotFoundError,
                                       InvalidRequestError, NoInstancesError,
                                       OverloadedError, RateLimitedError)
from dynamo_tpu.runtime.logging import (current_trace, get_logger,
                                        parse_traceparent)
from dynamo_tpu.runtime.overload import (PRIORITY_BATCH, PRIORITY_INTERACTIVE,
                                         AdaptiveLimiter)
from dynamo_tpu.runtime.tracing import NULL_SPAN, get_recorder, span

log = get_logger("http")

# Overload-defense request headers (docs/RESILIENCE.md "Overload model").
DEADLINE_HEADER = "x-request-deadline-ms"
PRIORITY_HEADER = "x-priority"
BROWNOUT_HEADER = "X-Overload-Brownout"
# Accounting: multi-tenant attribution for /debug/requests rollups.
TENANT_HEADER = "x-tenant"


def _response_object(full: dict, model: str, text: str | None) -> dict:
    """OpenAI Responses-API response object from an aggregated chat result."""
    usage = full.get("usage") or {}
    return {
        "id": f"resp-{full.get('id')}",
        "object": "response",
        "created_at": full.get("created"),
        "model": model,
        "status": "completed",
        "output": [{
            "type": "message", "role": "assistant",
            "content": [{"type": "output_text", "text": text or ""}],
        }],
        "output_text": text or "",
        "usage": {
            "input_tokens": usage.get("prompt_tokens", 0),
            "output_tokens": usage.get("completion_tokens", 0),
            "total_tokens": usage.get("total_tokens", 0),
        },
    }


def _adapter_of(served) -> str | None:
    """The LoRA adapter name a served model resolves to (None = base):
    register_adapter stamps the binding into the card's runtime extras."""
    extra = (served.entry.card.runtime_config.extra or {})
    return extra.get("adapter") if extra.get("lora_base") else None


def _error_body(message: str, err_type: str = "invalid_request_error",
                code: int = 400,
                retry_after_s: float | None = None) -> web.Response:
    headers = {}
    if retry_after_s is not None:
        # Retry-After is integer seconds (RFC 9110); round UP so "0.4s"
        # doesn't tell clients to hammer back immediately.
        headers["Retry-After"] = str(max(1, int(-(-retry_after_s // 1))))
    return web.Response(
        status=code,
        content_type="application/json",
        headers=headers,
        text=json.dumps({"error": {"message": message, "type": err_type,
                                   "param": None, "code": None}}))


class HttpService:
    def __init__(self, runtime, manager: ModelManager,
                 host: str = "0.0.0.0", port: int = 8000,
                 tls_cert_path: str | None = None,
                 tls_key_path: str | None = None,
                 overload: AdaptiveLimiter | None = None):
        self._runtime = runtime
        self.manager = manager
        self.host, self.port = host, port
        # TLS (reference frontend main.py --tls-cert-path/--tls-key-path):
        # both paths -> serve HTTPS; one without the other is a config
        # error surfaced at start().
        self.tls_cert_path = tls_cert_path
        self.tls_key_path = tls_key_path
        # Overload defense (runtime/overload.py): adaptive admission +
        # deadline-aware shedding + brownout around the generate routes.
        # None = no admission control (tests, embedded use).
        self.overload = overload
        # GET /debug/timeline provider: the frontend entrypoint installs
        # its TimelineCollector's merged fleet view before start(); left
        # None, the route serves this process's own journal.
        self.timeline_provider = None
        self._runner: web.AppRunner | None = None
        metrics = runtime.metrics.namespace("http")
        self._m_requests = metrics.counter(
            "http_requests_total", "HTTP requests", ["route", "status"])
        self._m_inflight = metrics.gauge(
            "http_inflight", "In-flight HTTP requests", ["route"])
        self._m_ttft = metrics.histogram(
            "ttft_seconds", "Time to first token", ["model"],
            buckets=[.01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10])
        self._m_itl = metrics.histogram(
            "itl_seconds", "Inter-token latency", ["model"],
            buckets=[.001, .0025, .005, .01, .025, .05, .1, .25, 1])
        self._m_duration = metrics.histogram(
            "http_request_duration_seconds", "Request duration", ["route"])

    # -- lifecycle ------------------------------------------------------------
    async def start(self) -> None:
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/completions", self._completion)
        app.router.add_post("/v1/embeddings", self._embeddings)
        app.router.add_post("/v1/audio/transcriptions", self._transcriptions)
        app.router.add_post("/v1/responses", self._responses)
        app.router.add_get("/v1/models", self._models)
        app.router.add_post("/clear_kv_blocks", self._clear_kv_blocks)
        app.router.add_get("/health", self._health)
        app.router.add_get("/live", self._live)
        app.router.add_get("/metrics", self._metrics)
        # Fleet KV/capacity pane (llm/fleet.py): fans out over every
        # registered worker status server; typed partial results.
        app.router.add_get("/debug/fleet", self._debug_fleet)
        # Tracing/profiling debug API (runtime/health.py): in-process
        # pipelines get /debug/traces + /debug/profile on the frontend
        # port too, not only on the per-worker status server. The
        # frontend's /debug/kv serves the KV routers' fleet view +
        # decision telemetry.
        from dynamo_tpu.runtime.health import add_debug_routes
        add_debug_routes(app, kv_provider=self._kv_router_status,
                         perf_provider=self._perf_status,
                         timeline_provider=self.timeline_provider)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        ssl_ctx = None
        if self.tls_cert_path or self.tls_key_path:
            if not (self.tls_cert_path and self.tls_key_path):
                raise ValueError(
                    "TLS needs BOTH tls_cert_path and tls_key_path")
            import ssl
            ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_ctx.load_cert_chain(self.tls_cert_path, self.tls_key_path)
        site = web.TCPSite(self._runner, self.host, self.port,
                           ssl_context=ssl_ctx)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # type: ignore[union-attr]
        log.info("OpenAI %s service on %s:%d",
                 "HTTPS" if ssl_ctx else "HTTP", self.host, self.port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    # -- helpers --------------------------------------------------------------
    def _make_context(self, request: web.Request) -> Context:
        traceparent = request.headers.get("traceparent")
        trace = parse_traceparent(traceparent) if traceparent else None
        ctx = Context(trace_id=trace["trace_id"] if trace else None,
                      parent_span_id=trace["parent_id"] if trace else None)
        # Publish the request's trace context so every log line this
        # handler task emits carries trace_id/span_id (the formatters in
        # runtime/logging.py read this contextvar).
        current_trace.set({"trace_id": ctx.trace_id, "span_id": ctx.span_id})
        return ctx

    def _retry_after(self, exc: Exception | None = None) -> float:
        """Retry-After seconds for a shed/overloaded response: the
        error's own projection if it carries one, else the limiter's
        admission-queue projection, else the config default."""
        hint = getattr(exc, "retry_after_s", None)
        if hint:
            return hint
        if self.overload is not None:
            return self.overload.retry_after_s()
        ov = getattr(self._runtime.config, "overload", None)
        return ov.retry_after_default_s if ov is not None else 1.0

    def _overload_params(self, request: web.Request
                         ) -> tuple[str, float | None, web.Response | None]:
        """(priority, deadline_ms, error_response) from the overload
        request headers. A malformed deadline is the caller's bug: 400,
        not a silent default."""
        priority = request.headers.get(
            PRIORITY_HEADER, PRIORITY_INTERACTIVE).strip().lower()
        if priority not in (PRIORITY_INTERACTIVE, PRIORITY_BATCH):
            return PRIORITY_INTERACTIVE, None, _error_body(
                f"unknown {PRIORITY_HEADER} {priority!r} "
                f"(use 'interactive' or 'batch')")
        raw = request.headers.get(DEADLINE_HEADER)
        deadline_ms: float | None = None
        if raw is not None:
            try:
                deadline_ms = float(raw)
                if deadline_ms <= 0:
                    raise ValueError
            except ValueError:
                return priority, None, _error_body(
                    f"invalid {DEADLINE_HEADER} {raw!r} "
                    "(positive milliseconds)")
        return priority, deadline_ms, None

    async def _admit(self, request: web.Request, route: str, acct=None,
                     ctx: Context | None = None):
        """Run the overload-defense admission for one request. Returns
        (permit_ctx, response_headers, error_response): on a shed,
        error_response is the typed 429/503 (+ Retry-After) and the
        caller returns it immediately. ``acct`` (the accounting record)
        picks up tenant/priority/deadline, the admission queue wait, and
        — on a shed — the limiter's typed reason. With ``ctx`` the wait
        is also the request's ``http.admit_wait`` span: in its trace,
        beside ``http.request`` (which opens only once a permit is
        held), so the trace of a request is as long as the request."""
        if acct is not None:
            acct["tenant"] = request.headers.get(TENANT_HEADER)
        null = contextlib.nullcontext()
        if self.overload is None:
            return null, {}, None
        priority, deadline_ms, bad = self._overload_params(request)
        if acct is not None:
            acct["priority"] = priority
            acct["deadline_ms"] = deadline_ms
        if bad is not None:
            self._m_requests.inc(route=route, status="400")
            if acct is not None:
                acct.update(status="error", reason="bad_overload_header",
                            http_status=400)
            return null, {}, bad
        t0 = time.monotonic()
        limit_at_entry = int(self.overload.limit)
        waiting_at_entry = self.overload.waiting()
        outcome = "cancelled"  # the caller went away while it queued
        try:
            permit = await self.overload.admit(priority, deadline_ms)
            outcome = "granted"
        except RateLimitedError as exc:
            outcome = "shed"
            self._m_requests.inc(route=route, status="429")
            if acct is not None:
                acct.update(status="shed", http_status=429,
                            reason=getattr(exc, "shed_reason",
                                           "rate_limited"))
            return null, {}, _error_body(
                str(exc), "rate_limited", 429,
                retry_after_s=self._retry_after(exc))
        except OverloadedError as exc:
            outcome = "shed"
            self._m_requests.inc(route=route, status="503")
            if acct is not None:
                acct.update(status="shed", http_status=503,
                            reason=getattr(exc, "shed_reason", "overloaded"))
            return null, {}, _error_body(
                str(exc), "overloaded", 503,
                retry_after_s=self._retry_after(exc))
        finally:
            if ctx is not None and get_recorder().enabled:
                get_recorder().add(
                    "http.admit_wait", ctx.trace_id, ctx.parent_span_id,
                    t0, time.monotonic(),
                    status="ok" if outcome == "granted" else "error",
                    attrs={"route": route, "priority": priority,
                           "limit": limit_at_entry,
                           "waiting": waiting_at_entry, "outcome": outcome})
        if acct is not None:
            acct["queue_wait_s"] = time.monotonic() - t0
        headers = {}
        level = self.overload.pressure_level()
        if acct is not None:
            acct["brownout_level"] = level
        if level > 0:
            # Brownout reported in response metadata so clients can see
            # (and log) that they got degraded service.
            headers[BROWNOUT_HEADER] = str(level)
        return permit, headers, None

    def _apply_brownout(self, req) -> None:
        """Degradation hook: clamp max_tokens under brownout (the
        clamped value is visible in the response's usage block)."""
        if self.overload is None:
            return
        clamped = self.overload.clamp_max_tokens(
            getattr(req, "max_tokens", None))
        if clamped is not None:
            req.max_tokens = clamped

    async def _timed_first(self, chunks: AsyncIterator[dict], permit,
                           started: float, acct: dict | None = None,
                           req_span=NULL_SPAN) -> AsyncIterator[dict]:
        """Report time-to-first-chunk (the per-phase latency AIMD adapts
        against) into the admission permit — and, from the SAME timing
        point, feed the SLO plane's TTFT/ITL SLIs and the accounting
        record (TTFT, inter-chunk gaps, the usage block's token
        counts). What the limiter will judge rides on ``req_span``, the
        request's open ``http.request`` span, as ``permit_to_first_ms``."""
        plane = slo_mod.get_plane()
        last_t = None
        async for chunk in chunks:
            now = time.monotonic()
            if last_t is None:
                ttft = now - started
                if permit is not None and hasattr(permit, "note_latency"):
                    permit.note_latency(ttft)
                    req_span.set(permit_to_first_ms=ttft * 1e3)
                plane.observe_ttft(ttft)
                if acct is not None:
                    acct["ttft_s"] = ttft
            else:
                plane.observe_itl(now - last_t)
                if acct is not None:
                    acct["_itls"].append(now - last_t)
            last_t = now
            if acct is not None and isinstance(chunk, dict):
                usage = chunk.get("usage")
                if usage:
                    acct["prompt_tokens"] = usage.get("prompt_tokens")
                    acct["output_tokens"] = usage.get("completion_tokens")
            yield chunk

    def _account_done(self, acct: dict | None, ctx=None) -> None:
        """Finalize + ledger the accounting record exactly once. Any
        path that reached the route body lands here via its ``finally``
        — an unmarked record means the handler unwound without an
        explicit outcome (client disconnect / task cancellation).
        Availability/goodput SLIs are fed for real outcomes only (400s
        are the caller's bug, not an SLO event)."""
        if acct is None or "_t0" not in acct:
            return
        status = acct.get("status") or "cancelled"
        reason = acct.get("reason") or (
            "client_disconnect" if status == "cancelled" else None)
        http_status = acct.get("http_status")
        feed = status in ("ok", "shed") or (http_status or 0) >= 500
        finish_account(
            acct, status, reason, http_status, ctx=ctx,
            slo_plane=slo_mod.get_plane() if feed else None)

    async def _sse_stream(self, request: web.Request, chunks: AsyncIterator[dict],
                          ctx: Context, model: str,
                          extra_headers: dict | None = None
                          ) -> web.StreamResponse:
        # Pull the first chunk BEFORE sending headers so pipeline errors
        # (no instances, overload) still surface as proper HTTP statuses.
        start_t = time.monotonic()
        aiter = chunks.__aiter__()
        try:
            first_chunk = await aiter.__anext__()
        except StopAsyncIteration:
            first_chunk = None
        self._m_ttft.observe(time.monotonic() - start_t, model=model)
        response = web.StreamResponse(
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     **(extra_headers or {})})
        await response.prepare(request)
        last_t = time.monotonic()
        try:
            if first_chunk is not None:
                await response.write(
                    b"data: " + json.dumps(first_chunk).encode() + b"\n\n")
            async for chunk in aiter:
                now = time.monotonic()
                self._m_itl.observe(now - last_t, model=model)
                last_t = now
                await response.write(
                    b"data: " + json.dumps(chunk).encode() + b"\n\n")
            await response.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away: propagate kill so the worker frees the slot
            # (reference http/service/disconnect.rs).
            ctx.kill()
            raise
        return response

    # -- routes ---------------------------------------------------------------
    async def _chat(self, request: web.Request) -> web.StreamResponse:
        route = "chat_completions"
        started = time.monotonic()
        self._m_inflight.inc(route=route)
        acct = None
        ctx = None
        try:
            try:
                body = await request.json()
                chat_req = ChatCompletionRequest.model_validate(body)
            except (json.JSONDecodeError, ValidationError) as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(str(exc))
            served = self.manager.get(chat_req.model)
            if served is None:
                self._m_requests.inc(route=route, status="404")
                return _error_body(f"model {chat_req.model!r} not found",
                                   "model_not_found", 404)
            acct = make_account(route, chat_req.model)
            acct["adapter"] = _adapter_of(served)
            ctx = self._make_context(request)
            acct["request_id"], acct["trace_id"] = ctx.id, ctx.trace_id
            permit, meta_headers, shed = await self._admit(request, route,
                                                           acct, ctx)
            if shed is not None:
                return shed
            try:
                with permit, span("http.request", ctx=ctx, route=route,
                                  model=chat_req.model) as req_span:
                    self._apply_brownout(chat_req)
                    chunks = self._timed_first(
                        served.preprocessor.generate(chat_req, ctx),
                        permit, time.monotonic(), acct, req_span)
                    if chat_req.stream:
                        resp = await self._sse_stream(request, chunks, ctx,
                                                      chat_req.model,
                                                      meta_headers)
                        self._m_requests.inc(route=route, status="200")
                        acct.update(status="ok", http_status=200)
                        return resp
                    # Non-streaming: force the usage chunk through the
                    # delta stream so the aggregate carries real token
                    # counts.
                    chat_req.stream_options = {"include_usage": True}
                    full = await aggregate_chat_stream(chunks, 0)
                    self._m_requests.inc(route=route, status="200")
                    acct.update(status="ok", http_status=200)
                    return web.json_response(full, headers=meta_headers)
            except NoInstancesError as exc:
                self._m_requests.inc(route=route, status="503")
                acct.update(status="shed", reason="no_instances",
                            http_status=503)
                return _error_body(str(exc), "service_unavailable", 503,
                                   retry_after_s=self._retry_after(exc))
            except AdapterNotFoundError as exc:
                # The model name resolved to an adapter card whose base
                # worker does not hold the adapter: a naming error — 404
                # like an unknown model, typed so clients can tell which.
                self._m_requests.inc(route=route, status="404")
                acct.update(status="error", reason="adapter_not_found",
                            http_status=404)
                return _error_body(str(exc), "adapter_not_found", 404)
            except RateLimitedError as exc:
                self._m_requests.inc(route=route, status="429")
                acct.update(status="shed", http_status=429,
                            reason=getattr(exc, "shed_reason",
                                           "rate_limited"))
                return _error_body(str(exc), "rate_limited", 429,
                                   retry_after_s=self._retry_after(exc))
            except OverloadedError as exc:
                self._m_requests.inc(route=route, status="503")
                acct.update(status="shed", http_status=503,
                            reason=getattr(exc, "shed_reason", "overloaded"))
                return _error_body(str(exc), "overloaded", 503,
                                   retry_after_s=self._retry_after(exc))
            except (ValueError, InvalidRequestError) as exc:
                # Engine-level request validation (unsupported sampling
                # features, over-length prompts): the caller's fault —
                # whether raised in-process or typed over the wire.
                self._m_requests.inc(route=route, status="400")
                acct.update(status="error", reason="invalid_request",
                            http_status=400)
                return _error_body(str(exc))
            except Exception as exc:  # noqa: BLE001
                if isinstance(exc, ConnectionResetError):
                    acct.update(status="cancelled",
                                reason="client_disconnect")
                else:
                    acct.update(status="error", reason=type(exc).__name__,
                                http_status=500)
                log.exception("chat handler failed")
                self._m_requests.inc(route=route, status="500")
                return _error_body(f"internal error: {exc}", "internal_error", 500)
        finally:
            self._account_done(acct, ctx)
            self._m_inflight.dec(route=route)
            self._m_duration.observe(time.monotonic() - started, route=route)

    async def _completion(self, request: web.Request) -> web.StreamResponse:
        route = "completions"
        started = time.monotonic()
        self._m_inflight.inc(route=route)
        acct = None
        ctx = None
        try:
            try:
                body = await request.json()
                comp_req = CompletionRequest.model_validate(body)
            except (json.JSONDecodeError, ValidationError) as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(str(exc))
            served = self.manager.get(comp_req.model)
            if served is None:
                self._m_requests.inc(route=route, status="404")
                return _error_body(f"model {comp_req.model!r} not found",
                                   "model_not_found", 404)
            acct = make_account(route, comp_req.model)
            acct["adapter"] = _adapter_of(served)
            ctx = self._make_context(request)
            acct["request_id"], acct["trace_id"] = ctx.id, ctx.trace_id
            permit, meta_headers, shed = await self._admit(request, route,
                                                           acct, ctx)
            if shed is not None:
                return shed
            try:
                with permit, span("http.request", ctx=ctx, route=route,
                                  model=comp_req.model) as req_span:
                    self._apply_brownout(comp_req)
                    if not comp_req.stream:
                        # Force the usage chunk so the folded response
                        # has counts.
                        comp_req.stream_options = {"include_usage": True}
                    chunks = self._timed_first(
                        served.preprocessor.generate_completion(
                            comp_req, ctx),
                        permit, time.monotonic(), acct, req_span)
                    if comp_req.stream:
                        resp = await self._sse_stream(request, chunks, ctx,
                                                      comp_req.model,
                                                      meta_headers)
                        self._m_requests.inc(route=route, status="200")
                        acct.update(status="ok", http_status=200)
                        return resp
                    texts: list[str] = []
                    finish = None
                    meta: dict = {}
                    usage = None
                    async for chunk in chunks:
                        meta = {k: chunk.get(k, meta.get(k))
                                for k in ("id", "created")}
                        if chunk.get("usage"):
                            usage = chunk["usage"]
                        for choice in chunk.get("choices", []):
                            texts.append(choice.get("text") or "")
                            finish = choice.get("finish_reason") or finish
                    self._m_requests.inc(route=route, status="200")
                    acct.update(status="ok", http_status=200)
                    return web.json_response({
                        "id": meta.get("id"), "object": "text_completion",
                        "created": meta.get("created"),
                        "model": comp_req.model,
                        "choices": [{"index": 0, "text": "".join(texts),
                                     "finish_reason": finish,
                                     "logprobs": None}],
                        "usage": usage or usage_block(0, 0),
                    }, headers=meta_headers)
            except AdapterNotFoundError as exc:
                self._m_requests.inc(route=route, status="404")
                acct.update(status="error", reason="adapter_not_found",
                            http_status=404)
                return _error_body(str(exc), "adapter_not_found", 404)
            except ValueError as exc:
                self._m_requests.inc(route=route, status="400")
                acct.update(status="error", reason="invalid_request",
                            http_status=400)
                return _error_body(str(exc))
            except NoInstancesError as exc:
                self._m_requests.inc(route=route, status="503")
                acct.update(status="shed", reason="no_instances",
                            http_status=503)
                return _error_body(str(exc), "service_unavailable", 503,
                                   retry_after_s=self._retry_after(exc))
            except RateLimitedError as exc:
                self._m_requests.inc(route=route, status="429")
                acct.update(status="shed", http_status=429,
                            reason=getattr(exc, "shed_reason",
                                           "rate_limited"))
                return _error_body(str(exc), "rate_limited", 429,
                                   retry_after_s=self._retry_after(exc))
            except OverloadedError as exc:
                self._m_requests.inc(route=route, status="503")
                acct.update(status="shed", http_status=503,
                            reason=getattr(exc, "shed_reason", "overloaded"))
                return _error_body(str(exc), "overloaded", 503,
                                   retry_after_s=self._retry_after(exc))
            except Exception as exc:  # noqa: BLE001
                if isinstance(exc, ConnectionResetError):
                    acct.update(status="cancelled",
                                reason="client_disconnect")
                else:
                    acct.update(status="error", reason=type(exc).__name__,
                                http_status=500)
                log.exception("completion handler failed")
                self._m_requests.inc(route=route, status="500")
                return _error_body(f"internal error: {exc}", "internal_error", 500)
        finally:
            self._account_done(acct, ctx)
            self._m_inflight.dec(route=route)
            self._m_duration.observe(time.monotonic() - started, route=route)

    async def _embeddings(self, request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings (reference openai.rs embeddings route):
        tokenizes the input(s) and asks an embedding-capable worker."""
        route = "embeddings"
        started = time.monotonic()
        self._m_inflight.inc(route=route)
        try:
            try:
                body = await request.json()
                model = body["model"]
                raw = body.get("input")
                if raw is None:
                    raise ValueError("missing 'input'")
            except (json.JSONDecodeError, KeyError, ValueError) as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(str(exc))
            served = self.manager.get(model)
            if served is None:
                self._m_requests.inc(route=route, status="404")
                return _error_body(f"model {model!r} not found",
                                   "model_not_found", 404)
            inputs = raw if isinstance(raw, list) else [raw]
            if inputs and isinstance(inputs[0], int):
                inputs = [inputs]  # a single pre-tokenized prompt
            tokenizer = served.preprocessor.tokenizer
            token_lists = [t if isinstance(t, list) else tokenizer.encode(t)
                           for t in inputs]
            limit = served.entry.card.context_length
            if not token_lists or any(not t for t in token_lists):
                self._m_requests.inc(route=route, status="400")
                return _error_body("'input' must contain at least one "
                                   "non-empty prompt")
            if any(len(t) > limit for t in token_lists):
                self._m_requests.inc(route=route, status="400")
                return _error_body(
                    f"input exceeds the model context length ({limit})")
            try:
                if served.client is None:
                    # Static/local pipeline (unified launcher): reach the
                    # in-process engine behind Preprocessor -> Backend.
                    engine = served.preprocessor.inner.inner
                    vectors = await engine.embed(
                        token_lists, body.get("pooling", "last"))
                else:
                    stream = await served.client.round_robin(
                        {"embed": True, "token_lists": token_lists,
                         "pooling": body.get("pooling", "last")})
                    vectors = None
                    async for item in stream:
                        if "embeddings" in item:
                            vectors = item["embeddings"]
                    if vectors is None:
                        raise RuntimeError("worker returned no embeddings")
            except NoInstancesError as exc:
                self._m_requests.inc(route=route, status="503")
                return _error_body(str(exc), "service_unavailable", 503,
                                   retry_after_s=self._retry_after(exc))
            self._m_requests.inc(route=route, status="200")
            total = sum(len(t) for t in token_lists)
            return web.json_response({
                "object": "list", "model": model,
                "data": [{"object": "embedding", "index": i, "embedding": v}
                         for i, v in enumerate(vectors)],
                "usage": {"prompt_tokens": total, "total_tokens": total},
            })
        except Exception as exc:  # noqa: BLE001
            log.exception("embeddings handler failed")
            self._m_requests.inc(route=route, status="500")
            return _error_body(f"internal error: {exc}", "internal_error", 500)
        finally:
            self._m_inflight.dec(route=route)
            self._m_duration.observe(time.monotonic() - started, route=route)

    async def _transcriptions(self, request: web.Request) -> web.Response:
        """OpenAI /v1/audio/transcriptions: WAV in (base64 ``file`` field;
        multipart upstreams decode before us), text out. The audio runs
        through the mel front end + audio encoder (llm/audio.py) and
        reaches the LLM as prompt-embedding spans (mm_embeds) — the
        reference's multimodal-processor contract
        (components/backends/trtllm multimodal), audio-first here."""
        route = "audio_transcriptions"
        started = time.monotonic()
        self._m_inflight.inc(route=route)
        try:
            import base64

            from dynamo_tpu.llm.audio import AudioEncoder, embed_audio
            from dynamo_tpu.llm.protocols import PreprocessedRequest
            try:
                body = await request.json()
                model = body["model"]
                wav = base64.b64decode(body["file"])
                max_tokens = int(body.get("max_tokens", 256))
                temperature = float(body.get("temperature", 0.0))
            except (json.JSONDecodeError, KeyError, ValueError,
                    TypeError) as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(f"need 'model' and base64 'file' "
                                   f"(+ numeric options): {exc}")
            served = self.manager.get(model)
            if served is None:
                self._m_requests.inc(route=route, status="404")
                return _error_body(f"model {model!r} not found",
                                   "model_not_found", 404)
            # The encoder projects to the LLM's hidden size, published in
            # the card's runtime extras (in-process engines expose it
            # directly).
            hidden = (served.entry.card.runtime_config.extra or {}) \
                .get("hidden_size")
            if hidden is None and served.client is None:
                hidden = served.preprocessor.inner.inner.runner.spec \
                    .hidden_size
            if hidden is None:
                self._m_requests.inc(route=route, status="400")
                return _error_body(
                    f"model {model!r} did not publish hidden_size; "
                    "audio input needs an embedding-capable worker")
            cache = getattr(self, "_audio_encoders", None)
            if cache is None:
                cache = self._audio_encoders = {}
            encoder = cache.get((model, hidden))
            if encoder is None:
                # Trained weights: card runtime extras or env override
                # (scripts/convert_whisper_encoder.py produces the
                # checkpoint). Without them the encoder is DETERMINISTIC
                # RANDOM INIT — the route works end to end but emits
                # model babble, flagged in the response.
                import os as _os
                weights = (_os.environ.get("DTPU_AUDIO_ENCODER_WEIGHTS")
                           or (served.entry.card.runtime_config.extra
                               or {}).get("audio_encoder_weights"))
                encoder = cache[(model, hidden)] = AudioEncoder(
                    hidden, weights_path=weights)
            span, n_audio = embed_audio(wav, encoder)
            tokenizer = served.preprocessor.tokenizer
            prompt_tokens = tokenizer.encode(
                body.get("prompt") or "Transcribe the audio.")
            req = PreprocessedRequest(
                model=model, token_ids=[0] * n_audio + prompt_tokens,
                mm_embeds=[span])
            req.stop_conditions.max_tokens = max_tokens
            req.sampling_options.temperature = temperature
            req.eos_token_ids = tokenizer.eos_token_ids()
            ctx = self._make_context(request)
            toks: list[int] = []
            try:
                if served.client is None:
                    engine = served.preprocessor.inner.inner
                    stream = engine.generate(req, ctx)
                else:
                    stream = await served.client.round_robin(
                        req.to_wire(), context=ctx)
                async for out in stream:
                    toks.extend(out.get("token_ids", []))
                    if out.get("finish_reason"):
                        break
            except NoInstancesError as exc:
                self._m_requests.inc(route=route, status="503")
                return _error_body(str(exc), "service_unavailable", 503,
                                   retry_after_s=self._retry_after(exc))
            self._m_requests.inc(route=route, status="200")
            resp = {
                "text": tokenizer.decode(toks),
                "usage": {"input_tokens": len(req.token_ids),
                          "output_tokens": len(toks),
                          "audio_tokens": n_audio},
            }
            if getattr(encoder, "untrained", False):
                resp["warnings"] = [
                    "audio encoder is random-init (no "
                    "audio_encoder_weights configured): output is not a "
                    "real transcription"]
            return web.json_response(resp)
        except Exception as exc:  # noqa: BLE001
            log.exception("transcriptions handler failed")
            self._m_requests.inc(route=route, status="500")
            return _error_body(f"internal error: {exc}", "internal_error",
                               500)
        finally:
            self._m_inflight.dec(route=route)
            self._m_duration.observe(time.monotonic() - started, route=route)

    async def _responses(self, request: web.Request) -> web.Response:
        """OpenAI /v1/responses (reference openai.rs:1023-1094 responses
        route): adapts the Responses API onto the chat pipeline
        (non-streaming)."""
        route = "responses"
        started = time.monotonic()
        self._m_inflight.inc(route=route)
        acct = None
        ctx = None
        try:
            try:
                body = await request.json()
                model = body["model"]
                raw_input = body.get("input", "")
            except (json.JSONDecodeError, KeyError) as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(str(exc))
            served = self.manager.get(model)
            if served is None:
                self._m_requests.inc(route=route, status="404")
                return _error_body(f"model {model!r} not found",
                                   "model_not_found", 404)
            if isinstance(raw_input, str):
                messages = [{"role": "user", "content": raw_input}]
            else:
                messages = [{"role": m.get("role", "user"),
                             "content": m.get("content", "")}
                            for m in raw_input]
            if body.get("instructions"):
                messages.insert(0, {"role": "system",
                                    "content": body["instructions"]})
            try:
                chat_req = ChatCompletionRequest(
                    model=model, messages=messages,
                    max_tokens=body.get("max_output_tokens"),
                    temperature=body.get("temperature"),
                    top_p=body.get("top_p"),
                    stream_options={"include_usage": True})
            except ValidationError as exc:
                self._m_requests.inc(route=route, status="400")
                return _error_body(str(exc))
            acct = make_account(route, model)
            ctx = self._make_context(request)
            acct["request_id"], acct["trace_id"] = ctx.id, ctx.trace_id
            permit, meta_headers, shed = await self._admit(request, route,
                                                           acct, ctx)
            if shed is not None:
                return shed
            with permit, span("http.request", ctx=ctx, route=route,
                              model=model) as req_span:
                self._apply_brownout(chat_req)
                chunks = self._timed_first(
                    served.preprocessor.generate(chat_req, ctx),
                    permit, time.monotonic(), acct, req_span)
                if body.get("stream"):
                    resp = await self._responses_sse(request, chunks, ctx,
                                                     model)
                    self._m_requests.inc(route=route, status="200")
                    acct.update(status="ok", http_status=200)
                    return resp
                full = await aggregate_chat_stream(chunks, 0)
                msg = full["choices"][0]["message"]
                usage = full.get("usage") or {}
                self._m_requests.inc(route=route, status="200")
                acct.update(status="ok", http_status=200)
                return web.json_response(
                    _response_object(full, model, msg.get("content")),
                    headers=meta_headers)
        except RateLimitedError as exc:
            outcome = "shed"
            self._m_requests.inc(route=route, status="429")
            if acct is not None:
                acct.update(status="shed", http_status=429,
                            reason=getattr(exc, "shed_reason",
                                           "rate_limited"))
            return _error_body(str(exc), "rate_limited", 429,
                               retry_after_s=self._retry_after(exc))
        except OverloadedError as exc:
            outcome = "shed"
            self._m_requests.inc(route=route, status="503")
            if acct is not None:
                acct.update(status="shed", http_status=503,
                            reason=getattr(exc, "shed_reason", "overloaded"))
            return _error_body(str(exc), "overloaded", 503,
                               retry_after_s=self._retry_after(exc))
        except NoInstancesError as exc:
            self._m_requests.inc(route=route, status="503")
            if acct is not None:
                acct.update(status="shed", reason="no_instances",
                            http_status=503)
            return _error_body(str(exc), "service_unavailable", 503,
                               retry_after_s=self._retry_after(exc))
        except AdapterNotFoundError as exc:
            self._m_requests.inc(route=route, status="404")
            if acct is not None:
                acct.update(status="error", reason="adapter_not_found",
                            http_status=404)
            return _error_body(str(exc), "adapter_not_found", 404)
        except Exception as exc:  # noqa: BLE001
            if acct is not None:
                if isinstance(exc, ConnectionResetError):
                    acct.update(status="cancelled",
                                reason="client_disconnect")
                else:
                    acct.update(status="error", reason=type(exc).__name__,
                                http_status=500)
            log.exception("responses handler failed")
            self._m_requests.inc(route=route, status="500")
            return _error_body(f"internal error: {exc}", "internal_error", 500)
        finally:
            self._account_done(acct, ctx)
            self._m_inflight.dec(route=route)
            self._m_duration.observe(time.monotonic() - started, route=route)

    async def _responses_sse(self, request: web.Request, chunks,
                             ctx: Context, model: str) -> web.StreamResponse:
        """Responses-API streaming: response.output_text.delta events per
        content delta, then response.completed with the final object."""
        response = web.StreamResponse(
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache"})
        await response.prepare(request)

        async def send(event: str, data: dict) -> None:
            await response.write(
                f"event: {event}\ndata: {json.dumps(data)}\n\n".encode())

        content: list[str] = []
        meta: dict = {}
        usage: dict = {}
        try:
            async for chunk in chunks:
                meta = {k: chunk.get(k, meta.get(k))
                        for k in ("id", "created")}
                if chunk.get("usage"):
                    usage = chunk["usage"]
                for choice in chunk.get("choices", []):
                    piece = choice.get("delta", {}).get("content")
                    if piece:
                        content.append(piece)
                        await send("response.output_text.delta",
                                   {"delta": piece})
            full = {"id": meta.get("id"), "created": meta.get("created"),
                    "usage": usage}
            await send("response.completed",
                       {"response": _response_object(full, model,
                                                     "".join(content))})
        except (ConnectionResetError, asyncio.CancelledError):
            ctx.kill()
            raise
        return response

    async def _clear_kv_blocks(self, _request: web.Request) -> web.Response:
        """Admin route (reference openai.rs clear_kv_blocks): tell every
        worker instance of every served model to drop its reusable prefix
        cache."""
        results: dict[str, dict] = {}
        for name, served in self.manager.models.items():
            per_model: dict[str, int] = {}
            if served.client is None:
                engine = served.preprocessor.inner.inner
                clear = getattr(engine, "clear_kv_blocks", None)
                if clear is not None:
                    per_model["local"] = await clear()
            else:
                for iid in served.client.instance_ids():
                    try:
                        stream = await served.client.direct(
                            {"clear_kv_blocks": True}, iid)
                        async for item in stream:
                            if "cleared" in item:
                                per_model[f"{iid:x}"] = item["cleared"]
                    except Exception as exc:  # noqa: BLE001 — report per-worker
                        per_model[f"{iid:x}"] = -1
                        log.warning("clear_kv_blocks failed on %x: %s",
                                    iid, exc)
            results[name] = per_model
        return web.json_response({"cleared": results})

    # -- KV & capacity pane (docs/OBSERVABILITY.md "KV & capacity") -----------
    def _kv_router_status(self) -> dict:
        """This frontend's /debug/kv: per-model KV-router fleet view +
        decision telemetry, plus in-process engines' KV state for the
        unified launcher (no worker status server to ask)."""
        routers = {}
        engines = {}
        for name, served in self.manager.models.items():
            status = getattr(served.router, "kv_status", None)
            if status is not None:
                routers[name] = status()
            if served.client is None:
                engine = getattr(
                    getattr(served.preprocessor, "inner", None), "inner",
                    None)
                engine_status = getattr(engine, "kv_status", None)
                if engine_status is not None:
                    engines[name] = engine_status()
        return {"role": "frontend", "routers": routers, "engines": engines}

    def _perf_status(self) -> dict:
        """This frontend's /debug/perf: the process-global compile
        observatory plus in-process engines' full perf view (unified
        launcher — no worker status server to ask)."""
        from dynamo_tpu.engine.perf import process_perf_status
        engines = {}
        for name, served in self.manager.models.items():
            if served.client is not None:
                continue
            engine = getattr(
                getattr(served.preprocessor, "inner", None), "inner", None)
            status = getattr(engine, "perf_status", None)
            if status is not None:
                engines[name] = status()
        body = process_perf_status()
        body["role"] = "frontend"
        body["engines"] = engines
        return body

    async def _debug_fleet(self, request: web.Request) -> web.Response:
        """GET /debug/fleet: merged per-worker KV/capacity view from
        every registered worker status server (bounded fan-out, typed
        partial results — one down worker never breaks the pane)."""
        from dynamo_tpu.llm.fleet import (DEFAULT_CONCURRENCY,
                                          DEFAULT_TIMEOUT_S,
                                          fleet_kv_snapshot)
        if not self._runtime.has_discovery:
            return web.json_response(
                {"error": "static runtime: no discovery plane to "
                 "enumerate worker status servers"}, status=503)
        try:
            timeout_s = float(request.query.get("timeout_s",
                                                DEFAULT_TIMEOUT_S))
            concurrency = int(request.query.get("concurrency",
                                                DEFAULT_CONCURRENCY))
        except ValueError:
            return _error_body("timeout_s/concurrency must be numeric")
        snapshot = await fleet_kv_snapshot(
            self._runtime, timeout_s=timeout_s, concurrency=concurrency,
            router_view=self._kv_router_status)
        return web.json_response(snapshot)

    async def _models(self, _request: web.Request) -> web.Response:
        return web.json_response({"object": "list",
                                  "data": self.manager.list_models()})

    async def _health(self, _request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy",
                                  "models": sorted(self.manager.models)})

    async def _live(self, _request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _metrics(self, _request: web.Request) -> web.Response:
        return web.Response(body=self._runtime.metrics.expose(),
                            content_type="text/plain")
