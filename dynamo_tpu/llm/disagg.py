"""Disaggregated prefill/decode serving: worker-side handlers + config.

TPU-native version of the reference's disaggregation path (SURVEY.md call
stack 3.3; components/backends/vllm/src/dynamo/vllm/handlers.py:113-199):

- The PREFILL worker serves a prefill-only endpoint: it computes the
  prompt's KV (engine.prefill_extract on the engine thread), samples the
  first token, and streams the KV back as a chunked parcel
  (llm/kv_transfer.py) — the host-staged stand-in for the reference's NIXL
  GPU->GPU writes (handlers.py:167-199 PrefillWorkerHandler).
- The DECODE worker conditionally forwards prompts longer than
  ``max_local_prefill_length`` to a discovered prefill worker
  (round-robin, like the reference's prefill_worker_client.round_robin at
  handlers.py:148-152), assembles the parcel, uploads it into its own KV
  pool (the mesh re-shards on upload, so TP-mismatched transfers work),
  and decodes from the returned first token. Anything shorter — or any
  remote failure — prefills locally (conditional disaggregation,
  lib/llm/src/disagg_router.rs:25-45).

The conditional threshold is dynamic: ``DisaggRouterConfig`` reads
``disagg/<model>`` from the coordinator KV store and watches it for
updates, mirroring DisaggRouterConf::from_etcd_with_watcher.
"""

from __future__ import annotations

import asyncio
import inspect
import time
from typing import AsyncIterator

from dynamo_tpu.llm.kv_transfer import collect_prefill_response, kv_to_chunks
from dynamo_tpu.llm.model_card import model_slug
from dynamo_tpu.llm.protocols import LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.errors import (
    EngineError, NoInstancesError, StreamIncompleteError)
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import span

log = get_logger("disagg")

DISAGG_CONFIG_ROOT = "disagg/"

# Default component name prefill workers serve under (decode workers
# discover them by this, namespaced like any endpoint).
PREFILL_COMPONENT = "prefill"
PREFILL_ENDPOINT = "generate"


def disagg_config_key(model_name: str) -> str:
    return f"{DISAGG_CONFIG_ROOT}{model_slug(model_name)}"


class DisaggRouterConfig:
    """Per-model conditional-disaggregation config, watchable from the
    coordinator KV store (reference DisaggRouterConf,
    disagg_router.rs:25-45: read once, then watched for updates)."""

    def __init__(self, max_local_prefill_length: int = 512):
        self.max_local_prefill_length = max_local_prefill_length
        self._watch = None
        self._client = None
        self._key: str | None = None
        self._task: asyncio.Task | None = None
        # Observable recovery count (tests + debugging): how many times
        # the watch loop survived a failure and re-established itself.
        self.watch_restarts = 0

    def prefill_remote(self, prompt_len: int) -> bool:
        return prompt_len > self.max_local_prefill_length

    @classmethod
    async def from_coordinator_with_watch(
            cls, client, model_name: str,
            default_max_local: int = 512) -> "DisaggRouterConfig":
        cfg = cls(default_max_local)
        cfg._client = client
        cfg._key = disagg_config_key(model_name)
        watch = await client.watch_prefix(cfg._key)
        for item in watch.snapshot:
            cfg._apply(item["v"])
        cfg._watch = watch
        cfg._task = asyncio.create_task(cfg._watch_loop())
        return cfg

    def _apply(self, value) -> None:
        if isinstance(value, dict) and "max_local_prefill_length" in value:
            self.max_local_prefill_length = int(
                value["max_local_prefill_length"])
            log.info("disagg config updated: max_local_prefill_length=%d",
                     self.max_local_prefill_length)

    async def _watch_loop(self) -> None:
        """Apply config puts until cancelled. Must never die silently: a
        dead watch freezes the conditional-disagg threshold at its last
        value for the life of the worker — so any failure (a malformed
        value raising in _apply, a watch lost to a coordinator restart
        the client could not replay) re-establishes the watch under the
        unified retry policy (runtime/retry.py) instead of returning."""
        from dynamo_tpu.runtime.retry import Backoff, policies
        backoff = Backoff(policies.COORD_RECONNECT)
        while True:
            try:
                async for event in self._watch:
                    if event["event"] != "put":
                        continue
                    try:
                        self._apply(event["value"])
                    except (TypeError, ValueError):
                        log.warning("malformed disagg config ignored: %r",
                                    event["value"])
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — survive, re-watch
                log.exception("disagg config watch failed; re-watching")
            await backoff.sleep()
            try:
                self._watch = await self._client.watch_prefix(self._key)
                for item in self._watch.snapshot:
                    try:
                        self._apply(item["v"])
                    except (TypeError, ValueError):
                        log.warning("malformed disagg config ignored: %r",
                                    item["v"])
                self.watch_restarts += 1
                backoff.reset()
            except (ConnectionError, OSError, RuntimeError):
                log.warning("disagg config re-watch failed; will retry")

    async def close(self) -> None:
        if self._task:
            self._task.cancel()
        if self._watch:
            await self._watch.cancel()


def make_prefill_handler(engine, plane=None):
    """Prefill-worker endpoint handler: prompt in, (KV + first token) out.

    With ``plane`` (a KvPlaneServer): the parcel is STAGED on the direct
    KV data plane and the response carries only a small transfer ticket —
    the decode worker pulls the bulk bytes worker-to-worker
    (llm/kv_plane.py, the NIXL role). Without it: the v0 inline-chunk
    contract (one meta frame {shape, dtype, n_chunks}, n_chunks kv_chunk
    frames, then the first token — the role of the reference's
    kv_transfer_params response, handlers.py:195-199)."""

    supports_streaming = "on_ticket" in getattr(
        inspect.signature(engine.prefill_extract_staged), "parameters", {}) \
        if hasattr(engine, "prefill_extract_staged") else False

    async def handle(request, context: Context) -> AsyncIterator[dict]:
        if isinstance(request, dict) and request.get("clear_kv_blocks"):
            yield {"cleared": await engine.clear_kv_blocks()}
            return
        req = (request if isinstance(request, PreprocessedRequest)
               else PreprocessedRequest.from_wire(request))
        phase = getattr(engine, "phase", None)  # tracing.PhaseMetrics
        if plane is not None:
            # Chunk-streamed extract (engine._prefill_extract_streamed):
            # the engine stages the ticket BEFORE prefilling and delivers
            # it via on_ticket — yield it to the decode worker right
            # away so its plane pull overlaps the remaining chunks; the
            # first token follows when the job completes. Engines
            # without the on_ticket parameter (scripted test engines,
            # older queue workers) keep the stage-after-prefill order.
            loop = asyncio.get_running_loop()
            ticket_fut: asyncio.Future = loop.create_future()
            staged: list[dict] = []  # the delivered ticket, loop-side

            def _deliver(t: dict) -> None:
                staged.append(t)
                if not ticket_fut.done():
                    ticket_fut.set_result(True)

            def on_ticket(t: dict) -> None:
                loop.call_soon_threadsafe(_deliver, t)

            with span("kv.transfer.send", ctx=context, path="plane") as sp:
                t0 = time.monotonic()
                if supports_streaming:
                    job = asyncio.ensure_future(engine.run_job(
                        lambda: engine.prefill_extract_staged(
                            req, plane, on_ticket=on_ticket)))
                else:
                    job = asyncio.ensure_future(engine.run_job(
                        lambda: engine.prefill_extract_staged(req, plane)))
                await asyncio.wait({job, ticket_fut},
                                   return_when=asyncio.FIRST_COMPLETED)
                streamed = bool(staged) and not job.done()
                if streamed:
                    # Ticket ahead of the first token: ship it now.
                    yield LLMEngineOutput(disagg_params={
                        "ticket": staged[0]}).to_wire()
                first_token, ticket, prompt_len = await job
                sp.set(nbytes=int(ticket.get("nbytes", 0)),
                       prompt_tokens=prompt_len, streamed=streamed)
                if phase is not None:
                    phase.kv_transfer.observe(time.monotonic() - t0,
                                              direction="send")
                    phase.kv_transfer_bytes.observe(
                        ticket.get("nbytes", 0), direction="send")
            log.info("prefill parcel staged%s: %d tokens, ticket %d",
                     " (chunk-streamed)" if streamed else "",
                     prompt_len, ticket["id"])
            if not streamed:
                yield LLMEngineOutput(
                    disagg_params={"ticket": ticket}).to_wire()
            yield LLMEngineOutput(token_ids=[first_token]).to_wire()
            return
        with span("kv.transfer.send", ctx=context, path="inline") as sp:
            t0 = time.monotonic()
            first_token, kv, prompt_len = await engine.run_job(
                lambda: engine.prefill_extract(req))
            meta, chunks = kv_to_chunks(kv)
            meta["prompt_len"] = prompt_len
            sp.set(nbytes=int(kv.nbytes), chunks=len(chunks),
                   prompt_tokens=prompt_len)
            yield LLMEngineOutput(disagg_params=meta).to_wire()
            for chunk in chunks:
                if context.is_killed or context.is_stopped:
                    return
                yield LLMEngineOutput(
                    disagg_params={"kv_chunk": chunk}).to_wire()
            if phase is not None:
                phase.kv_transfer.observe(time.monotonic() - t0, direction="send")
                phase.kv_transfer_bytes.observe(kv.nbytes,
                                                direction="send")
        yield LLMEngineOutput(token_ids=[first_token]).to_wire()

    return handle


class DisaggDecodeHandler:
    """Decode-worker handler with conditional remote prefill (reference
    DecodeWorkerHandler, handlers.py:113-162)."""

    def __init__(self, engine, prefill_client, config: DisaggRouterConfig,
                 plane_client=None, queue_dispatcher=None):
        self.engine = engine
        self.prefill_client = prefill_client
        self.config = config
        # Pull side of the direct KV data plane (created on demand: a
        # plane-less prefill worker just sends inline chunks instead).
        if plane_client is None:
            from dynamo_tpu.llm.kv_plane import KvPlaneClient
            plane_client = KvPlaneClient()
        self.plane_client = plane_client
        # Queue-based dispatch (llm/prefill_queue.py): when set, remote
        # prefills go through the shared coordinator queue with depth
        # backpressure instead of direct round-robin.
        self.queue_dispatcher = queue_dispatcher
        # Telemetry for tests + metrics.
        self.remote_prefills = 0
        self.local_prefills = 0
        self.remote_failures = 0

    def handler(self):
        async def handle(request, context):
            if isinstance(request, dict) and request.get("clear_kv_blocks"):
                # Clear our own pool AND fan out to the prefill workers
                # this decode worker fronts (the frontend only discovers
                # decode endpoints).
                freed = await self.engine.clear_kv_blocks()
                for iid in self.prefill_client.instance_ids():
                    try:
                        stream = await self.prefill_client.direct(
                            {"clear_kv_blocks": True}, iid)
                        async for item in stream:
                            freed += item.get("cleared", 0)
                    except Exception:  # noqa: BLE001 — best-effort admin
                        log.warning("clear_kv_blocks failed on prefill %x",
                                    iid, exc_info=True)
                yield {"cleared": freed}
                return
            if isinstance(request, dict) and request.get("embed"):
                # Embeddings don't involve the disagg path: serve locally.
                vectors = await self.engine.embed(
                    request["token_lists"], request.get("pooling", "last"))
                yield {"embeddings": vectors}
                return
            async for out in self.generate(request, context):
                yield out
        return handle

    async def generate(self, request, context: Context) -> AsyncIterator[dict]:
        req = (request if isinstance(request, PreprocessedRequest)
               else PreprocessedRequest.from_wire(request))
        # LoRA adapter requests always prefill locally: the prefill
        # worker holds base weights only, and base-computed KV under an
        # adapter-salted hash chain would be silently wrong KV.
        if self.config.prefill_remote(len(req.token_ids)) \
                and not getattr(req, "adapter", None):
            injected = await self._remote_prefill(req, context)
            if injected is not None:
                self.remote_prefills += 1
                first_token, kv = injected
                log.info("remote prefill injected: %d tokens",
                         len(req.token_ids))
                async for out in self.engine.generate_injected(
                        req, context, first_token, kv):
                    yield out
                return
        self.local_prefills += 1
        async for out in self.engine.generate(req, context):
            yield out

    async def _remote_prefill(self, req: PreprocessedRequest,
                              context: Context):
        """Forward the prompt to a prefill worker (direct round-robin, or
        the shared queue when a dispatcher is configured); returns
        (first_token, kv parcel) or None to fall back to local prefill
        (any remote failure degrades to aggregated serving, never fails
        the request)."""
        try:
            if self.queue_dispatcher is not None:
                return await self.queue_dispatcher.remote_prefill(
                    req, context=context)
            stream = await self.prefill_client.round_robin(
                req.to_wire(), context=context)
            return await collect_prefill_response(
                stream, plane_client=self.plane_client,
                metrics=getattr(self.engine, "phase", None),
                page_size=self.engine.config.page_size)
        except (NoInstancesError, StreamIncompleteError, EngineError,
                ConnectionError, OSError, RuntimeError) as exc:
            self.remote_failures += 1
            log.warning("remote prefill failed (%s: %s); prefilling locally",
                        type(exc).__name__, exc)
            return None
