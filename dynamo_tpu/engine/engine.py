"""TPUEngine: continuous batching over the ModelRunner.

The engine thread owns all device work (JAX calls block): it admits waiting
requests (batched prefill; chunked for long prompts; cached prefix pages are
skipped), then decodes in M-step WINDOWS: one device program runs M decode
steps with tokens chained on-device, so the per-token path has no
host<->device round-trip. While window w executes, the host processes window
w-1's tokens (async readback), emits them to streams, registers completed
blocks, and prepares page tables — a software pipeline replacing the
reference's per-step GPU loop (SURVEY.md call stack 3.1 "GPU hot loop");
emits the same KV events and ForwardPassMetrics the router consumes.

KV-pressure policy: when the pool is exhausted mid-decode the engine
preempts the youngest slot — its pages are released (prefix-cache entries
kept) and the request is requeued to re-prefill from its accumulated tokens
(reference vLLM preempt-and-recompute semantics) — instead of failing it.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import dataclasses
import os
import queue
import threading
import time
from typing import AsyncIterator

import numpy as np

from dynamo_tpu.engine.config import (RECURRENT_NAMES, EngineConfig,
                                      block_refusals, device_peaks)
from dynamo_tpu.engine.kv_cache import PageAllocator
from dynamo_tpu.engine.runner import (
    ModelRunner, PrefillSeq, PK_OVERRIDE, PK_TOKEN, PK_POS, PK_SEQLEN,
    PK_TOPK, PK_TEMP, PK_TOPP, PK_CAP, PK_LOGPROB, PK_FREQPEN, PK_PRESPEN,
    PK_SEED, PK_SEEDED, PK_ADAPTER, PK_PREFIX, TOP_LOGPROBS)
from dynamo_tpu.engine.sampler import MAX_TOPK
from dynamo_tpu.llm.kv_router.protocols import (ForwardPassMetrics, KvStats,
                                                SpecDecodeStats, WorkerStats)
from dynamo_tpu.llm.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.llm.tokens import TokenBlockSequence
from dynamo_tpu.engine import perf as perf_plane
from dynamo_tpu.runtime import chaos, flight, journal, tracing
from dynamo_tpu.runtime.journal import EventKind
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import AsyncEngine
from dynamo_tpu.runtime.logging import current_trace, get_logger
from dynamo_tpu.runtime.tracing import (_LATENCY_BUCKETS, get_recorder,
                                        phase_metrics, startup_stage)

log = get_logger("tpu_engine")


@dataclasses.dataclass
class _Request:
    req: PreprocessedRequest
    ctx: Context
    out_q: asyncio.Queue
    loop: asyncio.AbstractEventLoop
    tokens_all: list[int] = dataclasses.field(default_factory=list)
    blocks: TokenBlockSequence = None  # type: ignore[assignment]
    pages: list[int] = dataclasses.field(default_factory=list)
    generated: int = 0
    slot: int = -1
    epoch: int = 0
    # None = first token still on device (async fetch pending).
    last_token: int | None = -1
    reuse_tokens: int = 0  # cached-prefix tokens pinned by the last plan
    # Disaggregation: (first_token, kv [2,L,Nkv,n,page,D]) from a remote
    # prefill — admission inserts the pages instead of prefilling locally.
    injected: tuple | None = None
    enqueue_t: float = dataclasses.field(default_factory=time.monotonic)
    # Upper bound on total sequence length (original prompt + max_tokens):
    # dispatch never allocates pages past it, so pipelined lookahead can't
    # demand pages a finishing request will never write.
    len_cap: int = 2**30
    # Multimodal requests skip the prefix cache entirely: the placeholder
    # ids under media spans would alias unrelated media in the
    # content-hash space. mm_buf carries the parsed full-prompt
    # (embeddings, mask) for the chunked path.
    no_cache: bool = False
    mm_buf: tuple | None = None
    # SLA-admission ledger entries: cold tokens this request contributes
    # while queued (full prompt; reuse unknown until planned) and while
    # admitted-but-first-token-unresolved (prompt minus prefix reuse).
    queued_cold: int = 0
    cold_tokens: int = 0
    # Queue-wait observed for the current stint (reset on requeue so a
    # preempted request's second wait records too).
    wait_noted: bool = False
    # Stall-free chunked prefill: while True the request owns a slot and
    # pages but is still being prefilled by SCHEDULED chunk dispatches
    # (decode windows never touch the slot). prefill_pos is the next
    # prompt position to dispatch; prefill_t0 anchors the end-to-end
    # prefill phase (admission -> first-token readback).
    prefilling: bool = False
    prefill_pos: int = 0
    prefill_t0: float = 0.0
    # The request's ONE engine.decode span: first token emitted -> finish
    # (attrs: tokens, decode windows that emitted for it, preemptions).
    decode_t0: float = 0.0
    decode_windows: int = 0
    preemptions: int = 0
    # Batched LoRA (engine/lora.py): the resident device slot this
    # request's adapter occupies (0 = base model) and the store
    # reference held while the request is live (released at slot
    # finish; a requeued request re-acquires at re-admission).
    adapter_slot: int = 0
    adapter_ref: str | None = None

    def push(self, item) -> None:
        self.loop.call_soon_threadsafe(self.out_q.put_nowait, item)


@dataclasses.dataclass
class _Window:
    toks: object  # [M,B] device array (or None when no active rows)
    slots: list   # per slot: (request, epoch, start_pos, cap) or None
    frozen: dict  # slot -> (request, epoch, "requeue" | "oom")
    size: int
    serial: int = 0  # dispatch order (pipelined deferred-release fencing)
    t0: float = 0.0  # dispatch time (latency through the pipeline)
    page_bucket: int = 0   # page-table width of the program dispatched
    # Slots a request held WITHOUT a row in this window, at the instant
    # ``slots`` was taken: in chunked prefill, stalled for pages, frozen
    # for a preemption, or owed only its first token's readback. With the
    # rows and the empty slots it adds up to max_num_seqs.
    prefilling: int = 0
    t_ready: float = 0.0   # readback complete (set when processed)
    # t_ready minus the previous window's, when this window was already
    # queued behind it (the device ran them back to back): one window of
    # the device. 0 when the pipe was not full.
    period_s: float = 0.0
    # What the window counted, by flight-ring column (runtime/flight.py
    # COUNTS has what each is): the vectors its program read back with the
    # tokens (a routed block's load, a latent block's keys, the live rows
    # of a block with recurrent layers) and, of a drafting window, what
    # its verify steps did, summed over its rows when it is processed.
    counts: dict = dataclasses.field(default_factory=dict)
    # Speculative windows: toks = (outs [m,B,S], emits [m,B],
    # ndrafts [m,B]), or under "mtp" the plain window's five with an axis
    # of spec_k + 1 positions behind the rows' and "emit" / "drafted"
    # [m,B] in the fifth; slots snaps carry the ASSUMED advance so
    # processing can correct the host's upper-bound positions.
    spec: bool = False


class TPUEngine(AsyncEngine):
    def __init__(self, config: EngineConfig, params=None,
                 devices=None, kv_publisher=None, metrics_publisher=None,
                 metrics_registry=None):
        self.config = config
        # Tracing + phase histograms (runtime/tracing.py). The recorder
        # is the process-global ring buffer; the histograms need a
        # MetricsRegistry node and stay None without one (call sites
        # without a runtime lose metrics, never correctness).
        self._recorder = get_recorder()
        self.phase = (phase_metrics(metrics_registry)
                      if metrics_registry is not None else None)
        self.runner = ModelRunner(config, params=params, devices=devices)
        # The serving device's published peaks (None on the CPU backend,
        # an error for an unknown accelerator) feed every bandwidth model.
        peaks = device_peaks(self.runner.device)
        self.decode_window = config.resolve_decode_window(peaks)
        self.prefill_chunk_tokens = config.resolve_prefill_chunk_tokens(peaks)
        self.allocator = PageAllocator(self.runner.num_pages, config.page_size)
        # KV tiering (G2 host DRAM + optional G3 disk): HBM evictions are
        # offloaded via async extracts; prefix hits on spilled blocks are
        # onboarded by upload instead of recomputing the prefill.
        self.host_cache = None
        if config.host_cache_pages > 0 or config.kv_disk_cache_dir:
            from dynamo_tpu.engine.kv_host_cache import (DiskKVCache,
                                                         HostKVCache)
            disk = (DiskKVCache(config.kv_disk_cache_dir,
                                config.disk_cache_pages)
                    if config.kv_disk_cache_dir else None)
            # A disk tier with no G2 capacity still needs a small DRAM
            # front (demotions flow through it).
            capacity = config.host_cache_pages or 16
            self.host_cache = HostKVCache(capacity, disk)
            self.allocator.evict_hook = self._on_evict
        # KVBM (engine/kvbm.py): the placement/eviction POLICY across
        # HBM -> host -> disk -> peer as one auditable object — watermark
        # demotion, pinning, promote-on-hit accounting, the G4 peer walk.
        # The engine keeps the device work (extracts/uploads); the
        # manager decides what moves where and journals it.
        from dynamo_tpu.engine.kvbm import KvBlockManager
        self.kvbm = KvBlockManager(self.allocator, self.host_cache,
                                   config.kvbm_policy())
        self._evict_buffer: list[tuple[int, int]] = []
        self._pending_spills: list[dict] = []
        self.onboard_blocks = 0
        self.g4_blocks = 0
        self.streamed_extracts = 0  # chunk-streamed disagg tickets staged
        self.kv_publisher = kv_publisher
        self.metrics_publisher = metrics_publisher
        # Set by the worker main when the KV data plane runs: the plane
        # server (outbound stats) and the periodic inventory-digest
        # publisher (docs/OBSERVABILITY.md "KV & capacity").
        self.plane = None
        self.inventory_publisher = None
        # dynamo_tpu_kv_* exporter (engine/kv_metrics.py): allocator /
        # tier / plane telemetry onto /metrics, throttled internally.
        self.kv_metrics = None
        if metrics_registry is not None:
            from dynamo_tpu.engine.kv_metrics import KvMetricsUpdater
            self.kv_metrics = KvMetricsUpdater(metrics_registry)
        # Multi-tenant batched LoRA (engine/lora.py; config.max_adapters
        # > 0): the store owns adapter registration, device-slot LRU
        # placement and hot-loads — the engine resolves a request's
        # adapter name at admission (engine thread: the upload is device
        # work) and threads the slot id through every dispatch.
        self.adapters = None
        if config.max_adapters > 0:
            from dynamo_tpu.engine.lora import AdapterStore
            self.adapters = AdapterStore(self.runner, config.max_adapters,
                                         config.lora_max_rank)
        self.adapter_metrics = None
        if metrics_registry is not None and self.adapters is not None:
            from dynamo_tpu.engine.kv_metrics import AdapterMetricsUpdater
            self.adapter_metrics = AdapterMetricsUpdater(metrics_registry)
        b = config.max_num_seqs
        # Slot state (host view; tokens chain on-device between windows).
        self.slot_req: list[_Request | None] = [None] * b
        # Per-slot resident adapter ids for the decode-window control
        # array (0 = base model).
        self.adapter_ids = np.zeros(b, np.int32)
        self.disp_positions = np.zeros(b, np.int64)
        self.disp_seq_lens = np.zeros(b, np.int64)
        self.temperature = np.zeros(b, np.float32)
        self.top_k = np.zeros(b, np.int32)
        self.top_p = np.ones(b, np.float32)
        self.freq_pen = np.zeros(b, np.float32)
        self.pres_pen = np.zeros(b, np.float32)
        self.seeds = np.zeros(b, np.int32)
        self.seeded = np.zeros(b, bool)
        self.overrides: dict[int, int] = {}  # slot -> first token next window
        self.waiting: queue.Queue[_Request] = queue.Queue()
        self.num_waiting = 0
        # Queue-accounting counters are read-modify-written from BOTH the
        # event loop (generate -> _queue_put) and the engine thread
        # (_admit / requeue): unguarded `+=` loses updates, and these
        # counters feed the SLA admission gate and TTFT projection
        # (caught by dtpu-lint engine-thread-shared-state).
        self._queue_stats_lock = threading.Lock()
        # SLA-aware admission (config.ttft_budget_ms): the measured
        # end-to-end prefill rate (EWMA over batched-prefill dispatch ->
        # first-token-readback intervals, so queueing behind decode
        # windows is priced in) and the cold-token ledger the TTFT
        # projection runs on. The disagg prefill-extract job path
        # (run_job) bypasses this — its admission belongs to the queue
        # dispatcher's depth backpressure (llm/prefill_queue.py).
        self.prefill_rate_tok_s: float | None = None
        self._cold_inflight = 0   # admitted; first token not yet resolved
        self._waiting_cold = 0    # queued; not yet admitted
        # _admit calls that ended with requests still queued, by cause
        # (flight.ADMIT_STOPS): no free slot, no KV room for the head, or
        # the SLA gate holding the head back. The HTTP limiter admits ahead
        # of this queue, so 0 everywhere says every empty slot is its.
        self.admit_stops = dict.fromkeys(flight.ADMIT_STOPS, 0)
        self.m_admit_stops = None
        # Deferred queue HEAD: the SLA gate parks the over-budget head
        # here instead of re-queueing at the tail — strict FIFO, so a
        # large prompt can't be starved by a stream of later small ones
        # slipping under the budget.
        self._deferred_head: _Request | None = None
        # Speculative decoding (config.spec_decode="ngram"): outer verify
        # steps per window sized so the worst case (nothing accepted
        # costs m_outer weight reads, everything accepted yields the
        # full M tokens for m_outer reads). Stats feed SpecDecodeStats.
        self.spec_m_outer = (max(1, self.decode_window
                                 // (config.spec_k + 1))
                             if config.spec_decode else 0)
        # The model's own prediction module drafts INSIDE the plain window
        # program (runner._get_mtp_window): every one of decode_window
        # scan steps is a draft and a verify of spec_k + 1 positions.
        self.mtp = config.spec_decode == "mtp"
        if self.mtp:
            self.spec_m_outer = self.decode_window
        # Set to a dict by a check (benchmark/draft_check.py): request id ->
        # [(index of the token drafted, the draft)] of every draft a drafting
        # window verified, kept as its readback is walked.
        self.draft_log: dict | None = None
        self.spec_drafts = 0        # verify steps that had drafts
        self.spec_tokens = 0        # draft tokens proposed
        self.spec_accepted = 0      # draft tokens accepted
        # Per-verify-step emitted-token histogram: index e = tokens the
        # step emitted (1 = no draft accepted .. spec_k+1 = all
        # accepted); index 0 counts dispatched-but-frozen steps.
        self.spec_emit_hist = ([0] * (config.spec_k + 2)
                               if config.spec_decode else [])
        # Engine-local brownout (see _update_brownout): 0..3 pressure
        # level from the TTFT projection; spec_brownout_windows counts
        # decode windows where drafting was suspended by it.
        self.brownout_level = 0
        self.spec_brownout_windows = 0
        # What every decode window processed so far counted
        # (_Window.counts), summed by flight-ring column.
        self.counts_total = dict.fromkeys(flight.COUNT_COLUMNS, 0.0)
        # Control jobs executed on the engine thread between windows
        # (disagg prefill-extract, KV injection helpers, etc.).
        self._jobs: queue.Queue = queue.Queue()
        # Dispatched-but-unprocessed windows, oldest first. Depth > 1
        # overlaps the host<->device round trips of consecutive windows.
        self._inflight: collections.deque[_Window] = collections.deque()
        self._dispatch_serial = 0
        # Batched-prefill first tokens awaiting async device->host fetch:
        # {"handle": device array, "rows": [(row, request, slot, epoch)]}.
        self._pending_first: list[dict] = []
        # Pages freed while windows that may still scatter to them are in
        # flight: (serial of the newest dispatched window at free time,
        # pages). Released once that window has been processed.
        self._pending_release: list[tuple[int, list[int]]] = []
        # Stall-free chunked prefill: requests whose long prompts are
        # scheduled as interleaved chunk work (oldest-first fair share of
        # prefill_chunk_tokens per loop iteration), and the chunk
        # programs dispatched but not yet observed complete (bounded by
        # pipeline_depth like decode windows).
        self._prefilling: list[_Request] = []
        self._chunk_inflight: collections.deque[dict] = collections.deque()
        self.chunk_tokens_total = 0     # prompt tokens dispatched as chunks
        self.chunk_dispatch_count = 0   # chunk programs dispatched
        self.decode_stall_max_s = 0.0   # widest observed dispatch gap
        self._last_decode_dispatch: float | None = None
        self.m_chunk_tokens = self.m_chunks_inflight = None
        self.m_decode_stall = None
        if metrics_registry is not None:
            self.m_chunk_tokens = metrics_registry.counter(
                "prefill_chunk_tokens_total",
                "Prompt tokens dispatched as scheduled prefill chunks")
            self.m_chunks_inflight = metrics_registry.gauge(
                "prefill_chunks_inflight",
                "Prefill chunk programs dispatched but not yet retired")
            self.m_decode_stall = metrics_registry.histogram(
                "decode_stall_seconds",
                "Gap between consecutive decode-window dispatches while "
                "decode slots are active",
                buckets=_LATENCY_BUCKETS)
            for bound in (self.m_chunk_tokens, self.m_chunks_inflight,
                          self.m_decode_stall):
                bound.ensure()
            self.m_admit_stops = metrics_registry.counter(
                "engine_admit_stops_total",
                "Engine admission passes that ended with requests still "
                "queued, by cause (no_slot, no_pages, ttft_budget)",
                ["cause"])
            for cause in flight.ADMIT_STOPS:
                self.m_admit_stops.ensure(cause=cause)
        # Flight recorder (runtime/flight.py): one compact row per
        # processed decode window into the process-global ring; the
        # deltas below turn cumulative counters into per-window values.
        self._flight = flight.get_recorder()
        self._flight_chunk_last = 0
        self._flight_stall_last = 0.0
        self._flight_tokens_last = 0
        self._flight_admit_stop = 0   # ADMIT_STOPS bits since the last row
        # The engine thread's phases (runtime/tracing.py ENGINE_PHASES) and
        # what of them the last flight row already carries.
        self.phase_clock = tracing.PhaseClock()
        self._flight_busy_last = 0.0
        self._flight_wait_last = 0.0
        self._flight_idle_last = 0.0
        self._last_ready_t: float | None = None
        # Perf plane (engine/perf.py): per-window roofline attribution
        # feeds the process-global compile registry; the exporter turns
        # it into dynamo_tpu_perf_* series alongside HBM gauges.
        self._perf = perf_plane.get_registry()
        self._perf_tokens_last = 0
        self.tokens_generated_total = 0  # decode-window tokens emitted
        # 0 without a published peak: no roofline share is attributed.
        self._step_floor_ms = config.weight_read_ms(peaks)
        self.perf_metrics = None
        if metrics_registry is not None:
            self.perf_metrics = perf_plane.PerfMetricsUpdater(
                metrics_registry)
        self._running = False
        self._thread: threading.Thread | None = None
        # Set once warm-up is over; _startup_error holds what it raised.
        self._ready = threading.Event()
        self._startup_error: BaseException | None = None
        self._publish_loop: asyncio.AbstractEventLoop | None = None
        self.step_count = 0
        self.prefix_hit_blocks = 0
        self.prefix_lookup_blocks = 0
        self.preempt_count = 0
        # Recent victims (bounded; observability + tests).
        self.preempted_ids: collections.deque[str] = collections.deque(
            maxlen=64)

    # -- lifecycle ------------------------------------------------------------
    def _raise_if_startup_failed(self) -> None:
        if self._startup_error is not None:
            raise RuntimeError(
                f"engine start-up failed: {self._startup_error!r}"
            ) from self._startup_error

    def start(self) -> None:
        self._raise_if_startup_failed()
        if self._running:
            return
        self._running = True
        try:
            self._publish_loop = asyncio.get_running_loop()
        except RuntimeError:
            self._publish_loop = None
        self._thread = threading.Thread(target=self._engine_loop,
                                        name="tpu-engine", daemon=True)
        self._thread.start()

    def wait_ready(self, timeout: float | None = None) -> None:
        """Block until the engine thread has finished warm-up (compiles
        included) and raise what warm-up raised: a launcher calls this
        after start() so a program that cannot be built fails the
        start-up instead of the first request."""
        if not self._ready.wait(timeout):
            raise TimeoutError(f"engine not ready after {timeout}s")
        self._raise_if_startup_failed()

    def stop(self) -> None:
        self._running = False
        if self._thread:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # Never drop the handle of a thread that still drives the
                # device: interpreter teardown under it is a crash.
                raise RuntimeError("engine thread did not stop within 10 s")
            self._thread = None

    # -- AsyncEngine ----------------------------------------------------------
    def _validate(self, req: PreprocessedRequest) -> None:
        if not req.token_ids:
            raise ValueError("empty token_ids")
        if getattr(req, "mm_embeds", None):
            for refusal in block_refusals(self.config.model, embeddings=True):
                raise refusal
        if self.config.spec_decode:
            # Spec decode serves the full sampling surface on-device
            # (temperature/top-k/top-p/seed as data in the verify
            # program; every emitted token is exactly target-distributed
            # via rejection sampling). Still outside it: OpenAI penalties
            # (the [B,V] count state threads through neither drafting
            # scan) and, under the n-gram drafter, logprobs (its program,
            # spec_window, has no per-step taps). Under "mtp" the window
            # program itself drafts and verifies, the verify has the
            # target's logits at every emitted position, and logprobs are
            # served from them.
            s = req.sampling_options
            unsupported = []
            if s.logprobs is not None and not self.mtp:
                unsupported.append("logprobs")
            if getattr(s, "frequency_penalty", None) or \
                    getattr(s, "presence_penalty", None):
                unsupported.append("frequency/presence penalties")
            if unsupported:
                raise ValueError(
                    f"speculative decoding ({self.config.spec_decode}) "
                    f"does not support: {', '.join(unsupported)}. "
                    f"Disable spec_decode or drop these options "
                    f"(temperature/top_k/top_p/seed are supported"
                    f"{', logprobs too' if self.mtp else ''})")
        if len(req.token_ids) >= self.config.max_model_len:
            raise ValueError(
                f"prompt length {len(req.token_ids)} exceeds max model len "
                f"{self.config.max_model_len}")
        adapter = getattr(req, "adapter", None)
        if adapter:
            from dynamo_tpu.runtime.errors import AdapterNotFoundError
            if self.adapters is None:
                raise AdapterNotFoundError(
                    f"adapter {adapter!r} requested but this engine "
                    f"serves no adapters (--max-adapters 0)")
            if not self.adapters.registered(adapter):
                # Fail fast at generate() — the authoritative (slot)
                # resolution happens at admission on the engine thread.
                raise AdapterNotFoundError(
                    f"adapter {adapter!r} is not registered on this "
                    f"worker (serving: {self.adapters.names() or 'none'})")
        s = req.sampling_options
        if s.logprobs is not None and s.logprobs > TOP_LOGPROBS:
            log.warning("top_logprobs=%d exceeds cap %d; clamping",
                        s.logprobs, TOP_LOGPROBS)
            s.logprobs = TOP_LOGPROBS
        if s.top_k and s.top_k > MAX_TOPK:
            # The sampler prefilters to the top-MAX_TOPK candidates (no
            # full-vocab sort on TPU) — top-k beyond that, and the top-p
            # nucleus, operate within those candidates. Clamp visibly
            # rather than silently truncating inside the kernel.
            log.warning(
                "top_k=%d exceeds sampler cap %d; clamping (top-k/top-p "
                "sample among the top-%d logits)", s.top_k, MAX_TOPK,
                MAX_TOPK)
            s.top_k = MAX_TOPK
        if getattr(s, "seed", None) is not None and \
                not 0 <= s.seed <= 0x7FFFFFFF:
            from dynamo_tpu.engine.runner import mask_seed
            log.warning("seed=%s outside the engine's 31-bit seed space; "
                        "using %d (distinct large seeds can collide)",
                        s.seed, mask_seed(s.seed))
        for field in ("frequency_penalty", "presence_penalty"):
            val = getattr(s, field, None)
            if val is not None and not -2.0 <= val <= 2.0:
                clamped = max(-2.0, min(2.0, val))
                log.warning("%s=%s outside [-2, 2]; clamping to %s",
                            field, val, clamped)
                setattr(s, field, clamped)


    # -- SLA-aware admission ---------------------------------------------------
    def _queue_put(self, r: _Request, cold: int | None = None) -> None:
        """Enqueue for admission, tracking the queued cold tokens the
        TTFT projection counts (every put site must come through here)."""
        r.queued_cold = len(r.tokens_all) if cold is None else cold
        with self._queue_stats_lock:
            self._waiting_cold += r.queued_cold
            self.num_waiting += 1
        self.waiting.put(r)

    def _queue_pop_accounting(self, r: _Request) -> None:
        with self._queue_stats_lock:
            self._waiting_cold -= r.queued_cold
            self.num_waiting -= 1
        r.queued_cold = 0

    def _note_queue_wait(self, r: _Request) -> None:
        """Admission reached: observe how long the request sat in the
        waiting queue (requeued requests keep their original enqueue
        time, so this is total time-to-slot, the operator-facing
        number). ENGINE THREAD."""
        if r.wait_noted:
            return
        r.wait_noted = True
        now = time.monotonic()
        if self.phase is not None:
            self.phase.queue_wait.observe(now - r.enqueue_t)
        rec = self._recorder
        if rec.enabled:
            rec.add("engine.queue_wait", r.ctx.trace_id, r.ctx.span_id,
                    r.enqueue_t, now)

    def _maybe_reject(self, prompt_tokens: int) -> None:
        """Raise OverloadedError (frontend: HTTP 503, router retries
        elsewhere) when the projected TTFT through the current backlog
        exceeds budget x reject_factor. Never rejects an idle engine:
        with no backlog the request's TTFT is its own prefill, which the
        budget can't improve by bouncing it."""
        cfg = self.config
        if not (cfg.ttft_budget_ms and cfg.admission_reject_factor):
            return
        backlog = self._cold_inflight + self._waiting_cold
        rate = self.prefill_rate_tok_s
        if backlog <= 0 or not rate:
            return
        projected = (backlog + prompt_tokens) / rate * 1e3
        limit = cfg.ttft_budget_ms * cfg.admission_reject_factor
        if projected > limit:
            from dynamo_tpu.runtime.errors import OverloadedError
            raise OverloadedError(
                f"projected TTFT {projected:.0f} ms exceeds "
                f"{limit:.0f} ms ({backlog} cold tokens backlogged at "
                f"{rate:.0f} tok/s)")

    def _prefill_rate_sample(self, tokens: int, elapsed_s: float) -> None:
        if tokens <= 0 or elapsed_s <= 1e-6:
            return
        s = tokens / elapsed_s
        self.prefill_rate_tok_s = (
            s if self.prefill_rate_tok_s is None
            else 0.7 * self.prefill_rate_tok_s + 0.3 * s)

    def estimated_ttft_ms(self, extra_tokens: int = 0) -> float | None:
        """Projected TTFT for a hypothetical arrival, from the measured
        prefill rate and the cold-token backlog. None until the first
        prefill has calibrated the rate.

        Chunked-prefill backlog is included: a long prompt's cold tokens
        stay in the ledger from admission until its FINAL chunk's
        first-token readback, and the rate EWMA is sampled over that same
        end-to-end interval — so the interleaved decode windows the
        chunk scheduler inserts are priced into the projection, and the
        frontend's deadline shedding / brownout (runtime/overload.py)
        sees long prompts at their true cost."""
        if not self.prefill_rate_tok_s:
            return None
        return ((self._cold_inflight + self._waiting_cold + extra_tokens)
                / self.prefill_rate_tok_s * 1e3)

    async def generate(self, request, context: Context) -> AsyncIterator[dict]:
        self.start()
        req = (request if isinstance(request, PreprocessedRequest)
               else PreprocessedRequest.from_wire(request))
        self._validate(req)
        # One emitted item per generated token, capped by len_cap; the
        # consumer is this generator's own caller.
        # dtpu: ignore[unbounded-queue] -- bounded by max_tokens via len_cap
        r = _Request(req=req, ctx=context, out_q=asyncio.Queue(),
                     loop=asyncio.get_running_loop(),
                     tokens_all=list(req.token_ids),
                     len_cap=len(req.token_ids)
                     + (req.stop_conditions.max_tokens or 2**30))
        self._maybe_reject(len(req.token_ids))
        # Request loop logs (admission warnings, preemptions surfaced to
        # the caller) carry the request's trace context.
        trace_tok = current_trace.set(
            {"trace_id": context.trace_id, "span_id": context.span_id})
        self._queue_put(r)
        # Warm-up may have failed between start() and the put, after the
        # engine thread drained the queue: fail here, not by hanging.
        self._raise_if_startup_failed()
        try:
            while True:
                item = await r.out_q.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.get("finish_reason"):
                    return
        finally:
            try:
                current_trace.reset(trace_tok)
            except ValueError:  # generator finalized from another context
                pass

    async def generate_injected(self, request, context: Context,
                                first_token: int, kv) -> AsyncIterator[dict]:
        """Serve a request whose prompt KV was prefilled REMOTELY: admission
        inserts the transferred pages and decoding starts at first_token
        (disaggregated decode side; reference handlers.py:113-162)."""
        self.start()
        req = (request if isinstance(request, PreprocessedRequest)
               else PreprocessedRequest.from_wire(request))
        self._validate(req)
        # dtpu: ignore[unbounded-queue] -- bounded by max_tokens via len_cap
        r = _Request(req=req, ctx=context, out_q=asyncio.Queue(),
                     loop=asyncio.get_running_loop(),
                     tokens_all=list(req.token_ids),
                     injected=(first_token, kv),
                     len_cap=len(req.token_ids)
                     + (req.stop_conditions.max_tokens or 2**30),
                     # The injected path never runs _plan_prefill, so the
                     # multimodal no-cache flag must be set here: the
                     # placeholder-id hash chain must not enter the
                     # prefix cache pointing at media-conditioned KV.
                     no_cache=bool(getattr(req, "mm_embeds", None)))
        # Injected requests carry their KV with them — no cold prefill,
        # so the SLA gate and the cold ledger both skip them.
        trace_tok = current_trace.set(
            {"trace_id": context.trace_id, "span_id": context.span_id})
        self._queue_put(r, cold=0)
        self._raise_if_startup_failed()
        try:
            while True:
                item = await r.out_q.get()
                if item is None:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.get("finish_reason"):
                    return
        finally:
            try:
                current_trace.reset(trace_tok)
            except ValueError:
                pass

    # -- engine-thread jobs (disaggregation control path) ---------------------
    async def run_job(self, fn):
        """Run ``fn`` on the engine thread (which owns all device work)
        between windows; await its result."""
        self.start()
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._jobs.put((fn, fut))
        return await asyncio.wrap_future(fut)

    def _run_jobs(self) -> None:
        while True:
            try:
                fn, fut = self._jobs.get_nowait()
            except queue.Empty:
                return
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except Exception as exc:  # noqa: BLE001 — deliver to caller
                fut.set_exception(exc)

    def prefill_extract(self, req: PreprocessedRequest):
        """ENGINE-THREAD ONLY (call via run_job). Prefill a prompt, register
        its blocks in the prefix cache, and extract the prompt's KV pages to
        host. Returns (first_token, kv [2,L,Nkv,n,page,D], prompt_len) —
        the disaggregated prefill side (reference PrefillWorkerHandler,
        handlers.py:167-199)."""
        first_token, handle, prompt_len = self._prefill_for_extract(req)
        return first_token, self.runner.finalize_extract(handle), prompt_len

    def _prefill_for_extract(self, req: PreprocessedRequest,
                             grouped: bool = False):
        """Prefill + dispatch the page gather; returns the UNRESOLVED
        extract handle so the device->host copy can overlap whatever the
        caller does next (stage-for-pull, decode windows). With
        ``grouped``, dispatches up to 4 page-group gathers instead of one
        (their D2H copies all start now; the plane then streams group i
        while group i+1's copy completes) and returns a list of
        handles."""
        self._reject_adapter_extract(req)
        self._validate(req)
        r = _Request(req=req, ctx=Context(), out_q=None, loop=None,  # type: ignore[arg-type]
                     tokens_all=list(req.token_ids))
        plan = self._plan_prefill(r)
        if plan is None:
            raise RuntimeError("prefill worker KV pool exhausted")
        try:
            if plan == "chunked":
                first_token = self._prefill_chunked_token(r)
            else:
                first_token = int(self.runner.prefill_batch([plan])[0])
            if not r.no_cache:
                for idx, h in enumerate(r.blocks.block_hashes):
                    self.allocator.register(r.pages[idx], h)
            if grouped:
                n = len(r.pages)
                per = -(-n // min(4, max(1, n)))
                handle = [self.runner.extract_pages_async(r.pages[i:i + per])
                          for i in range(0, n, per)]
            else:
                handle = self.runner.extract_pages_async(r.pages)
        finally:
            # The gather is dispatched: device-stream order guarantees it
            # reads the pages before any later program can overwrite them,
            # so the pages release immediately.
            self.allocator.release(r.pages)
            r.pages = []
        return first_token, handle, len(r.tokens_all)

    def prefill_extract_staged(self, req: PreprocessedRequest, plane,
                               on_ticket=None):
        """ENGINE-THREAD ONLY (call via run_job). Disaggregated prefill
        over the direct KV data plane: prefill, stage the extract with
        the plane (host fetches resolve lazily on the plane thread,
        overlapping this engine's next windows), return (first_token,
        ticket, prompt_len). The ticket rides the small response stream;
        the KV bytes take the plane's direct path (llm/kv_plane.py) —
        the jax device path when the parcel shape allows it, else the
        socket path with PIPELINED page groups (extract was ~97% of the
        round-4 transfer tax; reference offload.rs overlap role).

        ``on_ticket`` (threadsafe callable) enables CHUNK-STREAMED
        extract: the ticket is staged and delivered BEFORE prefill
        completes, with one page group per prefill chunk gated on that
        chunk's extract — the decode worker pulls KV while later chunks
        are still computing, hiding the per-prompt transfer tax
        (15-20 ms projected before the chip; not measured on this chip,
        ROADMAP D6) behind prefill compute."""
        spec = self.runner.spec
        page = self.config.page_size
        n = -(-len(req.token_ids) // page)
        quant = self.runner.quant_kv == "int8"
        # The jax device-path needs the staged array to be EXACTLY the
        # advertised shape; the gather output is bucket-padded and
        # kv-head-replicated, so only offer it when neither applies —
        # and quantized parcels are host-packed (int8+scales -> uint8),
        # so they always take the socket path.
        dev_ok = (getattr(plane, "_use_jax", False)
                  and self.runner.kv_rep == 1
                  and self.runner._page_bucket(n) == n
                  and not quant)
        # Socket-path grouping pays the per-fetch D2H latency once per
        # group, so it is gated on the measured floor (about 0.45 ms on a
        # local v5e, PR 21 chip run: the gate is open there).
        grouped = (not dev_ok
                   and self.runner.d2h_fetch_floor_ms() < 10.0 and n > 1)
        if quant:
            # Packed int8+scales parcel (engine/kv_quant.py): the wire
            # carries ~half the bf16 bytes, and the disagg transfer tax
            # halves with it.
            from dynamo_tpu.engine.kv_quant import KV_SCALE_BYTES
            shape = [2, spec.num_layers, self.runner.canonical_nkv, n,
                     self.config.page_size, spec.head_dim + KV_SCALE_BYTES]
            meta = {"shape": shape, "dtype": "uint8"}
        else:
            shape = [2, spec.num_layers, self.runner.canonical_nkv, n,
                     self.config.page_size, spec.head_dim]
            meta = {"shape": shape, "dtype": "bfloat16"}
        if on_ticket is not None and not dev_ok and \
                self.runner.d2h_fetch_floor_ms() < 10.0:
            # Chunk-streamed path: stage BEFORE prefilling (the jax
            # device path can't stream — it registers one finished
            # device array — so it keeps the stage-after-prefill
            # order). Same per-group D2H floor gate as `grouped`.
            return self._prefill_extract_streamed(req, plane, meta,
                                                  on_ticket)
        first_token, handle, prompt_len = self._prefill_for_extract(
            req, grouped=grouped)
        if grouped:
            groups = [(h[1], (lambda hh=h:
                              self.runner.finalize_extract(hh)))
                      for h in handle]
            ticket = plane.stage(meta=meta, resolve_groups=groups,
                                 prompt_len=prompt_len)
        else:
            ticket = plane.stage(
                meta=meta,
                resolve=lambda: self.runner.finalize_extract(handle),
                device_array=handle[0] if dev_ok else None,
                prompt_len=prompt_len)
        if on_ticket is not None:
            on_ticket(ticket)
        return first_token, ticket, prompt_len

    @staticmethod
    def _reject_adapter_extract(req: PreprocessedRequest) -> None:
        """Disaggregated prefill serves the BASE model only: the decode
        side keeps adapter requests local (llm/disagg.py gate), so an
        adapter reaching a prefill worker is a routing bug — fail typed
        rather than compute base KV under an adapter-salted hash chain."""
        if getattr(req, "adapter", None):
            from dynamo_tpu.runtime.errors import InvalidRequestError
            raise InvalidRequestError(
                f"disaggregated prefill does not serve LoRA adapter "
                f"requests (adapter={req.adapter!r}); the decode worker "
                f"prefills these locally")

    # Backstop for streamed-extract group resolvers: the plane thread
    # waits on the chunk's extract event at most this long before
    # failing the pull (an aborted prefill sets the events, so only a
    # wedged engine thread ever reaches it).
    STREAM_RESOLVE_TIMEOUT_S = 120.0

    def _prefill_extract_streamed(self, req: PreprocessedRequest, plane,
                                  meta: dict, on_ticket):
        """ENGINE-THREAD ONLY. Chunk-streamed disagg extract: stage the
        transfer ticket FIRST — one page group per prefill chunk, each
        gated on a threading.Event its extract dispatch sets — deliver
        it through ``on_ticket`` (the handler yields it to the decode
        worker immediately), THEN run the chunk loop. The plane thread
        streams group i to the sink while chunk i+1 is still computing,
        so by the time the first token resolves most of the parcel is
        already across the wire. A whole-prompt (non-chunked) plan
        degenerates to one group staged before its single dispatch —
        same contract, no special casing downstream.

        Failure mid-loop marks every pending group failed (resolvers
        raise, the sink's pull errors, the decode worker falls back to
        local prefill) and re-raises to the handler."""
        self._reject_adapter_extract(req)
        self._validate(req)
        r = _Request(req=req, ctx=Context(), out_q=None, loop=None,  # type: ignore[arg-type]
                     tokens_all=list(req.token_ids))
        plan = self._plan_prefill(r)
        if plan is None:
            raise RuntimeError("prefill worker KV pool exhausted")
        cfg = self.config
        page = cfg.page_size
        prompt = r.tokens_all
        max_chunk = min(cfg.max_prefill_tokens, cfg.prefill_buckets[-1])
        # Page-group boundaries are known at PLAN time: the reused
        # prefix extracts immediately; each chunk's pages extract as its
        # program dispatches (device-stream order: the gather reads the
        # chunk's writes).
        first_page = r.reuse_tokens // page
        bounds: list[tuple[int, int]] = []
        chunks: list[tuple[int, int, bool]] = []  # (start, n_tok, final)
        if first_page:
            bounds.append((0, first_page))
        start = r.reuse_tokens
        while start < len(prompt):
            n_tok = min(max_chunk, len(prompt) - start)
            bounds.append((start // page, -(-(start + n_tok) // page)))
            chunks.append((start, n_tok, start + n_tok >= len(prompt)))
            start += n_tok
        state: dict = {"handles": {}, "error": None}
        events = [threading.Event() for _ in bounds]
        timeout_s = self.STREAM_RESOLVE_TIMEOUT_S

        def _resolver(idx: int):
            def resolve():
                if not events[idx].wait(timeout=timeout_s):
                    raise RuntimeError(
                        f"streamed extract group {idx} never became "
                        "ready (prefill wedged?)")
                if state["error"] is not None:
                    raise RuntimeError(
                        f"chunked prefill failed: {state['error']}")
                return self.runner.finalize_extract(state["handles"][idx])
            return resolve

        groups = [(hi - lo, _resolver(i))
                  for i, (lo, hi) in enumerate(bounds)]
        ticket = plane.stage(meta=meta, resolve_groups=groups,
                             prompt_len=len(prompt))
        self.streamed_extracts += 1
        on_ticket(ticket)
        gi = 0
        try:
            if first_page:
                state["handles"][0] = self.runner.extract_pages_async(
                    r.pages[:first_page])
                events[0].set()
                gi = 1
            if plan != "chunked":
                # Whole-prompt plan: one dispatch, one streamed group.
                first_token = int(self.runner.prefill_batch([plan])[0])
                lo, hi = bounds[gi]
                state["handles"][gi] = self.runner.extract_pages_async(
                    r.pages[lo:hi])
                events[gi].set()
            else:
                first_token = None
                for ci, (c_start, n_tok, final) in enumerate(chunks):
                    seq = self._chunk_seq(r, c_start, n_tok, final)
                    if final:
                        pen = self._penalties_of(r)
                        rows = (self._count_row_of(r)[None]
                                if any(pen) else None)
                        first_token = int(self.runner.prefill_batch(
                            [seq], count_rows=rows)[0])
                    else:
                        self.runner.prefill_chunk_async(seq)
                    lo, hi = bounds[gi]
                    state["handles"][gi] = \
                        self.runner.extract_pages_async(r.pages[lo:hi])
                    events[gi].set()
                    gi += 1
            if not r.no_cache:
                for idx, h in enumerate(r.blocks.block_hashes):
                    self.allocator.register(r.pages[idx], h)
            return first_token, ticket, len(prompt)
        except BaseException as exc:
            # Pending resolvers must fail fast, not wait out the
            # backstop: mark, wake, re-raise to the handler.
            state["error"] = f"{type(exc).__name__}: {exc}"
            for ev in events:
                ev.set()
            raise
        finally:
            # Every extract is dispatched (or the parcel is failed):
            # device-stream order protects the pages, so release now —
            # same fencing argument as _prefill_for_extract.
            self.allocator.release(r.pages)
            r.pages = []

    def register_adapter(self, name: str, path: str | None = None,
                         weights: dict | None = None,
                         pin: bool = False) -> None:
        """Register a LoRA adapter (host-side: parse/pad/stack only —
        the device upload happens lazily at first use on the engine
        thread, which IS the hot-load path). Safe from any thread."""
        if self.adapters is None:
            raise RuntimeError(
                "engine built without adapters (config.max_adapters=0)")
        self.adapters.register(name, path=path, weights=weights)
        if pin:
            self.adapters.pin(name)

    # -- engine-thread adapter resolution -------------------------------------
    def _acquire_adapter(self, r: _Request) -> bool:
        """Resolve the request's adapter name to a resident device slot
        (hot-loading on miss — ENGINE THREAD). Returns False after
        pushing the typed error when resolution fails (unknown name ->
        404 at the frontend; all slots busy -> 503, router retries)."""
        name = getattr(r.req, "adapter", None)
        if not name:
            return True
        if r.adapter_ref is not None:
            return True  # already held (shouldn't happen, but idempotent)
        try:
            if self.adapters is None:
                from dynamo_tpu.runtime.errors import AdapterNotFoundError
                raise AdapterNotFoundError(
                    f"adapter {name!r} requested but this engine serves "
                    f"no adapters")
            r.adapter_slot = self.adapters.acquire(name)
        except Exception as exc:  # noqa: BLE001 — typed errors reach the stream
            r.push(exc)
            return False
        r.adapter_ref = name
        # Accounting attribution: scripts/slo_report.py --by adapter.
        r.ctx.values["adapter"] = name
        return True

    def _release_adapter(self, r: _Request | None) -> None:
        if r is not None and r.adapter_ref is not None \
                and self.adapters is not None:
            self.adapters.release(r.adapter_ref)
            r.adapter_ref = None
            r.adapter_slot = 0

    async def embed(self, token_lists: list[list[int]],
                    pooling: str = "last") -> list[list[float]]:
        """Batch embeddings, computed on the engine thread between windows
        (/v1/embeddings backend)."""
        out = await self.run_job(
            lambda: self.runner.embed(token_lists, pooling))
        return [row.tolist() for row in out]

    async def clear_kv_blocks(self) -> int:
        """Admin: drop the reusable (inactive) prefix cache; host tiers
        flush too. Returns pages freed in HBM."""
        def job():
            n = self.allocator.clear_inactive()
            if self.host_cache is not None:
                self.host_cache.clear()
            return n
        return await self.run_job(job)

    # -- KV observability (docs/OBSERVABILITY.md "KV & capacity") -------------
    def inventory_digest(self):
        """Compact what-KV-lives-here summary for the event plane
        (KvInventoryDigest): block counts per tier, capacity headroom,
        and a k-min sketch over every hash this worker can serve."""
        from dynamo_tpu.llm.kv_router.protocols import (KvInventoryDigest,
                                                        kmin_sketch)
        hashes = list(self.allocator.cached.keys())
        tier_blocks = {"g1": len(hashes)}
        if self.host_cache is not None:
            host_hashes = self.host_cache.block_hashes()
            tier_blocks["g2"] = len(host_hashes)
            hashes.extend(host_hashes)
            disk = self.host_cache.disk
            if disk is not None:
                with disk._lock:
                    disk_hashes = list(disk._index.keys())
                tier_blocks["g3"] = len(disk_hashes)
                hashes.extend(disk_hashes)
        return KvInventoryDigest(
            blocks=len(self.allocator.cached),
            tier_blocks=tier_blocks,
            pages_total=self.allocator.num_pages,
            pages_free=self.allocator.num_free,
            pages_active=self.allocator.num_active,
            sketch=kmin_sketch(hashes))

    def kv_status(self) -> dict:
        """The /debug/kv body for this worker (runtime/health.py):
        allocator occupancy/lifecycle counters, offload-tier stats, KV
        data plane + G4 remote-source telemetry, reuse attribution, and
        the current inventory digest."""
        onboard = self.onboard_blocks
        status = {
            "role": "engine",
            "allocator": self.allocator.stats(),
            "tiers": (self.host_cache.stats()
                      if self.host_cache is not None else {}),
            "reuse": {
                "prefix_hit_blocks": self.prefix_hit_blocks,
                "prefix_lookup_blocks": self.prefix_lookup_blocks,
                "onboard_blocks_host": onboard - self.g4_blocks,
                "onboard_blocks_peer": self.g4_blocks,
            },
            "plane": self.plane.stats() if self.plane is not None else None,
            "remote": (self.remote_source.stats()
                       if self.remote_source is not None else None),
            "kvbm": self.kvbm.status(),
            "adapters": (self.adapters.status()
                         if self.adapters is not None else None),
            "digest": self.inventory_digest().to_wire(),
        }
        return status

    def perf_status(self) -> dict:
        """The /debug/perf body for this worker (runtime/health.py;
        docs/OBSERVABILITY.md "Engine perf plane"): per-program compile
        stats from the process-global observatory, live window/roofline
        series, HBM gauges, and the runner's params/KV/workspace memory
        breakdown."""
        expected = self.config.expected_roofline_frac
        raw = os.environ.get("DTPU_EXPECTED_ROOFLINE_FRAC")
        if raw:
            expected = float(raw)
        compiles = self._perf.snapshot()
        window = self.runner.backends.labels("decode_window")
        total = self.counts_total
        status = {
            "role": "engine",
            "compiles": compiles,
            "window": self._perf.window_snapshot(),
            # Where the start's seconds went: stages, first calls a family.
            "startup": perf_plane.startup_status(),
            "roofline": {
                "weight_read_step_ms": round(self._step_floor_ms, 4)
                or None,
                "frac": round(self._perf.roofline_frac, 4),
                "expected_frac": expected,
            },
            "hbm": self.runner.hbm_stats(),
            "memory": self.runner.memory_breakdown(),
            # Static per runner, the window program's labels (the record
            # of engine/backends.py): who reads the pool in decode and how
            # the window program writes it; who scores a latent pool's
            # index keys or a compressed-key array's stripes in decode
            # (None for a block whose queries choose nothing);
            # who drafts inside the window program's steps: "mtp" (the
            # model's own prediction module, spec_decode mtp) or "none";
            # tokens a KV page holds (config.resolve_page_size): over 16
            # where the page was derived for the Pallas reader.
            **{key: window.get(key) for key in (
                "attention_backend", "kv_commit_backend", "index_backend",
                "draft", "page_size")},
            # The K-and-V kernel's chunk turn, where it is not a KV head
            # at a time (attention.reader_turn).
            **({"kv_reader_turn": window["kv_reader_turn"]}
               if "kv_reader_turn" in window else {}),
            # Engine-thread self time by loop phase, seconds since the
            # loop started (engine_phase_seconds_total on /metrics).
            "phases": {k: round(v, 6)
                       for k, v in self.phase_clock.totals().items()},
        }
        if self.runner.spec.num_experts:
            touched, load, n = (total[c] for c in (
                "moe_touched", "moe_load", "moe_layer_steps"))
            spec = self.runner.spec
            experts = spec.num_experts
            status["moe"] = {
                # Experts this device holds; touched and load count them.
                "experts": experts,
                "experts_per_tok": spec.num_experts_per_tok,
                # (decode step, expert layer) pairs with a live row.
                "layer_steps": int(n),
                "experts_touched_pct": round(100.0 * touched / (n * experts),
                                             3) if n else None,
                "load_max_over_mean": round(load / n, 4) if n else None,
                # The product each program family's expert layers take
                # (static by a program's rows: model.expert_product),
                # and the pairs a layer the grouped prefill calls sorted.
                "expert_product": self._perf.label_values("expert_product"),
                "grouped_pairs": self.runner.moe_grouped_pairs,
            }
            if spec.num_routed_experts is not None:
                local, picks = total["moe_local_picks"], total["moe_picks"]
                status["moe"].update(
                    experts_routed=spec.router_width,
                    first_expert=spec.first_expert,
                    experts_shared=spec.num_shared_experts,
                    local_picks_pct=round(100.0 * local / picks, 3)
                    if picks else None)
        if self.runner.spec.latent:
            spec = self.runner.spec
            selected, context = total["attn_selected"], total["attn_context"]
            status["attn"] = {
                # Keys a query attends at most (the indexer's choice).
                "index_topk": spec.index_topk,
                # What a token holds in the pool, every layer, lane padding
                # included (config.kv_token_bytes).
                "kv_entry_bytes": self.config.kv_token_bytes(),
                # Keys attended over keys in context, live rows, every
                # layer and decode step so far.
                "selected_pct": round(100.0 * selected / context, 3)
                if context else None,
            }
        if self.runner.spec.compressed_keys:
            spec = self.runner.spec
            selected, context = total["attn_selected"], total["attn_context"]
            status["attn"] = {
                # Blocks of keys a query attends at most, a KV group.
                "topk_blocks": spec.sparse_topk,
                "block": spec.sparse_block,
                # K, V and the compressed-key array's share, every
                # attention layer (config.kv_token_bytes).
                "kv_entry_bytes": self.config.kv_token_bytes(),
                # Keys of the kept blocks over keys in context, live rows,
                # every attention layer and decode step so far.
                "selected_pct": round(100.0 * selected / context, 3)
                if context else None,
                # Keys whose stripes the choice read over keys in context:
                # 100 where the kernel walks the live rows' pages, slots x
                # bucket over the live rows' keys under XLA's gather.
                "index_read_pct": round(
                    100.0 * total["attn_index_read"] / context, 3)
                if context else None,
            }
        if self.runner.spec.recurrent:
            spec = self.runner.spec
            status["ssm"] = {
                # Recurrent layers, and what a row (a slot) keeps over them
                # beside its pages (float32 S, the convolution's inputs).
                "layers": spec.ssm_layers,
                # The mixer's kind: mamba2 | lightning | delta_rule.
                "kind": RECURRENT_NAMES[spec.ssm_kind],
                # Whether a group's mixer and its attention layer read ONE
                # normed input side by side (then a row holds a state AND
                # pages in every such layer).
                "parallel": bool(spec.parallel_mixers),
                "state_bytes_per_row": spec.ssm_state_bytes_per_row,
                "state_dtype": window["ssm_state"],
                # Who updates S in a decode step: "kernel" (the live slots,
                # in place: engine/recurrence.py) | "xla" (every slot).
                "backend": window["ssm_backend"],
                # (decode step, live row) pairs so far: the rows the kernel
                # visited, a layer.
                "row_steps": int(total["ssm_row_steps"]),
                # Static: a page's border has no state to continue from.
                "prefix_reuse": window["prefix_reuse"],
            }
        if self.runner.spec.loop_passes > 1:
            spec = self.runner.spec
            steps = total["loop_row_steps"]
            status["loop"] = {
                # Passes a token takes over the same layers, the (pass,
                # layer) pairs it leaves K and V in, and their bytes.
                "passes": spec.loop_passes,
                "pool_layers": spec.pool_layers,
                "kv_token_bytes": self.config.kv_token_bytes(),
                # Counted in the window program: passes the live rows
                # took over their decode steps.
                "passes_per_token": round(total["loop_passes"] / steps, 4)
                if steps else None,
            }
        if self.config.spec_decode:
            # Verify-of-k bandwidth: the spec program runs m_outer verify
            # steps of S = spec_k + 1 positions each, so cost-registry
            # bytes over m_outer * S is HBM bytes per VERIFIED position —
            # the number the fused multi-token verify keeps near the
            # single-token step's (one weight read covers S positions).
            cost = (compiles["programs"].get(
                "decode_window" if self.mtp else "spec_window") or {}).get(
                "cost") or {}
            positions = self.spec_m_outer * (self.config.spec_k + 1)
            vb = cost.get("bytes_accessed")
            status["spec"] = {
                "draft": self.config.spec_decode,
                "k": self.config.spec_k,
                "m_outer": self.spec_m_outer,
                "drafts": self.spec_drafts,
                "draft_tokens": self.spec_tokens,
                "accepted_tokens": self.spec_accepted,
                "acceptance_rate": round(
                    self.spec_accepted / self.spec_tokens, 4)
                if self.spec_tokens else None,
                # emit_hist[e] = verify steps that emitted e tokens
                # (0 = dispatched frozen, spec_k+1 = all drafts landed).
                "emit_hist": list(self.spec_emit_hist),
                "brownout_windows": self.spec_brownout_windows,
                "verify_bytes_per_token": round(vb / positions, 1)
                if vb and positions else None,
                "verify_cost_source": cost.get("source"),
            }
        return status

    def handler(self):
        async def handle(request, context):
            if isinstance(request, dict) and request.get("clear_kv_blocks"):
                freed = await self.clear_kv_blocks()
                yield {"cleared": freed}
                return
            if isinstance(request, dict) and request.get("embed"):
                vectors = await self.embed(request["token_lists"],
                                           request.get("pooling", "last"))
                yield {"embeddings": vectors}
                return
            async for out in self.generate(request, context):
                yield out

        return handle

    # -- engine thread --------------------------------------------------------
    def _warmup_window_programs(self) -> None:
        """Compile the decode-window program (smallest page-table bucket)
        and the smallest prefill bucket before serving — the runner
        compiles lazily per shape key on the engine thread, so without
        this the first request stalls on XLA compiles for both. Larger
        prefill buckets / page-table widths still compile on first use.
        Warmup work is inert: all-zero packed rows are inactive
        (PK_SEQLEN=0) and prefill rows write only the reserved scratch
        page 0."""
        bucket_pages = self.runner.bucket_pages_for(1)
        packed = np.zeros((self.config.max_num_seqs,
                           PK_PREFIX + bucket_pages), np.int32)
        if self.config.spec_decode == "ngram":
            # ONE spec program covers greedy, sampled and seeded verify:
            # temperature/top-k/top-p/seed are data (packed columns),
            # not trace-time specializations, so warming it once also
            # warms every sampling mix. (Penalties are rejected at
            # validation — no penalized variant exists to warm.)
            outs = self.runner.decode_spec_window(
                packed, self.spec_m_outer, self.config.spec_k)
            np.asarray(outs[0])
        else:
            outs = self.runner.decode_window(packed, self.decode_window)
            np.asarray(outs[0])  # force compile + execute
            # The penalized variant too: a first penalized request must not
            # stall every in-flight stream on its compile. One inactive row
            # with penalty bits set selects it; inactive rows do no work.
            # (The drafting window has none: penalties are refused at
            # validation.)
            # TWICE: under tp > 1, GSPMD re-shards counts_dev in the first
            # penalized program's output (replicated P() in, vocab-sharded
            # out), so the SECOND call traces a new input signature — warm
            # both here or the first real penalized request still pays that
            # second compile (found by the perf plane's recompile detector).
            variants = [({PK_SEEDED: 1}, 1)]
            if not self.mtp:
                pen = {PK_FREQPEN: np.float32(1.0).view(np.int32)}
                variants = [(pen, 2), *variants, ({PK_SEEDED: 1, **pen}, 2)]
            for columns, times in variants:
                packed_var = packed.copy()
                for column, value in columns.items():
                    packed_var[0, column] = value
                for _ in range(times):
                    outs = self.runner.decode_window(packed_var,
                                                     self.decode_window)
                    np.asarray(outs[0])
        bucket = self.config.prefill_buckets[0]
        seq = PrefillSeq(tokens=np.zeros(min(4, bucket), np.int32),
                         start_pos=0,
                         chunk_pages=np.zeros(1, np.int32),  # scratch page
                         hist_pages=None, sampling=(0.0, 0, 1.0))
        self.runner.prefill_batch([seq])  # slots=None blocks until done
        with startup_stage("startup.prefill_ladder"):
            self._warmup_prefill_ladder()

    def _warmup_prefill_ladder(self) -> None:
        """Pre-compile EVERY prefill bucket, with and without history
        (config.warmup_prefill_ladder): larger buckets otherwise compile
        on first use — the first long prompt then pays seconds of XLA
        compile per bucket while every live decode slot waits. Warmup
        rows are inert: zero tokens, all writes to the reserved scratch
        page 0. jit
        COMPILATION blocks the caller, so each call here really pays its
        compile (a first-call record each, engine/perf.py); the inert
        executions drain async."""
        if not self.config.warmup_prefill_ladder:
            return
        page = self.config.page_size
        for bucket in self.config.prefill_buckets:
            for with_h in (False, True):
                seq = PrefillSeq(
                    tokens=np.zeros(bucket, np.int32),
                    start_pos=page if with_h else 0,
                    chunk_pages=np.zeros(1, np.int32),
                    hist_pages=(np.zeros(1, np.int32) if with_h
                                else None),
                    sampling=(0.0, 0, 1.0))
                self.runner.prefill_batch([seq], fetch=False)

    def _engine_loop(self) -> None:
        log.info("engine loop starting (slots=%d pages=%d window=%d)",
                 self.config.max_num_seqs, self.runner.num_pages,
                 self.decode_window)
        if self.config.warmup_windows:
            t0 = time.monotonic()
            try:
                # A stage of the launcher's start-up trace (and whatever a
                # subclass runs in here before ready falls inside it).
                with startup_stage("startup.warmup"):
                    self._warmup_window_programs()
            except Exception as exc:  # noqa: BLE001 — reported, then fatal
                # A program that does not compile or run at warm-up will
                # not do so for a request either: fail the start-up
                # (wait_ready / start raise) and whoever already queued.
                log.exception("engine warm-up failed; not serving")
                self._startup_error = exc
                self._running = False
                self._ready.set()
                while True:
                    try:
                        r = self.waiting.get_nowait()
                    except queue.Empty:
                        return
                    r.push(RuntimeError(f"engine start-up failed: {exc!r}"))
        # Perf plane warmup boundary: compiles past here show up in the
        # pane as post-warmup (larger buckets still compile lazily and
        # legitimately; only SAME-signature recompiles are flagged).
        self._perf.mark_ready()
        self._ready.set()
        start = tracing.last_startup()
        if self.config.warmup_windows and not (start and start.open):
            # No launcher tells this start in its ready line: the engine's
            # own ONE line, in place of a line a bucket.
            log.info("warm-up %.1f s (%s)", time.monotonic() - t0,
                     perf_plane.describe_first_calls())
        depth = max(1, self.config.pipeline_depth)
        # Each phase below is a TraceAnnotation on this thread's line of a
        # profiler trace and self time in phase_clock; what an iteration
        # spends in none of them is engine.other.
        phase = self.phase_clock.phase
        self.phase_clock.restart()
        while self._running:
            if chaos.ACTIVE:
                # Chaos site "engine": engine.stall_ms freezes the loop
                # thread mid-iteration — the observable effect is a real
                # decode-dispatch gap (decode_stall_seconds tail) which
                # the flight-recorder anomaly trigger must catch.
                stall = chaos.value("engine.stall_ms", "engine")
                if stall is not None:
                    time.sleep(stall / 1e3)
            with phase("engine.jobs"):
                self._run_jobs()
            with phase("engine.resolve_first"):
                self._resolve_ready_first()
            with phase("engine.kvbm"):
                self._resolve_spills()
                self._maintain_kvbm()
            with phase("engine.retire_chunks"):
                self._retire_chunks()
            try:
                with phase("engine.admit"):
                    admitted = self._admit()
            except Exception:  # noqa: BLE001
                log.exception("admission failed")
                admitted = False
            # Stall-free chunked prefill: at most prefill_chunk_tokens of
            # chunk work BEFORE the decode window, so a long prompt's
            # interference with live decode slots is bounded by ~one
            # chunk's compute per window instead of the whole prompt.
            with phase("engine.dispatch_chunks"):
                chunk_dispatched = self._dispatch_prefill_chunks()
            have_active = any(r is not None and not r.prefilling
                              for r in self.slot_req)
            dispatched = False
            if have_active and len(self._inflight) < depth:
                now = time.monotonic()
                if self._last_decode_dispatch is not None:
                    gap = now - self._last_decode_dispatch
                    self.decode_stall_max_s = max(self.decode_stall_max_s,
                                                  gap)
                    if self.m_decode_stall is not None:
                        self.m_decode_stall.observe(gap)
                    self._flight_stall_last = max(self._flight_stall_last,
                                                  gap)
                    if (flight.stall_threshold_s
                            and gap >= flight.stall_threshold_s):
                        # Decode-stall tail spike: freeze the flight ring
                        # and capture a diagnostic bundle (throttled).
                        flight.trigger(f"decode_stall_{gap:.2f}s")
                self._last_decode_dispatch = now
                try:
                    with phase("engine.dispatch_window"):
                        window = self._dispatch_window()
                except Exception as exc:  # noqa: BLE001 — fail all, keep serving
                    log.exception("decode window dispatch failed")
                    for i, r in enumerate(self.slot_req):
                        if r is not None and not r.prefilling:
                            r.push(RuntimeError(f"engine step failed: {exc}"))
                            self._finish_slot(i, register=False)
                else:
                    if window.toks is None:
                        # No device work (every live slot frozen): handle
                        # the preemption records immediately.
                        with phase("engine.process_window"):
                            self._do_process(window)
                    else:
                        self._inflight.append(window)
                        dispatched = True
            elif not have_active:
                self._last_decode_dispatch = None
            # Process the oldest window once the pipe is full (or drain it
            # when nothing new can be dispatched).
            if self._inflight and (len(self._inflight) >= depth
                                   or not dispatched):
                window = self._inflight.popleft()
                with phase("engine.process_window"):
                    self._do_process(window)
                self.step_count += 1
                with phase("engine.publish"):
                    self._publish()
                self._note_flight(window)
            self._release_ready_pages()
            if self._inflight or chunk_dispatched:
                continue  # device busy; windows/chunks pace the loop
            if not have_active and self._chunk_inflight:
                # Prefill-only phase at full chunk depth: block on the
                # oldest chunk program instead of spinning.
                with phase("engine.retire_chunks"):
                    self._retire_chunks(block=True)
            elif self._pending_first:
                # Nothing left on the device but first tokens unfetched
                # (e.g. a lone max_tokens=1 request): block on them now.
                with phase("engine.resolve_first"):
                    self._resolve_ready_first(force=True)
            elif not admitted and not have_active and not self._prefilling:
                with phase("engine.kvbm"):
                    self._resolve_spills(force=True)
                with phase("engine.idle"):
                    time.sleep(0.002)  # fully idle

    # -- KV tiering (G2/G3 offload + onboard) ---------------------------------
    @property
    def remote_source(self):
        """G4 remote tier (kv_plane.RemoteBlockSource, set by the worker
        main once the KV plane is up). Lives on the KVBM so the peer
        tier is part of the one placement-policy object; this property
        keeps every existing call site working."""
        return self.kvbm.remote_source

    @remote_source.setter
    def remote_source(self, source) -> None:
        self.kvbm.remote_source = source

    def _maintain_kvbm(self) -> None:
        """Watermark sweep, once per engine-loop iteration: proactive
        LRU demotions queue their extracts through the evict hook; the
        flush dispatches them before any later program can overwrite
        the freed pages."""
        if self.kvbm.maintain():
            self._flush_spills()

    def _to_local_parcel(self, kv):
        """Convert a KV block to this worker's parcel form: packed
        int8+scales (uint8) when the pool is quantized, bf16 otherwise
        (engine/kv_quant.py codec; mixed-dtype fleets interoperate)."""
        from dynamo_tpu.engine.kv_quant import (parcel_to_bf16,
                                                parcel_to_packed)
        if self.runner.quant_kv == "int8":
            return parcel_to_packed(kv)
        return parcel_to_bf16(kv)

    def _on_evict(self, block_hash: int, page: int) -> None:
        self._evict_buffer.append((block_hash, page))

    def _flush_spills(self) -> None:
        """Dispatch one batched extract for pages evicted since the last
        flush. MUST run before any program that writes KV pages (the
        device stream then orders the read before the overwrite); the host
        fetch resolves asynchronously."""
        if not self._evict_buffer:
            return
        batch, self._evict_buffer = self._evict_buffer, []
        hashes = [h for h, _ in batch]
        pages = [p for _, p in batch]
        try:
            handle = self.runner.extract_pages_async(pages)
        except Exception:  # noqa: BLE001 — offload is best-effort
            log.exception("spill extract failed; blocks dropped from tiers")
            return
        self._pending_spills.append({"handle": handle, "hashes": hashes})

    def _resolve_spills(self, force: bool = False) -> None:
        if not self._pending_spills or self.host_cache is None:
            return
        for entry in list(self._pending_spills):
            dev, _ = entry["handle"]
            if isinstance(dev, tuple):  # quantized extract: (data, scale)
                dev = dev[0]
            ready = getattr(dev, "is_ready", lambda: True)()
            if not (ready or force):
                continue
            self._pending_spills.remove(entry)
            try:
                kv = self.runner.finalize_extract(entry["handle"])
            except Exception:  # noqa: BLE001
                log.exception("spill fetch failed; blocks dropped")
                continue
            for i, h in enumerate(entry["hashes"]):
                self.kvbm.offload(h, kv[:, :, :, i])

    def _try_onboard(self, r: _Request, hashes: list[int],
                     cached_pages: list[int]) -> tuple[list[int], int, int]:
        """Extend the G1 prefix hit with consecutive G2/G3 blocks — and
        past those, G4 blocks fetched from peer workers' host tiers —
        uploading them into fresh pages (re-registered for sharing)
        instead of recomputing. Returns (extra_pages, extra_tokens,
        peer_tokens) — peer_tokens is the G4 share of extra_tokens, for
        per-request tier attribution."""
        page = self.config.page_size
        if self.host_cache is None and self.remote_source is None:
            return [], 0, 0
        # Never reuse past the second-to-last block (the last token must
        # always be recomputed for logits), matching the G1 rule.
        allowed = (len(r.tokens_all) - 1) // page - len(cached_pages)
        if allowed <= 0:
            return [], 0, 0
        # KVBM tier walk: host/disk first, then one bounded peer consult
        # (engine/kvbm.py owns the policy; device uploads stay here).
        blocks, n_peer = self.kvbm.onboard_walk(
            hashes, len(cached_pages), allowed, trace_id=r.ctx.trace_id)
        if n_peer:
            n_host = len(blocks) - n_peer
            normalized = []
            for h, kv in blocks[n_host:]:
                # Peers may run the other KV dtype: normalize fetched
                # blocks to THIS worker's parcel form (packed uint8 for
                # int8 pools, bf16 otherwise) so tier entries and the
                # onboard stack below stay uniform.
                kv = self._to_local_parcel(kv)
                normalized.append((h, kv))
                if self.host_cache is not None:
                    # Promote into the local G2 so the next hit is one
                    # NIC hop shorter.
                    self.host_cache.put(h, kv, promotion=True)
            blocks = blocks[:n_host] + normalized
            self.g4_blocks += n_peer
        if not blocks:
            return [], 0, 0
        pages = self.allocator.allocate(len(blocks))
        if pages is None:
            return [], 0, 0
        self._flush_spills()  # the allocation may itself have evicted
        stacked = np.stack([kv for _, kv in blocks], axis=3)
        try:
            self.runner.insert_pages(stacked, pages)
        except Exception:  # noqa: BLE001
            log.exception("onboard upload failed; recomputing instead")
            self.allocator.release(pages)
            return [], 0, 0
        for (h, _), p in zip(blocks, pages):
            self.allocator.register(p, h)
        self.onboard_blocks += len(blocks)
        self.kvbm.note_promoted(len(blocks) - n_peer, n_peer,
                                trace_id=r.ctx.trace_id)
        return pages, len(blocks) * page, n_peer * page

    def _release_ready_pages(self) -> None:
        """Release deferred pages whose potential writers are done. An
        entry (s, pages) may still be scattered to by any window with
        device work dispatched at-or-before serial s; windows process in
        serial order, so the fence is just below the oldest in-flight
        window (everything, if none are in flight — toks=None windows
        never carry device work and never enter the deque)."""
        if not self._pending_release:
            return
        with self.phase_clock.phase("engine.release_pages"):
            fence = (self._inflight[0].serial - 1 if self._inflight
                     else self._dispatch_serial)
            keep = []
            for serial, pages in self._pending_release:
                if serial <= fence:
                    self.allocator.release(pages)
                else:
                    keep.append((serial, pages))
            self._pending_release = keep

    def _resolve_ready_first(self, force: bool = False) -> None:
        for entry in list(self._pending_first):
            handle = entry["handle"]["tokens"]
            ready = getattr(handle, "is_ready", lambda: True)()
            if not (ready or force):
                continue
            self._pending_first.remove(entry)
            self._resolve_first(entry)

    def _force_resolve_first_for(self, slots_needed: set[int]) -> None:
        """Block on the fetches whose first tokens the caller is about to
        need (their windows are being processed — the fetch predates those
        windows' compute, so it is effectively ready)."""
        with self.phase_clock.phase("engine.resolve_first"):
            for entry in list(self._pending_first):
                if any(slot in slots_needed and self.slot_req[slot] is r
                       for _, r, slot, _ in entry["rows"]):
                    self._pending_first.remove(entry)
                    self._resolve_first(entry)

    def _resolve_first(self, entry: dict) -> None:
        cold = entry.get("cold", 0)
        if cold:
            # The batch's cold tokens leave the SLA ledger, and its
            # dispatch->readback interval calibrates the projection rate
            # (end-to-end: queueing behind decode windows is priced in).
            self._cold_inflight -= cold
            self._prefill_rate_sample(
                cold, time.monotonic() - entry.get("t0", 0.0))
        h = entry["handle"]
        want_lp = any(r.req.sampling_options.logprobs is not None
                      for _, r, _, _ in entry["rows"])
        try:
            vals = np.asarray(h["tokens"])
            lps = np.asarray(h["lp"]) if want_lp else None
            top_vs = np.asarray(h["top_v"]) if want_lp else None
            top_is = np.asarray(h["top_i"]) if want_lp else None
        except Exception as exc:  # noqa: BLE001 — device fault at fetch
            log.exception("first-token fetch failed")
            for _, r, slot, epoch in entry["rows"]:
                if self.slot_req[slot] is r and r.epoch == epoch:
                    r.push(RuntimeError(f"prefill readback failed: {exc}"))
                    self._finish_slot(slot, register=False)
            return
        t1 = time.monotonic()
        t0 = entry.get("t0")
        if t0:
            # Batched-prefill phase: dispatch -> first-token readback.
            if self.phase is not None:
                self.phase.prefill.observe(t1 - t0)
            rec = self._recorder
            if rec.enabled:
                for _, r, slot, epoch in entry["rows"]:
                    if self.slot_req[slot] is r and r.epoch == epoch:
                        rec.add("engine.prefill", r.ctx.trace_id,
                                r.ctx.span_id, t0, t1,
                                attrs={"prompt_tokens":
                                       len(r.req.token_ids),
                                       "reuse_tokens": r.reuse_tokens,
                                       "chunked": bool(
                                           entry.get("chunked"))})
        for row, r, slot, epoch in entry["rows"]:
            if self.slot_req[slot] is not r or r.epoch != epoch:
                continue  # slot reassigned (failure path already notified)
            tok = int(vals[row])
            r.generated += 1
            finish = self._check_finish(r, tok)
            lp_out = None
            if r.req.sampling_options.logprobs is not None:
                k = r.req.sampling_options.logprobs or 0
                lp_out = ([float(lps[row])],
                          [[{"token_id": int(top_is[row, j]),
                             "logprob": float(top_vs[row, j])}
                            for j in range(k)]])
            self._emit(r, [tok], finish, lp_out)
            r.last_token = tok
            r.tokens_all.append(tok)
            if finish is not None:
                self._finish_slot(slot, register=True)

    def _do_process(self, w: _Window) -> None:
        try:
            self._process_window(w)
        except Exception as exc:  # noqa: BLE001
            # Device faults surface at the readback: host token state has
            # diverged from the on-device chain, so fail every request this
            # window covered rather than continue with silently-wrong
            # streams/prefix hashes.
            log.exception("window processing failed")
            for i, snap in enumerate(w.slots):
                if snap is not None and self.slot_req[i] is snap[0]:
                    snap[0].push(RuntimeError(
                        f"window processing failed: {exc}"))
                    self._finish_slot(i, register=False)

    # -- engine-local brownout -------------------------------------------------
    def _update_brownout(self) -> None:
        """Pressure level 0..3 from the projected-TTFT/budget ratio —
        the engine-local analogue of the frontend limiter's
        pressure_level() (runtime/overload.py). Level >=
        brownout_spec_disable_level suspends speculative drafting: under
        prefill backlog the verify steps' extra positions are pure decode
        overhead whenever drafts stop being accepted."""
        cfg = self.config
        projected = (self.estimated_ttft_ms()
                     if cfg.ttft_budget_ms else None)
        if not projected:
            self.brownout_level = 0
            return
        ratio = projected / cfg.ttft_budget_ms
        self.brownout_level = (0 if ratio < 1.0 else
                               1 if ratio < 1.5 else
                               2 if ratio < 2.5 else 3)

    # -- admission / prefill --------------------------------------------------
    def _note_admit_stop(self, cause: str) -> None:
        """This _admit call ends with requests still queued (ENGINE
        THREAD): once a call, on the counter and in the next flight row."""
        self.admit_stops[cause] += 1
        self._flight_admit_stop |= flight.ADMIT_STOPS[cause]
        if self.m_admit_stops is not None:
            self.m_admit_stops.inc(cause=cause)

    def _admit(self) -> bool:
        self._update_brownout()
        free_slots = [i for i, r in enumerate(self.slot_req) if r is None]
        staged: list[tuple[_Request, int, PrefillSeq]] = []
        while free_slots:
            if self._deferred_head is not None:
                r, self._deferred_head = self._deferred_head, None
            else:
                try:
                    r = self.waiting.get_nowait()
                except queue.Empty:
                    break
            self._queue_pop_accounting(r)
            if r.ctx.is_killed or r.ctx.is_stopped:
                r.push(LLMEngineOutput(
                    token_ids=[], finish_reason=FinishReason.CANCELLED).to_wire())
                continue
            # Adapter resolution first (engine thread: the hot-load is
            # device work): a missing adapter 404s here, a slot-starved
            # store 503s — either way before any pages are touched.
            if not self._acquire_adapter(r):
                continue
            if r.injected is not None:
                self._note_queue_wait(r)
                slot = free_slots.pop(0)
                try:
                    if self._admit_injected(r, slot):
                        continue
                except Exception as exc:  # noqa: BLE001
                    log.exception("KV injection failed")
                    r.push(RuntimeError(f"kv injection failed: {exc}"))
                    free_slots.insert(0, slot)
                    self._release_adapter(r)
                    continue
                # No pages for the transferred KV: fall back to a normal
                # local prefill of the full prompt (correctness preserved).
                free_slots.insert(0, slot)
                r.injected = None
            if (self.config.ttft_budget_ms and self._cold_inflight > 0
                    and self.prefill_rate_tok_s):
                # SLA gate: admitting this prompt must not push the
                # projected prefill backlog past the TTFT budget. With
                # nothing cold in flight the head always admits (an
                # over-budget single prompt must not starve).
                projected = ((self._cold_inflight + len(r.tokens_all))
                             / self.prefill_rate_tok_s * 1e3)
                if projected > self.config.ttft_budget_ms:
                    # Park at the HEAD (strict FIFO): re-queueing at the
                    # tail would let later small prompts starve this one.
                    r.queued_cold = len(r.tokens_all)
                    with self._queue_stats_lock:
                        self._waiting_cold += r.queued_cold
                        self.num_waiting += 1
                    self._deferred_head = r
                    self._note_admit_stop("ttft_budget")
                    break
            self._note_queue_wait(r)
            try:
                plan = self._plan_prefill(r)
            except Exception as exc:  # noqa: BLE001
                log.exception("prefill planning failed")
                r.push(RuntimeError(f"prefill failed: {exc}"))
                self._release_adapter(r)
                continue
            if plan is None:
                # No KV room: put back and stop admitting (drop the
                # adapter ref while queued so it can't pin the slot).
                self._release_adapter(r)
                self._queue_put(r)
                self._note_admit_stop("no_pages")
                break
            slot = free_slots.pop(0)
            if plan == "chunked":
                # Stall-free chunked prefill: the long prompt becomes
                # SCHEDULED chunk work interleaved with decode windows
                # (_dispatch_prefill_chunks) instead of a blocking loop.
                # The slot and all pages are held now; decode windows
                # skip the slot until the final chunk places it.
                r.cold_tokens = len(r.tokens_all) - r.reuse_tokens
                self._cold_inflight += r.cold_tokens
                r.prefilling = True
                r.prefill_pos = r.reuse_tokens
                r.prefill_t0 = time.monotonic()
                r.slot = slot
                self.slot_req[slot] = r
                self.disp_positions[slot] = 0
                self.disp_seq_lens[slot] = 0
                self.overrides.pop(slot, None)
                self._prefilling.append(r)
                continue
            r.cold_tokens = len(r.tokens_all) - r.reuse_tokens
            self._cold_inflight += r.cold_tokens
            staged.append((r, slot, plan))
        else:
            # Every slot is taken (the loop did not break on an empty
            # queue, no KV room or the SLA gate): who still queues waits
            # for a slot.
            if self.num_waiting:
                self._note_admit_stop("no_slot")
        if not staged:
            return False
        # Batch the staged whole-prompt rows (split by history-ness; the
        # history variant costs a full-maxp gather per row).
        for with_h in (False, True):
            group = [(r, s, p) for (r, s, p) in staged
                     if (p.hist_pages is not None) == with_h]
            while group:
                chunk, group = group[:8], group[8:]
                rows = None
                if any(any(self._penalties_of(r)) for r, _, _ in chunk):
                    rows = np.stack([self._count_row_of(r)
                                     for r, _, _ in chunk])
                try:
                    handle = self.runner.prefill_batch(
                        [p for _, _, p in chunk],
                        slots=[s for _, s, _ in chunk],
                        count_rows=rows)
                except Exception as exc:  # noqa: BLE001
                    log.exception("batched prefill failed")
                    for r, _, _ in chunk:
                        self._cold_inflight -= r.cold_tokens
                        r.cold_tokens = 0
                        self.allocator.release(r.pages)
                        r.pages = []
                        self._release_adapter(r)
                        r.push(RuntimeError(f"prefill failed: {exc}"))
                    continue
                rows = []
                for row, (r, slot, _) in enumerate(chunk):
                    self._place_in_slot_pending(r, slot)
                    rows.append((row, r, slot, r.epoch))
                if self.runner.hist_dev is not None:
                    # Spec decode: full prompts (including any reused
                    # prefix; tokens_all also covers requeued requests'
                    # generated tokens) into the on-device draft
                    # history; the chained first token rides from
                    # tokens_dev.
                    self.runner.seed_history([
                        (slot, np.asarray(r.tokens_all, np.int32), 0,
                         True, None) for r, slot, _ in chunk])
                # First tokens are already chained on-device (tokens_dev);
                # their host values arrive asynchronously.
                self._pending_first.append({
                    "handle": handle, "rows": rows,
                    "cold": sum(r.cold_tokens for r, _, _ in chunk),
                    "t0": time.monotonic()})
        return True

    def _admit_injected(self, r: _Request, slot: int) -> bool:
        """Place a remotely-prefilled request: allocate pages, upload the
        transferred KV, start decoding at its first token. Returns False if
        the pool has no room (caller falls back to local prefill)."""
        page = self.config.page_size
        first_token, kv = r.injected
        prompt = r.tokens_all
        from dynamo_tpu.llm.tokens import chain_salt
        r.blocks = TokenBlockSequence(
            page, prompt, salt=chain_salt(getattr(r.req, "adapter", None)))
        total_pages = -(-len(prompt) // page)
        from dynamo_tpu.llm.kv_transfer import foreign_pages
        refusal = foreign_pages(kv.shape, page)
        if refusal:
            raise ValueError(refusal)
        if kv.shape[3] != total_pages:
            raise ValueError(
                f"transferred KV has {kv.shape[3]} pages, prompt needs "
                f"{total_pages}")
        pages = self.allocator.allocate(total_pages)
        if pages is None:
            return False
        self._flush_spills()
        self.runner.insert_pages(kv, pages)
        r.pages = pages
        r.injected = None
        if self.runner.hist_dev is not None:
            # No local prefill ran, so the draft history and position
            # seed from host values (first_token is known here).
            self.runner.seed_history([
                (slot, np.asarray(prompt, np.int32), 0, True,
                 int(first_token))])
        self._place_in_slot(r, slot, first_token)
        return True

    def _plan_prefill(self, r: _Request):
        """Pin cached prefix pages + allocate the rest. Returns a PrefillSeq
        (whole-prompt row), "chunked" (long prompt; caller runs the chunk
        loop), or None (no KV room)."""
        cfg = self.config
        page = cfg.page_size
        prompt = r.tokens_all
        # Adapter-conditioned KV must never alias base (or other-adapter)
        # KV: the same tokens forwarded through adapter A produce
        # different K/V, so the hash chain roots at the adapter's salt —
        # prefix reuse, onboarding tiers and KV events all stay correct
        # per adapter with zero extra bookkeeping (llm/tokens.py).
        from dynamo_tpu.llm.tokens import chain_salt
        salt = chain_salt(getattr(r.req, "adapter", None))
        r.blocks = TokenBlockSequence(page, prompt, salt=salt)
        hashes = r.blocks.block_hashes
        mm = getattr(r.req, "mm_embeds", None)
        if mm:
            r.no_cache = True
            return self._plan_prefill_multimodal(r, mm)
        # Exact-reproduction contract for seeded sampling (temperature
        # > 0, tests/test_seeded_sampling.py): prefix reuse changes
        # WHICH program computes the non-reused tail (with-history
        # buckets vs the whole/chunked-prompt path), and the low-bit
        # logit differences flip near-ties under temperature sampling —
        # the same (prompt, seed) would emit different tokens depending
        # on what happens to be cached. First admission therefore
        # always takes the canonical no-reuse path; preemption
        # recompute (r.generated > 0) keeps reuse, because the pages it
        # finds are the original run's own bit-identical history.
        s = r.req.sampling_options
        canonical = (getattr(s, "seed", None) is not None
                     and (s.temperature or 0.0) > 0.0
                     and r.generated == 0)
        # A block with recurrent layers takes no cached page and registers
        # no hash (no_cache: nothing is published to the router either): a
        # page's border has no recurrent state to continue from until
        # snapshots exist, so every prompt is computed from its first token
        # (/debug/perf ``ssm.prefix_reuse``).
        recurrent = self.runner.spec.recurrent
        if recurrent:
            r.no_cache = True
        canonical = canonical or recurrent
        cached_pages = ([] if canonical
                        else self.allocator.acquire_cached(hashes))
        reuse_tokens = len(cached_pages) * page
        if reuse_tokens >= len(prompt):
            # Always recompute at least the last token so we have logits.
            drop = (reuse_tokens - len(prompt)) // page + 1
            self.allocator.release(cached_pages[len(cached_pages) - drop:])
            cached_pages = cached_pages[:len(cached_pages) - drop]
            reuse_tokens = len(cached_pages) * page
        self.prefix_lookup_blocks += max(1, len(hashes))
        self.prefix_hit_blocks += len(cached_pages)
        hbm_tokens = reuse_tokens
        # Extend the prefix from the host tiers (G2/G3) before recomputing.
        extra_pages, extra_tokens, peer_tokens = (
            ([], 0, 0) if canonical
            else self._try_onboard(r, hashes, cached_pages))
        cached_pages = cached_pages + extra_pages
        reuse_tokens += extra_tokens
        r.reuse_tokens = reuse_tokens
        # Accounting attribution (in-process pipelines: the frontend's
        # ctx IS this ctx, so the ledger record picks these up), incl.
        # which tier served the reuse — the "was the cache cold, and
        # where" signal scripts/slo_report.py rolls up per tenant.
        r.ctx.values["reuse_tokens"] = reuse_tokens
        r.ctx.values["kv_hit_ratio"] = (
            round(reuse_tokens / len(prompt), 4) if prompt else 0.0)
        r.ctx.values["kv_tiers"] = {
            "hbm": hbm_tokens,
            "host": extra_tokens - peer_tokens,
            "peer": peer_tokens}
        # (A drafting module's entry of the last prompt token lies at the
        # slot after it: the page of that slot is allocated with the rest.)
        total_prompt_pages = -(-(len(prompt) + int(self.mtp)) // page)
        need = total_prompt_pages - len(cached_pages)
        new_pages = self.allocator.allocate(need)
        if new_pages is None:
            self.allocator.release(cached_pages)
            return None
        r.pages = cached_pages + new_pages
        # Any evictions the allocations above caused must be extracted
        # before the prefill program overwrites those pages.
        self._flush_spills()
        rest = len(prompt) - reuse_tokens
        # A prompt goes whole only if it fits one iteration's chunk budget:
        # past it the whole-prompt program stalls every decoder for its
        # length and, at a 7B-class model's default pool, does not fit the
        # chip (8192 tokens whole: 7.5 GB of float32 scores; a 5,000-token
        # prompt was answered 500). Chunks attend over history pages.
        max_chunk = min(cfg.max_prefill_tokens, cfg.prefill_buckets[-1],
                        self.prefill_chunk_tokens)
        if rest > max_chunk:
            return "chunked"
        first_page = reuse_tokens // page
        chunk_pages = np.asarray(
            r.pages[first_page:-(-len(prompt) // page)], np.int32)
        hist = (np.asarray(r.pages[:first_page], np.int32)
                if first_page else None)
        return PrefillSeq(
            tokens=np.asarray(prompt[reuse_tokens:], np.int32),
            start_pos=reuse_tokens, chunk_pages=chunk_pages,
            hist_pages=hist, sampling=self._sampling_of(r),
            logprobs=r.req.sampling_options.logprobs is not None,
            penalties=self._penalties_of(r), seed=self._seed_of(r),
            adapter_id=r.adapter_slot,
            next_page=self._page_of_slot(r, len(prompt)))

    def _page_of_slot(self, r: _Request, slot: int) -> int:
        """The page of ``r`` that holds position ``slot``, for a drafting
        module's entry of the token before it (PrefillSeq.next_page); 0,
        the scratch page, where nothing drafts or no such page is held."""
        index = slot // self.config.page_size
        return int(r.pages[index]) if self.mtp and index < len(r.pages) else 0

    def _plan_prefill_multimodal(self, r: _Request, mm: list[dict]):
        """Plan a prompt with encoder-embedding spans (reference
        multimodal processor role): no prefix reuse or onboarding
        (placeholder ids under spans don't content-hash the media).
        Prompts longer than one bucket take the chunked path — each chunk
        carries its slice of the embedding buffer — so a preempted
        multimodal request recomputes like any other. Returns a
        PrefillSeq, "chunked", or None (no KV room)."""
        cfg = self.config
        page = cfg.page_size
        prompt = r.tokens_all
        n = len(prompt)
        emb = np.zeros((n, self.runner.spec.hidden_size), np.float32)
        mask = np.zeros((n,), bool)
        for span in mm:
            start = int(span["start"])
            arr = np.frombuffer(span["b"], dtype=span.get(
                "dtype", "float32")).reshape(span["shape"])
            if start < 0 or start + arr.shape[0] > n:
                raise ValueError(
                    f"multimodal span [{start}, {start + arr.shape[0]}) "
                    f"outside the {n}-token prompt")
            if arr.shape[1] != emb.shape[1]:
                raise ValueError(
                    f"multimodal embedding width {arr.shape[1]} != model "
                    f"hidden size {emb.shape[1]}")
            emb[start:start + arr.shape[0]] = arr
            mask[start:start + arr.shape[0]] = True
        r.mm_buf = (emb, mask)
        self.prefix_lookup_blocks += max(1, len(r.blocks.block_hashes))
        total_pages = -(-n // page)
        pages = self.allocator.allocate(total_pages)
        if pages is None:
            return None
        r.pages = pages
        r.reuse_tokens = 0
        self._flush_spills()
        if n > min(cfg.max_prefill_tokens, cfg.prefill_buckets[-1]):
            return "chunked"
        return PrefillSeq(
            tokens=np.asarray(prompt, np.int32), start_pos=0,
            chunk_pages=np.asarray(pages, np.int32), hist_pages=None,
            sampling=self._sampling_of(r),
            logprobs=r.req.sampling_options.logprobs is not None,
            penalties=self._penalties_of(r), seed=self._seed_of(r),
            embeds=emb, embeds_mask=mask, adapter_id=r.adapter_slot)

    # -- stall-free chunked prefill -------------------------------------------
    def _chunk_seq(self, r: _Request, start: int, n: int,
                   final: bool) -> PrefillSeq:
        """One chunk row of ``r``'s prompt at [start, start+n). Penalty/
        seed/logprob state matters only for the FINAL chunk — earlier
        chunks' sampled tokens are discarded, so they take the cheapest
        (greedy, common-variant) program."""
        page = self.config.page_size
        first_page = start // page
        chunk_pages = np.asarray(
            r.pages[first_page:first_page + (-(-n // page))], np.int32)
        hist = np.asarray(r.pages[:first_page], np.int32)
        emb = emb_mask = None
        if r.mm_buf is not None:
            full_emb, full_mask = r.mm_buf
            sl = full_mask[start:start + n]
            if sl.any():
                emb, emb_mask = full_emb[start:start + n], sl
        tokens = np.asarray(r.tokens_all[start:start + n], np.int32)
        next_page = self._page_of_slot(r, start + n)
        if not final:
            return PrefillSeq(
                tokens=tokens, start_pos=start, chunk_pages=chunk_pages,
                hist_pages=hist if len(hist) else None,
                sampling=(0.0, 0, 1.0), embeds=emb, embeds_mask=emb_mask,
                adapter_id=r.adapter_slot,
                next_token=int(r.tokens_all[start + n]), next_page=next_page,
                slot=r.slot)
        return PrefillSeq(
            tokens=tokens, start_pos=start, chunk_pages=chunk_pages,
            hist_pages=hist if len(hist) else None,
            sampling=self._sampling_of(r),
            logprobs=r.req.sampling_options.logprobs is not None,
            penalties=self._penalties_of(r), seed=self._seed_of(r),
            embeds=emb, embeds_mask=emb_mask, adapter_id=r.adapter_slot,
            next_page=next_page, slot=r.slot)

    def _dispatch_prefill_chunks(self) -> bool:
        """One scheduling pass over the prefilling requests: dispatch at
        most ``prefill_chunk_tokens`` of chunk work, shared fairly
        oldest-first (each request's slice rounds down to page alignment
        — non-final chunks must end on a page boundary). Chunk programs
        in flight are bounded by pipeline_depth, like decode windows.
        Returns True when anything was dispatched. ENGINE THREAD."""
        if not self._prefilling:
            return False
        page = self.config.page_size
        depth = max(1, self.config.pipeline_depth)
        max_chunk = min(self.config.max_prefill_tokens,
                        self.config.prefill_buckets[-1])
        budget = self.prefill_chunk_tokens
        dispatched = False
        queue_snap = sorted(self._prefilling, key=lambda x: x.enqueue_t)
        for idx, r in enumerate(queue_snap):
            if budget < page or len(self._chunk_inflight) >= depth:
                break
            if r.ctx.is_killed or r.ctx.is_stopped:
                self._abort_prefilling(r, finish=FinishReason.CANCELLED)
                continue
            share = max(page, budget // (len(queue_snap) - idx))
            remaining = len(r.tokens_all) - r.prefill_pos
            n = min(share, max_chunk, remaining)
            final = n >= remaining
            if not final:
                n = (n // page) * page
                if n <= 0:
                    continue
            try:
                self._dispatch_one_chunk(r, n, final)
            except Exception as exc:  # noqa: BLE001
                log.exception("chunk prefill dispatch failed")
                self._abort_prefilling(r, error=exc)
                continue
            budget -= n
            dispatched = True
        if self.m_chunks_inflight is not None:
            self.m_chunks_inflight.set(len(self._chunk_inflight))
        return dispatched

    def _dispatch_one_chunk(self, r: _Request, n: int, final: bool) -> None:
        start = r.prefill_pos
        seq = self._chunk_seq(r, start, n, final)
        t0 = time.monotonic()
        if not final:
            # Intermediate chunk: KV state chains ON DEVICE; no host
            # readback of any kind (not even an async copy).
            arr = self.runner.prefill_chunk_async(seq)
            self._chunk_inflight.append(
                {"arr": arr, "r": r, "tokens": n, "t0": t0, "start": start})
            r.prefill_pos = start + n
            self._note_chunk_dispatch(n)
            return
        # Final chunk: a 1-row batched prefill — the sampled first token
        # is scattered into tokens_dev[slot] on device (decode windows
        # chain from it with no override) and its host value resolves
        # asynchronously through the _pending_first machinery.
        pen = self._penalties_of(r)
        rows = self._count_row_of(r)[None] if any(pen) else None
        slot = r.slot
        handle = self.runner.prefill_batch([seq], slots=[slot],
                                           count_rows=rows)
        self._place_in_slot_pending(r, slot)
        if self.runner.hist_dev is not None:
            # Spec decode: seed the on-device draft history with the full
            # accumulated tokens; the chained first token rides from
            # tokens_dev (dispatched after the scatter above).
            self.runner.seed_history([
                (slot, np.asarray(r.tokens_all, np.int32), 0, True, None)])
        self._prefilling.remove(r)
        r.prefilling = False
        r.prefill_pos = start + n
        self._pending_first.append({
            "handle": handle, "rows": [(0, r, slot, r.epoch)],
            "cold": r.cold_tokens, "t0": r.prefill_t0, "chunked": True})
        self._note_chunk_dispatch(n)

    def _note_chunk_dispatch(self, n: int) -> None:
        self.chunk_tokens_total += n
        self.chunk_dispatch_count += 1
        if self.m_chunk_tokens is not None:
            self.m_chunk_tokens.inc(n)

    def _retire_chunks(self, block: bool = False) -> None:
        """Pop completed chunk programs off the in-flight deque (oldest
        first; they complete in dispatch order) and record their spans.
        With ``block``, wait for the oldest — the prefill-only phase's
        pacing when the pipeline is full. ENGINE THREAD."""
        while self._chunk_inflight:
            entry = self._chunk_inflight[0]
            arr = entry["arr"]
            if not getattr(arr, "is_ready", lambda: True)():
                if not block:
                    break
                try:
                    with self.phase_clock.phase("engine.readback_wait"):
                        arr.block_until_ready()
                except Exception:  # noqa: BLE001 — surfaces at final fetch
                    pass
                block = False  # only ever block on the oldest
            self._chunk_inflight.popleft()
            r = entry["r"]
            if self._recorder.enabled:
                self._recorder.add(
                    "prefill.chunk", r.ctx.trace_id, r.ctx.span_id,
                    entry["t0"], time.monotonic(),
                    attrs={"tokens": entry["tokens"],
                           "start": entry["start"]})
        if self.m_chunks_inflight is not None:
            self.m_chunks_inflight.set(len(self._chunk_inflight))

    def _abort_prefilling(self, r: _Request,
                          finish: FinishReason | None = None,
                          error: Exception | None = None) -> None:
        """Terminate a request mid-chunked-prefill (cancellation or a
        dispatch failure): the cold ledger is squared, the slot and pages
        free (deferred past in-flight device work), and the stream is
        closed with the finish reason or error. Chunk pages were never
        registered, so the prefix cache needs no scrub."""
        if r in self._prefilling:
            self._prefilling.remove(r)
        r.prefilling = False
        self._cold_inflight -= r.cold_tokens
        r.cold_tokens = 0
        if error is not None:
            r.push(RuntimeError(f"prefill failed: {error}"))
        else:
            r.push(LLMEngineOutput(
                token_ids=[],
                finish_reason=finish or FinishReason.CANCELLED).to_wire())
        self._finish_slot(r.slot, register=True)

    def _preempt_prefilling(self, r: _Request) -> None:
        """KV-pressure victim while still prefilling: drop the remaining
        chunk plan and requeue the whole request (recompute semantics —
        seeded draws are position-stable, so the retry's tokens are
        identical to an uninterrupted run)."""
        self._prefilling.remove(r)
        r.prefilling = False
        self._cold_inflight -= r.cold_tokens
        r.cold_tokens = 0
        self._requeue_slot(r.slot)

    def _prefill_chunked_token(self, r: _Request) -> int:
        """SYNCHRONOUS chunked prefill for the disagg extract path (runs
        as an engine-thread job between windows). Chunks are dispatched
        back-to-back with NO per-chunk host readback — only the final
        chunk's sampled token is fetched, one blocking round trip total.
        The serving path never comes here; it schedules chunks through
        _dispatch_prefill_chunks instead."""
        cfg = self.config
        prompt = r.tokens_all
        start = r.reuse_tokens  # cached prefix pinned by the plan
        max_chunk = min(cfg.max_prefill_tokens, cfg.prefill_buckets[-1])
        while start < len(prompt):
            n = min(max_chunk, len(prompt) - start)
            final = start + n >= len(prompt)
            seq = self._chunk_seq(r, start, n, final)
            if final:
                pen = self._penalties_of(r)
                rows = self._count_row_of(r)[None] if any(pen) else None
                return int(self.runner.prefill_batch(
                    [seq], count_rows=rows)[0])
            self.runner.prefill_chunk_async(seq)
            start += n
        raise AssertionError("chunked plan with no chunks")

    def _sampling_of(self, r: _Request) -> tuple[float, int, float]:
        s = r.req.sampling_options
        return (s.temperature or 0.0, s.top_k or 0, s.top_p or 1.0)

    def _set_seed_slot(self, r: _Request, slot: int) -> None:
        from dynamo_tpu.engine.runner import mask_seed
        seed = self._seed_of(r)
        self.seeded[slot] = seed is not None
        self.seeds[slot] = 0 if seed is None else mask_seed(seed)

    @staticmethod
    def _seed_of(r: _Request) -> int | None:
        return getattr(r.req.sampling_options, "seed", None)

    @staticmethod
    def _penalties_of(r: _Request) -> tuple[float, float]:
        s = r.req.sampling_options
        return (getattr(s, "frequency_penalty", None) or 0.0,
                getattr(s, "presence_penalty", None) or 0.0)

    def _count_row_of(self, r: _Request) -> np.ndarray:
        """uint8 [vocab] counts of this request's generated tokens so far
        (penalty state; saturates at 255). tokens_all is authoritative —
        every placement path appends the first token before calling."""
        row = np.zeros(self.runner.spec.vocab_size, np.int64)
        gen = r.tokens_all[len(r.req.token_ids):]
        if gen:
            np.add.at(row, np.asarray(gen, np.int64), 1)
        return np.minimum(row, 255).astype(np.uint8)

    def _place_in_slot_pending(self, r: _Request, slot: int) -> None:
        """Occupy a slot whose first token is still on device (scattered
        into tokens_dev by the prefill program): decode windows chain from
        it with no override; the host value is emitted when the async
        fetch resolves (_resolve_first)."""
        prompt_len = len(r.tokens_all)
        if not r.no_cache:
            for idx, h in enumerate(r.blocks.block_hashes):
                self.allocator.register(r.pages[idx], h)
        r.slot = slot
        r.epoch += 1
        r.last_token = None
        self.slot_req[slot] = r
        self.disp_positions[slot] = prompt_len
        self.disp_seq_lens[slot] = prompt_len + 1
        temp, tk, tp = self._sampling_of(r)
        self.temperature[slot] = temp
        self.top_k[slot] = tk
        self.top_p[slot] = tp
        self.freq_pen[slot], self.pres_pen[slot] = self._penalties_of(r)
        self.adapter_ids[slot] = r.adapter_slot
        self._set_seed_slot(r, slot)
        self.overrides.pop(slot, None)

    def _place_in_slot(self, r: _Request, slot: int, first_token: int,
                       lp_out: tuple[list, list] | None = None) -> None:
        prompt_len = len(r.tokens_all)
        # The prompt's complete blocks are now resident: register them for
        # prefix reuse + router events (multimodal requests skip the
        # cache: placeholder ids don't content-hash the media).
        if not r.no_cache:
            for idx, h in enumerate(r.blocks.block_hashes):
                self.allocator.register(r.pages[idx], h)
        r.generated += 1
        finish = self._check_finish(r, first_token)
        self._emit(r, [first_token], finish, lp_out)
        if finish is not None:
            self._pending_release.append((self._dispatch_serial, r.pages))
            r.pages = []
            self._release_adapter(r)
            return
        r.slot = slot
        r.epoch += 1
        r.last_token = first_token
        r.tokens_all.append(first_token)
        self.slot_req[slot] = r
        self.disp_positions[slot] = prompt_len
        self.disp_seq_lens[slot] = prompt_len + 1
        temp, tk, tp = self._sampling_of(r)
        self.temperature[slot] = temp
        self.top_k[slot] = tk
        self.top_p[slot] = tp
        fp, pp = self._penalties_of(r)
        self.freq_pen[slot], self.pres_pen[slot] = fp, pp
        self.adapter_ids[slot] = r.adapter_slot
        self._set_seed_slot(r, slot)
        if fp or pp:
            # tokens_all already includes first_token (appended above).
            self.runner.set_count_rows([slot], self._count_row_of(r)[None])
        self.overrides[slot] = first_token

    # -- decode windows -------------------------------------------------------
    # dtpu: hotpath -- decode-window dispatch: a sync device->host readback anywhere below stalls the software pipeline
    def _dispatch_window(self) -> _Window:
        cfg = self.config
        page = cfg.page_size
        # Window size is fixed: admission is never window-blocked in this
        # loop (_admit drains the waiting queue into free slots before
        # every dispatch, and dispatches are async), so an adaptive
        # shrink-while-waiting policy was tried and reverted — the only
        # states where requests persist in the queue are slot/KV
        # saturation, where short windows just multiply dispatch overhead
        # without admitting anyone (tried before the chip; not measured on
        # this chip, ROADMAP D6).
        M = self.decode_window
        if self.mtp:
            # The most a row advances: every step's drafts accepted. The
            # pages a row needs are taken for that case; processing takes
            # back what the window did not use (_process_spec_window).
            M *= cfg.spec_k + 1
        b = cfg.max_num_seqs
        frozen: dict[int, tuple] = {}
        stalled: set[int] = set()
        satisfied: set[int] = set()
        deficits: dict[int, int] = {}
        needed_max = 1
        # Prefilling slots are invisible to the decode window: they have
        # no token chain yet, and their pages were fully allocated at
        # admission (chunk work never allocates mid-flight).
        live = [i for i, r in enumerate(self.slot_req)
                if r is not None and not r.prefilling]
        n_live = len(live)
        # Allocate pages oldest-request-first (requeued requests keep their
        # original enqueue time, so they age past new arrivals — no
        # starvation).
        order = sorted(live, key=lambda j: self.slot_req[j].enqueue_t)
        for i in order:
            r = self.slot_req[i]
            if int(self.disp_seq_lens[i]) >= r.len_cap:
                # Every token this request may emit is already produced
                # (the prefill's first token) or covered by an in-flight
                # window: more decode steps are dead compute. For a
                # max_tokens=1 burst — the disagg prefill-worker serving
                # pattern — this slot is only waiting on its first-token
                # readback, and a dispatched window would delay it.
                satisfied.add(i)
                continue
            # (A prediction module's entry of a position lies one slot on.)
            last_pos = int(self.disp_positions[i]) + M - 1 + int(self.mtp)
            # Clamp to the model-length cap AND the request's own length
            # cap: the slot decodes up to its allocated capacity within the
            # window and freezes in-graph (the host emits LENGTH when
            # processing reaches the cap).
            needed = min(last_pos // page + 1, cfg.max_pages_per_seq,
                         (r.len_cap - 1) // page + 1)
            ok = True
            while len(r.pages) < needed:
                new = self.allocator.allocate(1)
                if new is None:
                    ok = False
                    break
                r.pages.extend(new)
            if not ok:
                pending = sum(len(p) for _, p in self._pending_release)
                if (n_live == 1 and not self._prefilling
                        and needed - len(r.pages)
                        > self.allocator.num_free + pending):
                    # Only live slot and the pool — even counting pages
                    # queued for release behind in-flight windows — is
                    # simply too small: fail it.
                    frozen[i] = (r, r.epoch, "oom")
                else:
                    deficits[i] = needed - len(r.pages)
                    stalled.add(i)
                continue
            needed_max = max(needed_max, len(r.pages))
        if deficits:
            # Preempt the YOUNGEST live slots (vLLM preempt-the-youngest
            # semantics) until the pages they will free (released after the
            # in-flight windows complete) — plus pages already queued for
            # release — cover what older slots still need. The
            # under-allocated older slots STALL this window: they keep all
            # state (pages, device token chain, pending override) and retry
            # next dispatch rather than being preempted themselves. The
            # very oldest slot is never a victim.
            freed = sum(len(p) for _, p in self._pending_release)
            want = sum(deficits.values())
            for j in reversed(order[1:]):
                if freed >= want:
                    break
                if j in frozen or j in satisfied:
                    # A satisfied slot's pages free the moment its
                    # first-token readback lands — preempting it would
                    # throw away a finished prefill for pages we get
                    # back on the next loop pass anyway.
                    continue
                r_j = self.slot_req[j]
                want -= deficits.pop(j, 0)  # a victim needs no pages
                stalled.discard(j)
                frozen[j] = (r_j, r_j.epoch, "requeue")
                freed += len(r_j.pages)
            if freed < want:
                # Decode victims alone can't cover the deficit: preempt
                # PREFILLING requests youngest-first (their chunk work is
                # recomputable, and prefix-cache hits make the re-prefill
                # cheap). Immediate — no in-flight window carries tokens
                # for a prefilling slot.
                for rp in sorted(self._prefilling,
                                 key=lambda x: x.enqueue_t, reverse=True):
                    if freed >= want:
                        break
                    freed += len(rp.pages)
                    self._preempt_prefilling(rp)
        active_rows = [i for i in live if i not in frozen
                       and i not in stalled and i not in satisfied]
        # A slot frozen at a PREVIOUS dispatch that this dispatch decided
        # to keep (allocation succeeded, or it merely stalls) is live again:
        # cancel the pending preemption records so processing the earlier
        # windows doesn't spuriously requeue or oom-fail it — this
        # dispatch's decision supersedes the previous ones.
        for w in self._inflight:
            for i in (*active_rows, *stalled, *satisfied):
                w.frozen.pop(i, None)
        self._dispatch_serial += 1
        held_without_row = (sum(1 for r in self.slot_req if r is not None)
                            - len(active_rows))
        if not active_rows:
            return _Window(toks=None, slots=[None] * b, frozen=frozen,
                           size=M, serial=self._dispatch_serial,
                           t0=time.monotonic(),
                           prefilling=held_without_row)
        bucket = self.runner.bucket_pages_for(needed_max)
        packed = np.zeros((b, PK_PREFIX + bucket), np.int32)
        slots: list = [None] * b
        for i in active_rows:
            r = self.slot_req[i]
            # Consume the override only when the slot actually dispatches
            # (a frozen slot's first-token override must survive a retry).
            tok = self.overrides.pop(i, None)
            if tok is not None:
                packed[i, PK_OVERRIDE] = 1
                packed[i, PK_TOKEN] = tok
            start = int(self.disp_positions[i])
            cap = len(r.pages) * page
            packed[i, PK_POS] = start
            packed[i, PK_SEQLEN] = self.disp_seq_lens[i]
            packed[i, PK_TOPK] = self.top_k[i]
            packed[i, PK_TEMP] = self.temperature[i:i + 1].view(np.int32)[0]
            packed[i, PK_TOPP] = self.top_p[i:i + 1].view(np.int32)[0]
            packed[i, PK_CAP] = cap
            if r.req.sampling_options.logprobs is not None:
                packed[i, PK_LOGPROB] = 1
            packed[i, PK_FREQPEN] = self.freq_pen[i:i + 1].view(np.int32)[0]
            packed[i, PK_PRESPEN] = self.pres_pen[i:i + 1].view(np.int32)[0]
            packed[i, PK_SEED] = self.seeds[i]
            packed[i, PK_SEEDED] = int(self.seeded[i])
            packed[i, PK_ADAPTER] = self.adapter_ids[i]
            packed[i, PK_PREFIX:PK_PREFIX + len(r.pages)] = r.pages
            slots[i] = (r, r.epoch, start, cap)
            adv = min(M, max(0, cap - start))
            self.disp_positions[i] += adv
            self.disp_seq_lens[i] += adv
        with self.phase_clock.phase("engine.kvbm"):
            self._flush_spills()
        # Brownout degradation hook: drop back to plain decode windows
        # while the engine-local pressure level is at/above the
        # configured threshold (0 in config disables the hook).
        use_spec = self.config.spec_decode == "ngram"
        if (use_spec and self.config.brownout_spec_disable_level
                and self.brownout_level
                >= self.config.brownout_spec_disable_level):
            use_spec = False
            self.spec_brownout_windows += 1
        if use_spec:
            outs = self.runner.decode_spec_window(
                packed, self.spec_m_outer, self.config.spec_k)
        else:
            outs = self.runner.decode_window(packed, self.decode_window)
        # (The drafting window's fifth output holds what its steps emitted.)
        for arr in (*outs, *(outs[4].values() if self.mtp else ())):
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 — not all backends support it
                pass
        return _Window(toks=outs, slots=slots, frozen=frozen, size=M,
                       serial=self._dispatch_serial,
                       spec=use_spec or self.mtp,
                       t0=time.monotonic(),
                       page_bucket=packed.shape[1] - PK_PREFIX,
                       prefilling=held_without_row)

    def _count(self, w: _Window, counted: dict) -> None:
        """What a window counted, into its own counts and this engine's
        totals by flight-ring column: ``counted`` holds a vector under each
        key of flight.COUNTS that the window has (a program's readback may
        hold other keys beside them: a drafting window's "emit" and
        "draft")."""
        for key in counted.keys() & flight.COUNTS.keys():
            for column, value in flight.columns_of(key, counted[key]).items():
                w.counts[column] = value
                self.counts_total[column] += value

    def _process_window(self, w: _Window) -> None:
        if w.spec and w.toks is not None:
            self._process_spec_window(w)
            return
        page = self.config.page_size
        if w.toks is not None:
            want_lp = any(
                snap is not None
                and snap[0].req.sampling_options.logprobs is not None
                for snap in w.slots)
            # asarray blocks on the device program: the one place the
            # engine thread waits for the device.
            with self.phase_clock.phase("engine.readback_wait"):
                toks = np.asarray(w.toks[0])
                lps = np.asarray(w.toks[1]) if want_lp else None
                top_vs = np.asarray(w.toks[2]) if want_lp else None
                top_is = np.asarray(w.toks[3]) if want_lp else None
                # What the block counted: a few bytes of the same
                # program's output, copied with the tokens: no second wait
                # for the device.
                self._count(w, w.toks[4])
            self._note_ready(w)
        else:
            toks = None
        self._release_ready_pages()
        # Window processing walks host token chains; make sure every slot
        # this window touches has its first token resolved.
        if self._pending_first:
            need = {i for i, snap in enumerate(w.slots)
                    if snap is not None and snap[0].last_token is None}
            need |= {i for i, (fr, _, _) in w.frozen.items()
                     if fr.last_token is None}
            if need:
                self._force_resolve_first_for(need)
        for i, (fr, fepoch, reason) in w.frozen.items():
            r = self.slot_req[i]
            if r is not fr or r is None or r.epoch != fepoch:
                continue  # slot was re-assigned since dispatch
            if reason == "oom":
                r.push(RuntimeError(
                    "KV pool exhausted and no other request to preempt"))
                self._finish_slot(i, register=False)
            else:  # requeue (preemption)
                self._requeue_slot(i)
        if toks is None:
            return
        for i, snap in enumerate(w.slots):
            if snap is None:
                continue
            r, epoch, start, cap = snap
            if self.slot_req[i] is not r or r.epoch != epoch:
                continue  # slot was re-assigned since dispatch
            if r.ctx.is_killed:
                self._end_decode_span(r)
                r.push(None)
                self._finish_slot(i, register=True)
                continue
            accepted: list[int] = []
            lp_out = ([], []) if r.req.sampling_options.logprobs is not None \
                else None
            finish = None
            inp = r.last_token
            for m in range(w.size):
                if start + m >= cap:
                    # The slot hit its page capacity (= max_model_len here:
                    # dispatch clamps allocation only at max_pages_per_seq)
                    # and froze in-graph.
                    finish = FinishReason.LENGTH
                    break
                token = int(toks[m, i])
                r.generated += 1
                new_block = r.blocks.append(inp)
                if new_block is not None and not r.no_cache:
                    # Register the just-completed page under its chained hash.
                    page_idx = (len(r.blocks.tokens) // page) - 1
                    self.allocator.register(r.pages[page_idx], new_block)
                accepted.append(token)
                if lp_out is not None:
                    k = r.req.sampling_options.logprobs or 0
                    lp_out[0].append(float(lps[m, i]))
                    lp_out[1].append(
                        [{"token_id": int(top_is[m, i, j]),
                          "logprob": float(top_vs[m, i, j])}
                         for j in range(k)])
                r.tokens_all.append(token)
                inp = token
                finish = self._check_finish(r, token)
                if finish is not None:
                    break
            r.last_token = inp
            if finish is None and r.ctx.is_stopped:
                finish = FinishReason.CANCELLED
            self.tokens_generated_total += len(accepted)
            if accepted:
                r.decode_windows += 1
            self._emit(r, accepted, finish, lp_out)
            if finish is not None:
                self._finish_slot(i, register=True)

    def _process_spec_window(self, w: _Window) -> None:
        """Host walk for a speculative window: per outer step the device
        emitted ``e`` tokens (1 + accepted drafts, 0 when frozen); the
        host appends them in order, applies stop conditions per token,
        and CORRECTS its dispatch-time position upper bound down to the
        actual advance (pipelined dispatches assumed the worst case)."""
        page = self.config.page_size
        lps = top_vs = top_is = None
        with self.phase_clock.phase("engine.readback_wait"):
            outs = np.asarray(w.toks[0])     # [m, B, S]
            if self.mtp:
                # The drafting window (runner._get_mtp_window): the plain
                # window's outputs with the positions' axis, and in the
                # same readback what its steps emitted and counted.
                counted = w.toks[4]
                emits = np.asarray(counted["emit"])
                proposed = np.asarray(counted["draft"])     # -1: none
                ndrafts = (proposed >= 0).astype(np.int64)
                if any(snap is not None and
                       snap[0].req.sampling_options.logprobs is not None
                       for snap in w.slots):
                    lps, top_vs, top_is = (np.asarray(a)
                                           for a in w.toks[1:4])
                self._count(w, counted)
            else:
                emits = np.asarray(w.toks[1])    # [m, B]
                ndrafts = np.asarray(w.toks[2])  # [m, B]
        self._note_ready(w)
        self._release_ready_pages()
        if self._pending_first:
            need = {i for i, snap in enumerate(w.slots)
                    if snap is not None and snap[0].last_token is None}
            need |= {i for i, (fr, _, _) in w.frozen.items()
                     if fr.last_token is None}
            if need:
                self._force_resolve_first_for(need)
        for i, (fr, fepoch, reason) in w.frozen.items():
            r = self.slot_req[i]
            if r is not fr or r is None or r.epoch != fepoch:
                continue
            if reason == "oom":
                r.push(RuntimeError(
                    "KV pool exhausted and no other request to preempt"))
                self._finish_slot(i, register=False)
            else:
                self._requeue_slot(i)
        steps = outs.shape[0]
        live_rows = [i for i, snap in enumerate(w.slots) if snap is not None]
        if live_rows:
            # What the window's verify steps did, for the flight ring.
            e_live, d_live = emits[:, live_rows], ndrafts[:, live_rows]
            self._count(w, {"spec": (d_live.sum(),
                                     np.maximum(e_live - 1, 0).sum(),
                                     (e_live > 0).sum())})
        for i, snap in enumerate(w.slots):
            if snap is None:
                continue
            r, epoch, start, cap = snap
            if self.slot_req[i] is not r or r.epoch != epoch:
                continue
            if r.ctx.is_killed:
                self._end_decode_span(r)
                r.push(None)
                self._finish_slot(i, register=True)
                continue
            accepted: list[int] = []
            lp_out = (([], []) if lps is not None and
                      r.req.sampling_options.logprobs is not None else None)
            finish = None
            inp = r.last_token
            pos = start
            for m in range(steps):
                e = int(emits[m, i])
                self.spec_emit_hist[e] += 1
                if e == 0:
                    if pos >= cap:
                        finish = FinishReason.LENGTH
                    break
                nd = int(ndrafts[m, i])
                if nd and self.mtp and self.draft_log is not None:
                    # The draft of the token after the chained one.
                    self.draft_log.setdefault(r.ctx.id, []).append(
                        (len(r.tokens_all), int(proposed[m, i])))
                if nd:
                    self.spec_drafts += 1
                    self.spec_tokens += nd
                    self.spec_accepted += e - 1
                for j in range(e):
                    token = int(outs[m, i, j])
                    r.generated += 1
                    new_block = r.blocks.append(inp)
                    if new_block is not None and not r.no_cache:
                        page_idx = (len(r.blocks.tokens) // page) - 1
                        self.allocator.register(r.pages[page_idx],
                                                new_block)
                    accepted.append(token)
                    if lp_out is not None:
                        k = r.req.sampling_options.logprobs or 0
                        lp_out[0].append(float(lps[m, i, j]))
                        lp_out[1].append(
                            [{"token_id": int(top_is[m, i, j, n]),
                              "logprob": float(top_vs[m, i, j, n])}
                             for n in range(k)])
                    r.tokens_all.append(token)
                    inp = token
                    finish = self._check_finish(r, token)
                    if finish is not None:
                        break
                pos += e
                if finish is not None:
                    break
            r.last_token = inp
            if finish is None and r.ctx.is_stopped:
                finish = FinishReason.CANCELLED
            if finish is None:
                # Undo the dispatch-time worst-case advance assumption.
                # delta can be NEGATIVE when the device chain advanced
                # past the dispatch-time clamp (an earlier pipelined
                # window over-assumed near the page-capacity/len_cap
                # clamp): dropping that correction undercounts
                # disp_positions vs the device and can leave a
                # cap-frozen slot (e==0, host pos < cap) never emitting
                # LENGTH — apply it in both directions.
                assumed = min(w.size, max(0, cap - start))
                delta = assumed - (pos - start)
                if delta != 0:
                    self.disp_positions[i] -= delta
                    self.disp_seq_lens[i] -= delta
            self.tokens_generated_total += len(accepted)
            if accepted:
                r.decode_windows += 1
            self._emit(r, accepted, finish, lp_out)
            if finish is not None:
                self._finish_slot(i, register=True)

    def _check_finish(self, r: _Request, token: int) -> FinishReason | None:
        sc = r.req.stop_conditions
        if r.generated >= (sc.max_tokens or 2**30):
            return FinishReason.LENGTH
        if sc.min_tokens and r.generated < sc.min_tokens:
            return None
        if not sc.ignore_eos and token in (r.req.eos_token_ids or []):
            return FinishReason.EOS
        if token in (sc.stop_token_ids or []):
            return FinishReason.STOP
        return None

    def _emit(self, r: _Request, tokens: list[int],
              finish: FinishReason | None = None,
              lp_out: tuple[list, list] | None = None) -> None:
        if tokens and not r.decode_t0:
            r.decode_t0 = time.monotonic()  # the first token: decode starts
        out = LLMEngineOutput(token_ids=tokens, finish_reason=finish)
        if lp_out is not None:
            out.log_probs = lp_out[0]
            out.top_log_probs = lp_out[1]
        if finish is not None:
            # Before the push: a caller that reads the trace when its
            # stream ends must find the span there.
            self._end_decode_span(r)
        r.push(out.to_wire())

    def _end_decode_span(self, r: _Request) -> None:
        """The request's ONE engine.decode span (a span per row per window
        turned the ring over in two minutes and nothing read them).
        Recorded ahead of whatever ends the client's stream; a second call
        is a no-op."""
        if not r.decode_t0:
            return
        if self._recorder.enabled:
            self._recorder.add(
                "engine.decode", r.ctx.trace_id, r.ctx.span_id,
                r.decode_t0, time.monotonic(),
                attrs={"tokens": r.generated, "windows": r.decode_windows,
                       "window": self.decode_window,
                       "preemptions": r.preemptions})
        r.decode_t0 = 0.0

    def _note_ready(self, w: _Window) -> None:
        """A window's readback is complete: its period (see _Window), and
        the decode histogram. A window that was queued behind the previous
        one is timed by its period, which is a window of the device;
        dispatch -> readback would count the windows queued ahead of it
        (pipeline_depth of them) into every sample."""
        now = time.monotonic()
        last = self._last_ready_t
        if last is not None and w.t0 and w.t0 <= last:
            w.period_s = now - last
        w.t_ready = now
        self._last_ready_t = now
        if self.phase is not None and w.t0:
            self.phase.decode.observe(w.period_s or now - w.t0)

    def _finish_slot(self, slot: int, register: bool,
                     requeue: bool = False) -> None:
        r = self.slot_req[slot]
        self.slot_req[slot] = None
        self.disp_positions[slot] = 0
        self.disp_seq_lens[slot] = 0
        if 0 <= slot < len(self.adapter_ids):
            self.adapter_ids[slot] = 0
        self.overrides.pop(slot, None)
        if r is None:
            return
        self._release_adapter(r)
        r.slot = -1
        r.epoch += 1
        if not requeue:
            # Failure paths only: a finish that went through _emit has
            # recorded the span already.
            self._end_decode_span(r)
        if not register:
            # Failure path: the pages' KV contents are suspect (partial
            # prefill / failed step) — drop their prefix-cache entries so no
            # future request reuses them.
            self.allocator.unregister(r.pages)
        # Defer the release until every in-flight window (which may still
        # scatter dummy K/V through the old page table) completes.
        self._pending_release.append((self._dispatch_serial, r.pages))
        r.pages = []

    def _requeue_slot(self, slot: int) -> None:
        """Preempt: free this slot's pages (prefix-cache entries survive so
        the re-prefill mostly hits) and requeue the request with its
        accumulated tokens. A block with recurrent layers registered no
        entry and has no snapshot of its state: the re-prefill recomputes
        the row from its first token, and starts (at position 0) from a
        zero state whatever the slot it lands in held."""
        r = self.slot_req[slot]
        self._finish_slot(slot, register=True, requeue=True)
        if r is None:
            return
        r.preemptions += 1
        if r.ctx.is_killed or r.ctx.is_stopped:
            self._end_decode_span(r)
            r.push(LLMEngineOutput(
                token_ids=[], finish_reason=FinishReason.CANCELLED).to_wire())
            return
        self.preempt_count += 1
        self.preempted_ids.append(r.ctx.id)
        r.wait_noted = False  # the second queue stint records its own wait
        log.warning("KV pool exhausted: preempting slot %d (request %s, "
                    "%d tokens so far) and requeueing", slot, r.ctx.id,
                    len(r.tokens_all))
        # Decision plane: preemption is an autonomous capacity decision
        # (engine thread; journal.emit is lock-only, no I/O). Cause: a
        # chaos injection when one is driving the pressure.
        journal.emit(EventKind.PREEMPT,
                     cause=(journal.recent_ref(EventKind.CHAOS_INJECT)
                            if chaos.ACTIVE else None),
                     trace_id=r.ctx.trace_id, request=r.ctx.id, slot=slot,
                     tokens=len(r.tokens_all),
                     free_pages=self.allocator.num_free)
        self._queue_put(r)

    # -- metrics + events -----------------------------------------------------
    def _note_flight(self, w: _Window) -> None:
        """One flight-recorder row per processed decode window (engine
        thread; the ring skips idle-stable windows itself) — plus the
        perf plane's roofline sample for the same window."""
        now = time.monotonic()
        tokens_total = self.tokens_generated_total
        # Roofline attribution (engine/perf.py): device window time +
        # tokens + dispatched rows -> EWMA step/tok_s/roofline gauges.
        # Plain stores; independent of the flight ring's frozen state.
        window_tokens = tokens_total - self._perf_tokens_last
        self._perf_tokens_last = tokens_total
        rows = sum(1 for snap in w.slots if snap is not None)
        if w.t0 and w.toks is not None:
            latency = (w.t_ready or now) - w.t0
            self._perf.note_window(
                w.period_s or latency, window_tokens, rows,
                w.size, self._step_floor_ms, latency_s=latency)
        fr = self._flight
        if not fr.enabled:
            return
        clock = self.phase_clock
        clock.sync(now)
        wait_total, idle_total = clock.waited()
        busy_total = clock.total() - wait_total - idle_total
        chunk_total = self.chunk_tokens_total
        accepted = fr.record(
            now, now - w.t0 if w.t0 else 0.0,
            sum(1 for r in self.slot_req if r is not None),
            self.num_waiting, self.allocator.num_free,
            chunk_total - self._flight_chunk_last,
            len(self._chunk_inflight), self.preempt_count,
            self.brownout_level, self._flight_stall_last,
            self.step_count, tokens_total - self._flight_tokens_last,
            w.period_s, busy_total - self._flight_busy_last,
            wait_total - self._flight_wait_last,
            idle_total - self._flight_idle_last, rows, w.page_bucket,
            prefilling=w.prefilling, admit_stop=self._flight_admit_stop,
            counts=w.counts)
        if accepted:
            # A frozen ring (bundle capture in flight) rejects the row:
            # keep accumulating so the stall/chunk/token/host-time deltas
            # land in the first post-thaw record instead of vanishing.
            self._flight_chunk_last = chunk_total
            self._flight_stall_last = 0.0
            self._flight_tokens_last = tokens_total
            self._flight_admit_stop = 0
            self._flight_busy_last = busy_total
            self._flight_wait_last = wait_total
            self._flight_idle_last = idle_total

    def _publish(self) -> None:
        if self.kv_metrics is not None:
            # /metrics export is loop-independent (in-process pipelines
            # without a coordinator still get dynamo_tpu_kv_* series).
            self.kv_metrics.update(self)
        if self.perf_metrics is not None:
            self.perf_metrics.update(self)
        if self.adapter_metrics is not None:
            self.adapter_metrics.update(self.adapters)
        loop = self._publish_loop
        if loop is None or loop.is_closed():
            self.allocator.drain_events()
            return
        stored, removed = self.allocator.drain_events()
        # Inventory digest: built on the engine thread only when the
        # publisher's cadence is due (a k-min sketch over the registered
        # hashes — bounded work, every ~2s).
        digest = None
        if self.inventory_publisher is not None \
                and self.inventory_publisher.due(time.monotonic()):
            digest = self.inventory_digest()
        active = sum(1 for r in self.slot_req if r is not None)
        hit = (self.prefix_hit_blocks / self.prefix_lookup_blocks
               if self.prefix_lookup_blocks else 0.0)
        metrics = ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_active_slots=active,
                request_total_slots=self.config.max_num_seqs,
                num_requests_waiting=self.num_waiting),
            kv_stats=KvStats(
                kv_active_blocks=self.allocator.num_active,
                kv_total_blocks=self.allocator.num_pages,
                gpu_cache_usage_perc=(self.allocator.num_active
                                      / self.allocator.num_pages),
                gpu_prefix_cache_hit_rate=hit),
            spec_decode_stats=(SpecDecodeStats(
                num_spec_tokens=self.spec_tokens,
                num_drafts=self.spec_drafts,
                num_accepted_tokens=self.spec_accepted)
                if self.config.spec_decode else None))

        async def do_publish():
            try:
                if self.kv_publisher is not None:
                    if stored:
                        await self.kv_publisher.stored(stored)
                    if removed:
                        await self.kv_publisher.removed(removed)
                if self.metrics_publisher is not None:
                    force = active == 0 and self.num_waiting == 0
                    await self.metrics_publisher.publish(metrics, force=force)
                if digest is not None:
                    await self.inventory_publisher.publish(digest)
            except Exception:  # noqa: BLE001
                log.exception("publish failed")

        if (self.kv_publisher is not None or self.metrics_publisher is not None
                or digest is not None):
            asyncio.run_coroutine_threadsafe(do_publish(), loop)
