"""TPU worker: serves the JAX engine as a registered model.

``python -m dynamo_tpu.backends.tpu --model llama-3-8b`` — the TPU-native
equivalent of the reference's vLLM worker (components/backends/vllm/src/dynamo/
vllm/main.py, SURVEY.md call stack 3.2): starts the engine, registers the
model with its runtime config, serves the endpoint, publishes KV events +
ForwardPassMetrics.

Disaggregated serving (reference handlers.py:113-199, SURVEY.md call stack
3.3): ``--mode prefill`` serves a prefill-only endpoint (computes prompt KV,
streams it back as a chunked parcel + first token); ``--mode decode``
conditionally forwards long prompts to discovered prefill workers
(``--max-local-prefill-length``, reference disagg_router.rs:25-45), injects
the transferred KV, and decodes. ``--mode agg`` (default) is fully local.
Handlers live in dynamo_tpu.llm.disagg; e2e-tested in tests/test_disagg.py.

``--mode`` is only the LAUNCH role: the worker is runtime-reconfigurable
via the SetRole protocol (llm/reconfig.py) — a planner directive or the
status server's POST /control/role drains in-flight streams through the
retire/migration machinery and rebuilds the serving profile around the
same engine, no weight reload (docs/RESILIENCE.md "Role transitions").

Multi-node (reference engines.rs:31-44 MultiNodeConfig): ``--num-nodes N
--node-rank R`` alone coordinates a per-host replica group over the
leader/worker barrier. With ``JAX_COORDINATOR_ADDRESS=host:port`` it
instead runs ONE engine whose mesh spans every host's chips
(multi-controller SPMD): rank 0 serves and publishes its device-dispatch
stream, ranks >0 replay it (engine/multihost.py); e2e-tested in
tests/test_multihost.py.
"""

from __future__ import annotations

import argparse
import asyncio
import os

from dynamo_tpu.engine.config import EngineConfig, PRESETS, ModelSpec
from dynamo_tpu.engine.engine import TPUEngine
from dynamo_tpu.llm.kv_router.publisher import (KvEventPublisher,
                                                KvInventoryPublisher,
                                                WorkerMetricsPublisher)
from dynamo_tpu.llm.model_card import ModelRuntimeConfig, register_llm
from dynamo_tpu.llm.tokenizer import Tokenizer, make_test_tokenizer
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging import get_logger

log = get_logger("tpu_worker")


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="dynamo-tpu TPU engine worker")
    parser.add_argument("--model", default="tiny-test",
                        help="preset name or path to a HF model dir")
    parser.add_argument("--model-name", default=None,
                        help="served model name (default: preset/dir name)")
    parser.add_argument("--namespace", default=None)
    parser.add_argument("--component", default="tpu")
    parser.add_argument("--endpoint", default="generate")
    parser.add_argument("--tokenizer", default=None)
    parser.add_argument("--page-size", default="auto",
                        type=_auto_or_positive,
                        help="tokens per KV page (= kv_cache_block_size): "
                             "a positive int, or 'auto': 16, and where the "
                             "Pallas kernel reads the pool on one TPU "
                             "device the page whose one copy moves 64 KB "
                             "(64 tokens at 4 KV heads of 128)")
    parser.add_argument("--num-pages", type=int, default=None)
    parser.add_argument("--max-num-seqs", type=int, default=32)
    parser.add_argument("--max-pages-per-seq", type=int, default=None,
                        help="page-table width of a sequence (default: "
                             "what holds 8192 tokens at the page size)")
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument("--pp", type=int, default=1,
                        help="layer-sharded pipeline axis")
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence (context) parallelism for prefill")
    parser.add_argument("--pp-microbatch", action="store_true",
                        help="with --pp > 1: microbatched pipeline-"
                             "parallel prefill (GPipe fill/drain over the "
                             "pp stages) instead of layer-sharded-only")
    parser.add_argument("--ring-attention", action="store_true",
                        help="with --sp > 1: rotate K/V blocks around "
                             "the sp ring (ppermute + online softmax) "
                             "instead of all-gathering the full K/V — "
                             "peak K/V memory is one block per device")
    parser.add_argument("--decode-window", default="auto",
                        type=_auto_or_positive,
                        help="decode steps per dispatched window: a "
                             "positive int, or 'auto' to size from the "
                             "model's weight-read step estimate "
                             "(DTPU_WINDOW_TARGET_MS)")
    parser.add_argument("--pipeline-depth", type=int, default=4,
                        help="decode windows in flight before the host "
                             "blocks on the oldest readback")
    parser.add_argument("--prefill-chunk-tokens", default="auto",
                        type=_auto_or_positive,
                        help="stall-free chunked prefill: prompt tokens "
                             "dispatched as prefill chunks per engine-loop "
                             "iteration before the next decode window; "
                             "'auto' sizes one chunk to ~one "
                             "DTPU_WINDOW_TARGET_MS window period "
                             "(DTPU_PREFILL_CHUNK_TOKENS overrides)")
    parser.add_argument("--warmup-prefill-ladder", action="store_true",
                        help="pre-compile EVERY prefill bucket incl. the "
                             "with-history chunk variants at startup, so "
                             "the first long prompt never pays per-bucket "
                             "XLA compiles while decode slots wait")
    parser.add_argument("--attention-backend", default="auto",
                        choices=["auto", "pallas", "xla"],
                        help="decode attention: 'auto' runs the Pallas "
                             "paged kernel on one TPU device at head_dim "
                             "128 and the XLA gather everywhere else (CPU, "
                             "a mesh, smaller heads); an explicit "
                             "'pallas' that cannot be had is an error")
    parser.add_argument("--quant", default=None, choices=["int8"],
                        help="weight-only quantization: int8 storage, "
                             "bf16 MXU compute (halves weight HBM — fits "
                             "full llama-3-8b on one 16 GB v5e)")
    parser.add_argument("--quant-kv", default=None, choices=["int8"],
                        help="KV-cache quantization: int8 pages with "
                             "per-token scales, dequant fused into the "
                             "attention kernels — ~2x KV pages per HBM "
                             "GB and ~half the attention/transfer bytes; "
                             "composes with --quant (DTPU_QUANT_KV "
                             "overrides)")
    parser.add_argument("--host-cache-pages", type=int, default=0,
                        help="G2 host-DRAM KV block cache capacity in "
                             "pages (0 = disabled); evicted HBM pages "
                             "offload here and onboard on prefix hits")
    parser.add_argument("--kv-disk-cache-dir", default=None,
                        help="G3 disk tier directory behind the host cache")
    parser.add_argument("--kv-watermarks", default=None,
                        help="KVBM proactive demotion watermarks "
                             "'low,high' as fractions of the HBM pool "
                             "free list (engine/kvbm.py): below low, LRU "
                             "inactive blocks demote to the host tier "
                             "until high (hysteresis); needs "
                             "--host-cache-pages (DTPU_KV_WATERMARKS "
                             "overrides)")
    parser.add_argument("--lora", action="append", default=[],
                        metavar="NAME=PATH",
                        help="serve a LoRA adapter: NAME becomes a "
                             "registered model name riding this "
                             "worker's base model; PATH is a HF PEFT "
                             "checkpoint dir (adapter_config.json + "
                             "adapter_model.safetensors). Repeatable — "
                             "heterogeneous adapters batch into one "
                             "decode window (engine/lora.py)")
    parser.add_argument("--max-adapters", type=int, default=None,
                        help="resident device adapter slots (default: "
                             "max(4, number of --lora flags)); registered "
                             "adapters beyond this hot-load on demand "
                             "with LRU eviction")
    parser.add_argument("--max-lora-rank", type=int, default=8,
                        help="adapter ranks pad to this fixed max so "
                             "stacks keep static shapes (checkpoints "
                             "with a larger rank are rejected)")
    parser.add_argument("--spec-decode", default=None,
                        choices=["ngram", "mtp"],
                        help="speculative decoding: 'ngram' = prompt-"
                             "lookup self-drafting verified in-window; "
                             "'mtp' = the model's own prediction module "
                             "(num_nextn_predict_layers) drafts inside the "
                             "window program, --spec-k 1. Both serve "
                             "greedy and temperature/top-k/top-p/seeded "
                             "sampling (on-device rejection sampling keeps "
                             "the exact output distribution); penalties "
                             "are not supported under either, logprobs "
                             "under 'mtp' only")
    parser.add_argument("--spec-k", type=int, default=3,
                        help="drafts verified per speculative step")
    parser.add_argument("--ttft-budget-ms", type=float, default=None,
                        help="SLA-aware admission: defer admitting cold "
                             "prefills while the projected TTFT (measured "
                             "prefill rate x cold-token backlog) exceeds "
                             "this budget")
    parser.add_argument("--admission-reject-factor", type=float, default=2.0,
                        help="with --ttft-budget-ms: reject (503) requests "
                             "whose projected TTFT through the backlog "
                             "exceeds budget x this factor, so the router "
                             "retries another worker; 0 = queue unboundedly")
    parser.add_argument("--migration-limit", type=int, default=0)
    parser.add_argument("--tool-call-parser", default=None,
                        help="tool-call format on the backward edge "
                             "(hermes, llama3_json, mistral, nemotron_deci, "
                             "phi4, default)")
    parser.add_argument("--reasoning-parser", default=None,
                        help="think-tag splitting (deepseek_r1, basic)")
    parser.add_argument("--coordinator-url", default=None)
    parser.add_argument("--mode", default="agg",
                        choices=["agg", "prefill", "decode"],
                        help="agg = fully local; prefill = prefill-only "
                             "worker (serves KV parcels); decode = decode "
                             "worker forwarding long prompts to prefill "
                             "workers")
    parser.add_argument("--standby", action="store_true",
                        help="park as a pre-warmed standby: weights "
                             "loaded and warmup run, but DEREGISTERED "
                             "— announced on a standby/ lease key and "
                             "joining the serving fleet in seconds on "
                             "a planner promote directive "
                             "(llm/standby.py; docs/RESILIENCE.md "
                             "\"Autoscaling\")")
    parser.add_argument("--max-local-prefill-length", type=int, default=512,
                        help="decode mode: prompts longer than this prefill "
                             "remotely (conditional disaggregation; dynamic "
                             "via the coordinator disagg/<model> key)")
    parser.add_argument("--prefill-dispatch", default="direct",
                        choices=["direct", "queue"],
                        help="remote-prefill dispatch: direct round-robin "
                             "to discovered prefill workers, or the shared "
                             "coordinator queue with worker-side pull and "
                             "depth backpressure (reference PrefillQueue, "
                             "nats.rs:433)")
    parser.add_argument("--max-prefill-queue-depth", type=int, default=8,
                        help="queue dispatch: enqueue only while the queue "
                             "is shallower than this; otherwise prefill "
                             "locally (load-leveling backpressure)")
    parser.add_argument("--prefill-component", default=None,
                        help="component name prefill workers serve under "
                             "(default: 'prefill')")
    parser.add_argument("--kv-plane-host", default="127.0.0.1",
                        help="address this worker's direct KV data plane "
                             "binds and advertises (the NIXL-role bulk "
                             "plane, llm/kv_plane.py); must be reachable "
                             "by peer workers")
    parser.add_argument("--no-kv-plane", action="store_true",
                        help="disable the direct KV data plane: disagg "
                             "parcels ride the request plane inline (v0 "
                             "fallback) and this worker serves no G4 "
                             "remote-tier blocks")
    parser.add_argument("--num-nodes", type=int, default=1,
                        help="hosts in this worker group; >1 gates serving "
                             "on a leader/worker barrier (rank 0 leads) so "
                             "all replicas agree on model + mesh shape "
                             "before any serves")
    parser.add_argument("--node-rank", type=int, default=0)
    parser.add_argument("--mh-group", default=None,
                        help="multi-host group id (default: model name). "
                             "REQUIRED to be distinct per group when two "
                             "multi-host groups of the same model share a "
                             "coordinator — it keys the dispatch stream "
                             "and bring-up barrier")
    return parser.parse_args(argv)


def build_engine_config(args) -> EngineConfig:
    import dataclasses

    from dynamo_tpu.engine.hub import resolve_model
    try:
        spec, ckpt = resolve_model(args.model)
    except FileNotFoundError as exc:
        raise SystemExit(str(exc)) from exc
    if getattr(args, "quant", None):
        spec = dataclasses.replace(spec, quant=args.quant)
    args.resolved_checkpoint = ckpt
    return EngineConfig(
        model=spec, page_size=args.page_size, num_pages=args.num_pages,
        max_num_seqs=args.max_num_seqs, max_pages_per_seq=args.max_pages_per_seq,
        tp=args.tp, dp=args.dp, pp=getattr(args, "pp", 1),
        sp=getattr(args, "sp", 1),
        pp_microbatch=getattr(args, "pp_microbatch", False),
        ring_attention=getattr(args, "ring_attention", False),
        attention_backend=args.attention_backend,
        decode_window=_auto_or_positive(
            getattr(args, "decode_window", "auto")),
        pipeline_depth=getattr(args, "pipeline_depth", 4),
        prefill_chunk_tokens=_auto_or_positive(
            getattr(args, "prefill_chunk_tokens", "auto")),
        warmup_windows=True,
        warmup_prefill_ladder=getattr(args, "warmup_prefill_ladder", False),
        quant_kv=getattr(args, "quant_kv", None),
        host_cache_pages=args.host_cache_pages,
        kv_disk_cache_dir=args.kv_disk_cache_dir,
        kv_demote_low_watermark=_watermark_arg(
            getattr(args, "kv_watermarks", None))[0],
        kv_demote_high_watermark=_watermark_arg(
            getattr(args, "kv_watermarks", None))[1],
        max_adapters=_max_adapters_arg(args),
        lora_max_rank=getattr(args, "max_lora_rank", 8),
        spec_decode=getattr(args, "spec_decode", None),
        spec_k=getattr(args, "spec_k", 3),
        ttft_budget_ms=getattr(args, "ttft_budget_ms", None),
        admission_reject_factor=(
            getattr(args, "admission_reject_factor", 0.0)
            if getattr(args, "ttft_budget_ms", None) else 0.0))


def _lora_args(args) -> list[tuple[str, str]]:
    """Parse repeated --lora NAME=PATH flags."""
    out = []
    for item in getattr(args, "lora", None) or []:
        name, sep, path = str(item).partition("=")
        if not sep or not name or not path:
            raise SystemExit(f"--lora expects NAME=PATH, got {item!r}")
        out.append((name, path))
    return out


def _max_adapters_arg(args) -> int:
    explicit = getattr(args, "max_adapters", None)
    if explicit is not None:
        return explicit
    loras = _lora_args(args)
    return max(4, len(loras)) if loras else 0


def _watermark_arg(value) -> tuple[float, float]:
    """Parse --kv-watermarks 'low[,high]' (None -> disabled)."""
    if not value:
        return 0.0, 0.0
    parts = [p for p in str(value).replace(",", " ").split() if p]
    low = float(parts[0])
    high = float(parts[1]) if len(parts) > 1 else 0.0
    return low, high


def _auto_or_positive(value) -> int | str:
    """argparse type of an option that is a positive int or 'auto'
    (--decode-window, --prefill-chunk-tokens, --page-size). ValueError ->
    argparse's clean 'invalid value' error at parse time."""
    if value == "auto":
        return value
    n = int(value)
    if n < 1:
        raise ValueError(f"must be >= 1 or 'auto', got {n}")
    return n


def make_profile_builder(runtime, args, engine, engine_cfg, tokenizer,
                         model_name, plane, prefill_component):
    """Per-role serving profiles around ONE engine (llm/reconfig.py).

    The engine object — weights, KV pool, compiled programs — lives
    outside the profile and survives role flips; a flip only swaps what
    this worker REGISTERS and which role-specific machinery (prefill
    queue worker, disagg client + config watch, queue dispatcher) runs
    around it. This is the factory both launch (initial ``--mode``) and
    the SetRole protocol build through, so a flipped-to role is
    byte-for-byte the role it would have launched as.
    """
    from dynamo_tpu.llm.disagg import (
        PREFILL_ENDPOINT, DisaggDecodeHandler, DisaggRouterConfig,
        make_prefill_handler)
    from dynamo_tpu.llm.model_card import deregister_llm, register_adapter
    from dynamo_tpu.llm.reconfig import ServingProfile
    lora_names = [name for name, _ in _lora_args(args)]

    async def build(role: str) -> ServingProfile:
        prof = ServingProfile(role)
        if role == "prefill":
            # Prefill workers register under their own component so decode
            # workers (not the frontend router) discover them; prefill
            # drains gracefully on shutdown (reference vllm main.py:151-161).
            endpoint = (runtime.namespace(None).component(prefill_component)
                        .endpoint(PREFILL_ENDPOINT))
            server = await endpoint.serve_endpoint(
                make_prefill_handler(engine, plane=plane),
                graceful_shutdown=True)
            prof.add_server(server)
            if plane is not None:
                # Also pull from the shared prefill queue (queue dispatch
                # needs the data plane for the reply ticket): serving both
                # paths lets direct- and queue-mode decode workers share
                # one prefill pool. A drain pauses the pull loop first so
                # queued prompts go to peers.
                from dynamo_tpu.llm.prefill_queue import QueuePrefillWorker
                queue_worker = QueuePrefillWorker(
                    engine, runtime.require_coordinator(), model_name,
                    plane)
                queue_worker.start()
                prof.add_pausable(queue_worker)
                prof.add_closer("prefill-queue", queue_worker.stop)
            else:
                log.warning(
                    "--no-kv-plane: this prefill worker will NOT pull "
                    "from the shared prefill queue (queue replies carry "
                    "data-plane tickets); queue-mode decode workers need "
                    "at least one plane-enabled prefill worker")
            return prof
        if role == "decode":
            prefill_ep = (runtime.namespace(None)
                          .component(prefill_component)
                          .endpoint(PREFILL_ENDPOINT))
            prefill_client = await prefill_ep.client()
            disagg_cfg = await DisaggRouterConfig.from_coordinator_with_watch(
                runtime.require_coordinator(), model_name,
                default_max_local=args.max_local_prefill_length)
            disagg_handler = DisaggDecodeHandler(engine, prefill_client,
                                                 disagg_cfg)
            if args.prefill_dispatch == "queue":
                from dynamo_tpu.llm.prefill_queue import (
                    QueuePrefillDispatcher)
                # Share the handler's plane client: one TCP connection
                # cache per prefill worker, one close at teardown.
                disagg_handler.queue_dispatcher = QueuePrefillDispatcher(
                    runtime.require_coordinator(), model_name,
                    disagg_handler.plane_client,
                    max_queue_depth=args.max_prefill_queue_depth)
            handler = disagg_handler.handler()
            prof.add_closer("prefill-client", prefill_client.close)
            prof.add_closer("disagg-config", disagg_cfg.close)

            async def _close_plane_client(h=disagg_handler):
                h.plane_client.close()

            prof.add_closer("plane-client", _close_plane_client)
        else:
            handler = engine.handler()
        endpoint = (runtime.namespace(None).component(args.component)
                    .endpoint(args.endpoint))
        server = await endpoint.serve_endpoint(handler,
                                               graceful_shutdown=False)
        prof.add_server(server)
        await register_llm(
            runtime, endpoint, model_name, tokenizer,
            context_length=engine_cfg.max_model_len,
            kv_cache_block_size=engine_cfg.page_size,
            migration_limit=args.migration_limit,
            tool_call_parser=args.tool_call_parser,
            reasoning_parser=args.reasoning_parser,
            runtime_config=ModelRuntimeConfig(
                total_kv_blocks=engine.runner.num_pages,
                max_num_seqs=engine_cfg.max_num_seqs,
                # The frontend's audio encoder projects to this width
                # (mm_embeds spans must match the model hidden size).
                # expected_roofline_frac: the perf expectation doctor
                # compares live perf_roofline_frac against.
                extra={"hidden_size": engine_cfg.model.hidden_size,
                       "expected_roofline_frac":
                           engine_cfg.expected_roofline_frac}))
        prof.add_closer("model-card",
                        lambda: deregister_llm(runtime, model_name))
        # LoRA adapters register as served names riding THIS endpoint
        # (adapter-aware model cards: the frontend resolves the OpenAI
        # model field to (base, adapter) from the card's extras). They
        # deregister with the base card on drains/role flips — a
        # prefill-only worker must not advertise adapter names either.
        for lname in lora_names:
            await register_adapter(
                runtime, endpoint, lname, model_name, tokenizer,
                context_length=engine_cfg.max_model_len,
                kv_cache_block_size=engine_cfg.page_size,
                migration_limit=args.migration_limit,
                tool_call_parser=args.tool_call_parser,
                reasoning_parser=args.reasoning_parser,
                runtime_config=ModelRuntimeConfig(
                    total_kv_blocks=engine.runner.num_pages,
                    max_num_seqs=engine_cfg.max_num_seqs,
                    extra={"hidden_size": engine_cfg.model.hidden_size}))
            prof.add_closer(f"adapter-card-{lname}",
                            lambda n=lname: deregister_llm(runtime, n))
        return prof

    return build


async def run(args: argparse.Namespace) -> None:
    cfg = RuntimeConfig.from_settings()
    if args.coordinator_url:
        cfg.coordinator_url = args.coordinator_url
    if args.namespace:
        cfg.namespace = args.namespace
    # Multi-host SINGLE engine (one jax.distributed mesh spanning hosts):
    # gated on JAX_COORDINATOR_ADDRESS + --num-nodes. Must initialize
    # before any JAX backend use.
    mh_addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    multihost_engine = args.num_nodes > 1 and bool(mh_addr)
    if multihost_engine:
        from dynamo_tpu.engine import multihost
        multihost.initialize(mh_addr, args.num_nodes, args.node_rank)
    runtime = await DistributedRuntime.from_settings(cfg)
    try:
        engine_cfg = build_engine_config(args)
        model_name = args.model_name or engine_cfg.model.name
        ckpt = args.resolved_checkpoint
        if args.tokenizer:
            tokenizer = Tokenizer.from_file(args.tokenizer)
        elif ckpt is not None:
            tokenizer = Tokenizer.from_pretrained_dir(ckpt)
        else:
            tokenizer = make_test_tokenizer()
        ns = cfg.namespace
        kv_pub = KvEventPublisher(runtime, ns, args.component,
                                  runtime.instance_id)
        metrics_pub = WorkerMetricsPublisher(runtime, ns, args.component,
                                             runtime.instance_id)
        inventory_pub = KvInventoryPublisher(runtime, ns, args.component,
                                             runtime.instance_id)
        def build_engine() -> TPUEngine:
            params = None
            if ckpt is not None:
                from dynamo_tpu.engine.weights import load_hf_weights
                params = load_hf_weights(engine_cfg.model, ckpt)
            return TPUEngine(engine_cfg, params=params, kv_publisher=kv_pub,
                             metrics_publisher=metrics_pub,
                             metrics_registry=runtime.metrics.namespace(ns)
                             .component(args.component))

        mh_group = (args.mh_group
                    or f"eng-{engine_cfg.model.name}").replace("/", "-")
        if multihost_engine and args.node_rank > 0:
            # SPMD follower: replay the leader's dispatch stream on this
            # host's shard of the global mesh. No registration, no HTTP.
            from dynamo_tpu.engine import multihost
            params = None
            if ckpt is not None:
                from dynamo_tpu.engine.weights import load_hf_weights
                params = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: load_hf_weights(engine_cfg.model, ckpt))
            print(f"TPU_FOLLOWER_READY rank={args.node_rank}", flush=True)
            await multihost.run_follower(
                engine_cfg, runtime.require_coordinator(), mh_group,
                args.node_rank, params=params)
            return

        if args.num_nodes > 1 and not multihost_engine:
            # Multi-node worker GROUP: each host runs its own single-host
            # mesh (a dp-style replica set) and the leader/worker barrier
            # coordinates bring-up — every host must agree on the model +
            # mesh shape before any of them starts serving (reference
            # multi-node bootstrap, leader_worker_barrier.rs). For a
            # SINGLE engine spanning hosts, set JAX_COORDINATOR_ADDRESS:
            # rank 0 serves through engine/multihost.LeaderRunner and the
            # other ranks replay its dispatch stream (handled above).
            from dynamo_tpu.runtime.barrier import (LeaderBarrier,
                                                    WorkerBarrier)
            client = runtime.require_coordinator()
            bid = f"engine-{model_name}"
            shape = {"model": model_name, "tp": args.tp, "pp": args.pp,
                     "sp": args.sp, "dp": args.dp}
            if args.node_rank == 0:
                peers = await LeaderBarrier(
                    client, bid, args.num_nodes - 1).sync(shape)
                log.info("multi-node group assembled: leader + %d peers",
                         len(peers))
            else:
                leader = await WorkerBarrier(
                    client, bid, str(args.node_rank)).sync(shape)
                if leader != shape:
                    raise SystemExit(
                        f"node {args.node_rank} config {shape} does not "
                        f"match leader {leader}")
        loras = _lora_args(args)
        if multihost_engine and loras:
            raise SystemExit(
                "--lora is not supported with a multi-host single engine "
                "yet: adapter hot-loads are not in the follower replay "
                "stream (engine/multihost.py)")
        # Engine construction blocks for seconds (weight load + sharded
        # device_put + first compiles); run it off the event loop so the
        # coordinator lease keepalives keep flowing.
        engine = await asyncio.get_running_loop().run_in_executor(
            None, build_engine)
        if loras:
            # Host-side parse/pad/stack only (device uploads happen
            # lazily on the engine thread at first use): off the loop so
            # large checkpoints don't stall lease keepalives.
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: [engine.register_adapter(n, path=p)
                               for n, p in loras])
        if multihost_engine:
            # Leader: publish every device call to the follower replay
            # stream, and hold serving until every follower is listening.
            from dynamo_tpu.engine import multihost
            engine.runner = multihost.LeaderRunner(
                engine.runner, runtime.require_coordinator(),
                asyncio.get_running_loop(), mh_group)
            await multihost.leader_barrier(
                runtime.require_coordinator(), mh_group, args.num_nodes - 1,
                {"model": engine_cfg.model.name,
                 "mesh": [args.dp, args.pp, args.sp, args.tp],
                 # Followers adopt the leader's ACTUAL pool size so
                 # auto-sizing can never diverge across hosts.
                 "num_pages": engine.runner.num_pages})
            log.info("multihost leader: %d followers in lockstep",
                     args.num_nodes - 1)
        from dynamo_tpu.llm.disagg import PREFILL_COMPONENT
        prefill_component = args.prefill_component or PREFILL_COMPONENT
        # Direct KV data plane (the NIXL role): every worker runs the
        # server side — prefill workers stage parcels on it, and any
        # worker with host tiers serves G4 remote-tier block fetches.
        plane = None
        peer_watch_task = None
        if not args.no_kv_plane:
            from dynamo_tpu.llm.kv_plane import (KvPlaneServer,
                                                 RemoteBlockSource)
            plane = KvPlaneServer(
                host=args.kv_plane_host,
                block_provider=(engine.host_cache.get
                                if engine.host_cache is not None else None))
            plane.start()
            engine.plane = plane  # /debug/kv + dynamo_tpu_kv_plane_* stats
            coordinator = runtime.require_coordinator()
            await coordinator.kv_put(
                f"kvplane/{cfg.namespace}/{runtime.instance_id:x}",
                {"addr": plane.address, "model": model_name},
                lease_id=coordinator.primary_lease_id)
            # G4 remote tier: watch peer plane registrations so prefix
            # extensions can onboard blocks a PEER's host tier holds
            # instead of recomputing (engine._try_onboard). Short-timeout
            # client: the consult runs on the engine thread.
            engine.remote_source = RemoteBlockSource(self_addr=plane.address)
            peer_watch = await coordinator.watch_prefix(
                f"kvplane/{cfg.namespace}/")
            peers: dict[str, str] = {
                item["k"]: item["v"]["addr"]
                for item in peer_watch.snapshot
                if item["v"].get("model") == model_name}
            engine.remote_source.peers = [a for a in peers.values()
                                          if a != plane.address]

            async def watch_peers() -> None:
                # Must not die silently: a frozen peer list both misses
                # new workers and keeps feeding dead addresses to the G4
                # consult. On watch failure, log and re-establish.
                watch = peer_watch
                while True:
                    try:
                        async for event in watch:
                            if event["event"] == "put" and \
                                    event["value"].get("model") == model_name:
                                peers[event["key"]] = event["value"]["addr"]
                            elif event["event"] == "delete":
                                gone = peers.pop(event["key"], None)
                                if gone is not None:
                                    # worker_leave/scale-in: drop the
                                    # peer AND its breaker state now,
                                    # not at staleness TTL.
                                    engine.remote_source.drop_peer(gone)
                            engine.remote_source.peers = [
                                a for a in peers.values()
                                if a != plane.address]
                    except asyncio.CancelledError:
                        raise
                    except Exception:  # noqa: BLE001 — log and re-watch
                        log.exception("kvplane peer watch failed; retrying")
                    await asyncio.sleep(2.0)
                    try:
                        watch = await coordinator.watch_prefix(
                            f"kvplane/{cfg.namespace}/")
                        peers.clear()
                        peers.update({
                            item["k"]: item["v"]["addr"]
                            for item in watch.snapshot
                            if item["v"].get("model") == model_name})
                        engine.remote_source.peers = [
                            a for a in peers.values() if a != plane.address]
                    except (ConnectionError, OSError):
                        log.warning("kvplane peer re-watch failed; will "
                                    "retry")

            peer_watch_task = asyncio.create_task(watch_peers())
        if args.prefill_dispatch == "queue" and args.no_kv_plane:
            raise SystemExit(
                "--prefill-dispatch queue needs the KV data plane "
                "(queue replies carry plane tickets); drop "
                "--no-kv-plane or use --prefill-dispatch direct")
        from dynamo_tpu.llm.reconfig import RoleManager
        from dynamo_tpu.llm.standby import ScaleAgent
        roles = RoleManager(
            runtime,
            make_profile_builder(runtime, args, engine, engine_cfg,
                                 tokenizer, model_name, plane,
                                 prefill_component),
            role=args.mode,
            status_extra={"backend": "tpu", "model": model_name})
        # Autoscaling (llm/standby.py): every worker answers scale
        # directives (retire drains it out); --standby parks it warm
        # and deregistered until the planner promotes it. The engine is
        # already built — weights loaded, warmup done — so the promote
        # pays only registration, not cold start.
        scale_agent = ScaleAgent(
            runtime, roles, standby=args.standby,
            status_extra={"backend": "tpu", "model": model_name},
            metrics=runtime.metrics)
        # Fleet inventory digests (KV & capacity plane): published from
        # the engine loop alongside KV events + ForwardPassMetrics, with
        # a periodic republish so an idle worker still shows up.
        engine.inventory_publisher = inventory_pub
        # Warm up BEFORE registering: a program that cannot compile fails
        # the worker here (wait_ready raises), not a routed request.
        engine.start()
        await asyncio.get_running_loop().run_in_executor(
            None, engine.wait_ready)
        if not args.standby:
            await roles.start()
        inventory_pub.start_periodic(engine.inventory_digest)
        # Observability plane (docs/OBSERVABILITY.md): flight-recorder
        # bundle context for THIS worker, and the per-worker system
        # status server (DTPU_SYSTEM_ENABLED=1) serving /metrics +
        # /debug/{traces,slo,requests,flight,kv} next to the engine.
        import dataclasses as _dc

        from dynamo_tpu.runtime import flight as _flight
        from dynamo_tpu.runtime import journal as _journal
        from dynamo_tpu.runtime import slo as _slo
        _flight.configure(metrics=runtime.metrics,
                          config_fingerprint=_dc.asdict(cfg))
        _slo.configure(cfg.slo, metrics=runtime.metrics).on_page(
            _flight.on_slo_page)
        # Decision plane (runtime/journal.py): this worker's preempts,
        # role-flip edges, and chaos injections ride the event plane
        # into the frontend's merged /debug/timeline.
        _journal.configure(worker=f"{runtime.instance_id:x}",
                           metrics=runtime.metrics)
        journal_pub = _journal.JournalPublisher(
            runtime.require_coordinator(), cfg.namespace,
            f"{runtime.instance_id:x}")
        journal_pub.start_periodic()
        # After journal.configure: the standby_ready event must carry
        # this worker's id, not the "proc" placeholder.
        await scale_agent.start()
        status_server = None
        if cfg.system_enabled:
            from dynamo_tpu.llm.fleet import register_status_server
            from dynamo_tpu.runtime.health import SystemStatusServer
            status_server = SystemStatusServer(runtime, host=cfg.bind_host,
                                               port=cfg.system_port,
                                               role_manager=roles,
                                               kv_provider=engine.kv_status,
                                               perf_provider=engine.perf_status,
                                               scale_agent=scale_agent)
            await status_server.start()
            # Advertise for the frontend's /debug/fleet fan-out
            # (lease-bound: the entry dies with this worker).
            await register_status_server(
                runtime, status_server.port,
                extra={"backend": "tpu", "component": args.component,
                       "model": model_name})
        port = (roles.profile.servers[0].port
                if roles.profile and roles.profile.servers else 0)
        mode = "standby" if args.standby else args.mode
        print(f"TPU_WORKER_READY mode={mode} port={port} "
              f"worker={runtime.instance_id:x} pages={engine.runner.num_pages}",
              flush=True)
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, runtime.shutdown)
            except NotImplementedError:
                pass
        await runtime.wait_for_shutdown()
        journal_pub.stop_periodic()
        inventory_pub.stop_periodic()
        engine.stop()
        if multihost_engine:
            # Engine loop is drained — no more dispatches can race this.
            from dynamo_tpu.engine import multihost
            try:
                # Surface a transport failure on the LAST dispatch (acks
                # are pipelined one behind) before declaring clean stop.
                pending = engine.runner.pending_ack()
                if pending is not None:
                    await asyncio.wrap_future(pending)
                await runtime.require_coordinator().publish(
                    multihost.DISPATCH_SUBJECT.format(group=mh_group),
                    {"m": "stop"})
            except (ConnectionError, OSError):
                # Coordinator already gone (whole-deployment teardown);
                # followers exit with it.
                pass
        # The role manager owns the serving profile: endpoint servers and
        # role-specific machinery (queue workers, disagg clients/watches)
        # all tear down through it, whatever role we ended up in.
        await scale_agent.stop()
        await roles.stop()
        if status_server is not None:
            await status_server.stop()
        if peer_watch_task is not None:
            peer_watch_task.cancel()
        if plane is not None:
            if engine.remote_source is not None:
                engine.remote_source.client.close()
            plane.close()
    finally:
        await runtime.close()


def main() -> None:
    asyncio.run(run(parse_args()))


if __name__ == "__main__":
    main()
