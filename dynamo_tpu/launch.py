"""Unified launcher: one process, pluggable input and output.

Capability parity with reference dynamo-run (launch/dynamo-run/src/
lib.rs:19-92, input adapters entrypoint/input/{http,grpc,text,batch}.rs):
``python -m dynamo_tpu.launch in=<http|grpc|text|batch> out=<tpu|
mocker|echo> [--model ...]`` assembles the whole pipeline statically —
tokenizer, preprocessor, detokenizing backend, engine — with no
coordinator, no registration, no network hop between frontend and engine.
``out=dyn`` connects to a coordinator instead and serves whatever workers
register (the distributed mode the separate frontend/worker mains also
provide).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher, ServedModel
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import (DEFAULT_CHAT_TEMPLATE,
                                       ModelDeploymentCard, ModelEntry)
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.llm.protocols import ChatCompletionRequest
from dynamo_tpu.llm.tokenizer import Tokenizer, make_test_tokenizer
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import startup_stage

log = get_logger("launch")


def parse_args(argv=None) -> argparse.Namespace:
    argv = list(sys.argv[1:] if argv is None else argv)
    io = {"in": "http", "out": "tpu"}
    rest = []
    for a in argv:
        if a.startswith("in=") or a.startswith("out="):
            k, v = a.split("=", 1)
            io[k] = v
        else:
            rest.append(a)
    parser = argparse.ArgumentParser(
        description="dynamo-tpu unified launcher (in=http|text "
                    "out=tpu|mocker|echo|dyn)")
    parser.add_argument("--model", default="tiny-test")
    parser.add_argument("--model-name", default=None)
    parser.add_argument("--tokenizer", default=None)
    parser.add_argument("--http-host", default="0.0.0.0")
    parser.add_argument("--http-port", type=int, default=8000)
    parser.add_argument("--num-pages", type=int, default=None)
    parser.add_argument("--max-num-seqs", type=int, default=32)
    parser.add_argument("--context-length", type=int, default=8192)
    # Engine knobs shared with the worker (backends.tpu.build_engine_config).
    from dynamo_tpu.backends.tpu import _auto_or_positive
    parser.add_argument("--page-size", default="auto",
                        type=_auto_or_positive,
                        help="tokens per KV page: a positive int or 'auto' "
                             "(16; where the Pallas kernel reads the pool "
                             "on one TPU device, the page whose one copy "
                             "moves 64 KB)")
    parser.add_argument("--max-pages-per-seq", type=int, default=None,
                        help="default: what holds 8192 tokens at the page "
                             "size")
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument("--pp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--attention-backend", default="auto",
                        choices=["auto", "pallas", "xla"],
                        help="decode attention: 'auto' runs the Pallas "
                             "paged kernel on one TPU device at head_dim "
                             "128 and the XLA gather everywhere else (CPU, "
                             "a mesh, smaller heads); an explicit "
                             "'pallas' that cannot be had is an error")
    parser.add_argument("--decode-window", default="auto",
                        type=_auto_or_positive,
                        help="positive int or 'auto' (size from the model's "
                             "weight-read step estimate)")
    parser.add_argument("--pipeline-depth", type=int, default=4)
    parser.add_argument("--prefill-chunk-tokens", default="auto",
                        type=_auto_or_positive,
                        help="stall-free chunked prefill budget per "
                             "engine-loop iteration (int or 'auto')")
    parser.add_argument("--warmup-prefill-ladder", action="store_true",
                        help="pre-compile every prefill bucket (incl. "
                             "chunk/history variants) at startup")
    parser.add_argument("--quant", default=None, choices=["int8"],
                        help="weight-only int8 quantization (halves "
                             "weight HBM reads)")
    parser.add_argument("--quant-kv", default=None, choices=["int8"],
                        help="int8 KV cache: ~2x pages per HBM GB, "
                             "dequant fused into attention; composes "
                             "with --quant (DTPU_QUANT_KV overrides)")
    parser.add_argument("--host-cache-pages", type=int, default=0)
    parser.add_argument("--kv-disk-cache-dir", default=None)
    # The worker's own two options (backends/tpu.py), handed through.
    parser.add_argument("--spec-decode", default=None,
                        choices=["ngram", "mtp"],
                        help="out=tpu: speculative decoding, as the "
                             "worker's --spec-decode ('mtp': the model's "
                             "own prediction module drafts inside the "
                             "window program)")
    parser.add_argument("--spec-k", type=int, default=3,
                        help="out=tpu: drafts verified per step")
    parser.add_argument("--lora", action="append", default=[],
                        metavar="NAME=PATH",
                        help="out=tpu: serve a LoRA adapter as its own "
                             "model name on the in-process engine "
                             "(HF PEFT checkpoint dir; repeatable)")
    parser.add_argument("--max-adapters", type=int, default=None)
    parser.add_argument("--max-lora-rank", type=int, default=8)
    parser.add_argument("--coordinator-url", default=None,
                        help="out=dyn: control plane to discover workers on")
    parser.add_argument("--tool-call-parser", default=None)
    parser.add_argument("--reasoning-parser", default=None)
    parser.add_argument("--input-file", default=None,
                        help="in=batch: JSONL of prompts ({'prompt': ...} or "
                             "{'messages': [...]}, optional max_tokens)")
    parser.add_argument("--output-file", default=None,
                        help="in=batch: JSONL results path "
                             "(default <input-file>.results.jsonl)")
    parser.add_argument("--batch-concurrency", type=int, default=8,
                        help="in=batch: max in-flight requests")
    parser.add_argument("--batch-max-tokens", type=int, default=128,
                        help="in=batch: default max_tokens per prompt")
    # SLO plane + per-request accounting (runtime/slo.py,
    # docs/OBSERVABILITY.md); fine-grained knobs via DTPU_SLO_*.
    parser.add_argument("--slo-ttft-p99-ms", type=float, default=None,
                        help="TTFT SLO target (99%% within this budget)")
    parser.add_argument("--request-log", default=None,
                        help="append per-request accounting records as "
                             "JSONL here (scripts/slo_report.py)")
    args = parser.parse_args(rest)
    args.input = io["in"]
    args.output = io["out"]
    if args.input not in ("http", "grpc", "text", "batch"):
        parser.error(f"in= must be http|grpc|text|batch, got {args.input!r}")
    if args.input == "batch" and not args.input_file:
        parser.error("in=batch requires --input-file")
    if args.output not in ("tpu", "mocker", "echo", "dyn"):
        parser.error(f"out= must be tpu|mocker|echo|dyn, got {args.output!r}")
    return args


def _build_engine(args, metrics_registry=None):
    if args.output == "echo":
        from dynamo_tpu.llm.engines import EchoEngine
        return EchoEngine(token_delay_s=0.005), make_test_tokenizer()
    if args.output == "mocker":
        from dynamo_tpu.llm.mocker import MockerConfig, MockerEngine
        eng = MockerEngine(MockerConfig(speedup_ratio=10.0))
        eng.start()
        return eng, make_test_tokenizer()
    # out=tpu: the real engine, in-process. Each step is a stage of the
    # start-up trace (runtime/tracing.py Startup; a no-op for a caller that
    # opened none); the runner's and the engine thread's stages lie under
    # startup.engine and beside it.
    with startup_stage("startup.config"):
        from dynamo_tpu.backends.tpu import build_engine_config
        from dynamo_tpu.engine import perf
        from dynamo_tpu.engine.engine import TPUEngine
        from dynamo_tpu.engine.weights import load_hf_weights
        # What is built from here to the engine's mark_ready is this
        # start's, also in a process that served before.
        perf.get_registry().mark_starting()
        cfg = build_engine_config(args)
    ckpt = args.resolved_checkpoint
    params = None
    if ckpt is not None:
        with startup_stage("startup.checkpoint", source="checkpoint",
                           path=str(ckpt)):
            params = load_hf_weights(cfg.model, ckpt)
    with startup_stage("startup.tokenizer"):
        if ckpt is not None:
            tokenizer = Tokenizer.from_pretrained_dir(ckpt)
        elif args.tokenizer:
            tokenizer = Tokenizer.from_file(args.tokenizer)
        else:
            tokenizer = make_test_tokenizer()
    with startup_stage("startup.engine"):
        engine = TPUEngine(cfg, params=params,
                           metrics_registry=metrics_registry)
    engine.start()
    # The warm-up runs on the engine thread (startup.warmup); this thread
    # waits for it, and for the device to finish the warm-up's runs.
    with startup_stage("startup.wait_ready"):
        engine.wait_ready()  # a warm-up or compile failure fails the launch
    return engine, tokenizer


def build_local_served(args, metrics_registry=None
                       ) -> tuple[ServedModel, object]:
    """Static pipeline: Preprocessor -> Backend -> engine, no network.
    With ``--lora``, the adapters register on the engine and each
    adapter name becomes its own ServedModel (attached as
    ``served.adapter_served``) whose card carries the (base, adapter)
    binding — the same resolution the distributed frontend does from
    discovered cards."""
    if getattr(args, "lora", None) and args.output != "tpu":
        raise SystemExit("--lora needs the real engine (out=tpu)")
    engine, tokenizer = _build_engine(args, metrics_registry)
    with startup_stage("startup.model_card"):
        return _local_served(args, engine, tokenizer), engine


def _local_served(args, engine, tokenizer) -> ServedModel:
    """The card, the preprocessor and the adapters' models over an engine."""
    name = args.model_name or os.path.basename(args.model.rstrip("/"))
    card = ModelDeploymentCard(
        name=name, chat_template=DEFAULT_CHAT_TEMPLATE,
        context_length=args.context_length,
        tool_call_parser=args.tool_call_parser,
        reasoning_parser=args.reasoning_parser)
    entry = ModelEntry(model_name=name, namespace="local", component="local",
                       endpoint="generate", model_type="chat", card=card)
    backend = Backend(tokenizer, inner=engine)
    pre = OpenAIPreprocessor(card, tokenizer, inner=backend)
    served = ServedModel(entry, pre, client=None, router=None)
    served.adapter_served = []
    for item in getattr(args, "lora", None) or []:
        lname, sep, path = str(item).partition("=")
        if not sep or not lname or not path:
            raise SystemExit(f"--lora expects NAME=PATH, got {item!r}")
        engine.register_adapter(lname, path=path)
        from dynamo_tpu.llm.model_card import ModelRuntimeConfig
        acard = ModelDeploymentCard(
            name=lname, chat_template=DEFAULT_CHAT_TEMPLATE,
            context_length=args.context_length,
            tool_call_parser=args.tool_call_parser,
            reasoning_parser=args.reasoning_parser,
            runtime_config=ModelRuntimeConfig(
                extra={"lora_base": name, "adapter": lname}))
        aentry = ModelEntry(model_name=lname, namespace="local",
                            component="local", endpoint="generate",
                            model_type="chat", card=acard)
        apre = OpenAIPreprocessor(acard, tokenizer, inner=backend)
        served.adapter_served.append(
            ServedModel(aentry, apre, client=None, router=None))
    return served


async def run_text_repl(served: ServedModel) -> None:
    """in=text: an interactive prompt loop on stdin (dynamo-run's text
    input)."""
    loop = asyncio.get_running_loop()
    print("dynamo-tpu text console — empty line or EOF exits", flush=True)
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line or not line.strip():
            return
        req = ChatCompletionRequest(
            model=served.name,
            messages=[{"role": "user", "content": line.strip()}],
            max_tokens=64, stream=True)
        async for chunk in served.preprocessor.generate(req, Context()):
            for choice in chunk.get("choices", []):
                piece = choice.get("delta", {}).get("content")
                if piece:
                    print(piece, end="", flush=True)
        print(flush=True)


async def run_batch(served: ServedModel, args) -> None:
    """in=batch: run a JSONL file of prompts through the pipeline with
    bounded concurrency, write one JSONL result per prompt (reference
    entrypoint/input/batch.rs: file of prompts -> completions + timing)."""
    import json
    import time

    jobs = []
    # One-shot batch-mode input read before any generation task exists;
    # nothing else shares the loop yet.
    # dtpu: ignore[blocking-call-in-async] -- one-shot startup I/O
    with open(args.input_file, "r", encoding="utf-8") as fh:
        for line in fh:
            if not line.strip():
                continue
            try:
                jobs.append(json.loads(line))
            except json.JSONDecodeError as exc:
                jobs.append(ValueError(f"unparseable JSONL line: {exc}"))
    out_path = args.output_file or args.input_file + ".results.jsonl"
    sem = asyncio.Semaphore(args.batch_concurrency)

    async def one(idx: int, job) -> dict:
        # Per-job isolation: a malformed line or a failed generation yields
        # an error row instead of losing the rest of the batch.
        try:
            if isinstance(job, Exception):
                raise job
            if not isinstance(job, dict):
                raise ValueError(f"line is {type(job).__name__}, "
                                 "expected a JSON object")
            messages = job.get("messages") or [
                {"role": "user", "content": job.get("prompt", "")}]
            req = ChatCompletionRequest(
                model=served.name, messages=messages,
                max_tokens=int(job.get("max_tokens", args.batch_max_tokens)),
                temperature=job.get("temperature", 0.0), stream=True,
                stream_options={"include_usage": True})
            text, n_tokens, finish = [], 0, None
            async with sem:
                t0 = time.monotonic()
                t_first = None
                async for chunk in served.preprocessor.generate(req,
                                                                Context()):
                    # Token counts come from the usage block (detokenizer
                    # delta chunks are not 1:1 with tokens).
                    usage = chunk.get("usage")
                    if usage:
                        n_tokens = usage.get("completion_tokens", n_tokens)
                    for choice in chunk.get("choices", []):
                        piece = choice.get("delta", {}).get("content")
                        if piece:
                            if t_first is None:
                                t_first = time.monotonic()
                            text.append(piece)
                        if choice.get("finish_reason"):
                            finish = choice["finish_reason"]
                elapsed = time.monotonic() - t0
            return {"index": idx, "text": "".join(text),
                    "finish_reason": finish, "tokens": n_tokens,
                    "elapsed_s": round(elapsed, 4),
                    "ttft_s": round((t_first or t0) - t0, 4)}
        except Exception as exc:  # noqa: BLE001 — keep the batch going
            return {"index": idx, "error": f"{type(exc).__name__}: {exc}",
                    "tokens": 0}

    t0 = time.monotonic()
    results = await asyncio.gather(*[one(i, j) for i, j in enumerate(jobs)])
    elapsed = time.monotonic() - t0
    # dtpu: ignore[blocking-call-in-async] -- results dump after the batch
    with open(out_path, "w", encoding="utf-8") as fh:
        for r in results:
            fh.write(json.dumps(r) + "\n")
    total_tokens = sum(r["tokens"] for r in results)
    n_errors = sum(1 for r in results if "error" in r)
    print(json.dumps({
        "batch_prompts": len(jobs), "errors": n_errors,
        "output_tokens": total_tokens,
        "elapsed_s": round(elapsed, 3),
        "tok_s": round(total_tokens / elapsed, 1) if elapsed else 0.0,
        "results": out_path}), flush=True)


async def run(args, ready=None) -> None:
    """Assemble and serve until shutdown. ``ready(runtime, service,
    engine)`` is called once the HTTP service listens (an embedding
    caller — chip_smoke.py, a test — gets the bound port and the handle
    to ``runtime.shutdown()`` without scraping stdout).

    The start is ONE trace (runtime/tracing.py ``Startup``): the root
    ``startup`` from here to the instant the engine is ready and the
    service listens, a stage where each step's work happens; a start that
    raises closes the root with ``status="error"`` and the stage."""
    start = tracing.begin_startup()
    runtime = engine = watcher = None
    try:
        with start.stage("startup.runtime"):
            if args.output == "dyn":
                cfg = RuntimeConfig.from_settings()
                if args.coordinator_url:
                    cfg.coordinator_url = args.coordinator_url
                runtime = await DistributedRuntime.from_settings(cfg)
                manager = ModelManager()
                watcher = ModelWatcher(runtime, manager)
                await watcher.start()
            else:
                runtime = await DistributedRuntime.detached(RuntimeConfig())
                manager = ModelManager()
        if args.output != "dyn":
            served, engine = build_local_served(
                args,
                runtime.metrics.namespace("local").component(args.output))
            manager.models[served.name] = served
            for extra in getattr(served, "adapter_served", []):
                manager.models[extra.name] = extra
        # SLO plane + accounting ledger + flight-bundle context: the static
        # pipeline gets the same decision-grade observability the
        # distributed frontend does (DTPU_SLO_* / [slo] TOML configurable).
        with start.stage("startup.observability"):
            from dynamo_tpu.frontend.main import init_observability
            if args.slo_ttft_p99_ms is not None:
                runtime.config.slo.ttft_p99_ms = args.slo_ttft_p99_ms
            if args.request_log is not None:
                runtime.config.slo.request_log_path = args.request_log
            init_observability(runtime.config, runtime)
        if args.input in ("text", "batch"):
            if args.output == "dyn":
                raise SystemExit(f"in={args.input} requires a local out= "
                                 "engine")
            _started(start, args, engine)
            if args.input == "text":
                await run_text_repl(served)
            else:
                await run_batch(served, args)
            return
        if args.input == "grpc":
            with start.stage("startup.grpc"):
                from dynamo_tpu.grpc.kserve import make_server
                server, port = make_server(manager, host=args.http_host,
                                           port=args.http_port)
                await server.start()
            _started(start, args, engine)
            print(f"LAUNCH_READY in=grpc out={args.output} port={port}",
                  flush=True)
            await runtime.wait_for_shutdown()
            await server.stop(grace=1.0)
            return
        with start.stage("startup.http"):
            # Overload defense (runtime/overload.py): same adaptive
            # admission the distributed frontend gets, DTPU_OVERLOAD_*
            # configurable (DTPU_OVERLOAD_ENABLED=0 disables).
            from dynamo_tpu.runtime.overload import AdaptiveLimiter
            ov = runtime.config.overload
            limiter = (AdaptiveLimiter(ov, metrics=runtime.metrics)
                       if ov.enabled else None)
            service = HttpService(runtime, manager, host=args.http_host,
                                  port=args.http_port, overload=limiter)
            await service.start()
        _started(start, args, engine)
        print(f"LAUNCH_READY in={args.input} out={args.output} "
              f"port={service.port}", flush=True)
        if ready is not None:
            ready(runtime, service, engine)
        await runtime.wait_for_shutdown()
        await service.stop()
    except BaseException as exc:
        if start.open:      # raised before ready: the start failed
            _started(start, args, None, error=exc)
        raise
    finally:
        if watcher is not None:
            await watcher.stop()
        if engine is not None:
            stop = getattr(engine, "stop", None)
            if stop is not None:
                res = stop()
                if asyncio.iscoroutine(res):
                    await res
        if runtime is not None:
            await runtime.close()


def _started(start: tracing.Startup, args, engine, error=None) -> None:
    """Ready (or failed): close the root, say where the start's seconds
    went in ONE line, and put the stages on /metrics."""
    start.finish(error)
    programs = ""
    if args.output == "tpu":    # the compile registry's first calls
        from dynamo_tpu.engine import perf
        programs = perf.describe_first_calls()
    (log.info if error is None else log.error)(
        "%s", start.ready_line(programs))
    updater = getattr(engine, "perf_metrics", None)
    if updater is not None:
        updater.update(engine, force=True)


def main() -> None:
    asyncio.run(run(parse_args()))


if __name__ == "__main__":
    main()
