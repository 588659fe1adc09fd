#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one owner of the chip. Drives the main path once through the
entry points a user would call, at the full width and depth of one supported
model (qwen2.5-0.5b, int8 weights, bf16 KV, random weights from the
program's init seed), and checks what comes out by the repo's own means:

  preflight  doctor's device + native checks, peaks, compile cache, memory
  server     ``python -m dynamo_tpu.launch in=http out=tpu --model
             qwen2.5-0.5b --quant int8`` started inside this process; real
             HTTP requests (plain, streamed, concurrent, repeated prompt ->
             prefix-cache hit through the with-history prefill, a prompt
             past the first prefill buckets, seeded sampling twice)
  reference  served greedy logprobs vs the plain forward the tests use
             (prefill_forward/decode_forward), teacher-forced, same weights
  kernels    attention_backend="pallas" (bf16 and int8 KV) vs XLA on engines
             with a small chunk size, so scheduled chunked prefill runs too,
             then "auto" on a small head_dim-128 model, which must resolve
             to the kernel on the chip, and on the Cohere2-MoE block at toy
             depth and Command A+'s head geometry (128 query heads over 8
             KV heads of 128, a share of the experts, a window the longest
             prompt passes: a page of 32 there), and on the DeepSeek-V3.2
             block at toy depth over a pool of latent entries (640 | 128
             lanes, a page of 64: the latent reader), then the same block
             with the published 64 index heads over prompts past the 2,048
             keys it keeps (the indexer's kernel: a row's live pages of
             index keys walked and scored, against XLA's gather of the
             bucket; select_topk chooses over either's scores), and on the
             Nemotron-H block at toy depth and its attention geometry (32
             query heads over 2 KV heads of 128, a page of 128; a pool of
             its ONE attention layer; Mamba-2 mixers whose float32 state
             lies by slot beside the pool, on the device, and is carried
             through chunked prefill and the windows; two-matrix relu2
             experts, a share of them: the grouped product in a 256-row
             chunk, the walk over the touched ones in the window), and on the Solar-Open2
             block at one period (* K K K; 64 delta-rule heads of 128 x
             128: the recurrence's second form, which reads the decayed
             state before it writes it, against XLA's ``delta_update``), and
             on the Falcon-H1 block (every layer a Mamba-2 mixer of 32 heads
             of 128 x 256, ONE head a copy of the kernel, AND rotary
             attention side by side on one normed input: a state a slot and
             pages a token in every layer);
             ``tpu_custom_call`` must be in the compiled window program
             wherever a kernel runs
  disagg     prefill engine -> KV plane -> decode engine on the one chip;
             tokens must equal the aggregated engine's

``--chips 4`` runs the path across chips and what it is compared with, and
no other phase: llama-3-8b-L8 (bf16) served at tp=4 (then tp=2 x dp=2) through
the same in-process HTTP path, against the same weights on one device.

Output: one JSON object per phase on stdout; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Any failed phase -> ``"ok": false`` and a non-zero exit. Without a TPU the
script refuses (non-zero exit, nothing on stdout) unless ``--rehearse-cpu``
is given: then it runs the same phases on tiny-test on the CPU backend and
its last line reports platform ``cpu`` and ``"rehearsal": true`` — never a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import faulthandler
import gc
import json
import os
import sys
import threading
import time

# What the engine emits for each request, recorded between the Backend and
# the engine: the OpenAI response carries token TEXT, and with a preset and
# no checkpoint the server detokenizes a 151,936-wide model with the
# 512-entry test tokenizer, so ids are only visible there. The benchmark's
# tap (it also stamps each emission); imports nothing of jax.
from benchmark.lib.server import EngineTap

# Served vs reference logprob agreement, in nats. bf16 keeps 8 mantissa bits:
# two correct programs that order a model's sums differently measured 0.001
# to 0.003 apart on the v5e at qwen2.5-0.5b (PR 21) and up to 0.03 on the
# sharper tiny-test model on the CPU, while a wrong context, page or position
# moves the greedy token's logprob by whole nats.
LOGPROB_ATOL = 0.1


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path (tp=4, tp=2 x dp=2) and "
                         "its one-device comparison")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same phases on tiny-test on the CPU "
                         "backend (never reports a TPU)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the prompts and of the seeded requests")
    ap.add_argument("--deadline-s", type=int, default=1150,
                    help="hard stop: dump every thread's stack and exit")
    return ap.parse_args(argv)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# Small helpers shared by the phases
# --------------------------------------------------------------------------

async def engine_generate(engine, prompt, max_tokens, **sampling):
    """Drive ``engine.generate`` directly; returns (tokens, logprobs)."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context
    req = PreprocessedRequest(model="smoke", token_ids=list(prompt))
    req.stop_conditions.max_tokens = max_tokens
    req.stop_conditions.ignore_eos = True
    req.sampling_options.logprobs = 0
    for key, val in sampling.items():
        setattr(req.sampling_options, key, val)
    tokens, lps = [], []
    async for out in engine.generate(req, Context()):
        tokens.extend(out.get("token_ids", []))
        lps.extend(out.get("log_probs") or [])
        if out.get("finish_reason"):
            break
    return tokens, lps


def reference_logprobs(params, spec, prompt, generated, device,
                       page: int = 16) -> list[float]:
    """Teacher-forced logprob of each generated token from the plain
    forward the tests use as reference (engine/model.py prefill_forward,
    then decode_forward per token) on a private little cache on ``device``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.model import decode_forward, prefill_forward

    n_prompt, n_gen = len(prompt), len(generated)
    bucket = 32
    while bucket < n_prompt:
        bucket *= 2
    maxp = -(-max(bucket, n_prompt + n_gen) // page)
    kv = jnp.zeros((spec.num_layers, spec.num_kv_heads, maxp + 1, page,
                    spec.head_dim), jnp.bfloat16, device=device)
    k, v = kv, kv + 0  # two buffers: both are donated below
    pages = np.arange(1, maxp + 1, dtype=np.int32)[None]  # page 0 = scratch

    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n_prompt] = prompt
    pos = np.minimum(np.arange(bucket), n_prompt - 1)[None].astype(np.int32)
    put = lambda a: jax.device_put(a, device)  # noqa: E731
    prefill = jax.jit(lambda p, k, v, t, po, pt, sl: prefill_forward(
        p, spec, k, v, t, po, pt, sl), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, k, v, t, po, pt, sl: decode_forward(
        p, spec, k, v, t, po, pt, sl), donate_argnums=(1, 2))

    def logprob_of(logits, token) -> float:
        row = np.asarray(logits[0], np.float64)
        lse = row.max() + np.log(np.exp(row - row.max()).sum())
        return float(row[token] - lse)

    logits, k, v = prefill(params, k, v, put(toks), put(pos),
                           put(pages[:, :bucket // page]),
                           put(np.asarray([n_prompt], np.int32)))
    out = [logprob_of(logits, generated[0])]
    for i in range(1, n_gen):
        at = n_prompt + i - 1  # position of the token fed this step
        logits, k, v = decode(
            params, k, v, put(np.asarray([generated[i - 1]], np.int32)),
            put(np.asarray([at], np.int32)), put(pages),
            put(np.asarray([at + 1], np.int32)))
        out.append(logprob_of(logits, generated[i]))
    return out


def max_abs_diff(a, b) -> float:
    return max((abs(x - y) for x, y in zip(a, b)), default=0.0)


def agreeing_prefix_diff(toks_a, lps_a, toks_b, lps_b) -> tuple[int, float]:
    """Greedy runs of two correct programs may part at a near-tie; compare
    logprobs up to and INCLUDING the first differing token (both are the
    max of nearly one distribution there), nothing after it."""
    n = 0
    for ta, tb in zip(toks_a, toks_b):
        n += 1
        if ta != tb:
            break
    return n, max_abs_diff(lps_a[:n], lps_b[:n])


def arrays_on(tree, platform: str) -> bool:
    import jax
    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def program_summary(snapshot: dict) -> dict:
    return {name: {"compiles": p["compiles"],
                   "seconds": p["compile_seconds"],
                   "unexpected": p["unexpected_recompiles"]}
            for name, p in snapshot["programs"].items() if p["compiles"]}


class Server:
    """The unified launcher's HTTP server, started inside this process."""

    def __init__(self, launch_argv: list[str]):
        self.launch_argv = launch_argv
        self.task = None
        self.runtime = self.service = self.engine = self.tap = None
        self.session = None
        self.startup_s = None

    async def __aenter__(self):
        from dynamo_tpu import launch
        largs = launch.parse_args(self.launch_argv)
        loop = asyncio.get_running_loop()
        ready = loop.create_future()
        t0 = time.monotonic()
        # launch.run prints its LAUNCH_READY line; stdout here is JSON only.
        with contextlib.redirect_stdout(sys.stderr):
            self.task = asyncio.create_task(launch.run(
                largs, ready=lambda *a: ready.set_result(a)))
            await asyncio.wait({self.task, ready},
                               return_when=asyncio.FIRST_COMPLETED)
        if not ready.done():
            self.task.result()  # raises what start-up raised
            raise SmokeFailure("launcher returned before it was ready")
        self.runtime, self.service, self.engine = ready.result()
        self.startup_s = time.monotonic() - t0
        try:
            return await self._attach(largs)
        except BaseException:
            await self.__aexit__()
            raise

    async def _attach(self, largs):
        import aiohttp
        served = self.service.manager.models[largs.model]
        backend = served.preprocessor.inner
        check(backend.inner is self.engine, "pipeline is not "
              "preprocessor -> backend -> engine")
        self.tap = backend.inner = EngineTap(self.engine)
        self.model = largs.model
        self.base = f"http://127.0.0.1:{self.service.port}"
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=600))
        for _ in range(100):
            async with self.session.get(self.base + "/health") as resp:
                if resp.status == 200:
                    return self
            await asyncio.sleep(0.1)
        raise SmokeFailure("/health never answered 200")

    async def __aexit__(self, *exc):
        if self.session is not None:
            await self.session.close()
        if self.runtime is not None:
            self.runtime.shutdown()  # launch.run stops service + engine
        if self.task is not None:
            await self.task
        # Drop the engine with the server: its KV pool is most of the chip.
        self.runtime = self.service = self.engine = self.tap = None

    async def get(self, path: str) -> dict:
        async with self.session.get(self.base + path) as resp:
            check(resp.status == 200, f"GET {path} -> {resp.status}")
            return await resp.json()

    async def chat(self, content: str, max_tokens: int, **extra) -> dict:
        """Non-streamed /v1/chat/completions. Returns the response body
        plus ``_tap`` (what the engine emitted) and ``_total_s``."""
        body = {"model": self.model, "max_tokens": max_tokens,
                "messages": [{"role": "user", "content": content}],
                "temperature": 0.0, "ignore_eos": True, **extra}
        n_before = len(self.tap.calls)
        t0 = time.monotonic()
        async with self.session.post(self.base + "/v1/chat/completions",
                                     json=body) as resp:
            text = await resp.text()
            check(resp.status == 200, f"chat -> {resp.status}: {text[:300]}")
        out = json.loads(text)
        out["_total_s"] = time.monotonic() - t0
        out["_tap"] = self.tap.calls[n_before:]
        return out

    async def chat_stream(self, content: str, max_tokens: int) -> dict:
        body = {"model": self.model, "max_tokens": max_tokens,
                "messages": [{"role": "user", "content": content}],
                "temperature": 0.0, "ignore_eos": True, "stream": True,
                "stream_options": {"include_usage": True}}
        t0 = time.monotonic()
        chunks, ttft, usage, done, finish = 0, None, None, False, None
        async with self.session.post(self.base + "/v1/chat/completions",
                                     json=body) as resp:
            check(resp.status == 200, f"stream -> {resp.status}")
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    continue
                chunk = json.loads(data)
                chunks += 1
                if ttft is None and chunk.get("choices"):
                    ttft = time.monotonic() - t0
                usage = chunk.get("usage") or usage
                for choice in chunk.get("choices", []):
                    finish = choice.get("finish_reason") or finish
        return {"chunks": chunks, "ttft_s": ttft, "usage": usage,
                "done": done, "finish_reason": finish,
                "total_s": time.monotonic() - t0}


def words(rng, n: int) -> str:
    """n words the test tokenizer knows (about one token per short word)."""
    vocab = ("hello world this is a test of the tpu native serving "
             "framework quick brown fox jumps over lazy dog").split()
    return " ".join(vocab[i] for i in rng.integers(0, len(vocab), size=n))


def usage_ok(resp: dict, max_tokens: int) -> None:
    usage = resp["usage"]
    check(usage["completion_tokens"] == max_tokens,
          f"usage.completion_tokens {usage['completion_tokens']} != "
          f"{max_tokens}")
    check(usage["prompt_tokens"] > 0, "usage.prompt_tokens is 0")
    check(resp["choices"][0]["finish_reason"] == "length",
          f"finish_reason {resp['choices'][0]['finish_reason']!r}")


# --------------------------------------------------------------------------
# One-chip phases
# --------------------------------------------------------------------------

def phase_preflight(args, jax) -> None:
    from dynamo_tpu import doctor
    from dynamo_tpu.engine import perf
    from dynamo_tpu.engine.config import device_peaks
    rep = doctor.Report()
    with contextlib.redirect_stdout(sys.stderr):  # doctor prints its rows
        doctor.check_devices(rep, require_tpu=not args.rehearse_cpu)
        doctor.check_native(rep)  # a failed C++ build WARNs here, by name
    dev = jax.devices()[0]
    peaks = device_peaks(dev)  # raises for an accelerator without a row
    emit("preflight",
         doctor=[{"status": s, "check": c, "detail": d}
                 for s, c, d in rep.rows],
         devices=[str(d) for d in jax.devices()], device_kind=dev.device_kind,
         peaks=dataclasses.asdict(peaks) if peaks else None,
         compile_cache=perf.compile_cache_status(),
         memory_stats=dev.memory_stats(), jax=jax.__version__)
    check(not rep.failed, "doctor reported a FAIL row")


async def phase_server(args, jax, rng) -> dict:
    """HTTP serving through the unified launcher. Returns what later phases
    reuse: the device-resident params and the engine's spec."""
    from dynamo_tpu.engine import perf
    model = "tiny-test" if args.rehearse_cpu else "qwen2.5-0.5b"
    argv = ["in=http", "out=tpu", "--model", model, "--quant", "int8",
            "--http-host", "127.0.0.1", "--http-port", "0"]
    if args.rehearse_cpu:
        argv += ["--num-pages", "256"]  # the CPU has no memory_stats
    cache0 = perf.compile_cache_status()
    platform = jax.devices()[0].platform
    async with Server(argv) as srv:
        eng, runner = srv.engine, srv.engine.runner
        warm = perf.get_registry().snapshot()
        emit("server.start", launch=" ".join(argv),
             startup_s=round(srv.startup_s, 2),
             compile_cache_before=cache0,
             compile_cache_after_warmup=perf.compile_cache_status(),
             warmup_programs=program_summary(warm),
             warmup_compile_s=warm["compile_seconds_total"],
             decode_window=eng.decode_window,
             pipeline_depth=eng.config.pipeline_depth,
             prefill_chunk_tokens=eng.prefill_chunk_tokens,
             attention_backend=runner.attention_backend,
             num_pages=runner.num_pages,
             d2h_fetch_floor_ms=round(runner.d2h_fetch_floor_ms(), 4),
             hbm_stats=runner.hbm_stats(),
             memory_breakdown=runner.memory_breakdown())
        check(srv.model in (await srv.get("/health"))["models"],
              "/health does not list the model")

        reqs = {}
        r = await srv.chat(words(rng, 6), 8)
        usage_ok(r, 8)
        reqs["plain"] = {"total_s": round(r["_total_s"], 4), **r["usage"]}

        s = await srv.chat_stream(words(rng, 6), 12)
        check(s["done"] and s["finish_reason"] == "length",
              f"stream ended badly: {s}")
        check(s["usage"] and s["usage"]["completion_tokens"] == 12,
              f"stream usage {s['usage']}")
        check(s["chunks"] >= 2, f"only {s['chunks']} SSE chunks")
        reqs["stream"] = {"sse_chunks": s["chunks"],
                          "ttft_s": round(s["ttft_s"], 4),
                          "total_s": round(s["total_s"], 4), **s["usage"]}

        both = await asyncio.gather(srv.chat(words(rng, 9), 10),
                                    srv.chat(words(rng, 14), 10))
        for r in both:
            usage_ok(r, 10)
        reqs["concurrent"] = [round(r["_total_s"], 4) for r in both]

        # The same prompt twice: the second must hit the prefix cache, and
        # its few uncached tail tokens go through the with-history prefill
        # program (runner._prefill_with_history), pages read back as history.
        repeated = words(rng, 60)
        kv0 = (await srv.get("/debug/kv"))["engines"][srv.model]["reuse"]
        first = await srv.chat(repeated, 8)
        kv1 = (await srv.get("/debug/kv"))["engines"][srv.model]["reuse"]
        second = await srv.chat(repeated, 8)
        kv2 = (await srv.get("/debug/kv"))["engines"][srv.model]["reuse"]
        usage_ok(first, 8)
        usage_ok(second, 8)
        hits = kv2["prefix_hit_blocks"] - kv1["prefix_hit_blocks"]
        check(hits > 0, f"repeated prompt hit no cached block: {kv1} {kv2}")
        check(any(key[2] for key in runner._prefill_cache),
              "no with-history prefill program was built")
        reqs["repeat"] = {
            # Reported, not required: the tail's logits come from another
            # program the second time, and random weights make near-ties.
            "tokens_equal": first["_tap"][0]["tokens"]
            == second["_tap"][0]["tokens"],
            "first_hit_blocks": kv1["prefix_hit_blocks"]
            - kv0["prefix_hit_blocks"], "second_hit_blocks": hits,
            "prompt_tokens": second["usage"]["prompt_tokens"],
            "total_s": [round(first["_total_s"], 4),
                        round(second["_total_s"], 4)]}

        # Past the first prefill buckets. The launcher's chunk size is its
        # largest bucket (8192 tokens, no flag), so scheduled chunking is
        # exercised in the kernels phase on an engine with a small one.
        long = await srv.chat(words(rng, 100 if args.rehearse_cpu else 900),
                              8)
        usage_ok(long, 8)
        check(long["usage"]["prompt_tokens"]
              > runner.config.prefill_buckets[0],
              "long prompt fits the smallest prefill bucket")
        reqs["long"] = {"total_s": round(long["_total_s"], 4),
                        **long["usage"]}

        prompt = words(rng, 7)
        sampled = [await srv.chat(prompt, 12, temperature=0.8, top_p=0.9,
                                  seed=args.seed + 1234) for _ in range(2)]
        for r in sampled:
            usage_ok(r, 12)
        check(sampled[0]["_tap"][0]["tokens"] == sampled[1]["_tap"][0][
            "tokens"], "same seed, different tokens")
        reqs["seeded"] = {"tokens": sampled[0]["_tap"][0]["tokens"]}

        # Greedy with logprobs, checked against the plain forward.
        n_lp = 16
        lp = await srv.chat(words(rng, 11), n_lp, logprobs=True,
                            top_logprobs=1)
        usage_ok(lp, n_lp)
        tap = lp["_tap"][0]
        served = [e["logprob"] for e in
                  lp["choices"][0]["logprobs"]["content"]]
        check(len(served) == n_lp == len(tap["tokens"]),
              f"{len(served)} logprobs for {n_lp} tokens")
        check(max_abs_diff(served, tap["logprobs"]) < 1e-6,
              "HTTP logprobs differ from what the engine emitted")
        t0 = time.monotonic()
        ref = reference_logprobs(runner.params, runner.spec, tap["prompt"],
                                 tap["tokens"], runner.device)
        dev = max_abs_diff(served, ref)
        emit("reference", tokens=n_lp, prompt_tokens=len(tap["prompt"]),
             max_abs_logprob_diff=round(dev, 5), tolerance=LOGPROB_ATOL,
             served=[round(x, 4) for x in served],
             reference=[round(x, 4) for x in ref],
             seconds=round(time.monotonic() - t0, 2))
        check(all(x == x and x <= 0.0 for x in served), "non-finite logprob")
        check(dev <= LOGPROB_ATOL,
              f"served logprobs off the reference by {dev:.4f} nats")

        perf_body = (await srv.get("/debug/perf"))["engines"][srv.model]
        compiles = perf_body["compiles"]
        stats = jax.devices()[0].memory_stats()
        emit("server.requests", requests=reqs,
             programs=program_summary(compiles),
             compile_seconds_total=compiles["compile_seconds_total"],
             unexpected_recompiles=compiles["unexpected_recompiles_total"],
             compile_cache=perf.compile_cache_status(),
             hbm_stats=perf_body["hbm"], memory=perf_body["memory"],
             kv_reuse=kv2)
        check(compiles["unexpected_recompiles_total"] == 0,
              "unexpected recompiles in the compile registry")
        check(args.rehearse_cpu or bool(stats), "empty device.memory_stats()")
        for name in ("params", "k_cache", "v_cache"):
            check(arrays_on(getattr(runner, name), platform),
                  f"runner.{name} is not wholly on a {platform} device")
        keep = {"params": runner.params, "spec": runner.spec}
    emit("server.stop", engine_thread_alive=eng._thread is not None)
    return keep


def small_config(args, spec, context: int = 1024, **kw):
    """Engines of the later phases: small pool, small chunk size (so a
    600-token prompt is chunked), short windows, ``context`` tokens a
    sequence."""
    from dynamo_tpu.engine.config import EngineConfig
    if args.rehearse_cpu:
        # (A bucket is whole pages, and a page whole blocks of a block
        # whose attention reads chosen blocks: 64 tokens off the chip.)
        sizes = dict(prefill_buckets=tuple(
            b for b in (32, 64, 128) if b % max(16, spec.sparse_block) == 0),
            max_prefill_tokens=64, num_pages=256)
    else:
        sizes = dict(prefill_buckets=(128, 256, 512, 1024),
                     max_prefill_tokens=256, num_pages=1024)
    cfg = EngineConfig(model=spec, max_num_seqs=8, decode_window=8,
                       pipeline_depth=2, **sizes, **kw)
    # The page is the launcher's ("auto": derived where the kernel reads
    # the pool on a TPU, 16 elsewhere); the context stays in tokens.
    cfg.max_pages_per_seq = context // cfg.page_size
    return cfg


async def phase_kernels(args, jax, rng, keep: dict):
    """The Pallas decode kernel: explicit "pallas" against explicit "xla"
    with bf16 and int8 KV on the served model (head_dim 64: the packed
    variant, which "auto" does not select), then what "auto" resolves to on
    a small head_dim-128 model (the kernel on one TPU device, XLA on the
    CPU rehearsal) against "xla" on the same weights, at 4 KV heads, on
    the Cohere2-MoE block at 8 KV heads under 16 query rows each, on the
    DeepSeek-V3.2 block over a pool of latent entries (every key attended:
    the reader), on that block past 2,048 tokens of context (the
    indexer chooses: its kernel), on the two blocks with recurrent layers
    and on a looped stack at one query row a KV head. Same prompts at mixed
    lengths per round.
    Returns the served model's bf16 XLA engine (the disagg phase's
    aggregated reference)."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import ModelSpec
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.engine.runner import PK_PREFIX
    spec, params = keep["spec"], keep["params"]
    # head_dim 128 over 4 KV heads: the benchmark cells' page (64 tokens
    # where "auto" derives it, EngineConfig.resolve_page_size).
    wide = ModelSpec(name="smoke-d128", vocab_size=2048, hidden_size=1024,
                     intermediate_size=1024, num_layers=2, num_heads=8,
                     num_kv_heads=4)
    # The Cohere2-MoE block at toy depth and Command A+'s head geometry: 128
    # query heads over 8 KV heads of 128 (16 query rows a KV head; a page
    # of 32 tokens by the same rule), one period of window and full layers
    # with a window the longest prompt passes, a share of the experts.
    from dynamo_tpu.engine.config import Cohere2MoeSpec
    share = Cohere2MoeSpec(
        name="smoke-ep-share", vocab_size=2048, hidden_size=512,
        intermediate_size=256, num_layers=4, num_heads=128, num_kv_heads=8,
        head_dim=128, tie_word_embeddings=True, num_experts=4,
        num_experts_per_tok=3, moe_intermediate_size=256,
        num_routed_experts=8, first_expert=4, num_shared_experts=2,
        sliding_window=64 if args.rehearse_cpu else 256,
        sliding_window_layout=(1, 1, 1, 0), rope_layout=(1, 1, 1, 0))
    # The DeepSeek-V3.2 block at toy depth with its pool's real widths (an
    # entry of 512 + 64 + 64 lanes, an index key of 128; a page of 64 by the
    # same rule), 16 heads, every expert chosen and the published 2,048
    # keys kept, so every key in context is attended: a key that swaps
    # sides at the indexer's rank moved these logprobs by 0.14 to 0.16 nats
    # between two correct readers (128 keys kept; PERF.md section 6, PR 35).
    # benchmark/selection_check.py holds the choice to its reference.
    from dynamo_tpu.engine.config import DeepseekV32Spec
    latent = DeepseekV32Spec(
        name="smoke-latent", vocab_size=2048, hidden_size=512,
        intermediate_size=256, num_layers=3, num_heads=16, num_kv_heads=16,
        head_dim=192, rms_norm_eps=1e-6, q_lora_rank=256, index_n_heads=8,
        num_experts=2, num_experts_per_tok=2,
        moe_intermediate_size=128, num_routed_experts=2,
        num_shared_experts=1, first_k_dense=1,
        rope_yarn=(40.0, 4096, 32.0, 1.0, 1.0))
    # The indexer's round: the same block with the published 64 index heads
    # of 128 (the kernel's dot: [64, 128] against a chunk's keys) and
    # prompts PAST the 2,048 keys a query keeps, so the indexer chooses in
    # every layer of every decode step (a fifth of the longest prompt's
    # keys dropped; a key that swaps sides at rank 2,048 carries a
    # two-thousandth of a query). On the chip the kernel walks a row's
    # live pages of index keys and scores them; XLA gathers every slot's
    # bucket and scores the copy; select_topk chooses over either's.
    indexed = dataclasses.replace(latent, name="smoke-latent-index",
                                  index_n_heads=64)
    # The Nemotron-H block at toy depth: all three kinds of layer, a *
    # between an M and an E, Nemotron-3-Nano's attention geometry (32 query
    # heads over 2 KV heads of 128: a page of 128 by the same rule) and its
    # mixer's head and state sizes, a share of two-matrix relu2 experts and
    # a shared expert of its own width. The longest prompt is prefilled in
    # chunks: the state is carried from chunk to chunk in its slot.
    from dynamo_tpu.engine.config import NemotronHSpec
    hybrid = NemotronHSpec(
        name="smoke-hybrid", vocab_size=2048, hidden_size=512,
        intermediate_size=256, num_layers=7, num_heads=32, num_kv_heads=2,
        head_dim=128, rms_norm_eps=1e-5, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=256,
        num_routed_experts=8, first_expert=4, num_shared_experts=1,
        shared_intermediate_size=384, routed_scaling_factor=2.5,
        layer_pattern="MEM*EME", ssm_heads=8, ssm_head_dim=64, ssm_groups=2,
        ssm_state=128, ssm_conv=4, ssm_chunk=128)
    # The MiniCPM-SALA block at toy depth: both mixers (S L L S L L S), its
    # attention geometry (32 query heads over 2 KV heads of 128: a page of
    # 128, two blocks of 64) and its lightning heads' state (128 x 128), a
    # query keeping 4 blocks of 64 of which the window's are 2, so the two
    # longer prompts choose in every attention layer of every step: on the
    # chip the recurrence's kernel at a group a head and the pool's reader
    # over the chosen blocks' table, against XLA's of both; the longest
    # prompt is prefilled in chunks over their history.
    from dynamo_tpu.engine.config import MiniCPMSALASpec
    sala = MiniCPMSALASpec(
        name="smoke-sala", vocab_size=2048, hidden_size=512,
        intermediate_size=1024, num_layers=7, num_heads=32, num_kv_heads=2,
        head_dim=128, rms_norm_eps=1e-6, layer_pattern="SDLDLDSDLDLDSD",
        ssm_heads=8, ssm_head_dim=128, ssm_groups=8, ssm_state=128,
        ssm_chunk=128, scale_emb=12.0, residual_scale=1.4 / 7 ** 0.5,
        logit_divisor=2.0, sparse_kernel=32, sparse_stride=16,
        sparse_block=64, sparse_topk=4, sparse_init_blocks=1,
        sparse_window=128)
    # A looped stack at toy depth and Ouro-2.6B's attention geometry: 16
    # query heads over 16 KV heads of 128 (ONE query row a KV head; a page
    # of 16 by the same rule: one copy across the heads is 64 KB), 3 layers
    # run 3 times, so the pool has 9 layers and a pass reads its own; the
    # sandwich norms and the norm between passes. The window counts the
    # passes its live rows took.
    from dynamo_tpu.engine.config import OuroSpec
    looped = OuroSpec(
        name="smoke-looped", vocab_size=2048, hidden_size=512,
        intermediate_size=1024, num_layers=3, num_heads=16, num_kv_heads=16,
        head_dim=128, rope_theta=1e6, rms_norm_eps=1e-6, loop_passes=3)
    # The Solar-Open2 block at one period (* K K K, an expert layer behind
    # each): its attention geometry (64 query heads over 8 KV heads of 128,
    # gated: a page of 32) and its delta-rule heads as published (64 of 128
    # x 128, a convolution over 24,576 channels), a share of SwiGLU experts:
    # on the chip the recurrence's SECOND form (the decayed state read
    # before it is written, in the one visit) against XLA's delta_update;
    # the longest prompt's chunks solve the delta rule over a carried state.
    # Every routed expert chosen (8 of 8, as the latent block's): with 2 of
    # 8 a router that stands within 0.01 of a tie chooses another expert
    # under the other runner's rounding, and 9 of 18 pairs of two correct
    # runners read 0.15 to 0.75 nat at such a token; with all 8 none of 18
    # passes 0.046 (PERF.md section 6, PR 53). The rounds of the Cohere2-MoE
    # and Nemotron-H blocks are where a router's choice is compared.
    from dynamo_tpu.engine.config import SolarOpen2Spec
    delta = SolarOpen2Spec(
        name="smoke-delta", vocab_size=2048, hidden_size=512,
        intermediate_size=1024, num_layers=4, num_heads=64, num_kv_heads=8,
        head_dim=128, rms_norm_eps=1e-5, num_experts=4,
        num_experts_per_tok=8, moe_intermediate_size=256,
        num_routed_experts=8, first_expert=4, num_shared_experts=1,
        layer_pattern="*EKEKEKE", ssm_heads=64, ssm_head_dim=128,
        ssm_groups=64, ssm_state=128, ssm_conv=4, ssm_low_rank=128)
    # The Falcon-H1 block: its Mamba-2 heads as published (32 of 128 over a
    # state of 256 in 2 groups: a head is ONE copy of the recurrence's
    # kernel, two lane tiles a row of S) and its attention geometry (20
    # query heads over 4 KV heads of 128, rotary: a page of 64) SIDE BY SIDE
    # on one normed input in every layer, the muP constants as published
    # but the head's 1/128 (under it every logprob is the uniform one to a
    # hundredth and a wrong kernel would not show).
    from dynamo_tpu.engine.config import FalconH1Spec
    parallel = FalconH1Spec(
        name="smoke-parallel", vocab_size=2048, hidden_size=512,
        intermediate_size=1024, num_layers=3, num_heads=20, num_kv_heads=4,
        head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5,
        layer_pattern="M*D" * 3, ssm_heads=32, ssm_head_dim=128,
        ssm_groups=2, ssm_state=256, ssm_conv=4, ssm_chunk=128,
        scale_emb=5.6569,
        key_multiplier=0.011049, attn_out_multiplier=0.0375,
        ssm_in_multiplier=0.25, ssm_out_multiplier=0.088388,
        ssm_multipliers=(0.35355, 0.25, 0.17678, 0.5, 0.35355),
        mlp_multipliers=(0.17678, 0.011161))
    pages = {wide.name: 64, share.name: 32, latent.name: 64,
             indexed.name: 64, hybrid.name: 128, sala.name: 128,
             looped.name: 16, delta.name: 32, parallel.name: 64}  # derived
    short = (20, 70, 150) if args.rehearse_cpu else (24, 200, 700)
    past_topk = (20, 2100) if args.rehearse_cpu else (24, 2200, 2600)
    assert max(short) + 64 < indexed.index_topk < min(past_topk[1:])
    n_out = 20
    on_tpu = jax.devices()[0].platform == "tpu"
    # The rehearsal interprets the kernels: "auto" is XLA's there.
    kernels = "pallas" if args.rehearse_cpu else "auto"
    agg = None
    for spec_r, params_r, quant_kv, backends, lengths, context in (
            (spec, params, None, ("xla", "pallas"), short, 1024),
            (spec, params, "int8", ("xla", "pallas"), short, 1024),
            (wide, None, None, ("xla", "auto"), short, 1024),
            (share, None, None, ("xla", "auto"), short, 1024),
            (latent, None, None, ("xla", kernels), short, 1024),
            (indexed, None, None, ("xla", kernels), past_topk, 4096),
            (hybrid, None, None, ("xla", "auto"), short, 1024),
            (sala, None, None, ("xla", "auto"), short, 1024),
            (looped, None, None, ("xla", "auto"), short, 1024),
            (delta, None, None, ("xla", "auto"), short, 1024),
            (parallel, None, None, ("xla", "auto"), short, 1024)):
        prompts = [rng.integers(2, spec_r.vocab_size, size=n).tolist()
                   for n in lengths]
        runs = {}
        # Both engines of a round at the page the round's second backend
        # resolves: the kernel is compared with XLA at the derived page.
        page = small_config(args, spec_r, context, quant_kv=quant_kv,
                            attention_backend=backends[1]).page_size
        # (Off the chip a page is 16 tokens, or one block of a block whose
        # attention reads chosen blocks.)
        check(page == (pages.get(spec_r.name, 16) if on_tpu
                       else max(16, spec_r.sparse_block)),
              f"{spec_r.name} under {backends[1]}: page of {page} tokens")
        for backend in backends:
            eng = TPUEngine(small_config(args, spec_r, context,
                                         quant_kv=quant_kv,
                                         attention_backend=backend,
                                         page_size=page),
                            params=params_r)
            params_r = eng.runner.params  # the round's engines share weights
            # What backends.choose decides "auto" from: K and V heads of
            # 128, or a pool of latent entries. The runner's record
            # (engine/backends.py) holds every choice compared below.
            want = (backend if backend != "auto" else "pallas"
                    if on_tpu and (spec_r.head_dim == 128 or spec_r.latent)
                    else "xla")
            resolved = eng.runner.attention_backend
            check(resolved == want,
                  f"asked for {backend}, expected {want}, runner resolved "
                  f"{resolved}")
            record = eng.runner.backends
            # The window's commit follows its reader (config.pool_access): in
            # place beside the kernel on a plain bf16 pool at head_dim 128,
            # so the third round compares it with the scatter's logprobs;
            # a latent pool's is in place on a TPU under either reader.
            # Whoever walks a latent pool's entries walks its index keys,
            # and whoever walks K and V pages walks a compressed-key
            # array's stripes.
            index = record.index
            check(index == (resolved if spec_r.latent
                            or spec_r.compressed_keys else None),
                  f"{resolved} reader of {spec_r.name}: indexer {index}")
            commit = record.kv_commit
            check((commit == "in_place") == (
                on_tpu if spec_r.latent else
                resolved == "pallas" and spec_r.head_dim == 128
                and quant_kv is None), f"{resolved} reader at head_dim "
                f"{spec_r.head_dim}, {quant_kv or 'bf16'} KV: commit {commit}")
            t0 = time.monotonic()
            runs[backend] = await asyncio.gather(
                *[engine_generate(eng, p, n_out) for p in prompts])
            seconds = time.monotonic() - t0
            for toks, lps in runs[backend]:
                check(len(toks) == n_out == len(lps), "short output")
            chunks = eng.chunk_tokens_total
            check(chunks > 0, "the longest prompt was not chunk-prefilled")
            selected = None
            if spec_r.latent or spec_r.compressed_keys:
                # Did the indexer choose (or the scores over compressed
                # keys)? Keys attended of keys in context.
                selected = eng.perf_status()["attn"]["selected_pct"]
                chooses = lengths is past_topk or (
                    spec_r.compressed_keys and max(lengths)
                    > spec_r.sparse_topk * spec_r.sparse_block)
                check((selected < 100) == chooses,
                      f"{spec_r.name} at {lengths}: {selected} % of the "
                      f"keys in context attended")
            custom_call = None
            if resolved == "pallas":
                # The window program that just served: is the kernel in it?
                runner = eng.runner
                key = next(k for k in runner._window_cache
                           if isinstance(k[0], int))
                packed = np.zeros((runner.config.max_num_seqs,
                                   PK_PREFIX + key[1]), np.int32)
                # (a block with recurrent layers: its state arrays too)
                state = runner.state_arrays
                *shapes, state = jax.tree.map(  # the engine owns the arrays
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=a.sharding),
                    (runner.params, runner.k_cache, runner.v_cache,
                     runner.tokens_dev, jnp.asarray(packed), runner._rng,
                     state if spec_r.recurrent else ()))
                with runner.mesh:
                    text = runner._window_cache[key].lower(
                        *shapes, **({"state": state} if state else {})
                    ).compile().as_text()
                custom_call = "tpu_custom_call" in text
                check(custom_call == on_tpu,
                      f"tpu_custom_call in the window program: "
                      f"{custom_call} on {jax.devices()[0].platform}")
            # A routed block's prefill chunks (256 rows on the chip) take
            # the grouped expert product, its window's rows the walk over
            # the touched experts (model.expert_product; the rehearsal's
            # 64-row chunks take the walk too): the programs' own label.
            products = {
                family: sorted({fn._labels["expert_product"]
                                for fn in cache.values()})
                for family, cache in (("prefill", eng.runner._prefill_cache),
                                      ("decode_window",
                                       eng.runner._window_cache))
            } if spec_r.num_experts else None
            grouped = not args.rehearse_cpu
            check(products is None or (
                ("grouped" in products["prefill"]) == grouped
                and products["decode_window"] == ["touched"]),
                f"expert products of {spec_r.name}: {products}")
            state_on = ssm = None
            if spec_r.recurrent:
                # The recurrent state beside the pool: where the arrays the
                # programs handed back lie, and that steps were counted.
                state_on = sorted({d.platform
                                   for a in eng.runner.state_arrays
                                   for d in a.devices()})
                check(state_on == [jax.devices()[0].platform]
                      and eng.perf_status()["ssm"]["row_steps"] > 0,
                      f"recurrent state of {spec_r.name} on {state_on}")
                # Who updates it in a decode step follows the reader: the
                # kernel beside the Pallas reader on the chip, XLA beside
                # XLA's (the round's comparison is kernel against XLA).
                ssm = eng.perf_status()["ssm"]["backend"]
                check(ssm == ("kernel" if on_tpu and resolved == "pallas"
                              else "xla") == record.ssm,
                      f"{resolved} reader of {spec_r.name}: state by {ssm}")
            passes = None
            if spec_r.loop_passes > 1:
                # Counted in the window program, where the passes run.
                passes = eng.perf_status()["loop"]["passes_per_token"]
                check(passes == spec_r.loop_passes
                      and eng.runner.k_cache.shape[0] == spec_r.pool_layers
                      == spec_r.loop_passes * spec_r.num_layers,
                      f"{spec_r.name}: {passes} passes a token, a pool of "
                      f"{eng.runner.k_cache.shape[0]} layers")
            emit("kernels.run", model=spec_r.name,
                 quant_kv=quant_kv or "bf16", attention_backend=backend,
                 resolved=resolved, kv_commit_backend=commit,
                 index_backend=index, attn_selected_pct=selected,
                 expert_product=products, ssm_state_on=state_on,
                 ssm_backend=ssm, loop_passes_per_token=passes,
                 page_size=eng.runner.page_size,
                 prompt_lengths=lengths, chunk_tokens=chunks,
                 seconds=round(seconds, 2), tpu_custom_call=custom_call)
            if backend == "xla" and spec_r is spec and quant_kv is None:
                agg = eng
            else:
                eng.stop()
        compared, worst = 0, 0.0
        for (tx, lx), (tp, lp) in zip(runs["xla"], runs[backends[1]]):
            n, diff = agreeing_prefix_diff(tx, lx, tp, lp)
            compared += n
            worst = max(worst, diff)
        emit("kernels.compare", model=spec_r.name,
             quant_kv=quant_kv or "bf16", against=backends[1],
             tokens_compared=compared, of=n_out * len(prompts),
             max_abs_logprob_diff=round(worst, 5), tolerance=LOGPROB_ATOL)
        check(compared >= len(prompts) * 2, "runs parted at once")
        check(worst <= LOGPROB_ATOL,
              f"{backends[1]} vs xla ({spec_r.name}, {quant_kv or 'bf16'} "
              f"KV) logprobs differ by {worst:.4f} nats")
    return agg


async def phase_disagg(args, jax, rng, keep: dict, agg) -> None:
    """prefill -> extract -> KV plane -> insert -> decode between two
    engines on the one chip (what tests/test_disagg.py start_stack does on
    the CPU), against the aggregated engine ``agg``."""
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm import kv_plane
    from dynamo_tpu.llm.disagg import (DisaggDecodeHandler,
                                       DisaggRouterConfig,
                                       make_prefill_handler)
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    # The device-to-device transfer probe, in a thread with a deadline: on
    # a backend where it wedges, say so instead of hanging the smoke.
    box: dict = {}
    t = threading.Thread(target=lambda: box.update(
        ok=kv_plane.jax_transfer_usable()), daemon=True)
    t.start()
    t.join(timeout=60)
    probe_ok = box.get("ok", False)
    emit("disagg.probe", jax_transfer_usable=box.get("ok"),
         timed_out=t.is_alive(), error=kv_plane.jax_probe_error)
    check(not t.is_alive(), "jax transfer probe did not return in 60 s")

    spec, params = keep["spec"], keep["params"]
    lengths = (40, 128, 150) if args.rehearse_cpu else (100, 128, 600)
    prompts = [rng.integers(2, spec.vocab_size, size=n).tolist()
               for n in lengths]
    n_out = 12
    coord = Coordinator()
    await coord.start()
    cfg = lambda: RuntimeConfig(coordinator_url=coord.url,  # noqa: E731
                                lease_ttl_s=10.0)
    p_rt = await DistributedRuntime.from_settings(cfg())
    d_rt = await DistributedRuntime.from_settings(cfg())
    f_rt = await DistributedRuntime.from_settings(cfg())
    plane = kv_plane.KvPlaneServer(use_jax_path=probe_ok)
    plane.start()
    p_eng = TPUEngine(small_config(args, spec), params=params)
    d_eng = TPUEngine(small_config(args, spec), params=params)
    try:
        p_ep = p_rt.namespace("smoke").component("prefill").endpoint(
            "generate")
        p_server = await p_ep.serve_endpoint(
            make_prefill_handler(p_eng, plane=plane), graceful_shutdown=True)
        pc = await d_rt.namespace("smoke").component("prefill").endpoint(
            "generate").client()
        dcfg = await DisaggRouterConfig.from_coordinator_with_watch(
            d_rt.require_coordinator(), spec.name, default_max_local=8)
        handler = DisaggDecodeHandler(d_eng, pc, dcfg)
        d_ep = d_rt.namespace("smoke").component("tpu").endpoint("generate")
        d_server = await d_ep.serve_endpoint(handler.handler(),
                                             graceful_shutdown=False)
        await pc.wait_for_instances(timeout=10)
        caller = await f_rt.namespace("smoke").component("tpu").endpoint(
            "generate").client()
        await caller.wait_for_instances(timeout=10)

        rows = []
        for prompt in prompts:  # one at a time: same batch shape as agg
            req = PreprocessedRequest(model=spec.name, token_ids=prompt)
            req.stop_conditions.max_tokens = n_out
            req.stop_conditions.ignore_eos = True
            t0 = time.monotonic()
            got = []
            async for out in await caller.round_robin(req.to_wire()):
                got.extend(out.get("token_ids", []))
                if out.get("finish_reason"):
                    break
            seconds = time.monotonic() - t0
            ref, _ = await engine_generate(agg, prompt, n_out)
            rows.append({"prompt_tokens": len(prompt), "equal": got == ref,
                         "seconds": round(seconds, 3)})
            check(got == ref, f"disaggregated tokens {got} != aggregated "
                  f"{ref} at prompt length {len(prompt)}")
        emit("disagg", rows=rows, remote_prefills=handler.remote_prefills,
             remote_failures=handler.remote_failures,
             streamed_extracts=p_eng.streamed_extracts,
             d2h_fetch_floor_ms=round(p_eng.runner.d2h_fetch_floor_ms(), 4),
             plane=plane.stats(), pulls=handler.plane_client.stats())
        check(handler.remote_prefills == len(prompts)
              and handler.remote_failures == 0,
              "a prompt was not prefilled remotely")
        check(handler.plane_client.stats()["transfers"] == len(prompts),
              "a parcel did not travel over the KV plane")
        await caller.close()
        await pc.close()
        await dcfg.close()
        await d_server.shutdown()
        await p_server.shutdown()
        handler.plane_client.close()
    finally:
        d_eng.stop()
        p_eng.stop()
        agg.stop()
        plane.close()
        await f_rt.close()
        await d_rt.close()
        await p_rt.close()
        await coord.stop()


# --------------------------------------------------------------------------
# Four chips: the sharded path and what it is compared with
# --------------------------------------------------------------------------

def shard_report(tree, devices) -> dict:
    """Bytes each device holds of ``tree`` (addressable_shards) over the
    tree's total bytes."""
    import jax
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return {"total_bytes": total,
            "share": {i: round(b / total, 4) for i, b in held.items()}}


async def phase_sharded(args, jax, rng, tp: int, dp: int) -> None:
    import numpy as np
    model = "tiny-test" if args.rehearse_cpu else "llama-3-8b-L8"
    argv = ["in=http", "out=tpu", "--model", model, "--tp", str(tp),
            "--dp", str(dp), "--http-host", "127.0.0.1", "--http-port", "0",
            "--max-num-seqs", "8", "--max-pages-per-seq", "64"]
    if args.rehearse_cpu:
        argv += ["--num-pages", "256"]
    devices = jax.devices()[:tp * dp]
    tag = f"tp{tp}dp{dp}"
    async with Server(argv) as srv:
        eng, runner = srv.engine, srv.engine.runner
        emit(f"sharded.{tag}.start", launch=" ".join(argv),
             startup_s=round(srv.startup_s, 2), mesh=dict(runner.mesh.shape),
             num_pages=runner.num_pages, decode_window=eng.decode_window,
             d2h_fetch_floor_ms=round(runner.d2h_fetch_floor_ms(), 4),
             hbm_stats=runner.hbm_stats(),
             memory_breakdown=runner.memory_breakdown())
        both = await asyncio.gather(srv.chat(words(rng, 9), 10),
                                    srv.chat(words(rng, 30), 10))
        for r in both:
            usage_ok(r, 10)
        n_lp = 12
        lp = await srv.chat(words(rng, 11), n_lp, logprobs=True,
                            top_logprobs=1)
        usage_ok(lp, n_lp)
        tap = lp["_tap"][0]
        served = [e["logprob"] for e in
                  lp["choices"][0]["logprobs"]["content"]]
        # Each device holds its share: params and KV shard over tp (dp
        # replicates), and no device carries the whole model.
        shares = {name: shard_report(getattr(runner, name), devices)
                  for name in ("params", "k_cache", "v_cache")}
        in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices}
        programs = program_summary((await srv.get("/debug/perf"))[
            "engines"][srv.model]["compiles"])
        params, spec = runner.params, runner.spec
        del eng, runner
    # The server is down and its KV pool released: now the same weights fit
    # on ONE device beside their shard, through the plain forward. The spec
    # may carry replicated KV heads (tp > num_kv_heads); the params match
    # it, so the comparison holds either way.
    gc.collect()
    one = jax.devices()[0]
    t0 = time.monotonic()
    ref = reference_logprobs(jax.device_put(params, one), spec,
                             tap["prompt"], tap["tokens"], one)
    dev = max_abs_diff(served, ref)
    emit(f"sharded.{tag}", max_abs_logprob_diff=round(dev, 5),
         tolerance=LOGPROB_ATOL, served=[round(x, 4) for x in served],
         reference=[round(x, 4) for x in ref],
         reference_s=round(time.monotonic() - t0, 2), shares=shares,
         bytes_in_use=in_use, programs=programs)
    check(dev <= LOGPROB_ATOL, f"{tag} served logprobs off the one-device "
          f"reference by {dev:.4f} nats")
    for name, rep in shares.items():
        for dev_id, share in rep["share"].items():
            # KV shards exactly; params carry a few replicated norms.
            check(abs(share - 1 / tp) < 0.02,
                  f"device {dev_id} holds {share:.3f} of {name}, "
                  f"expected 1/{tp}")
    if not args.rehearse_cpu:
        vals = list(in_use.values())
        check(all(vals) and max(vals) < 1.5 * min(vals),
              f"bytes_in_use uneven across devices: {in_use}")
    check(np.isfinite(served).all(), "non-finite logprob")


# --------------------------------------------------------------------------

async def run_phases(args, jax) -> bool:
    import numpy as np
    rng = np.random.default_rng(args.seed)
    try:
        phase_preflight(args, jax)
        if args.chips == 4:
            await phase_sharded(args, jax, rng, tp=4, dp=1)
            await phase_sharded(args, jax, rng, tp=2, dp=2)
        else:
            keep = await phase_server(args, jax, rng)
            agg = await phase_kernels(args, jax, rng, keep)
            await phase_disagg(args, jax, rng, keep, agg)
        return True
    except Exception as exc:  # noqa: BLE001 — report the phase, then fail
        import traceback
        traceback.print_exc()
        emit("failed", error=f"{type(exc).__name__}: {exc}"[:2000])
        return False


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = " ".join(
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    # Flight-recorder bundles (a decode stall while a program compiles) come
    # back with the chip tool's output directory, not under /tmp.
    os.environ.setdefault("DTPU_FLIGHT_DIR", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "flight"))
    # A hang must end inside the time limit, with every thread's stack.
    faulthandler.dump_traceback_later(args.deadline_s, exit=True)
    try:
        import jax

        import dynamo_tpu  # noqa: F401 — chip_smoke.py alone is not the repo
    except ImportError as exc:
        print(f"chip_smoke.py needs the repository around it: {exc}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke.py needs a TPU; jax found platform "
              f"{dev.platform!r}. (--rehearse-cpu runs tiny-test on the CPU.)",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    t0 = time.monotonic()
    ok = asyncio.run(run_phases(args, jax))
    faulthandler.cancel_dump_traceback_later()
    emit("done", seconds=round(time.monotonic() - t0, 1))
    final = {"ok": ok, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(devices)}}
    if args.rehearse_cpu:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
