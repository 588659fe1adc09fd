"""Benchmark: steady-state serving throughput of the TPU engine on one chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "detail"}.

Workload: qwen2.5-0.5b-shaped model (random bf16 weights) served through the
FULL TPUEngine path — batched prefill, M-step decode windows, continuous
batching — with BENCH_BATCH concurrent requests, ISL 128 / OSL 128
(BENCH_BATCH / BENCH_ISL / BENCH_OSL / BENCH_MODEL / BENCH_WINDOW /
BENCH_DEPTH env vars override; docs/PERF_NOTES.md records the sweep behind
the defaults). A full-shape warmup round compiles every bucket first; then
BENCH_ROUNDS (default 3) measured rounds run and the MEDIAN round (by
decode tok/s) is reported with min/max spread: the SLA claim must hold
across repeats, not once.

Refuses to run unless jax's first device is a TPU: every number printed
here is named as a device metric, and a CPU timing under that name is a lie
(a CPU-only box exits non-zero and prints nothing).

Defaults: bs40/M=32/D=4 — one notch below the bs48 throughput optimum,
chosen so p99 TTFT holds the 500 ms north-star SLO with ~100 ms headroom
under environment variance (the driver's round-3 capture measured 651 ms
at the zero-headroom bs48 default; PERF_NOTES "SLA headroom" section).

``vs_baseline`` is the fraction of the chip's own weight-read roofline
(weights as stored: int8 by default) that the measured decode throughput
achieves (hardware-anchored, same-workload). The reference publishes NO
comparable absolute number (BASELINE.md: its only in-repo figures are a
70B-class TP4 profiler example), so a cross-hardware ratio against its 51.22 tok/s/GPU decode
ITL example — headlined in earlier rounds — was apples-to-oranges and is
now in ``detail.ref_example_ratio`` with that caveat attached.
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import numpy as np

def perf_snapshot(engine) -> dict:
    """The perf-plane section every bench JSON embeds (scripts/
    perf_gate.py diffs it against a committed baseline): per-program
    compile counts/seconds, the unexpected-recompile total (MUST be 0
    in steady state), and the roofline-attributed window series."""
    from dynamo_tpu.engine import perf
    reg = perf.get_registry()
    return {"compiles": reg.snapshot(), "window": reg.window_snapshot(),
            "hbm": engine.runner.hbm_stats(),
            "memory": engine.runner.memory_breakdown()}


def require_tpu():
    """The TPU this process measures (jax.devices()[0]); SystemExit
    without one. Nothing here falls back to the CPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}). Not running.")
    return dev


ISL = int(os.environ.get("BENCH_ISL", "128"))
OSL = int(os.environ.get("BENCH_OSL", "128"))
BATCH = int(os.environ.get("BENCH_BATCH", "40"))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "3"))
# Mixed-workload mode (BENCH_MIXED=1 or --mode mixed): long prompts
# arriving mid-steady-decode; the headline is the steady decoders'
# itl_gap_p99 DURING prefill interference (stall-free chunked prefill,
# docs/PERF_NOTES.md "Stall-free prefill").
LONG_ISL = int(os.environ.get("BENCH_LONG_ISL", "4096"))
LONG_N = int(os.environ.get("BENCH_LONG_N", "4"))


async def run_round(engine, spec, rng, tag, batch=BATCH, osl=OSL):
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context

    async def one(i):
        prompt = rng.integers(0, spec.vocab_size, size=ISL).tolist()
        req = PreprocessedRequest(model="bench", token_ids=prompt)
        req.stop_conditions.max_tokens = osl
        req.stop_conditions.ignore_eos = True
        t_submit = time.monotonic()
        t_first = None
        arrivals = []  # (t, n_tokens)
        async for out in engine.generate(req, Context()):
            n = len(out.get("token_ids", []))
            now = time.monotonic()
            if n and t_first is None:
                t_first = now
            if n:
                arrivals.append((now, n))
            if out.get("finish_reason"):
                break
        return t_submit, t_first, arrivals

    t0 = time.monotonic()
    results = await asyncio.gather(*[one(i) for i in range(batch)])
    elapsed = time.monotonic() - t0
    ttfts = [t_first - t_submit for t_submit, t_first, _ in results]
    total_tokens = sum(sum(n for _, n in arr) for _, _, arr in results)
    itl_means = []
    gaps = []  # true per-token inter-arrival gaps (tokens arrive in
    # window-sized bursts: in-burst gaps are ~0, burst gaps ~window time)
    decode_tokens = 0
    decode_span = 0.0
    for _, t_first, arr in results:
        n_after_first = sum(n for _, n in arr) - arr[0][1]
        span = arr[-1][0] - t_first
        if n_after_first > 0 and span > 0:
            itl_means.append(span / n_after_first)
            decode_tokens += n_after_first
            decode_span = max(decode_span, span)
        for (t_prev, _), (t_cur, n_cur) in zip(arr, arr[1:]):
            gaps.append(t_cur - t_prev)       # first token of the burst
            gaps.extend([0.0] * (n_cur - 1))  # rest arrive together
    return {
        "elapsed_s": elapsed,
        "total_tokens": total_tokens,
        "decode_tok_s": decode_tokens / decode_span if decode_span else 0.0,
        "ttft_p50_ms": 1e3 * float(np.percentile(ttfts, 50)),
        "ttft_p99_ms": 1e3 * float(np.percentile(ttfts, 99)),
        "itl_mean_ms": 1e3 * float(np.mean(itl_means)) if itl_means else 0.0,
        "itl_gap_p99_ms": 1e3 * float(np.percentile(gaps, 99)) if gaps
        else 0.0,
    }


async def run_mixed(engine, spec, rng):
    """Steady decoders + LONG_N long prompts injected mid-decode.

    Returns the steady decoders' inter-burst gap p99 split into the
    interference window (first long submitted -> last long's first
    token) vs outside it, plus the longs' TTFTs. With stall-free
    chunked prefill the two p99s should be within ~one chunk's compute;
    the pre-rework engine stalled every decoder for the WHOLE long
    prompt (one gap >= full prefill per long)."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context

    window = {"t0": None, "t1": None}
    first_tokens = asyncio.Event()
    started = 0

    async def steady(i):
        nonlocal started
        prompt = rng.integers(0, spec.vocab_size, size=ISL).tolist()
        req = PreprocessedRequest(model="bench", token_ids=prompt)
        req.stop_conditions.max_tokens = OSL
        req.stop_conditions.ignore_eos = True
        arrivals = []
        async for out in engine.generate(req, Context()):
            n = len(out.get("token_ids", []))
            if n:
                arrivals.append((time.monotonic(), n))
                if len(arrivals) == 1:
                    started += 1
                    if started >= BATCH:
                        first_tokens.set()
            if out.get("finish_reason"):
                break
        return arrivals

    async def long_one(i):
        prompt = rng.integers(0, spec.vocab_size, size=LONG_ISL).tolist()
        req = PreprocessedRequest(model="bench", token_ids=prompt)
        req.stop_conditions.max_tokens = 8
        req.stop_conditions.ignore_eos = True
        t_submit = time.monotonic()
        t_first = None
        async for out in engine.generate(req, Context()):
            if out.get("token_ids") and t_first is None:
                t_first = time.monotonic()
            if out.get("finish_reason"):
                break
        return t_submit, t_first

    steady_tasks = [asyncio.ensure_future(steady(i)) for i in range(BATCH)]
    await first_tokens.wait()
    window["t0"] = time.monotonic()
    long_results = await asyncio.gather(
        *[long_one(i) for i in range(LONG_N)])
    window["t1"] = max(t for _, t in long_results)
    steady_results = await asyncio.gather(*steady_tasks)
    gaps_in, gaps_out = [], []
    for arrivals in steady_results:
        for (t_prev, _), (t_cur, n_cur) in zip(arrivals, arrivals[1:]):
            gap = t_cur - t_prev
            bucket = (gaps_in if window["t0"] <= t_cur <= window["t1"]
                      else gaps_out)
            bucket.append(gap)
            bucket.extend([0.0] * (n_cur - 1))
    ttfts = [t1 - t0 for t0, t1 in long_results]
    p99 = lambda xs: 1e3 * float(np.percentile(xs, 99)) if xs else 0.0
    return {
        "itl_gap_p99_ms_during_prefill": p99(gaps_in),
        "itl_gap_p99_ms_steady": p99(gaps_out),
        "itl_gap_max_ms_during_prefill":
            1e3 * max(gaps_in) if gaps_in else 0.0,
        "long_ttft_p50_ms": 1e3 * float(np.percentile(ttfts, 50)),
        "long_ttft_max_ms": 1e3 * max(ttfts),
        "interference_window_s": window["t1"] - window["t0"],
    }


async def main_async(mode: str = "serve"):
    from dynamo_tpu.engine.config import (EngineConfig, PRESETS,
                                          device_peaks)
    from dynamo_tpu.engine.engine import TPUEngine

    device = require_tpu()
    spec = PRESETS[os.environ.get("BENCH_MODEL", "qwen2.5-0.5b")]
    # int8 weights by default: measured faster AND more SLO headroom than
    # bf16 at the default config (21.9K vs 18.0K tok/s, TTFT p99 343 vs
    # 428 ms), with quality CI-gated (tests/test_quant.py). BENCH_QUANT
    # overrides; "none" selects bf16.
    quant = os.environ.get("BENCH_QUANT", "int8")
    if quant and quant != "none":
        import dataclasses
        spec = dataclasses.replace(spec, quant=quant)
    # KV-cache quantization (engine/kv_quant.py): BENCH_QUANT_KV=int8
    # opts in; "none"/unset keeps bf16 KV so committed baselines stay
    # like-for-like. The kv-quant config is embedded in detail either
    # way so scripts/perf_gate.py can tell the configurations apart.
    quant_kv = os.environ.get("BENCH_QUANT_KV", "none")
    quant_kv = None if quant_kv in ("", "none") else quant_kv
    page = 16
    maxp = 64  # up to 1024 tokens/seq
    seqs = BATCH
    if mode == "mixed":
        # Long prompts need room (LONG_ISL + outputs), and the longs ride
        # ALONGSIDE the steady batch. Page budget: steady seqs at their
        # full length + the longs at theirs.
        maxp = max(maxp, -(-(LONG_ISL + 64) // page))
        seqs = BATCH + LONG_N
    steady_pages = BATCH * (-(-(ISL + OSL) // page))
    config = EngineConfig(
        model=spec, page_size=page,
        num_pages=(steady_pages + LONG_N * maxp + 16 if mode == "mixed"
                   else BATCH * 64 + 16),
        max_pages_per_seq=maxp, max_num_seqs=seqs,
        prefill_buckets=(128, 256, 512, 1024),
        max_prefill_tokens=int(os.environ.get("BENCH_MAX_PREFILL", "1024")),
        attention_backend=os.environ.get("BENCH_ATTN", "auto"),
        decode_window=int(os.environ.get("BENCH_WINDOW", "32")),
        pipeline_depth=int(os.environ.get("BENCH_DEPTH", "4")),
        prefill_chunk_tokens=os.environ.get("BENCH_CHUNK_TOKENS", "auto")
        if not os.environ.get("BENCH_CHUNK_TOKENS", "auto").isdigit()
        else int(os.environ["BENCH_CHUNK_TOKENS"]),
        quant_kv=quant_kv)
    engine = TPUEngine(config)
    engine.start()
    rng = np.random.default_rng(0)

    if mode == "prefill":
        # Worker-level prefill bench: the disaggregated prefill worker's
        # serving pattern (every request is prompt -> first token). The
        # engine dispatches NO decode windows for these slots.
        await run_round(engine, spec, rng, "warmup", osl=1)
        pres = [await run_round(engine, spec, rng, f"prefill{i}", osl=1)
                for i in range(max(3, ROUNDS))]
        by_el = sorted(r["elapsed_s"] for r in pres)
        med_round = sorted(pres, key=lambda r: r["elapsed_s"])[len(pres) // 2]
        med = BATCH * ISL / by_el[len(by_el) // 2]
        perf = perf_snapshot(engine)
        engine.stop()
        print(json.dumps({
            "metric": f"prefill_tok_s_per_chip_{spec.name}_bs{BATCH}"
                      f"_isl{ISL}",
            "value": round(med, 1),
            "unit": "tok/s/chip",
            "vs_baseline": round(
                med / (BATCH * ISL / by_el[0]), 3) if by_el[0] else 0.0,
            "detail": {
                "vs_baseline_semantics": "median/best across rounds "
                                         "(stability; 1.0 = no outliers)",
                "rounds": [round(BATCH * ISL / e, 1) for e in by_el],
                "ttft_p99_ms": round(med_round["ttft_p99_ms"], 1),
                "quant": spec.quant,
                "quant_kv": config.quant_kv,
                "platform": device.platform,
                "device": str(device),
                "perf": perf,
            },
        }))
        return

    if mode == "mixed":
        # Warm every bucket incl. the chunk/history variants, then run
        # the interference rounds; the headline is the steady decoders'
        # gap p99 DURING long-prompt prefill.
        await run_round(engine, spec, rng, "warmup", batch=4, osl=8)
        warm = await run_mixed(engine, spec, rng)  # compiles long path
        rounds_m = [await run_mixed(engine, spec, rng)
                    for _ in range(max(1, ROUNDS))]
        med = sorted(rounds_m,
                     key=lambda r: r["itl_gap_p99_ms_during_prefill"])[
                         len(rounds_m) // 2]
        perf = perf_snapshot(engine)
        engine.stop()
        steady_p99 = med["itl_gap_p99_ms_steady"]
        during_p99 = med["itl_gap_p99_ms_during_prefill"]
        print(json.dumps({
            "metric": f"mixed_itl_gap_p99_ms_during_prefill_{spec.name}"
                      f"_bs{BATCH}_long{LONG_ISL}x{LONG_N}",
            "value": round(during_p99, 3),
            "unit": "ms",
            # 1.0 = stall-free ideal (interference-window gap p99 equals
            # the steady-state gap p99); the pre-rework engine stalled
            # decoders for the whole long prefill.
            "vs_baseline": round(steady_p99 / during_p99, 3)
            if during_p99 else 0.0,
            "detail": {
                "vs_baseline_semantics": "steady gap p99 / during-prefill "
                                         "gap p99 (1.0 = no decode stall "
                                         "from long-prompt prefill)",
                "rounds": [
                    {k: round(v, 3) for k, v in r.items()}
                    for r in rounds_m],
                "warmup_round": {k: round(v, 3) for k, v in warm.items()},
                "prefill_chunk_tokens": engine.prefill_chunk_tokens,
                "decode_window": config.decode_window,
                "quant": spec.quant,
                "quant_kv": config.quant_kv,
                "platform": device.platform,
                "device": str(device),
                "perf": perf,
            },
        }))
        return

    t0 = time.monotonic()
    await run_round(engine, spec, rng, "warmup")  # compiles all buckets
    warm_s = time.monotonic() - t0
    rounds = [await run_round(engine, spec, rng, f"steady{i}")
              for i in range(max(1, ROUNDS))]
    # Median round by decode throughput; spread shows run-to-run variance
    # (host contention) so one lucky/unlucky round can't carry the claim.
    by_tok_s = sorted(rounds, key=lambda r: r["decode_tok_s"])
    steady = by_tok_s[len(by_tok_s) // 2]
    spread = {
        "rounds": len(rounds),
        "decode_tok_s": [round(r["decode_tok_s"], 1) for r in rounds],
        "ttft_p99_ms": [round(r["ttft_p99_ms"], 1) for r in rounds],
        "ttft_p99_ms_worst": round(max(r["ttft_p99_ms"] for r in rounds), 1),
        "decode_tok_s_min": round(by_tok_s[0]["decode_tok_s"], 1),
        "decode_tok_s_max": round(by_tok_s[-1]["decode_tok_s"], 1),
    }
    # Concurrency sweep (VERDICT r2 weak #8: one ISL/OSL/bs point isn't
    # steady-state evidence): same engine, lower concurrency.
    sweep = {}
    for bs in (8, 16):
        r = await run_round(engine, spec, rng, f"bs{bs}", batch=bs)
        sweep[f"bs{bs}_decode_tok_s"] = round(r["decode_tok_s"], 1)
    # MEASURED prefill throughput: max_tokens=1 rounds — the clock stops
    # when every first token has arrived (not the TTFT-derived proxy).
    # Median of 3: one outlier round must not carry (or sink) the claim.
    pres = [await run_round(engine, spec, rng, f"prefill{i}", osl=1)
            for i in range(3)]
    pre_elapsed = sorted(r["elapsed_s"] for r in pres)
    prefill_tok_s_measured = BATCH * ISL / pre_elapsed[1]
    prefill_spread = [round(BATCH * ISL / e, 1) for e in pre_elapsed]
    perf = perf_snapshot(engine)
    engine.stop()

    # Roofline context: one decode step must read all weights once.
    step_floor_ms = spec.weight_read_step_ms(device_peaks(device).hbm_gbps)
    roofline_tok_s = BATCH / (step_floor_ms / 1e3)
    tok_s = steady["decode_tok_s"]
    print(json.dumps({
        "metric": f"decode_tok_s_per_chip_{spec.name}_bs{BATCH}_isl{ISL}",
        "value": round(tok_s, 2),
        "unit": "tok/s/chip",
        # Fraction of this chip's weight-read roofline for this
        # batch — the honest same-hardware baseline (see module docstring).
        "vs_baseline": round(tok_s / roofline_tok_s, 3),
        "detail": {
            "vs_baseline_semantics": "fraction of the weight-read "
                                     "roofline on this chip (the "
                                     "reference publishes no comparable "
                                     "absolute number; BASELINE.md)",
            "ttft_p50_ms": round(steady["ttft_p50_ms"], 1),
            "ttft_p99_ms": round(steady["ttft_p99_ms"], 1),
            "itl_mean_ms": round(steady["itl_mean_ms"], 3),
            "itl_gap_p99_ms": round(steady["itl_gap_p99_ms"], 3),
            "spread": spread,
            "osl": OSL,
            "round_s": round(steady["elapsed_s"], 2),
            "prefill_tok_s": round(prefill_tok_s_measured, 1),
            "prefill_tok_s_rounds": prefill_spread,
            "sweep": sweep,
            "warmup_s": round(warm_s, 1),
            "roofline_tok_s_weight_read": round(roofline_tok_s, 0),
            # Cross-hardware, cross-model ratio vs the reference's only
            # absolute figure (51.22 tok/s/GPU decode ITL example on a
            # 70B-class TP4 config) — apples-to-oranges, context only.
            "ref_example_ratio": round(tok_s / 51.22, 1),
            "decode_window": config.decode_window,
            "pipeline_depth": config.pipeline_depth,
            "quant": spec.quant,
            "quant_kv": config.quant_kv,
            "platform": device.platform,
            "device": str(device),
            "perf": perf,
        },
    }))


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("serve", "prefill", "mixed"),
                    default=os.environ.get("BENCH_MODE", "serve"),
                    help="serve: full continuous-batching bench (default); "
                         "prefill: disagg prefill-worker pattern "
                         "(max_tokens=1 bursts, headline = prefill tok/s); "
                         "mixed: long prompts injected mid-steady-decode "
                         "(headline = decode itl_gap_p99 during prefill "
                         "interference; also BENCH_MIXED=1)")
    args = ap.parse_args()
    if os.environ.get("BENCH_MIXED") == "1":
        args.mode = "mixed"
    asyncio.run(main_async(args.mode))


if __name__ == "__main__":
    main()
