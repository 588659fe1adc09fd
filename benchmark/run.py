#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell of BENCHMARK.json, one run: the cell's configuration served through
``dynamo_tpu.launch`` over HTTP inside this process (the chip belongs to one
process), with weights made on the device from ONE draw
(``lib/weights.py CELL_WEIGHTS_SEED``); the cell's traffic, one replayed trace
with its words, offered by a client process of its own (``--seed`` draws the
check's prompts); the last line of standard output is the result.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, with a profiler trace of a few steady seconds.

This file knows no cell, configuration, traffic mix or metric by name: it
finds ``benchmark/configs/<config>.json``, ``benchmark/traffic/<mix>.json``,
``benchmark/cells/<cell>.json``, ``benchmark/generators/<generator>.py`` and
``benchmark/layer_metrics/<metric>.py`` from the names in BENCHMARK.json,
and ``benchmark/references/<name>.py`` and ``benchmark/rooflines/<name>.py``
where the configuration's file names them.

Without a TPU (or with fewer chips than the cell asks for) it exits with
code 2 and prints nothing, unless ``--rehearse-cpu`` is given: then the same
code runs a toy model on the CPU backend and the last line carries no
device metric.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import manifest, measure, weights  # noqa: E402
from benchmark.lib import tokenizer as bench_tok  # noqa: E402
from benchmark.lib.lengths import prompt_ids  # noqa: E402

NO_DEVICE = 2


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--probe-faults", action="store_true",
                    help="after the window, say how far a skipped layer "
                         "moves the logprobs the check bounds")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy model on the CPU backend; never a device "
                         "number")
    return ap.parse_args(argv)


def emit(kind: str, **fields) -> None:
    """An earlier output line: one JSON object, never the last line."""
    print(json.dumps({"line": kind, **fields}, default=str), flush=True)


def die(code: int, message: str) -> None:
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    raise SystemExit(code)


# -- the cell, cut down for a CPU rehearsal ----------------------------------

def rehearsal_cut(files: dict) -> dict:
    toy = manifest.load_json(os.path.join(manifest.BENCH, "rehearsal",
                                          "tiny.json"))
    div = toy["length_divisor"]
    params = json.loads(json.dumps(files["params"]))
    for key in ("prompt_tokens", "output_tokens"):
        dist = params.get(key, {})
        for field in ("median", "min", "max", "value"):
            if field in dist:
                dist[field] = max(12 if key == "prompt_tokens" else 4,
                                  dist[field] // div)
    if "clients" in params:
        params["clients"] = min(params["clients"], toy["max_clients"])
    if "rate_rps" in params:
        params["rate_rps"] = min(params["rate_rps"], toy["max_rate_rps"])
    for key, cap in (("ramp_seconds", 3), ("preroll_seconds", 2),
                     ("drain_seconds", 30)):
        if key in params:
            params[key] = min(params[key], cap)
    config = dict(files["config"])
    # The configuration's own toy where its block is not the dense one.
    config.update(config.pop("rehearsal_model", None) or toy["model"])
    launch = dict(config.get("launch", {}))
    launch.update(toy["launch_extra"])
    config["launch"] = launch
    return {**files, "params": params, "config": config}


def launch_argv(name: str, config: dict, tokenizer_path: str) -> list[str]:
    argv = ["in=http", "out=tpu", "--model", name, "--tokenizer",
            tokenizer_path, "--http-host", "127.0.0.1", "--http-port", "0"]
    for key, value in config.get("launch", {}).items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            argv.append(flag)
        elif value is not False and value is not None:
            argv += [flag, str(value)]
    return argv


# -- counters -------------------------------------------------------------------

_XLA_BUILDS = {"compiled": 0}


def _count_backend_compiles() -> None:
    import jax

    def on_duration(event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _XLA_BUILDS["compiled"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def snapshot(engine) -> dict:
    from dynamo_tpu.engine import perf
    reg = perf.get_registry()
    return {"t": time.monotonic(), "compiles_total": reg.compiles_total,
            "unexpected_recompiles": reg.unexpected_total,
            "windows_total": reg.windows_total,
            "window_tokens_total": reg.window_tokens_total,
            "window_seconds_total": reg.window_seconds_total,
            "xla_programs_compiled": _XLA_BUILDS["compiled"],
            "compile_cache": perf.compile_cache_status(),
            "prefix_hit_blocks": engine.prefix_hit_blocks,
            "preempt_count": engine.preempt_count,
            "allocator": engine.allocator.stats()}


def occupancy(engine) -> dict:
    live = [i for i, r in enumerate(engine.slot_req)
            if r is not None and not r.prefilling]
    return {"t": time.monotonic(),
            "pages_active": engine.allocator.stats()["pages_active"],
            "rows": len(live),
            "context": int(sum(int(engine.disp_seq_lens[i]) for i in live)),
            "max_context": int(max((int(engine.disp_seq_lens[i])
                                    for i in live), default=0))}


async def sample_until(engine, stop: asyncio.Event, out: list,
                       period: float = 0.2) -> None:
    while not stop.is_set():
        out.append(occupancy(engine))
        try:
            await asyncio.wait_for(stop.wait(), period)
        except asyncio.TimeoutError:
            pass


def widest_row_shares(reading) -> dict:
    """Share of the window's occupancy samples by the widest live row's
    context, in powers of two: the window program gathers that row's page
    bucket for every row."""
    inside = reading.samples_in(reading.t0, reading.t1)
    shares: dict = {}
    for s in inside:
        top = 1
        while top < s["max_context"]:
            top *= 2
        shares[top] = shares.get(top, 0) + 1
    return {str(k): v / len(inside) for k, v in sorted(shares.items())}


def program_spans() -> list[dict]:
    from dynamo_tpu.runtime import tracing
    rec = tracing.get_recorder()
    return [{"name": s.name, "start": s.start_mono, "end": s.end_mono}
            for s in rec.snapshot()[0] if s.end_mono is not None]


# -- the profiler ---------------------------------------------------------------

def _start_trace(trace_dir: str) -> int:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    mono_ns = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(f"bench.mark mono_ns={mono_ns}"):
        time.sleep(0.001)
    return mono_ns


def _stop_trace() -> int:
    import jax
    mono_ns = time.monotonic_ns()
    jax.profiler.stop_trace()
    return mono_ns


async def capture_trace(trace_dir: str, start_at: float, seconds: float
                        ) -> dict:
    """Trace ``seconds`` of the steady window. Starting and stopping the
    profiler run in a worker thread: the server's loop keeps serving."""
    import shutil
    from benchmark.lib import trace_reduce
    shutil.rmtree(trace_dir, ignore_errors=True)
    await asyncio.sleep(max(start_at - time.monotonic(), 0.0))
    lo_mono = await asyncio.to_thread(_start_trace, trace_dir)
    await asyncio.sleep(seconds)
    hi_mono = await asyncio.to_thread(_stop_trace)
    path = trace_reduce.find_xplane(trace_dir)
    trace = await asyncio.to_thread(trace_reduce.load, path)
    offset = trace_reduce.clock_offset_ns(trace)
    span = ((lo_mono + offset, hi_mono + offset) if offset is not None
            else trace_reduce.window_ns(trace))
    return {"trace": trace, "span_ns": span, "file": path,
            "bytes": os.path.getsize(path), "clock_offset_ns": offset,
            "mono": (lo_mono / 1e9, hi_mono / 1e9)}


# -- correctness ------------------------------------------------------------------

async def check_logprobs(srv, judged: dict, seed: int, overhead: int,
                         vocab: int, prompts: int = 4,
                         prompt_tokens: int = 64, n_gen: int = 16) -> dict:
    """Check (a): served logprobs of seeded prompts against the plain
    float32 forward over the same device-resident parameters, by the
    reference and the tolerance of the configuration
    (``reference.for_config``). The verdict keeps what was served, for
    probe_faults()."""
    from benchmark.lib import reference
    runner = srv.engine.runner
    served, full, rows, taps = [], [], [], []
    shape_ok = True
    for k in range(prompts):
        ids = prompt_ids(seed, 900_000 + k, prompt_tokens - overhead, vocab,
                         bench_tok.RESERVED)
        body = await srv.chat(bench_tok.text_of(ids), n_gen, logprobs=True,
                              top_logprobs=1)
        tap = body["_tap"][0]
        got = [e["logprob"] for e in
               body["choices"][0]["logprobs"]["content"]]
        t0 = time.monotonic()
        ref = judged["logprobs"](runner.params, runner.spec, tap["prompt"],
                                 tap["tokens"])
        shape_ok = shape_ok and (len(got) == n_gen == len(tap["tokens"])
                                 and len(tap["prompt"]) == prompt_tokens)
        served += got
        full += ref
        taps.append((tap["prompt"], tap["tokens"]))
        rows.append({**reference.diff_stats(got, ref),
                     "served_first": got[:3], "reference_first": ref[:3],
                     "reference_seconds": time.monotonic() - t0,
                     "usage": body.get("usage")})
    verdict = reference.judge(served, full, judged["allowed"])
    verdict["ok"] = bool(verdict["ok"] and shape_ok)
    emit("reference", **verdict, module=judged["module"], prompts=rows)
    return {**verdict, "_served": served, "_full": full, "_taps": taps}


def probe_faults(runner, judged: dict, checked: dict) -> None:
    """``--probe-faults``, after the window: how far the plain forward with
    its first or its last layer left out is from what was served, in the
    statistics the check bounds. Says what the tolerance would catch; costs
    two more forwards a prompt, so it is no part of a run the driver makes."""
    from benchmark.lib import reference
    last = runner.spec.num_layers - 1
    for label, layer in (("first_layer_skipped", 0),
                         ("last_layer_skipped", last)):
        faulty = []
        for prompt, tokens in checked["_taps"]:
            faulty += judged["logprobs"](
                runner.params, runner.spec, prompt, tokens, skip_layer=layer)
        emit("fault_probe", fault=label,
             would_pass=reference.judge(checked["_served"], faulty,
                                        judged["allowed"])["ok"],
             served_vs_faulty=reference.diff_stats(checked["_served"],
                                                   faulty),
             reference_vs_faulty=reference.diff_stats(checked["_full"],
                                                      faulty))


def timed_words(req: dict, overhead: int, vocab: int) -> list[int]:
    """The word ids of a timed request, exactly the length drawn (template
    included): the replayed trace's, drawn from the cell's constant whatever
    ``--seed`` is (lib/weights.py says why)."""
    return req.get("prompt_ids") or prompt_ids(
        weights.CELL_WEIGHTS_SEED, req["id"],
        max(1, req["prompt_len"] - overhead), vocab, bench_tok.RESERVED)


def check_requests(reading: measure.Reading) -> dict:
    """Check (b): every completed request carried the tokens asked for, the
    prompt had the length drawn, and the words counted on the stream are
    the tokens the server says it sent."""
    bad = []
    for r in reading.records:
        if not r["ok"]:
            continue
        usage = r["usage"] or {}
        if (usage.get("completion_tokens") != r["max_tokens"]
                or usage.get("prompt_tokens") != r["prompt_len"]
                or sum(r["chunk_n"]) != r["max_tokens"]):
            bad.append({"id": r["id"], "usage": usage,
                        "asked": [r["prompt_len"], r["max_tokens"]],
                        "words_streamed": sum(r["chunk_n"])})
    return {"ok": not bad, "bad": bad[:5], "n_bad": len(bad)}


def arrays_on(tree, platform: str) -> bool:
    import jax
    return all(d.platform == platform
               for leaf in jax.tree.leaves(tree) for d in leaf.devices())


# -- one run ------------------------------------------------------------------------

async def run_cell(args, files: dict, man: dict, jax) -> dict:
    from benchmark.lib import reference, roofline, server
    from dynamo_tpu.engine import perf

    cell, config = files["cell"], files["config"]
    name = cell["config"]
    # What the configuration names is found before anything is launched.
    judged = reference.for_config(config)
    counted_by = roofline.counting(config)[1]
    seconds = float(args.seconds)
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    launch = config.get("launch", {})
    spec = server.model_spec(name, config, launch.get("quant"))
    tok_path = bench_tok.write_tokenizer(os.path.join(
        manifest.RUN_DIR, f"tokenizer-{spec.vocab_size}.json"),
        spec.vocab_size)

    generator = manifest.load_module("generators", files["generator"])
    plan = generator.plan(files["params"], args.seed, seconds)
    open_loop = plan["mode"] == "open"
    all_reqs = (plan["requests"] if open_loop
                else [r for seq in plan["sequences"] for r in seq])
    shapes = server.WarmShapes(
        max_prompt=max(r["prompt_len"] for r in all_reqs),
        max_context=max(r["prompt_len"] + r["max_tokens"]
                        for r in all_reqs),
        max_batch=(server.PREFILL_GROUP_ROWS if open_loop
                   else min(server.PREFILL_GROUP_ROWS,
                            len(plan["sequences"]))))

    # One draw of the weights a cell, whatever --seed is (lib/weights.py).
    seams = server.Seams(name, spec, weights.CELL_WEIGHTS_SEED, shapes)
    seams.install()
    try:
        argv = launch_argv(name, config, tok_path)
        emit("launch", argv=argv, cell=cell["name"], seed=args.seed,
             weights_seed=seams.seed, seconds=seconds, trace=args.trace,
             generator=files["generator"],
             requests_planned=len(all_reqs), shapes=vars(shapes),
             reference=judged["module"], roofline=counted_by,
             compile_cache=perf.compile_cache_status())
        async with server.Server(argv) as srv:
            return await _serve_and_measure(args, files, man, jax, srv,
                                            seams, plan, open_loop, spec,
                                            judged)
    finally:
        seams.restore()


async def _serve_and_measure(args, files, man, jax, srv, seams, plan,
                             open_loop, spec, judged) -> dict:
    from benchmark.lib import roofline, trace_reduce
    from dynamo_tpu.engine import perf

    cell, config = files["cell"], files["config"]
    seconds = float(plan["seconds"])
    eng, runner = srv.engine, srv.engine.runner
    platform = jax.devices()[0].platform
    overhead = bench_tok.template_overhead(
        srv.launch_argv[srv.launch_argv.index("--tokenizer") + 1],
        srv.chat_template)
    emit("server", startup_s=srv.startup_s, timings=seams.timings,
         decode_window=eng.decode_window,
         pipeline_depth=eng.config.pipeline_depth,
         prefill_chunk_tokens=eng.prefill_chunk_tokens,
         max_num_seqs=eng.config.max_num_seqs, num_pages=runner.num_pages,
         attention_backend=runner.attention_backend,
         template_overhead_tokens=overhead, hbm=runner.hbm_stats(),
         memory=runner.memory_breakdown(),
         compile_cache=perf.compile_cache_status())

    requests = (plan["requests"] if open_loop
                else [r for seq in plan["sequences"] for r in seq])
    prompt_keys = {}
    for req in requests:
        ids = timed_words(req, overhead, spec.vocab_size)
        req["content"] = bench_tok.text_of(ids)
        prompt_keys[req["id"]] = tuple(ids[:6])

    checked = await check_logprobs(srv, judged, args.seed, overhead,
                                   spec.vocab_size)
    on_device = all(arrays_on(getattr(runner, n), platform)
                    for n in ("params", "k_cache", "v_cache"))
    # The cache holds what the configuration says: an int8 cache rounds as
    # coarsely as bfloat16 does, so no tolerance on logprobs tells it from
    # the bf16 path, and its type is checked instead.
    kv_types = sorted({str(leaf.dtype) for n in ("k_cache", "v_cache")
                       for leaf in jax.tree.leaves(getattr(runner, n))})
    kv_as_configured = (("int8" in kv_types)
                        == (config.get("launch", {}).get("quant_kv")
                            == "int8"))

    # The client: a process of its own; the window opens at t0 on the one
    # CLOCK_MONOTONIC both processes read.
    plan_path = os.path.join(manifest.RUN_DIR, "plan.json")
    results_path = os.path.join(manifest.RUN_DIR, "results.json")
    if os.path.exists(results_path):
        os.remove(results_path)
    t0 = time.monotonic() + plan["lead_seconds"] + 1.5
    t1 = t0 + seconds
    plan.update(base=srv.base, model=srv.model, t0=t0)
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump(plan, fh)
    at_setup = snapshot(eng)
    emit("setup", until_window_s=t0 - _T_START,
         compile_cache=at_setup["compile_cache"],
         xla_programs_compiled=at_setup["xla_programs_compiled"],
         registry=at_setup["compiles_total"])
    client = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(manifest.BENCH, "lib",
                                     "client_proc.py"),
        plan_path, results_path, stdout=asyncio.subprocess.DEVNULL)
    samples: list = []
    stop = asyncio.Event()
    sampler = asyncio.create_task(sample_until(eng, stop, samples))
    tracer = None
    try:
        if args.trace:
            trace_s = min(4.0, seconds / 3.0)
            tracer = asyncio.create_task(capture_trace(
                os.path.join(manifest.RUN_DIR, "trace"),
                t0 + min(10.0, seconds / 3.0), trace_s))
        await asyncio.sleep(max(t0 - time.monotonic(), 0.0))
        before = snapshot(eng)
        await asyncio.sleep(max(t1 - time.monotonic(), 0.0))
        after = snapshot(eng)
        metrics_text = await srv.get_text("/metrics")
        limit = seconds + plan.get("drain_seconds", 0.0) + 120.0
        try:
            await asyncio.wait_for(client.wait(), limit)
        except asyncio.TimeoutError:
            client.kill()
            await client.wait()
            raise server.BenchError("the client did not end in time")
        traced = await tracer if tracer is not None else None
    finally:
        stop.set()
        await sampler
        if client.returncode is None:
            client.kill()
            await client.wait()
        if tracer is not None and not tracer.done():
            tracer.cancel()
    if client.returncode != 0:
        raise server.BenchError(f"client exited {client.returncode}")
    # Streams the client cut are cancelled by the server when their
    # connections close; leave once the engine has let go of their rows.
    for _ in range(100):
        if all(r is None for r in eng.slot_req):
            break
        await asyncio.sleep(0.1)
    results = manifest.load_json(results_path)

    emissions: dict = {}
    for call in srv.tap.calls:
        if call["t_out"]:
            emissions.setdefault(tuple(call["prompt"][2:8]), []).append(
                call["t_out"][0])
    dev = jax.devices()[0]
    peaks = None
    if platform != "cpu":
        peaks = roofline.peaks_of(dev.device_kind, manifest.load_json(
            os.path.join(manifest.BENCH, "peaks.json")))
    reading = measure.Reading(
        records=results["records"], open_loop=open_loop, t0=t0, t1=t1,
        t_end=results["ended"], before=before, after=after, samples=samples,
        spans=program_spans(), emissions=emissions, prompt_keys=prompt_keys,
        engine={"decode_window": eng.decode_window,
                "num_pages": runner.num_pages, "tp": eng.config.tp,
                "quant": spec.quant,
                "max_num_seqs": eng.config.max_num_seqs},
        model=config, peaks=peaks, metrics_text=metrics_text)
    if traced is not None:
        reading.trace = traced["trace"]
        reading.trace_span_ns = traced["span_ns"]
        reading.trace_mono = traced["mono"]
        emit("trace", file=traced["file"], bytes=traced["bytes"],
             clock_offset_ns=traced["clock_offset_ns"],
             planes={k: {ln: len(ev) for ln, ev in v.items()}
                     for k, v in traced["trace"].items()},
             programs=trace_reduce.program_times(traced["trace"]),
             program_runs_ms=trace_reduce.program_runs_ms(traced["trace"]))

    emit("window", **measure.summary(reading),
         compiles_in_window=after["compiles_total"]
         - before["compiles_total"],
         xla_programs_compiled_in_window=after["xla_programs_compiled"]
         - before["xla_programs_compiled"],
         # the ramp is set-up: what still compiles there in a checkout's
         # first run is no fault, and says why that run's ramp was slower
         xla_programs_compiled_in_ramp=before["xla_programs_compiled"]
         - at_setup["xla_programs_compiled"],
         unexpected_recompiles=after["unexpected_recompiles"],
         windows=after["windows_total"] - before["windows_total"],
         preempted=after["preempt_count"] - before["preempt_count"],
         prefix_hit_blocks=after["prefix_hit_blocks"]
         - before["prefix_hit_blocks"],
         allocator=after["allocator"],
         occupancy_mean_rows=(sum(s["rows"] for s in samples)
                              / max(1, len(samples))),
         # Who stood still, if the streams did (about one run in twenty
         # stalls for 2 to 9 s, cause not found): the engine's widest
         # distance between two window dispatches, this loop's between
         # two samples, and how late the client's own loop ever woke.
         stalls={"engine_dispatch_gap_max_s": eng.decode_stall_max_s,
                 "server_loop_gap_max_s": max(
                     (b["t"] - a["t"] for a, b in zip(samples, samples[1:])),
                     default=None),
                 "client_loop_late_max_s": (results.get("heartbeat") or {}
                                            ).get("late_max_s")},
         widest_row_tokens_share=widest_row_shares(reading))

    by_request = check_requests(reading)
    no_compiles = (after["compiles_total"] == before["compiles_total"]
                   and after["unexpected_recompiles"] == 0)
    measured = reading.measured()
    failed = sum(1 for r in measured if not r["ok"])
    correct = bool(checked["ok"] and by_request["ok"] and no_compiles
                   and on_device and kv_as_configured and measured)
    emit("checks", logprobs={k: v for k, v in checked.items()
                             if not k.startswith("_")},
         requests=by_request, no_compiles_in_window=no_compiles,
         arrays_on_device=on_device, kv_cache_types=kv_types,
         kv_cache_as_configured=kv_as_configured, platform=platform)
    if args.probe_faults:
        probe_faults(runner, judged, checked)
    # Every number ``correct`` compared, beside its limit (main() prints
    # them last on standard error; they are the result line's last key).
    compared = {f"logprob_{k}_nats": {"value": checked[f"{k}_nats"],
                                      "limit": checked["allowed_nats"][k]}
                for k in checked["allowed_nats"]}
    inside = all(pair["value"] <= pair["limit"] for pair in compared.values())
    for name, value in (
            # a count or a sign of the served logprobs that is off
            ("logprobs_malformed", int(inside and not checked["ok"])),
            ("requests_off_what_was_asked", by_request["n_bad"]),
            ("compiles_in_window",
             after["compiles_total"] - before["compiles_total"]),
            ("unexpected_recompiles", after["unexpected_recompiles"]),
            ("arrays_off_device", int(not on_device)),
            ("kv_cache_not_as_configured", int(not kv_as_configured)),
            ("no_request_measured", int(not measured))):
        compared[name] = {"value": value, "limit": 0}

    entries = manifest.metrics_of(
        man, "per_layer" if args.trace else "end_to_end", cell["name"])
    values: dict = {}
    for entry in entries:
        if entry["name"] == "setup_s":
            value = t0 - _T_START
        elif args.trace:
            value = manifest.load_module(
                "layer_metrics", entry["name"]).read(reading)
        else:
            fn = measure.END_TO_END.get(entry["name"]) or manifest.load_module(
                "end_to_end", entry["name"]).read
            value = fn(reading)
        if value is not None:
            values[entry["name"]] = {"value": value, "unit": entry["unit"]}

    used = list(runner.mesh.devices.flat)
    peak_mem = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in used), default=0)
    device = {"platform": platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": int(peak_mem)}
    out = {"correct": correct, "attempted": len(measured), "failed": failed,
           "metrics": values, "device": device}
    if args.trace and reading.trace is not None and reading.trace_span_ns:
        lo, hi = reading.trace_span_ns
        busy = trace_reduce.busy_seconds(reading.trace, lo, hi)
        if busy:
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = (hi - lo) / 1e9
            emit("device_busy", per_device=busy, window_s=device["window_s"])
        shown = measure.breakdown(reading)
        if shown:
            out["breakdown"] = shown
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        man = manifest.load_manifest()
        files = manifest.cell_files(man, args.workload)
    except (OSError, manifest.ManifestError) as exc:
        die(2, str(exc))
    if args.seconds is None:
        args.seconds = int(man["run_seconds"])
    chips = int(files["cell"]["chips"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
        files = rehearsal_cut(files)
    # The program's flight recorder writes to /tmp/dtpu-flight unless told
    # otherwise; a run keeps what it writes inside its checkout.
    os.environ.setdefault("DTPU_FLIGHT_DIR",
                          os.path.join(manifest.RUN_DIR, "flight"))
    try:
        import dynamo_tpu  # noqa: F401 — the system under test
    except ImportError as exc:
        die(2, f"the system under test is not in this checkout: {exc}")
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as exc:
        die(NO_DEVICE, f"JAX found no device: {exc}")
    platform = devices[0].platform
    if not args.rehearse_cpu and platform != "tpu":
        die(NO_DEVICE, f"no TPU: JAX reports platform {platform!r} "
                       f"(--rehearse-cpu runs the harness on the CPU)")
    if len(devices) < chips:
        die(NO_DEVICE, f"cell {args.workload!r} needs {chips} chips, "
                       f"JAX reports {len(devices)}")
    _count_backend_compiles()
    out = asyncio.run(run_cell(args, files, man, jax))
    compared = out.pop("compared")
    if args.rehearse_cpu:
        # A CPU number is never written under a device metric's name.
        emit("rehearsal.cpu_numbers_not_device_metrics", **out["metrics"])
        out["metrics"] = {}
        out["rehearsal"] = True
    for name, pair in compared.items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    out["compared"] = compared  # the result line's last key
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
