"""Beyond a cell's check (64-token prompts): ONE long prompt served through
the HTTP path and a few tokens decoded, its logprobs against the
configuration's reference over the same device-resident parameters, and
against the reference's named controls, which must fall outside the
tolerance. For a configuration with sliding-window layers the prompt passes
the window, so the served path's window masks (chunk prefill over history,
the decode kernel's walk) are what is checked, and ``use_window=false`` is
the control. A builder's chip run, not a run the driver makes:

    python3 benchmark/long_prompt.py --workload <cell> --seed <n> \\
        [--prompt-tokens 5000] [--decode 16] [--control use_window=false]

Same server, seams and weight law as benchmark/run.py, the weights drawn
from this tool's own ``--seed`` (a dozen seeds are a dozen draws; run.py
serves a cell's ONE, lib/weights.py); the warm-up covers one
row of every prefill bucket and page-table width up to the prompt. Prints
one JSON line a step and the verdict last; exits 1 where the served
logprobs fall outside the tolerance or a control inside it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.lib import tokenizer as bench_tok  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-tokens", type=int, default=5000)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--control", action="append", default=[],
                    help="key=false or key=<name>: a switch of the "
                         "reference's control_logprobs (repeatable)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the configuration's toy on the CPU backend: "
                         "control flow only, no device number")
    return ap.parse_args(argv)


async def check(args, files: dict) -> dict:
    from benchmark.lib import reference, server
    config = files["config"]
    name = files["cell"]["config"]
    judged = reference.for_config(config)
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    spec = server.model_spec(name, config, config.get("launch", {}).get(
        "quant"))
    tok_path = bench_tok.write_tokenizer(os.path.join(
        manifest.RUN_DIR, f"tokenizer-{spec.vocab_size}.json"),
        spec.vocab_size)
    shapes = server.WarmShapes(
        max_prompt=args.prompt_tokens,
        max_context=args.prompt_tokens + args.decode, max_batch=1)
    seams = server.Seams(name, spec, args.seed, shapes)
    seams.install()
    try:
        argv = run.launch_argv(name, config, tok_path)
        async with server.Server(argv) as srv:
            eng, runner = srv.engine, srv.engine.runner
            overhead = bench_tok.template_overhead(tok_path,
                                                   srv.chat_template)
            run.emit("server", startup_s=srv.startup_s,
                     timings=seams.timings, decode_window=eng.decode_window,
                     prefill_chunk_tokens=eng.prefill_chunk_tokens,
                     num_pages=runner.num_pages,
                     attention_backend=runner.attention_backend)
            checked = await run.check_logprobs(
                srv, judged, args.seed, overhead, spec.vocab_size,
                prompts=1, prompt_tokens=args.prompt_tokens,
                n_gen=args.decode)
            verdict = {"served_ok": bool(checked["ok"]), "controls": {}}
            module = manifest.load_module("references", config["reference"]) \
                if args.control else None
            for item in args.control:
                key, _, value = item.partition("=")
                switches = {key: {"true": True, "false": False}.get(
                    value.lower(), value)}
                wrong = []
                for prompt, tokens in checked["_taps"]:
                    wrong += module.control_logprobs(
                        runner.params, runner.spec, prompt, tokens,
                        **switches)
                against = reference.judge(checked["_served"], wrong,
                                          judged["allowed"])
                run.emit("control", switch=item, would_pass=against["ok"],
                         served_vs_control=reference.diff_stats(
                             checked["_served"], wrong),
                         reference_vs_control=reference.diff_stats(
                             checked["_full"], wrong))
                verdict["controls"][item] = not against["ok"]
            return verdict
    finally:
        seams.restore()


def main(argv=None) -> int:
    args = parse_args(argv)
    files = manifest.cell_files(manifest.load_manifest(), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        files = run.rehearsal_cut(files)
    os.environ.setdefault("DTPU_FLIGHT_DIR",
                          os.path.join(manifest.RUN_DIR, "flight"))
    verdict = asyncio.run(check(args, files))
    verdict["ok"] = bool(verdict["served_ok"]
                         and all(verdict["controls"].values()))
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
