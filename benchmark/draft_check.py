"""What a cell's check cannot see: a WRONG prediction module. Served logprobs
are the target's (a rejected draft is simply not emitted), so a module that
drafted nonsense would pass check (a) and only cost time. This serves the
check's prompts through the HTTP path with drafting on, keeps every draft the
window program verified (``engine.draft_log``), and holds each to the
configuration's reference, teacher-forced over the same device-resident
parameters (``draft_logits``): one draft in two must be the reference's
argmax of row p - 2, and every other within ``--margin`` standard deviations
of the row's logits of it. Measured on one v5e at the published widths (PR
39, calls 3 and 6): 49 and 43 of 60 the argmax, the farthest 0.71 and 0.46
deviations off (bfloat16 against float32 breaks near-ties of 154,880 flat
logits: the largest two lie about 0.2 deviations apart), where the same
drafts judged against the reference's rows ONE POSITION ON (``shifted``: what
a module reading a wrong position, half or norm would look like) are the
argmax 3 and 0 times in 60 and lie 0.8 to 1.9 deviations off in the median,
4.0 at most. It also reads the reference's named
controls against what was served (``--control key=value``, as long_prompt.py),
over all of the check's 64 tokens. A builder's chip run, not a run the driver
makes:

    python3 benchmark/draft_check.py --workload <a drafting cell> --seed <n> \\
        [--margin 0.5] [--control precision=float8_e4m3fn] [--rehearse-cpu]

Prints one JSON line a step and the verdict last; exits 1 where the served
logprobs fall outside the tolerance, a control inside it (``precision=
bfloat16`` is the served precision and is expected inside: name it to read
it, not to pass), a draft off its margin, or nothing was drafted.
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
    ap.add_argument("--margin", type=float, default=1.0,
                    help="largest distance of a served draft's reference "
                         "logit from the row's largest, in standard "
                         "deviations of the row")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


def judge_drafts(rows, drafts: list[tuple[int, int]], offset: int = 2
                 ) -> dict:
    """``drafts`` (index p of the token drafted, the draft) against ``rows``
    [n - 1, V] (row i drafts token i + 2): how many are the row's argmax,
    and the others' distance from it in the row's standard deviations."""
    import numpy as np
    margins, agree = [], 0
    for p, token in drafts:
        i = p - offset
        if not 0 <= i < rows.shape[0]:
            continue
        row = np.asarray(rows[i], np.float64)
        gap = (row.max() - row[token]) / max(row.std(), 1e-9)
        agree += int(gap == 0.0)
        margins.append(float(gap))
    return {"drafts": len(margins), "agree": agree,
            "margin_max": max(margins, default=None),
            "margin_median": (sorted(margins)[len(margins) // 2]
                              if margins else None)}


async def check(args, files: dict) -> dict:
    import numpy as np

    from benchmark.lib import reference, server
    config = files["config"]
    name = files["cell"]["config"]
    judged = reference.for_config(config)
    module = manifest.load_module("references", config["reference"])
    os.makedirs(manifest.RUN_DIR, exist_ok=True)
    spec = server.model_spec(name, config, config.get("launch", {}).get(
        "quant"))
    tok_path = bench_tok.write_tokenizer(os.path.join(
        manifest.RUN_DIR, f"tokenizer-{spec.vocab_size}.json"),
        spec.vocab_size)
    seams = server.Seams(name, spec, args.seed, server.WarmShapes(
        max_prompt=64, max_context=96, max_batch=1))
    seams.install()
    try:
        argv = run.launch_argv(name, config, tok_path)
        async with server.Server(argv) as srv:
            eng, runner = srv.engine, srv.engine.runner
            overhead = bench_tok.template_overhead(tok_path,
                                                   srv.chat_template)
            run.emit("server", startup_s=srv.startup_s,
                     timings=seams.timings, decode_window=eng.decode_window,
                     num_pages=runner.num_pages, draft=eng.config.spec_decode,
                     attention_backend=runner.attention_backend,
                     memory=runner.memory_breakdown())
            eng.draft_log = {}
            checked = await run.check_logprobs(
                srv, judged, args.seed, overhead, spec.vocab_size)
            logs = list(eng.draft_log.values())
            verdict = {"served_ok": bool(checked["ok"]), "controls": {}}
            sound = {"drafts": 0, "agree": 0, "margin_max": 0.0}
            shifted = {"drafts": 0, "agree": 0, "margin_max": 0.0,
                       "margin_median": []}
            for (prompt, tokens), drafts in zip(checked["_taps"], logs):
                rows = np.asarray(module.draft_logits(
                    runner.params, runner.spec, list(prompt) + list(tokens)))
                for into, off in ((sound, 2), (shifted, 1)):
                    got = judge_drafts(rows, drafts, off)
                    into["drafts"] += got["drafts"]
                    into["agree"] += got["agree"]
                    into["margin_max"] = max(into["margin_max"],
                                             got["margin_max"] or 0.0)
                    if into is shifted and got["margin_median"] is not None:
                        into["margin_median"].append(got["margin_median"])
            run.emit("drafts", served=sound, shifted_by_one=shifted,
                     requests=len(logs), spec={
                         "draft_tokens": eng.spec_tokens,
                         "accepted_tokens": eng.spec_accepted})
            verdict["drafts"] = sound
            verdict["drafts_ok"] = bool(
                sound["drafts"] > 0 and len(logs) == len(checked["_taps"])
                and 2 * sound["agree"] >= sound["drafts"]
                and sound["margin_max"] <= args.margin)
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
                             checked["_served"], wrong))
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
    verdict["ok"] = bool(verdict["served_ok"] and verdict["drafts_ok"]
                         and all(verdict["controls"].values()))
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
