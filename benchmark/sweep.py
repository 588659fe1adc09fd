#!/usr/bin/env python3
"""Find the rate a cell's configuration sustains under a traffic mix, once.

    python3 benchmark/sweep.py --workload <open-loop cell> [--rates 1,1.5,...]
        [--step-seconds 10] [--seed 1]

One process, one server, steps of ``--step-seconds`` at rising rates with
the cell's own lengths (generators/rate_steps.py). For each step it prints
what was offered, the time to first token, how many requests were in flight
when the step ended and how many failed. The knee is the highest rate at
which the backlog does not grow and nothing fails; a cell below the knee is
given four fifths of it, by hand, in benchmark/cells/<cell>.json. No check
runs this file.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import manifest, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="1,1.5,2,2.5,3,3.5,4,5")
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ns = ap.parse_args()
    man = manifest.load_manifest()
    files = manifest.cell_files(man, ns.workload)
    if ns.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        files = run.rehearsal_cut(files)
    rates = [float(x) for x in ns.rates.split(",")]
    files["generator"] = "rate_steps"
    files["params"] = dict(files["params"], preroll_seconds=0,
                           steps=[[r, ns.step_seconds] for r in rates])
    import jax
    if not ns.rehearse_cpu and jax.devices()[0].platform != "tpu":
        run.die(run.NO_DEVICE, "no TPU")
    run._count_backend_compiles()
    args = argparse.Namespace(workload=ns.workload, seed=ns.seed,
                              seconds=int(ns.step_seconds * len(rates)),
                              trace=0, rehearse_cpu=ns.rehearse_cpu,
                              probe_faults=False)
    out = asyncio.run(run.run_cell(args, files, man, jax))
    plan = manifest.load_json(os.path.join(manifest.RUN_DIR, "plan.json"))
    records = manifest.load_json(os.path.join(
        manifest.RUN_DIR, "results.json"))["records"]
    t0 = plan["t0"]
    for k, rate in enumerate(rates):
        lo, hi = t0 + k * ns.step_seconds, t0 + (k + 1) * ns.step_seconds
        mine = [r for r in records if lo <= r["due"] < hi]
        ttft = stats.ttfts_ms(mine, True, hi + plan["drain_seconds"])
        flying = sum(1 for r in records
                     if r["sent"] <= hi < (r["done"] or float("inf")))
        tokens = stats.tokens_in_window(records, lo, hi)
        run.emit("sweep.step", rate_rps=rate, offered=len(mine),
                 failed=sum(1 for r in mine if not r["ok"]),
                 ttft_p50_ms=stats.percentile(ttft, 50),
                 ttft_p90_ms=stats.percentile(ttft, 90),
                 in_flight_at_end=flying,
                 out_tok_s=tokens / ns.step_seconds)
    print(json.dumps({"sweep": "done", "correct": out["correct"],
                      "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
