"""Open loop at a rate that rises in steps: the sweep that finds the knee.

Parameters: ``steps`` is a list of [rate_rps, seconds]; the lengths are as
in open_loop. Each step has its own fixed schedule. Used by benchmark/sweep.py, once, to fix a cell's rate; no cell
of BENCHMARK.json runs it.
"""

from __future__ import annotations

from benchmark.generators.open_loop import _block


def plan(params: dict, seed: int, seconds: float) -> dict:
    requests, start = [], 0.0
    for k, (rate, length) in enumerate(params["steps"]):
        step = dict(params, rate_rps=rate)
        requests += _block(step, int(rate * length), start, True,
                           len(requests), 10 + k)
        start += length
    return {"mode": "open", "lead_seconds": 0.0, "seconds": start,
            "requests": requests,
            "drain_seconds": float(params.get("drain_seconds", 60)),
            "headers": dict(params.get("headers", {}))}
