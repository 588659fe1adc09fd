"""Open loop: requests are due on a schedule, whatever the server does.

Parameters (traffic file, overridden by the cell's file):
  rate_rps          offered rate, fixed in the cell (never searched for)
  preroll_seconds   arrivals at the same rate before the window opens, so
                    that the window starts on a loaded server; not measured
  prompt_tokens     distribution of whole prompts (template included)
  output_tokens     distribution of ``max_tokens`` (``ignore_eos`` is set)

Inter-arrival gaps are the quantiles of an exponential law (a Poisson
process's gaps) and lengths the quantiles of their laws, tied into requests
in ONE order (``ORDER``): a cell replays one trace, words and all, over one
draw of the weights (``lib/weights.py CELL_WEIGHTS_SEED``, PR 58), and the
run's seed draws the check's prompts. With the schedule turned
round by the seed, the same seed read the same TTFT twice to 0.3 % and six
seeds spread by 3.7 % (PERF.md, Findings): where the window's edges fell in
the cycle was changing the work.
"""

from __future__ import annotations

import random

from benchmark.lib.lengths import exponential_gaps, stratified


#: The one order of the replayed trace (a seed of Python's generator).
ORDER = 24 * 7919


def master_block(params: dict, n: int, salt: int) -> list[tuple]:
    """n (gap, prompt_len, max_tokens) entries in the schedule's order."""
    rng = random.Random(ORDER + salt)
    columns = (exponential_gaps(params["rate_rps"], n),
               stratified(params["prompt_tokens"], n),
               stratified(params["output_tokens"], n))
    for column in columns:
        rng.shuffle(column)
    return list(zip(*columns))


def _block(params: dict, n: int, start: float, measured: bool,
           first_id: int, salt: int) -> list[dict]:
    if n <= 0:
        return []
    requests, t = [], start
    for i, (gap, prompt, output) in enumerate(master_block(params, n, salt)):
        requests.append({"id": first_id + i, "due": t + gap / 2.0,
                         "prompt_len": prompt, "max_tokens": output,
                         "measured": measured})
        t += gap
    return requests


def plan(params: dict, seed: int, seconds: float) -> dict:
    """A pure function of its arguments. ``due`` is in seconds from the
    opening of the window; pre-roll requests are due before 0."""
    rate = params["rate_rps"]
    preroll = float(params.get("preroll_seconds", 0))
    n_pre = int(rate * preroll)
    n_win = int(rate * seconds)
    requests = (_block(params, n_pre, -n_pre / rate, False, 0, 1)
                + _block(params, n_win, 0.0, True, n_pre, 2))
    return {"mode": "open", "lead_seconds": n_pre / rate,
            "seconds": seconds, "requests": requests,
            "drain_seconds": float(params.get("drain_seconds", 60)),
            "headers": dict(params.get("headers", {}))}
