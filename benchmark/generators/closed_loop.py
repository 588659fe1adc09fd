"""Closed loop: each client sends its next request when the last one ends.

Parameters (traffic file, overridden by the cell's file):
  clients              callers that each wait for their reply
  ramp_seconds         the clients run this long before the window opens
  requests_per_client  sizes dealt to each client (it starts over, with a
                       new first word, if it ever runs out)
  prompt_tokens, output_tokens   as in open_loop

Sizes are the quantiles of their laws, dealt to the clients in ONE order
(``ORDER``): a cell replays one trace, words and all, over one draw of the
weights (``lib/weights.py CELL_WEIGHTS_SEED``, PR 58), and the run's seed
draws the check's prompts. Which sequences make up the first wave of
streams decides how the run unfolds (when rows end together, how long the
widest row is past a page bucket): with the clients turned round by the
seed, each turn gave its own throughput, 5 % apart and the same again in a
second set, and shuffled by the seed a seventh apart (PERF.md, Findings).
A window of 51 s sees each caller finish about once, so no order averages
out inside one run. The first request of a sequence keeps only the share
(k + 0.5) / clients of its output: the run starts as if it had been going
for a long time, with the streams at staggered depths.
"""

from __future__ import annotations

import random

from benchmark.lib.lengths import stratified


#: The one order of the replayed trace (a seed of Python's generator).
ORDER = 24 * 7919 + 3


def plan(params: dict, seed: int, seconds: float) -> dict:
    rng = random.Random(ORDER)
    clients = int(params["clients"])
    per = int(params.get("requests_per_client", 8))
    n = clients * per
    prompts = stratified(params["prompt_tokens"], n)
    outputs = stratified(params["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    shares = [(k + 0.5) / clients for k in range(clients)]
    rng.shuffle(shares)
    sequences = []
    for c in range(clients):
        seq = [{"id": c * 10_000 + k, "prompt_len": prompts[k * clients + c],
                "max_tokens": outputs[k * clients + c]} for k in range(per)]
        seq[0]["max_tokens"] = max(2, round(seq[0]["max_tokens"]
                                            * shares[c]))
        sequences.append(seq)
    return {"mode": "closed", "lead_seconds": float(params["ramp_seconds"]),
            "seconds": seconds, "sequences": sequences,
            "headers": dict(params.get("headers", {}))}
