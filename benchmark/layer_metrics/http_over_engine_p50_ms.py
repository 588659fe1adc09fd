"""What the HTTP service, the preprocessor and the detokenizer add to a first
token: the client's first content chunk minus the engine's first emission
for the same request (seen by the benchmark's tap between backend and
engine), both on CLOCK_MONOTONIC."""
from benchmark.lib import stats

NAME = "http_over_engine_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "http frontend"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def read(r):
    left = {k: list(v) for k, v in r.emissions.items()}
    diffs = []
    for x in sorted(r.measured(), key=lambda x: x["sent"]):
        times = left.get(r.prompt_keys.get(x["id"]))
        if x["chunk_t"] and times:
            diffs.append((x["chunk_t"][0] - times.pop(0)) * 1e3)
    return stats.percentile(diffs, 50)
