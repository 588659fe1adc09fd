"""Time requests sat in the engine's waiting queue before they got a slot:
the program's own ``engine.queue_wait`` spans that ended in the window."""
from benchmark.lib import stats

NAME = "queue_wait_p90_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(r):
    waits = [(s["end"] - s["start"]) * 1e3 for s in r.spans
             if s["name"] == "engine.queue_wait"
             and r.t0 <= s["end"] <= r.t1]
    return stats.percentile(waits, 90)
