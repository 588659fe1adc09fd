"""How late the load generator sent what was due: a starved generator is
not a fast server. Read beside ttft_p50_ms, which counts from the due time."""
from benchmark.lib import stats

NAME = "loadgen_late_p99_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "benchmark client"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def read(r):
    late = [(x["sent"] - x["due"]) * 1e3 for x in r.records
            if x["measured"] and x["due"] is not None
            and x["sent"] is not None]
    return stats.percentile(late, 99)
