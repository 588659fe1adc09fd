"""Time requests waited in the HTTP admission queue (runtime/overload.py) before
they held a permit: the program's own ``http.admit_wait`` spans that ended in
the window, whatever their outcome (granted, shed, or cancelled by a caller
that left the queue: the harness hands readers names and times only, and a cell
in which no request fails has only the first). In a closed loop with more callers
than the limit admits this is most of a request's time to first token."""
from benchmark.lib import stats

NAME = "http_admit_wait_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    waits = [(s["end"] - s["start"]) * 1e3 for s in r.spans
             if s["name"] == "http.admit_wait"
             and r.t0 <= s["end"] <= r.t1]
    return stats.percentile(waits, 50)
