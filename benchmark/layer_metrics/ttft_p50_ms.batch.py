"""Median time to first token where streams queue by design (a closed loop
of more callers than the admission admits). A few tens of samples: it says
how long a caller waits for a row, and is not fit to decide a PR. Every cell
may list it: an open loop has no such wait, and reads nothing here."""
from benchmark.lib import measure

NAME = "ttft_p50_ms.batch"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "host_clock"


def read(r):
    return None if r.open_loop else measure.ttft_ms(r, 50)
