"""The stutter: p99 of the time between successive content chunks of one
stream, pooled. Gaps are a window period plus whole prefill programs, so the
distribution has steps, and p99 of a few thousand gaps sits on one: it
flipped between 391 and 471 ms in runs of one tree (PR 24), and p95 between
320 and 331 (gap_p95_ms.closed). Neither is bounded in a closed loop; this
shows preemptions and stalls, which lengthen every stream's token times."""
from benchmark.lib import measure

NAME = "gap_p99_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(r):
    return measure.gap_ms(r, 99)
