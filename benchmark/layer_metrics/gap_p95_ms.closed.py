"""p95 of the time between successive content chunks of one stream, pooled,
in a closed loop. It was this benchmark's bounded gap until the check of
PR 24: the gaps are a window period (292 ms) plus whole prefill programs,
and whether two arrivals share one prefill program decides whether 293 or
311 of a window's 2,950 gaps are lengthened; the 95th percentile lies
between the two counts and read 320 or 331 ms in runs of one tree. No bound
holds both. An open-loop cell may bound ``gap_p95_ms`` (measure.END_TO_END)
where it is steady; this reads nothing there."""
from benchmark.lib import measure

NAME = "gap_p95_ms.closed"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(r):
    return None if r.open_loop else measure.gap_ms(r, 95)
