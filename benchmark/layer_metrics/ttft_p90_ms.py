"""The tail of time to first token, from the due time. With about 60 requests
due in a window it spreads by 9 % between runs of one tree (PR 24), too wide
for a bound; the median is the bounded metric, this is read beside it."""
from benchmark.lib import measure

NAME = "ttft_p90_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def read(r):
    return measure.ttft_ms(r, 90)
