"""ISSUE 24's time per output token: median over the requests completed in
the window of (last chunk - first chunk) / (completion_tokens - 1). Where a
request lasts as long as the window it reaches back into the ramp, and one
stall of 3 s anywhere in a request's 40 s moves it by 8 %: the driver's
check of PR 24 read it 3 ms off in two runs of six while the window's
throughput and gaps kept their values. The bounded metric is the median
over the window's tokens; this is read beside it."""
from benchmark.lib import measure

NAME = "tpot_request_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
SOURCE = "host_clock"


def read(r):
    return measure.tpot_request_p50_ms(r)
