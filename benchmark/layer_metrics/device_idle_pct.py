"""Share of the traced window in which no operation ran on the device: one
minus the union of the trace's device-operation intervals over the window;
with several chips, the mean."""

NAME = "device_idle_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import trace_reduce
    if r.trace is None or r.trace_span_ns is None:
        return None
    lo, hi = r.trace_span_ns
    busy = trace_reduce.busy_seconds(r.trace, lo, hi)
    if not busy or hi <= lo:
        return None
    mean_busy = sum(busy.values()) / len(busy)
    return (1.0 - mean_busy / ((hi - lo) / 1e9)) * 100.0
