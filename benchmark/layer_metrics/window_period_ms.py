"""A decode window on the program's own clock: the median ``period_s`` of the
flight ring's rows of the measured window whose pipe was full (the window was
queued behind the one before it, so readback complete to readback complete is
one window of the device; the ring stores 0 otherwise). Taken with the
profiler off, over the whole window: it should be decode_window x
``decode_step_ms``."""
import statistics

NAME = "window_period_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "compiled programs"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    rows = host_phases.window_rows(r)
    if rows is None:
        return None
    full = [p for p in rows["period_s"].tolist() if p > 0]
    return statistics.median(full) * 1e3 if full else None
