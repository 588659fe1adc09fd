"""Host time a decode window costs: the median over the flight ring's rows of the
measured window of ``host_s``, the engine thread's seconds since the previous
row in every phase but ``engine.readback_wait`` and ``engine.idle`` (admit,
dispatch, token walk, emit, publish, ...). While this is under the window's
period the pipeline hides the host; a window that gets shorter than it does
not get faster."""
import statistics

NAME = "host_ms_per_window"
UNIT = "ms"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    rows = host_phases.window_rows(r)
    if rows is None:
        return None
    return statistics.median(rows["host_s"].tolist()) * 1e3
