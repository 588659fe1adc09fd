"""Device time of one decode step: the median device time of an execution
of the decode-window program (``jit_run_window`` in the trace's XLA Modules
line) over the steps it runs. The median, because the executions at the
edges of the traced window are recorded in part."""
import statistics

NAME = "decode_step_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "compiled programs"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

PROGRAM = "run_window"


def read(r):
    from benchmark.lib import trace_reduce
    if r.trace is None:
        return None
    runs = [ms for name, each in trace_reduce.program_runs_ms(r.trace).items()
            if PROGRAM in name for ms in each]
    if not runs:
        return None
    return statistics.median(runs) / r.engine["decode_window"]
