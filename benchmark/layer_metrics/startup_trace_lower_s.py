"""Seconds the start spent tracing Python and lowering to MLIR: the sum of
``trace_s + lower_s`` over the first-call records taken before ready. The
persistent cache keys on the lowered module, so a warm start pays these in
full. None where the program keeps no such record."""

NAME = "startup_trace_lower_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import startup
    return startup.seconds_of("trace_s", "lower_s")
