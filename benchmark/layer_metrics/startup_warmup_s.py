"""The span ``startup.warmup`` on the engine thread: the engine's own warm-up
of its window and prefill programs and whatever a subclass runs inside it
before ready (the harness's ``warm_traffic_shapes``), so nearly all of a
start's first calls. None where the program records no such span."""

NAME = "startup_warmup_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import startup
    return startup.stage_s(startup.WARMUP)
