"""The root span ``startup`` less the union of its DIRECT child spans: seconds
of a start that no stage covers (as ``idle_unattributed_pct`` is for the
device: it must stay small, or a stage is missing). None where the program
records no such span."""

NAME = "startup_unattributed_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import startup
    return startup.unattributed_s()
