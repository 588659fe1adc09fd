"""Seconds the start spent in XLA compiles proper (the backend event less the
cache retrieval on a hit): the sum of ``compile_s`` over the first-call records
taken before ready; about 0 in a warm run. None where the program keeps no
such record."""

NAME = "startup_compile_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import startup
    return startup.seconds_of("compile_s")
