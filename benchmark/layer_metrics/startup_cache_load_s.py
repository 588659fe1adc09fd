"""Seconds the start spent retrieving executables from the persistent compile
cache: the sum of ``cache_load_s`` over the first-call records taken before
ready. None where the program keeps no such record."""

NAME = "startup_cache_load_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import startup
    return startup.seconds_of("cache_load_s")
