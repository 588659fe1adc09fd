"""First calls before ready whose program the persistent compile cache did not
hold (``cache: "miss"``): 0 in a warm run, so it tells a cold ``setup_s`` from
a warm one. None where the program keeps no such record."""

NAME = "startup_cache_misses"
UNIT = "count"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import startup
    return startup.cache_misses()
