"""Launcher entry to ready, by the program's own record: the root span
``startup`` of the start-up trace (dynamo_tpu/runtime/tracing.py ``Startup``),
from ``launch.run``'s first line to the instant the engine is ready AND the
HTTP service listens. The largest term of ``setup_s``; the ``server`` line's
``startup_s`` is the outside timing it has to agree with. Also prints the
start's stage table on stderr, once (``lib/startup.py report``). None where
the program records no such span."""

NAME = "startup_ready_s"
UNIT = "s"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import startup
    startup.report(r)
    return startup.ready_s()
