"""Compiled programs first called before the engine reported ready: the
compile registry's first-call records with ``when: "startup"``
(dynamo_tpu/engine/perf.py ``CompileRegistry.first_calls``). Each is traced,
lowered and loaded or compiled at every start. None where the program keeps
no such record."""

NAME = "startup_programs"
UNIT = "count"
BETTER = "lower"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import startup
    return startup.programs()
