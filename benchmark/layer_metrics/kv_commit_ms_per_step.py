"""Device time of one decode step in writing the window's new K and V into the
pool, the pool's layout copies around that scatter included (scope
``kv.commit``): the trace's operations inside the window program's executions
whose instruction the compile registry maps to the scope (lib/scopes.py), over
the steps traced. None where the executable carries no scopes."""

NAME = "kv_commit_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("kv.commit",)


def read(r):
    from benchmark.lib import scopes
    return scopes.ms_per_step(r, SCOPES)
