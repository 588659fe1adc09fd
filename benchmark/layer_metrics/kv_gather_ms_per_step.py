"""Device time of one decode step in the page-table gather of K and V (scope
``attn.kv_gather``): the trace's operations inside the window program's
executions whose instruction the compile registry maps to the scope
(lib/scopes.py), over the steps traced. None where the executable carries no
scopes."""

NAME = "kv_gather_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.kv_gather",)


def read(r):
    from benchmark.lib import scopes
    return scopes.ms_per_step(r, SCOPES)
