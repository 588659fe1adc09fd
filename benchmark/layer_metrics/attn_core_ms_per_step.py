"""Device time of one decode step in scores, softmax and the weighted sum (scope
``attn.core``): the trace's operations inside the window program's executions
whose instruction the compile registry maps to the scope (lib/scopes.py), over
the steps traced. None where the executable carries no scopes."""

NAME = "attn_core_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.core",)


def read(r):
    from benchmark.lib import scopes
    return scopes.ms_per_step(r, SCOPES)
