"""Device time of one decode step in the regions that read the weights (scopes
``embed``, ``attn.qkv``, ``attn.out``, ``mlp``, ``lm_head``): the trace's
operations inside the window program's executions whose instruction the
compile registry maps to the scope (lib/scopes.py), over the steps traced.
None where the executable carries no scopes."""

NAME = "weights_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("embed", "attn.qkv", "attn.out", "mlp", "lm_head")


def read(r):
    from benchmark.lib import scopes
    return scopes.ms_per_step(r, SCOPES)
