"""Device time of one decode step inside the update and read of a recurrent
STATE alone: the trace's leaf operations inside the window program's
executions whose instruction carries the sub-scope ``ssm.state`` among its
scopes (set intersection, as ``moe_shared_ms_per_step`` reads ``moe.shared``:
the kernel of engine/recurrence.py on the chip, ``hybrid.delta_update``'s
fusions and the layer's slice in and out under XLA; neither the projections
nor the convolution nor the gates, which ``ssm_ms_per_step`` counts beside
it), over the steps traced. None where the executable draws no such scope
(every block without delta-rule mixers, a program before PR 52)."""

NAME = "ssm_state_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SUBSCOPES = ("ssm.state",)


def read(r):
    from benchmark.lib import manifest
    if r.trace is None:
        return None
    return manifest.load_module(
        "layer_metrics", "attn_index_ms_per_step").ms_in(r, SUBSCOPES)
