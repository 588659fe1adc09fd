"""Device time of one scan step inside the prediction module that drafts: the
trace's leaf operations inside the window program's executions whose
instruction carries the scope ``mtp`` among its scopes (set intersection, as
``moe_shared_ms_per_step`` reads ``moe.shared``: the module's projection of
[embedding ; hidden], its block with that block's attention and expert layer
(``mtp+moe.experts``), its norm, its read of the head and the argmax), over
the steps traced. None where the executable draws no such scope (every
program that does not draft with a module)."""

NAME = "mtp_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("mtp",)


def read(r):
    from benchmark.lib import manifest
    if r.trace is None:
        return None
    return manifest.load_module(
        "layer_metrics", "attn_index_ms_per_step").ms_in(r, SCOPES)
