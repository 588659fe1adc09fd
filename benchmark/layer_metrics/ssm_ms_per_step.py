"""Device time of one decode step inside the recurrent layers: the trace's
leaf operations inside the window program's executions whose instruction
carries the scope ``ssm`` among its scopes (set intersection, as
``mtp_ms_per_step`` reads ``mtp``: a Mamba-2 mixer's norm, in-projection,
convolution, the update of the state and its read, the gated norm and the
out-projection, over all such layers), over the steps traced. None where the
executable draws no such scope (every block whose whole per-request state is
pages, and a program before PR 41)."""

NAME = "ssm_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("ssm",)


def read(r):
    from benchmark.lib import manifest
    if r.trace is None:
        return None
    return manifest.load_module(
        "layer_metrics", "attn_index_ms_per_step").ms_in(r, SCOPES)
