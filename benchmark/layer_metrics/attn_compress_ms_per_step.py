"""Device time of one decode step in writing the compressed-key array: the
trace's leaf operations inside the window program's executions whose
instruction carries the scope ``attn.compress`` among its scopes (set
intersection, as ``ssm_ms_per_step`` reads ``ssm``: at a window's commit, the
stripes that the window's tokens completed, read back from the pool's last
rows and the window's buffer and written where their pages lie), over the
steps traced. The choice over the array is ``attn_index_ms_per_step``. None
where the executable draws no such scope (every block whose attention reads
every key, and a program before PR 45)."""

NAME = "attn_compress_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.compress",)


def read(r):
    from benchmark.lib import manifest
    if r.trace is None:
        return None
    return manifest.load_module(
        "layer_metrics", "attn_index_ms_per_step").ms_in(r, SCOPES)
