"""Device time of one decode step inside a latent block's INDEXER: the trace's
leaf operations inside the window program's executions whose instruction
carries the scope ``attn.index`` among its scopes (the index query, key and
head weights, the read of every index key in context, their scores, the
choice of ``index_topk``), over the steps traced. By set intersection, as
``moe_shared_ms_per_step`` reads its sub-scope: an instruction the compiler
fused indexer and other work into counts whole, so a share of a roofline over
this time reads low there, never high. None where the executable draws no
such scope (every other block, a program before PR 34)."""

NAME = "attn_index_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.index",)


def ms_in(r, scopes_: tuple):
    """Milliseconds a traced decode step spends in instructions that carry
    any of ``scopes_``."""
    from benchmark.lib import manifest, scopes
    routed = manifest.load_module("layer_metrics", "moe_ms_per_step")
    seconds = routed.seconds_in(r, scopes_)
    got = scopes.reduced(r)
    if seconds is None or not got["median_ms"]:
        return None
    steps = (got["module_seconds"] * 1e3 / got["median_ms"]
             * r.engine["decode_window"])
    return seconds * 1e3 / steps


def read(r):
    return ms_in(r, SCOPES)
