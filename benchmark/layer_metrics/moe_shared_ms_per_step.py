"""Device time of one decode step inside a routed block's SHARED experts: the
trace's leaf operations inside the window program's executions whose
instruction carries the sub-scope ``moe.shared`` among its scopes (set
intersection, as ``moe_ms_per_step`` reads ``moe.router`` / ``moe.experts``:
an instruction the compiler fused shared and routed work into counts whole
here AND there), over the steps traced. It is part of what
``weights_ms_per_step`` sums under ``mlp``. None where the executable draws
no such scope (a dense model, a routed block without shared experts, a
program before PR 32)."""

NAME = "moe_shared_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SUBSCOPES = ("moe.shared",)


def read(r):
    from benchmark.lib import manifest, scopes
    routed = manifest.load_module("layer_metrics", "moe_ms_per_step")
    seconds = routed.seconds_in(r, SUBSCOPES)
    got = scopes.reduced(r)
    if seconds is None or not got["median_ms"]:
        return None
    steps = (got["module_seconds"] * 1e3 / got["median_ms"]
             * r.engine["decode_window"])
    return seconds * 1e3 / steps
