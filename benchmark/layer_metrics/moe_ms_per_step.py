"""Device time of one decode step inside a routed block's expert layers: the
trace's leaf operations inside the window program's executions whose
instruction the compile registry maps to the sub-scopes ``moe.router`` or
``moe.experts`` (``mlp+moe.experts``: the router's logits and choice, the
expert products, their combine), over the steps traced. It is part of what
``weights_ms_per_step`` sums under ``mlp``. None where the executable draws
no such scope (a dense model, a program before PR 28)."""

NAME = "moe_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SUBSCOPES = ("moe.router", "moe.experts")


def seconds_in(r, subscopes: tuple) -> float | None:
    """Leaf device seconds, inside the executions of the window program
    that ran most, of instructions in any of ``subscopes``."""
    from benchmark.lib import scopes, trace_reduce
    got = scopes.reduced(r)
    ops = scopes._ops_by_scope(r) if got else None
    if not got or not ops:
        return None
    mine = {op for op, scope in ops.items()
            if scope and set(scope.split("+")) & set(subscopes)}
    if not mine:
        return None
    planes = trace_reduce.device_planes(r.trace)
    per_op = trace_reduce.op_times(
        {"/device:TPU:0": planes[min(planes)]}, inside=got["module"],
        leaves_only=True)
    return sum(s for op, s in per_op.items() if op.split(" ", 1)[0] in mine)


def read(r):
    from benchmark.lib import scopes
    seconds = seconds_in(r, SUBSCOPES)
    got = scopes.reduced(r)
    if seconds is None or not got["median_ms"]:
        return None
    steps = (got["module_seconds"] * 1e3 / got["median_ms"]
             * r.engine["decode_window"])
    return seconds * 1e3 / steps
