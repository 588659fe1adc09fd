"""Share of the experts HELD here that one decode step touches: the flight
ring's ``moe_touched`` over ``moe_layer_steps`` (``moe_experts_touched_pct``'s
sums: per decode step and expert layer the distinct held experts the LIVE
rows chose), of the configuration's ``num_experts``, which counts the experts
this chip holds where ``expert_parallel`` states a wider router. What a step
that read only what it needs would read of the held experts' weights. None
for a configuration without those keys or a program without the columns."""

NAME = "moe_held_touched_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import manifest
    held = r.model.get("num_experts")
    if not held or "expert_parallel" not in r.model:
        return None
    got = manifest.load_module("layer_metrics",
                               "moe_experts_touched_pct").sums(r)
    if got is None:
        return None
    return 100.0 * got[0] / (got[2] * held)
