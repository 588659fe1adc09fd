"""How unevenly a decode step's rows load the experts: per decode step and
expert layer, the fullest expert's tokens over the mean per expert (live
rows x experts a token / experts), summed on the device and kept per window
in the flight ring (``moe_load`` over ``moe_layer_steps``); the mean over
the measured window. 1.0 is an even load; a grouped product's slowest group
and an expert-parallel layout's slowest chip take this many times the mean.
None for a dense model or a program without the columns."""

NAME = "moe_expert_load_max_over_mean"
UNIT = "ratio"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import manifest
    got = manifest.load_module("layer_metrics",
                               "moe_experts_touched_pct").sums(r)
    if got is None:
        return None
    return got[1] / got[2]
