"""The expert layers' share of their roofline: the bytes of the experts a
decode step TOUCHED (the program's own count over the traced seconds,
``moe_experts_touched_pct``'s sums, through the configuration's roofline
module's ``expert_layer_bytes``: the router and the touched experts' three
matrices as stored, every expert layer) over the chip's peak bandwidth, over
the device time the expert layers took (``moe_ms_per_step``). The count and
never the expectation, and a step cannot touch more experts than it has, so
a product over all resident experts reads at most touched / experts of 100.
None where the configuration's roofline module has no ``expert_layer_bytes``
or the program no counters."""

NAME = "moe_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace_mono is None or r.peaks is None:
        return None
    ms = manifest.load_module("layer_metrics", "moe_ms_per_step").read(r)
    counted = manifest.load_module("layer_metrics", "moe_experts_touched_pct")
    got = counted.sums(r, r.trace_mono) or counted.sums(r)
    layer_bytes = getattr(roofline.counting(r.model)[0],
                          "expert_layer_bytes", None)
    if not ms or got is None or layer_bytes is None:
        return None
    touched = got[0] / got[2]               # experts a layer-step touched
    layers = r.model["num_hidden_layers"]
    seconds = (layers * layer_bytes(r.model, r.engine.get("quant"), touched)
               / (r.peaks["hbm_gbps"] * 1e9))
    return seconds / (ms / 1e3) * 100.0
