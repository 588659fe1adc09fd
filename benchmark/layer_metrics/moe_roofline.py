"""The expert layers' share of their roofline: the bytes of the experts a
decode step TOUCHED (the program's own count over the traced seconds,
``moe_experts_touched_pct``'s sums, through the configuration's roofline
module: ``expert_layer_bytes``, the router and the touched experts' matrices
as stored, times ``expert_layers``, the expert layers the module states: not
the leading dense layers, not a hybrid's other mixers) over the chip's peak
bandwidth, over the device time the expert layers took
(``moe_ms_per_step``). The count and never the expectation, and a step
cannot touch more experts than it has, so a product over all resident experts
reads at most touched / experts of 100. None where the configuration's
roofline module lacks either function or the program has no counters."""

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
    touched = manifest.load_module(
        "layer_metrics", "moe_experts_touched_pct").per_layer_step(r)
    counts = roofline.counting(r.model)[0]
    layer_bytes = getattr(counts, "expert_layer_bytes", None)
    layers = getattr(counts, "expert_layers", None)
    if not ms or touched is None or layer_bytes is None or layers is None:
        return None
    seconds = (layers(r.model)
               * layer_bytes(r.model, r.engine.get("quant"), touched)
               / (r.peaks["hbm_gbps"] * 1e9))
    return seconds / (ms / 1e3) * 100.0
