"""A looped stack's weight read's share of its roofline: what one decode step
has to read of the weights (the configuration's roofline module's
``loop_weight_bytes``: the layers as stored once a PASS, the head once, an
embedding row a sequence) over the chip's peak bandwidth, over the device
time of the regions that read the weights (``weights_ms_per_step``: scopes
``embed``, ``attn.qkv``, ``attn.out``, ``mlp``, ``lm_head``). Counted from
shapes, whatever implements the passes; thin products over small matrices
read lower than one read of a large stack does. None where the roofline
module has no ``loop_weight_bytes`` (every other block) or the executable
carries no scopes."""

NAME = "loop_weights_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    weight_bytes = getattr(roofline.counting(r.model)[0],
                           "loop_weight_bytes", None)
    if weight_bytes is None:
        return None
    ms = manifest.load_module("layer_metrics", "weights_ms_per_step").read(r)
    got = manifest.load_module("layer_metrics",
                               "decode_window_roofline").inputs(r)
    if not ms or got is None:
        return None
    n_bytes = weight_bytes(r.model, r.engine.get("quant"), got[0])
    return n_bytes / (r.peaks["hbm_gbps"] * 1e9) / (ms / 1e3) * 100.0
