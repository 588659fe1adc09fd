"""The shared experts' share of their roofline: the bytes every layer's
shared experts hold as stored (the configuration's roofline module's
``shared_layer_bytes``: each is read once a step, whatever the rows chose)
over the chip's peak bandwidth, over the device time they took
(``moe_shared_ms_per_step``). An instruction fused with routed work counts
whole in that time, so the share reads low there, never over 100 %. None
where the roofline module counts no shared experts or the program draws no
``moe.shared`` scope."""

NAME = "moe_shared_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    ms = manifest.load_module("layer_metrics", "moe_shared_ms_per_step").read(r)
    layer_bytes = getattr(roofline.counting(r.model)[0],
                          "shared_layer_bytes", None)
    if not ms or layer_bytes is None:
        return None
    seconds = (r.model["num_hidden_layers"]
               * layer_bytes(r.model, r.engine.get("quant"))
               / (r.peaks["hbm_gbps"] * 1e9))
    return seconds / (ms / 1e3) * 100.0
