"""The drafting module's share of its roofline: what one run of it has to
read (the configuration's roofline module's ``draft_module_bytes``: its
projection, norms, whole expert layer and the head; plus its OWN layer's live
entries, the program's ``attn_selected`` a step over the pool's layers, each
read once) over the chip's peak bandwidth, over the device time in scope
``mtp`` (``mtp_ms_per_step``). None where the roofline module has no
``draft_module_bytes`` or the program no such scope."""

NAME = "mtp_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    counts = roofline.counting(r.model)[0]
    module_bytes = getattr(counts, "draft_module_bytes", None)
    ms = manifest.load_module("layer_metrics", "mtp_ms_per_step").read(r)
    if module_bytes is None or not ms:
        return None
    n_bytes = module_bytes(r.model, r.engine.get("quant"))
    keys = manifest.load_module("layer_metrics",
                                "attn_selected_pct").per_step(r)
    if keys is not None:
        # The module's layer is one of the pool's: its share of the keys.
        n_bytes += keys[0] / counts.pool_layers(r.model) \
            * counts.entry_bytes(r.model)
    return n_bytes / (r.peaks["hbm_gbps"] * 1e9) / (ms / 1e3) * 100.0
