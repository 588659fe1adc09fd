"""The indexer's share of its roofline: what one decode step's indexers have
to move and compute, from the program's own count of the keys in context
(``attn_context`` a step: each index key is read once and scored by every
index head) and each layer's indexer matrices as stored (the configuration's
roofline module's ``index_counts``), over the chip's peak bandwidth or its
peak rate, whichever takes longer, over the device time the indexer took
(``attn_index_ms_per_step``). The work is counted from the counter, whatever
implements it. None where the roofline module has no ``index_counts``, the
program no counters or the executable no ``attn.index`` scope."""

NAME = "attn_index_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def rows_of(r):
    """Mean live rows inside the traced seconds (decode_window_roofline's)."""
    from benchmark.lib import manifest
    got = manifest.load_module("layer_metrics",
                               "decode_window_roofline").inputs(r)
    return got[0] if got else None


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    ms = manifest.load_module("layer_metrics", "attn_index_ms_per_step").read(r)
    keys = manifest.load_module("layer_metrics", "attn_selected_pct").per_step(r)
    counts = getattr(roofline.counting(r.model)[0], "index_counts", None)
    rows = rows_of(r)
    if not ms or keys is None or counts is None or rows is None:
        return None
    n_bytes, ops = counts(r.model, r.engine.get("quant"), rows, keys[1])
    seconds = max(n_bytes / (r.peaks["hbm_gbps"] * 1e9),
                  ops / (r.peaks["bf16_tflops"] * 1e12))
    return seconds / (ms / 1e3) * 100.0
