"""The sparse attention's share of its roofline: what one decode step's
attention over the CHOSEN latent entries has to move and compute, from the
program's own count of the keys attended (``attn_selected`` a step: each
entry is read once, every head scores it in the latent's space and weighs
the latent; the configuration's roofline module's
``sparse_attention_counts``), over the chip's peak bandwidth or its peak
rate, whichever takes longer, over the device time of ``attn.core`` and
``attn.kv_gather`` (instructions that carry either scope, by set
intersection: one fused with other work counts whole, so the share reads low
there, never high). The work is counted from the counter, whatever implements
it: a walk that reads every entry in context and masks reads low by the share
it did not need. None where the roofline module has no
``sparse_attention_counts`` or the program no counters."""

NAME = "attn_sparse_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.core", "attn.kv_gather")


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    ms = manifest.load_module("layer_metrics",
                              "attn_index_ms_per_step").ms_in(r, SCOPES)
    keys = manifest.load_module("layer_metrics", "attn_selected_pct").per_step(r)
    counts = getattr(roofline.counting(r.model)[0],
                     "sparse_attention_counts", None)
    if not ms or keys is None or counts is None:
        return None
    n_bytes, ops = counts(r.model, keys[0])
    seconds = max(n_bytes / (r.peaks["hbm_gbps"] * 1e9),
                  ops / (r.peaks["bf16_tflops"] * 1e12))
    return seconds / (ms / 1e3) * 100.0
