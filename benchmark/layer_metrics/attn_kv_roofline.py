"""The K/V reader's share of its roofline in a looped stack: what one decode
step's attention has to move (the configuration's roofline module's
``attention_bytes``: K and V of every live token in every (pass, layer) pair
of the pool read, one token's written a sequence; rows and context the means
of the engine's occupancy samples inside the traced window, as
``decode_window_roofline`` takes them) over the chip's peak bandwidth, over
the device time of ``attn.core`` and ``attn.kv_gather`` (lib/scopes.py: an
instruction counts under the first of its scopes in PRECEDENCE). The work is
counted from shapes and the live tokens, whatever reads them: at one query
row a KV head and pages of 16 tokens the reader's page copies and its
per-head turns read under what a copy of the same bytes would. None where
the roofline module has no ``attention_bytes`` (every other block) or the
executable carries no scopes."""

NAME = "attn_kv_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"

SCOPES = ("attn.core", "attn.kv_gather")


def read(r):
    from benchmark.lib import manifest, roofline, scopes
    if r.trace is None or r.peaks is None:
        return None
    n_bytes = getattr(roofline.counting(r.model)[0], "attention_bytes", None)
    if n_bytes is None:
        return None
    ms = scopes.ms_per_step(r, SCOPES)
    got = manifest.load_module("layer_metrics",
                               "decode_window_roofline").inputs(r)
    if not ms or got is None:
        return None
    return (n_bytes(r.model, *got) / (r.peaks["hbm_gbps"] * 1e9)
            / (ms / 1e3) * 100.0)
