"""The parallel pair's share of its roofline: what one decode step's two
branches have to move in every layer (the configuration's roofline module's
``mixer_bytes``: both branches' weights as stored, the float32 state of the
rows that were LIVE read and written (the program's ``ssm_row_steps`` a
step), the K and V of every token in context read and one token's written a
row; rows and context the means of the engine's occupancy samples inside the
traced window, as ``decode_window_roofline`` takes them) over the chip's peak
bandwidth, over the device time of both branches together
(``mixers_ms_per_step``). A branch that waits on the other, or a sum that
copies, reads low; a reading over 100 is a fault of the count. None where
the roofline module has no ``mixer_bytes``, the program runs no mixers side
by side or the ring has no such column."""

NAME = "mixers_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    n_bytes = getattr(roofline.counting(r.model)[0], "mixer_bytes", None)
    ms = manifest.load_module("layer_metrics", "mixers_ms_per_step").read(r)
    live = manifest.load_module("layer_metrics",
                                "ssm_state_rows_pct").per_step(r)
    got = manifest.load_module("layer_metrics",
                               "decode_window_roofline").inputs(r)
    if n_bytes is None or not ms or live is None or got is None:
        return None
    return (n_bytes(r.model, r.engine.get("quant"), live, *got)
            / (r.peaks["hbm_gbps"] * 1e9) / (ms / 1e3) * 100.0)
