"""The recurrent layers' share of their roofline: what one decode step's
Mamba-2 mixers have to move (the configuration's roofline module's
``ssm_layer_bytes``: every mixer's weights as stored, and the state of the
rows that were LIVE read and written, the program's ``ssm_row_steps`` a
step) over the chip's peak bandwidth, over the device time in scope ``ssm``
(``ssm_ms_per_step``). The work is counted from the counter, whatever
implements it: a program that updates dead slots too reads low. None where
the roofline module has no ``ssm_layer_bytes``, the program no such scope
or the ring no such column."""

NAME = "ssm_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    layer_bytes = getattr(roofline.counting(r.model)[0], "ssm_layer_bytes",
                          None)
    ms = manifest.load_module("layer_metrics", "ssm_ms_per_step").read(r)
    rows = manifest.load_module("layer_metrics",
                                "ssm_state_rows_pct").per_step(r)
    if layer_bytes is None or not ms or rows is None:
        return None
    n_bytes = layer_bytes(r.model, r.engine.get("quant"), rows)
    return n_bytes / (r.peaks["hbm_gbps"] * 1e9) / (ms / 1e3) * 100.0
