"""The state update's share of its roofline: the float32 state of the rows
that were LIVE in one decode step (the program's ``ssm_row_steps`` a step),
read ONCE and written ONCE in every recurrent layer (the configuration's
roofline module's ``state_bytes``), over the chip's peak bandwidth, over the
device time in the sub-scope ``ssm.state`` (``ssm_state_ms_per_step``). The
bytes are counted from the counter, whatever implements the update: one that
reads the state twice, or touches dead slots, reads low. None where the
roofline module has no ``state_bytes``, the program no such scope or the
ring no such column."""

NAME = "ssm_state_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import manifest, roofline
    if r.trace is None or r.peaks is None:
        return None
    state_bytes = getattr(roofline.counting(r.model)[0], "state_bytes", None)
    ms = manifest.load_module("layer_metrics",
                              "ssm_state_ms_per_step").read(r)
    rows = manifest.load_module("layer_metrics",
                                "ssm_state_rows_pct").per_step(r)
    if state_bytes is None or not ms or rows is None:
        return None
    return (state_bytes(r.model, rows) / (r.peaks["hbm_gbps"] * 1e9)
            / (ms / 1e3) * 100.0)
