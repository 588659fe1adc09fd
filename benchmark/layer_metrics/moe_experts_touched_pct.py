"""Share of a layer's experts one decode step touches: per decode step and
expert layer the window program counts the distinct experts its LIVE rows
chose, sums them on the device and hands the sum back with the window's
tokens; the flight ring keeps it per window (``moe_touched`` over
``moe_layer_steps``). Over the measured window, of the configuration's
experts. What a step that read only what it needs would read of the expert
weights; None for a dense model or a program without the columns."""

NAME = "moe_experts_touched_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def sums(r, span=None):
    """(sum of distinct experts, sum of max over mean, layer-steps) of the
    flight rows of the measured window, or of ``span`` (t_lo, t_hi)."""
    from benchmark.lib import host_phases
    if span is None:
        cols = host_phases.window_rows(r)
    else:
        try:
            from dynamo_tpu.runtime import flight
            cols = flight.get_recorder().between(*span)["columns"]
        except (ImportError, AttributeError):
            return None
    if cols is None or "moe_layer_steps" not in cols:
        return None
    n = float(cols["moe_layer_steps"].sum())
    if n <= 0:
        return None
    return float(cols["moe_touched"].sum()), float(cols["moe_load"].sum()), n


def per_layer_step(r):
    """Distinct held experts ONE layer-step's live rows chose: the mean the
    program counted over the traced seconds, else over the window; None
    where it counted none. What the two rooflines take as ``touched``."""
    got = sums(r, r.trace_mono) or sums(r)
    return None if got is None else got[0] / got[2]


def experts_of(model: dict):
    return model.get("moe_num_primary_experts") or model.get(
        "num_local_experts")


def read(r):
    got = sums(r)
    experts = experts_of(r.model)
    if got is None or not experts:
        return None
    return 100.0 * got[0] / (got[2] * experts)
