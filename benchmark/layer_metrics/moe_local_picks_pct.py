"""Share of the router's choices that fell on experts held HERE: an expert
layer that is told its share of a wider router counts, per decode step and
layer, the (live row, choice) pairs whose expert it holds and all pairs,
sums both on the device and hands them back with the window's tokens; the
flight ring keeps them per window (``moe_local_picks`` over ``moe_picks``).
Over the measured window. An even router gives held / routed (12.5 % for 16
of 128): how near each held expert's load is to its even eighth, and what
share of a row's routed work this chip does. None for a dense model, a
routed block that holds every expert, or a program without the columns."""

NAME = "moe_local_picks_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    cols = host_phases.window_rows(r)
    if cols is None or "moe_picks" not in cols:
        return None
    picks = float(cols["moe_picks"].sum())
    if picks <= 0:
        return None
    return 100.0 * float(cols["moe_local_picks"].sum()) / picks
