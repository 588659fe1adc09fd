"""Passes over the layers a token took in a looped stack: per decode step the
window program counts, where the passes run, the live rows of every pass
(``loop_passes``) and the live rows themselves (``loop_row_steps``), sums
both on the device and hands them back with the window's tokens; the flight
ring keeps them per window. ``loop_passes`` over ``loop_row_steps`` of the
measured window: counted in the program, never assumed from the
configuration (4.0 for ``total_ut_steps`` 4 while no row leaves the loop
early). None for a block without the columns (every block whose layers run
once, a program before PR 48)."""

NAME = "loop_passes_per_token"
UNIT = "count"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    cols = host_phases.window_rows(r)
    if cols is None or "loop_row_steps" not in cols:
        return None
    row_steps = float(cols["loop_row_steps"].sum())
    if row_steps <= 0:
        return None
    return float(cols["loop_passes"].sum()) / row_steps
