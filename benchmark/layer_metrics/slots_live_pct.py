"""Share of the engine's slots that held a live row, over the window: the
flight ring's ``rows`` (taken as each window is DISPATCHED) weighted by the
seconds between successive rows, over ``max_num_seqs``. The inside measurement
of what ``rows_per_window`` takes from outside (tokens over windows and
steps): x ``max_num_seqs`` / 100 they should agree within 2 %."""

NAME = "slots_live_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import admission
    return admission.slot_share_pct(r, "rows")
