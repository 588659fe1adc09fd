"""Share of the engine's slots that a request held WITHOUT a live row, over the
window: the flight ring's ``prefilling`` (chunked prefill, stalled for pages,
frozen for a preemption, or owed only a first token's readback), taken at the
instant ``rows`` is and weighted as ``slots_live_pct`` is. With it and the
live share, what is left of 100 is EMPTY slots."""

NAME = "slots_prefill_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import admission
    return admission.slot_share_pct(r, "prefilling")
