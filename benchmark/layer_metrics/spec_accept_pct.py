"""Share of the draft tokens a drafting window's verify steps took in that
the target's own draws confirmed: the flight ring's ``spec_accepted`` over
``spec_drafted``, summed over the windows of the measured seconds. With
random weights the module's argmax is right about once in the vocabulary's
size, so the cell reads about 0: it measures what a draft and a verified
position COST, not what they yield. None for a program without the columns
or a window that drafted nothing (every cell but a drafting one)."""

NAME = "spec_accept_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "decode window"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def sums(r):
    """(drafted, accepted, live row-steps) over the flight rows of the
    measured window; None without the columns or a draft."""
    from benchmark.lib import host_phases
    cols = host_phases.window_rows(r)
    names = ("spec_drafted", "spec_accepted", "spec_row_steps")
    if cols is None or any(n not in cols for n in names):
        return None
    got = tuple(float(cols[n].sum()) for n in names)
    return got if got[0] > 0 and got[2] > 0 else None


def read(r):
    got = sums(r)
    return None if got is None else 100.0 * got[1] / got[0]
