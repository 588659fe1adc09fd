"""Share of the recurrent state arrays a decode step HAD to touch: per decode
step the window program counts its live rows, sums them on the device and
hands the sum back with the window's tokens; the flight ring keeps it per
window (``ssm_row_steps``). Over the measured window, of steps x
``max_num_seqs`` (every slot holds a row's state in every recurrent layer,
live or not). What a step that read and wrote only live rows' state would
move of the arrays; a program that updates every slot moves 100. None for a
block without the column (every block whose whole per-request state is
pages, a program before PR 41)."""

NAME = "ssm_state_rows_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def sums(r, span=None):
    """(live row-steps, windows that counted any) of the flight rows of the
    measured window, or of ``span`` (t_lo, t_hi); None without the column
    or without a row counted."""
    from benchmark.lib import host_phases
    if span is None:
        cols = host_phases.window_rows(r)
    else:
        try:
            from dynamo_tpu.runtime import flight
            cols = flight.get_recorder().between(*span)["columns"]
        except (ImportError, AttributeError):
            return None
    if cols is None or "ssm_row_steps" not in cols:
        return None
    row_steps = float(cols["ssm_row_steps"].sum())
    if row_steps <= 0:
        return None
    return row_steps, int((cols["ssm_row_steps"] > 0).sum())


def per_step(r):
    """Live rows of ONE decode step inside the traced seconds (else the
    measured window): the windows' sums over the steps they hold."""
    got = (sums(r, r.trace_mono) if r.trace_mono is not None else None) \
        or sums(r)
    if got is None:
        return None
    return got[0] / (got[1] * r.engine["decode_window"])


def read(r):
    got = sums(r)
    slots = (r.engine or {}).get("max_num_seqs")
    if got is None or not slots:
        return None
    return 100.0 * got[0] / (got[1] * r.engine["decode_window"] * slots)
