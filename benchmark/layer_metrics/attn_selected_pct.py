"""Share of the keys in context that a latent block's decode steps ATTEND: per
decode step and layer the window program counts, over its live rows, the keys
the indexer's choice kept (at most ``index_topk`` a row) and the keys in
context, sums both on the device and hands them back with the window's
tokens; the flight ring keeps them per window (``attn_selected`` over
``attn_context``). Over the measured window. 100 while no row is past
``index_topk`` tokens; the further under it, the more the selection does.
None for a block without the columns (every other block, a program before PR
34)."""

NAME = "attn_selected_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def sums(r, span=None):
    """(keys attended, keys in context, windows) of the flight rows of the
    measured window, or of ``span`` (t_lo, t_hi); None without the columns
    or without a key counted."""
    from benchmark.lib import host_phases
    if span is None:
        cols = host_phases.window_rows(r)
    else:
        try:
            from dynamo_tpu.runtime import flight
            cols = flight.get_recorder().between(*span)["columns"]
        except (ImportError, AttributeError):
            return None
    if cols is None or "attn_context" not in cols:
        return None
    context = float(cols["attn_context"].sum())
    if context <= 0:
        return None
    counted = int((cols["attn_context"] > 0).sum())
    return float(cols["attn_selected"].sum()), context, counted


def per_step(r):
    """(keys attended, keys in context) of ONE decode step inside the traced
    seconds (else the measured window), summed over rows and layers: the
    windows' sums over the steps they hold."""
    got = (sums(r, r.trace_mono) if r.trace_mono is not None else None) \
        or sums(r)
    if got is None:
        return None
    steps = got[2] * r.engine["decode_window"]
    return got[0] / steps, got[1] / steps


def read(r):
    got = sums(r)
    if got is None:
        return None
    return 100.0 * got[0] / got[1]
