"""Serving programs compiled inside the measured window (compile registry
delta). Must read 0: a compile on the engine thread stalls every stream."""

NAME = "compiles_in_window"
UNIT = "count"
BETTER = "lower"
LAYER = "compiled programs"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    return float(r.after["compiles_total"] - r.before["compiles_total"])
