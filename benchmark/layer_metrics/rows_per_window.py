"""Mean live rows per decode step: tokens the window programs emitted over
windows processed times the steps of a window (compile registry deltas)."""

NAME = "rows_per_window"
UNIT = "count"
BETTER = "higher"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    windows = r.after["windows_total"] - r.before["windows_total"]
    tokens = r.after["window_tokens_total"] - r.before["window_tokens_total"]
    if windows <= 0:
        return None
    return tokens / (windows * r.engine["decode_window"])
