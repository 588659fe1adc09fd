"""Requests that hold a permit and no decoding row, averaged over the window:
``permits_held_mean`` less the seconds of ``engine.decode`` spans (first token
to finish: the time a request holds a live row) inside the window over its
seconds. Where they stand goes to stderr, from the spans that exist: before
the engine, in its queue, in prefill, after the last token."""

NAME = "permits_without_row"
UNIT = "count"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    import sys
    from benchmark.lib import admission
    held = admission.permits_held_mean(r)
    parts = admission.stages(r)
    if held is None or parts is None:
        return None
    print("benchmark: permits held %.2f = " % held + ", ".join(
        f"{name} {value:.2f}" for name, value in parts.items()),
        file=sys.stderr)
    return held - parts["engine.decode"]
