"""How often the limiter LOWERED its limit (x0.7, a first token slower than
``target_latency_ms``) from the server's start to the window's end: its
``overload.limit`` events with ``direction`` ``decrease``. One in the ramp
sets the limit of the whole window, so the ramp counts. 0 in a run without a
freeze; None where the program records no such event."""

NAME = "admit_decreases"
UNIT = "count"
BETTER = "lower"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import admission
    events = admission.limit_events()
    if not events:
        return None
    return sum(1 for t, _b, _a, direction in events
               if direction == "decrease" and t <= r.t1)
