"""Permits held, averaged over the window: ``http.request`` is open exactly as
long as its permit (``with permit, span("http.request", ...)``), so the
seconds of those spans inside [t0, t1] over the window's seconds is the mean
number of requests past the limiter. It cannot pass the limit by more than a
decrease leaves standing."""

NAME = "permits_held_mean"
UNIT = "count"
BETTER = "higher"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import admission
    return admission.permits_held_mean(r)
