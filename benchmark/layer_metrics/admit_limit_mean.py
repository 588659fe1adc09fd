"""The concurrency limit of the HTTP admission over the WINDOW, where
``admit_limit`` reads it at the last instant: the step function of
``int(limit)`` (what ``AdaptiveLimiter.admit`` compares the permits held with)
rebuilt from the limiter's own ``overload.limit`` events, each with the limit
before and after it, weighted by time over [t0, t1]. The limit climbs by
1/limit a completion, so a window that opens at 19 and closes at 22 admitted
at about 20.5."""

NAME = "admit_limit_mean"
UNIT = "count"
BETTER = "higher"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    from benchmark.lib import admission
    return admission.step_mean(admission.limit_events(), r.t0, r.t1)
