"""Tokens a live row emits a scan step of a drafting window: a row-step
emits one token and its accepted drafts, so the flight ring's
``spec_row_steps`` + ``spec_accepted`` over ``spec_row_steps``
(``spec_accept_pct.sums``): 1 where no draft is ever accepted, spec_k + 1
where all are. What divides
the step's time into ``tpot_p50_ms``. None where nothing drafts."""

NAME = "spec_tokens_per_step"
UNIT = "tokens"
BETTER = "higher"
LAYER = "decode window"
MOVES = "tpot_p50_ms"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import manifest
    got = manifest.load_module("layer_metrics", "spec_accept_pct").sums(r)
    return None if got is None else (got[2] + got[1]) / got[2]
