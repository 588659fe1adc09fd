"""Windows of the measured window before which the ENGINE's own admission
(``TPUEngine._admit``) left a request queued (no free slot, no KV room, the
TTFT budget): flight rows whose ``admit_stop`` is not 0. While the HTTP
limiter holds callers outside the engine's queue this reads 0, and every
empty slot is the limiter's."""

NAME = "engine_admit_stops"
UNIT = "count"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    cols = host_phases.window_rows(r)
    if cols is None or "admit_stop" not in cols:
        return None
    return int((cols["admit_stop"] != 0).sum())
