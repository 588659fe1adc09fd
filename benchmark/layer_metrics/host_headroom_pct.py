"""Share of its time the engine thread waits for the device: over the flight
ring's rows of the measured window, the seconds in ``engine.readback_wait``
(``wait_s``) over those plus the host seconds (``host_s``). What is left of it
is how much faster the device may get before the host is in the way."""

NAME = "host_headroom_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    from benchmark.lib import host_phases
    rows = host_phases.window_rows(r)
    if rows is None:
        return None
    wait, host = float(rows["wait_s"].sum()), float(rows["host_s"].sum())
    if wait + host <= 0:
        return None
    return 100.0 * wait / (wait + host)
