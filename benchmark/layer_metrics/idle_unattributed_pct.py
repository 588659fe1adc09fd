"""Device-idle time the host does not explain: of the traced span, the share in
which no program ran on the device while the engine thread was in no phase, or
in one in which it waits (``engine.readback_wait``, ``engine.idle``). Idle time
under any other phase (admit, dispatch, token walk, ...) is the host's, by
name (lib/host_phases.py). None where the trace has no engine phase."""

NAME = "idle_unattributed_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "out_tok_s"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import host_phases
    if r.trace is None or r.trace_span_ns is None:
        return None
    lo, hi = r.trace_span_ns
    table = host_phases.idle_by_phase(r.trace, lo, hi)
    if table is None or hi <= lo:
        return None
    return (100.0 * host_phases.idle_unattributed_seconds(table)
            / ((hi - lo) / 1e9))
