"""Coverage of the scopes: the share of the window program's leaf device time
(operations that contain no others) whose instruction lies in a named scope.
What is left are instructions the compiler made without a name (lib/scopes.py
lists the largest on the ``scopes`` line of a traced run)."""

NAME = "scoped_device_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p50_ms"
SOURCE = "device_trace"


def read(r):
    from benchmark.lib import scopes
    got = scopes.reduced(r)
    if got is None or not got["leaf_seconds"]:
        return None
    return 100.0 * (1.0 - got["unscoped"] / got["leaf_seconds"])
