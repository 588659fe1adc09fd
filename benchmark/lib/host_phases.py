"""What the engine thread was doing, from the program's own record.

Two sources, both written by the program (PR 25):

* its **phases** in a profiler trace: ``jax.profiler.TraceAnnotation``s named
  ``engine.<phase>`` (dynamo_tpu/runtime/tracing.py ``ENGINE_PHASES``) on the
  engine thread's line of the host plane, on the clock of the device planes.
  ``phase_intervals`` turns them into self-time intervals (a phase entered
  inside another suspends the outer one), ``idle_by_phase`` splits the
  device's idle gaps (``trace_reduce.idle_gaps``) by the phase that covers
  them;
* its **flight ring**: one row per processed decode window with the
  engine-thread seconds since the previous row (``host_s``, ``wait_s``) and
  the window's period (``period_s``). ``window_rows`` reads the rows of the
  measured window, or nothing when the ring does not hold all of them.

A program that has neither (the parent of PR 25) gives every reader here
nothing to read: they return None and do not raise.

    python3 -m benchmark.lib.host_phases <file.xplane.pb>

prints engine-thread time by phase, the device's idle time by phase and,
where a ``scopes.json`` lies beside the file (lib/scopes.py leaves one),
device time by scope.
"""

from __future__ import annotations

import bisect
import sys

PREFIX = "engine."
#: Phases in which the engine thread waits, for the device or for work: idle
#: time they cover is not explained by the host.
WAITS = ("engine.readback_wait", "engine.idle")
NO_PHASE = "(no engine phase)"


def phase_events(trace: dict) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, phase) of every engine-phase annotation on the
    host planes of a loaded trace (trace_reduce.load)."""
    found = []
    for plane, lines in trace.items():
        if not plane.startswith("/host:"):
            continue
        for events in lines.values():
            found += [(start, start + dur, name)
                      for name, start, dur in events
                      if name.startswith(PREFIX)]
    return sorted(found, key=lambda e: (e[0], -e[1]))


def phase_intervals(trace: dict) -> list[tuple[float, float, str]]:
    """Non-overlapping (start_ns, end_ns, phase), in order: each instant
    belongs to the innermost phase open at it."""
    out: list = []
    stack: list = []   # (end, phase) of the open phases, outermost first
    cursor = 0.0       # up to where the innermost open phase is accounted

    def close(until: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in phase_events(trace):
        close(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = start
        stack.append((end, name))
    close(float("inf"))
    return out


def seconds_by_phase(intervals: list) -> dict[str, float]:
    table: dict = {}
    for start, end, name in intervals:
        table[name] = table.get(name, 0.0) + (end - start) / 1e9
    return table


def idle_by_phase(trace: dict, lo: float | None = None,
                  hi: float | None = None,
                  intervals: list | None = None) -> dict[str, float] | None:
    """Seconds of the first device's idle gaps (no program running) inside
    [lo, hi] ns, by the engine phase that covers them; what no phase
    covers is under NO_PHASE. None when the trace has no engine phase or
    no device plane."""
    from benchmark.lib import trace_reduce
    if intervals is None:
        intervals = phase_intervals(trace)
    if not intervals or not trace_reduce.device_planes(trace):
        return None
    starts = [iv[0] for iv in intervals]
    table: dict = {}
    for start, dur, _next in trace_reduce.idle_gaps(trace):
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(intervals) and intervals[i][0] < b:
            s, e, name = intervals[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                table[name] = table.get(name, 0.0) + part / 1e9
                covered += part
            i += 1
        if b - a > covered:
            table[NO_PHASE] = table.get(NO_PHASE, 0.0) + (b - a - covered) / 1e9
    return table


def idle_unattributed_seconds(table: dict[str, float]) -> float:
    """Idle seconds during which the engine thread was in no phase, or in
    one in which it waits."""
    return sum(v for k, v in table.items() if k == NO_PHASE or k in WAITS)


def window_rows(r) -> dict | None:
    """The program's flight rows of the measured window [r.t0, r.t1] as
    numpy columns, or None (and a line on stderr) when the ring lacks any
    row of the window: a mean over what is left would be another number."""
    try:
        from dynamo_tpu.runtime import flight
        between = getattr(flight.get_recorder(), "between", None)
    except ImportError:
        return None
    if between is None:
        return None
    got = between(r.t0, r.t1)
    if "host_s" not in got["columns"]:
        return None
    if got["missed"] or not got["rows"]:
        print(f"benchmark: flight ring holds {got['rows']} rows of the "
              f"window and lacks {got['missed']}: not read", file=sys.stderr)
        return None
    return got["columns"]


def _print_table(title: str, table: dict[str, float], total: float) -> None:
    print(title)
    for name, seconds in sorted(table.items(), key=lambda kv: -kv[1]):
        share = 100.0 * seconds / total if total else 0.0
        print(f"  {seconds * 1e3:12.3f} ms  {share:6.2f} %  {name}")


def main(argv: list[str]) -> int:
    import json
    import os
    from benchmark.lib import scopes, trace_reduce
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2], file=sys.stderr)
        return 2
    trace = trace_reduce.load(argv[0])
    span = trace_reduce.window_ns(trace)
    window_s = (span[1] - span[0]) / 1e9 if span else 0.0
    intervals = phase_intervals(trace)
    _print_table("engine thread, self time by phase:",
                 seconds_by_phase(intervals),
                 sum((e - s) for s, e, _ in intervals) / 1e9)
    idle = idle_by_phase(trace, *(span or (None, None)), intervals=intervals)
    if idle is None:
        print("device idle time by phase: the trace has no engine phase, or no "
              "device plane")
    else:
        _print_table(f"device idle time by engine phase (of {window_s:.3f} s"
                     " traced):", idle, window_s)
    beside = os.path.join(os.path.dirname(argv[0]), scopes.MAP_FILE)
    if os.path.exists(beside):
        with open(beside, encoding="utf-8") as fh:
            saved = json.load(fh)
        got = scopes.seconds_by_scope(trace, saved["ops_by_scope"])
        if got is not None:
            _print_table(f"device time by scope inside {got['module']}:",
                         {**got["scopes"], "(no scope)": got["unscoped"]},
                         got["leaf_seconds"])
            return 0
    print(f"device time by scope: no usable {scopes.MAP_FILE} beside the "
          "file (a traced run of benchmark/run.py leaves one)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
