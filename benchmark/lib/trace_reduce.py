"""From a profiler trace (.xplane.pb) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX: planes,
their lines, and events with a start and a duration in nanoseconds. On a TPU
each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per executed program (named ``jit_<function>(<fingerprint>)``) and its
line ``XLA Ops`` one event per HLO operation. The host is ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` made by the benchmark lands on the line of
the thread that made it.

Everything below works on plain tuples, so the tests can feed it a recorded
trace or a hand-made one.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: The benchmark's own mark on the host line: ``bench.mark mono_ns=<n>``
#: ties the trace's clock to time.monotonic().
MARK = re.compile(r"^bench\.mark mono_ns=(\d+)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_OPCODE = re.compile(r"\s([a-z][a-z0-9_.-]*)\(")
#: Operations that only contain others (a layer scan is one ``while``): they
#: count as busy time, and are left out where leaf work is listed.
CONTAINERS = ("while", "conditional", "call")


def short_op(name: str) -> str:
    """An event of the XLA Ops line is named by its whole HLO instruction
    (kilobytes for a while loop). Keep ``%name opcode``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def is_container(op: str) -> bool:
    return op.rsplit(" ", 1)[-1] in CONTAINERS


def load(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}}.
    Lines of one name within a plane are merged; operation names are
    shortened (short_op)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            shorten = short_op if line.name == OPS_LINE else str
            for ev in line.events:
                events.append((shorten(ev.name), float(ev.start_ns),
                               float(ev.duration_ns)))
    return out


def device_planes(trace: dict) -> dict[int, dict]:
    found = {}
    for name, lines in trace.items():
        m = DEVICE_PLANE.match(name)
        if m:
            found[int(m.group(1))] = lines
    return found


def clock_offset_ns(trace: dict) -> float | None:
    """trace clock minus time.monotonic(), in ns, from the benchmark's
    mark; None when the trace has none."""
    for name, lines in trace.items():
        if not name.startswith("/host:"):
            continue
        for events in lines.values():
            for ev_name, start, _ in events:
                m = MARK.match(ev_name)
                if m:
                    return start - float(m.group(1))
    return None


def union_ns(intervals, lo: float | None = None, hi: float | None = None
             ) -> float:
    """Length of the union of (start, duration) intervals, clipped to
    [lo, hi] when given."""
    spans = []
    for start, dur in intervals:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    total, end = 0.0, None
    for a, b in spans:
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def busy_line(lines: dict) -> list:
    """The events whose union is 'an operation ran on the device': HLO
    operations when the trace has them, else whole programs."""
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def window_ns(trace: dict) -> tuple[float, float] | None:
    """[first start, last end] over every device event: the traced window
    as the devices saw it."""
    lo = hi = None
    for lines in device_planes(trace).values():
        for _, start, dur in busy_line(lines):
            lo = start if lo is None else min(lo, start)
            hi = start + dur if hi is None else max(hi, start + dur)
    return None if lo is None else (lo, hi)


def busy_seconds(trace: dict, lo: float | None = None,
                 hi: float | None = None) -> dict[int, float]:
    """Per device: seconds in which an operation ran, inside [lo, hi] ns."""
    return {dev: union_ns(((s, d) for _, s, d in busy_line(lines)), lo, hi)
            / 1e9 for dev, lines in device_planes(trace).items()}


def program_name(module_event: str) -> str:
    """``jit_run_window(1234567)`` -> ``jit_run_window``."""
    return module_event.split("(", 1)[0]


def program_times(trace: dict) -> dict[str, dict]:
    """Per program name: executions and device seconds, averaged over the
    devices (each device of a sharded program runs its own copy)."""
    planes = device_planes(trace)
    out: dict = {}
    for lines in planes.values():
        for name, _, dur in lines.get(MODULES_LINE, []):
            row = out.setdefault(program_name(name),
                                 {"count": 0.0, "seconds": 0.0})
            row["count"] += 1
            row["seconds"] += dur / 1e9
    n = max(1, len(planes))
    return {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
            for k, v in out.items()}


def program_runs_ms(trace: dict, device: int | None = None) -> dict:
    """Per program name: the device milliseconds of each execution on one
    device (the first by default), in order."""
    planes = device_planes(trace)
    if not planes:
        return {}
    lines = planes[min(planes) if device is None else device]
    out: dict = {}
    for name, _, dur in sorted(lines.get(MODULES_LINE, []),
                               key=lambda e: e[1]):
        out.setdefault(program_name(name), []).append(round(dur / 1e6, 3))
    return out


def op_times(trace: dict, inside: str | None = None,
             leaves_only: bool = False) -> dict[str, float]:
    """Device seconds per HLO operation name, averaged over the devices;
    with ``inside``, only operations that ran while a program whose name
    contains it was running; with ``leaves_only``, without the operations
    that only contain others."""
    planes = device_planes(trace)
    out: dict = {}
    for lines in planes.values():
        spans = None
        if inside is not None:
            spans = sorted((s, s + d) for name, s, d
                           in lines.get(MODULES_LINE, []) if inside in name)
        for name, start, dur in lines.get(OPS_LINE, []):
            if spans is not None and not _within(spans, start):
                continue
            if leaves_only and is_container(name):
                continue
            out[name] = out.get(name, 0.0) + dur / 1e9
    n = max(1, len(planes))
    return {k: v / n for k, v in out.items()}


def _within(spans: list, t: float) -> bool:
    import bisect
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t < spans[i][1]


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: dict, device: int | None = None
              ) -> list[tuple[float, float, str]]:
    """(start_ns, duration_ns, program that ran next) of every interval in
    which no program ran on the device (the first one by default)."""
    planes = device_planes(trace)
    if not planes:
        return []
    lines = planes[min(planes) if device is None else device]
    events = sorted((s, s + d, name) for name, s, d
                    in lines.get(MODULES_LINE, []))
    gaps, end = [], None
    for a, b, name in events:
        if end is not None and a > end:
            gaps.append((end, a - end, program_name(name)))
        end = b if end is None else max(end, b)
    return gaps
