"""What the program's own record says of its start (PR 50), for the eight
readers that share it, and ``python3 -m benchmark.lib.startup``.

Two sources, both written by the program, both on ``time.monotonic``, the
clock of the benchmark's ``_T_START`` and ``t0``:

* the **start-up trace** in the span ring (dynamo_tpu/runtime/tracing.py
  ``Startup``): a root span ``startup`` from the launcher's entry to the
  instant the engine is ready and the service listens, and under it one span
  a stage (``startup.engine`` with the runner's stages inside,
  ``startup.warmup`` on the engine thread with ``startup.prefill_ladder``
  inside, ``startup.wait_ready``, ``startup.http``, ...);
* the compile registry's **first calls** (dynamo_tpu/engine/perf.py
  ``CompileRegistry.first_calls``): one record a compiled program's first
  call with its wall seconds split into ``trace_s``, ``lower_s``,
  ``cache_load_s`` and ``compile_s``, ``when`` (``startup`` before the engine
  reported ready) and ``cache`` (``hit`` | ``miss`` | ``off``).

``setup_s`` = (launcher entry - ``_T_START``: imports, the plan, the seams)
+ ``startup_ready_s`` + (ready to the window's opening: the check against
the reference and the ramp, the benchmark's own). Under the harness the
seam makes the weights inside ``startup.engine`` and ahead of the runner's
own stages, so those seconds are that stage's SELF time, beside a
``startup.weights`` that reads ``given``; the seam's ``weights_s`` (the
``server`` line) is the outside figure to set against it.

A program that has no such span or record (the parent of PR 50) gives every
function here nothing to read: None, never an error.

    python3 -m benchmark.lib.startup <file>   # a saved /debug/perf body
"""

from __future__ import annotations

import json
import sys

ROOT = "startup"
WARMUP = "startup.warmup"
PARTS = ("wall_s", "trace_s", "lower_s", "cache_load_s", "compile_s")


# -- the program's record -----------------------------------------------------------

def start_spans(ring: list | None = None):
    """(root, the other spans of its trace) of the NEWEST finished start in
    the span ring; None where the ring holds none."""
    if ring is None:
        from benchmark.lib import admission
        ring = admission.ring_spans()
    roots = [s for s in ring
             if s.name == ROOT and getattr(s, "parent_span_id", None) is None
             and s.end_mono is not None]
    if not roots:
        return None
    root = max(roots, key=lambda s: s.start_mono)
    return root, [s for s in ring if s.trace_id == root.trace_id
                  and s is not root and s.end_mono is not None]


def first_calls(when: str = "startup", records: list | None = None):
    """The registry's first-call records taken ``when``; None where the
    program keeps none."""
    if records is None:
        try:
            from dynamo_tpu.engine import perf
            records = getattr(perf.get_registry(), "first_calls", None)
        except ImportError:
            return None
        if records is None:
            return None
    return [r for r in list(records) if r.get("when") == when]


# -- what the readers return --------------------------------------------------------

def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            total, reach = total + (hi - lo), hi
        elif hi > reach:
            total, reach = total + (hi - reach), hi
    return total


def ready_s(ring: list | None = None):
    found = start_spans(ring)
    return None if found is None else found[0].end_mono - found[0].start_mono


def stage_s(name: str, ring: list | None = None):
    """Seconds of the stage ``name`` (the sum, should a start hold two)."""
    found = start_spans(ring)
    if found is None:
        return None
    spans = [s for s in found[1] if s.name == name]
    return sum(s.end_mono - s.start_mono for s in spans) if spans else None


def unattributed_s(ring: list | None = None):
    """The root less the union of its DIRECT children, each cut to the
    root: what no stage covers. Stages on two threads may lie beside one
    another (the warm-up and the launcher's wait for it): the union counts
    an instant once."""
    found = start_spans(ring)
    if found is None:
        return None
    root, spans = found
    direct = [(max(s.start_mono, root.start_mono),
               min(s.end_mono, root.end_mono))
              for s in spans if s.parent_span_id == root.span_id]
    covered = union_seconds(iv for iv in direct if iv[1] > iv[0])
    return (root.end_mono - root.start_mono) - covered


def programs(records: list | None = None):
    rows = first_calls("startup", records)
    return None if rows is None else len(rows)


def seconds_of(*parts: str, records: list | None = None):
    rows = first_calls("startup", records)
    if rows is None:
        return None
    return sum(float(r.get(part) or 0.0) for r in rows for part in parts)


def cache_misses(records: list | None = None):
    rows = first_calls("startup", records)
    if rows is None:
        return None
    return sum(1 for r in rows if r.get("cache") == "miss")


# -- the tables ---------------------------------------------------------------------

def stage_rows(ring: list | None = None) -> list[dict]:
    """The stages in the order they began: name, parent's name, seconds
    from the root's start, seconds, and self seconds (less the union of
    the stage's own children)."""
    found = start_spans(ring)
    if found is None:
        return []
    root, spans = found
    names = {root.span_id: ROOT, **{s.span_id: s.name for s in spans}}
    under: dict = {}
    for s in spans:
        under.setdefault(s.parent_span_id, []).append(
            (s.start_mono, s.end_mono))
    return [{"name": s.name, "parent": names.get(s.parent_span_id),
             "at_s": s.start_mono - root.start_mono,
             "seconds": s.end_mono - s.start_mono,
             "self_s": (s.end_mono - s.start_mono)
             - union_seconds(under.get(s.span_id, ())),
             **({"attrs": dict(s.attrs)} if s.attrs else {})}
            for s in sorted(spans, key=lambda s: s.start_mono)]


def by_family(rows: list[dict]) -> dict:
    out: dict = {}
    for r in rows:
        fam = out.setdefault(r["program"], {
            "programs": 0, **dict.fromkeys(PARTS, 0.0), "hits": 0,
            "misses": 0})
        fam["programs"] += 1
        for part in PARTS:
            fam[part] += float(r.get(part) or 0.0)
        fam["hits"] += r.get("cache") == "hit"
        fam["misses"] += r.get("cache") == "miss"
    return out


def arithmetic(t_start: float | None, t0: float | None,
               ring: list | None = None) -> dict | None:
    """``setup_s`` in its three terms; ``t_start`` is run.py's ``_T_START``
    and ``t0`` the window's opening."""
    found = start_spans(ring)
    if found is None or t_start is None or t0 is None:
        return None
    root = found[0]
    return {"before_launcher_s": root.start_mono - t_start,
            "startup_ready_s": root.end_mono - root.start_mono,
            "ready_to_window_s": t0 - root.end_mono,
            "setup_s": t0 - t_start}


def table(stages: list[dict], ready: float, unattributed: float,
          families: dict, sums: dict | None = None) -> str:
    lines = ["%-28s %-16s %9s %9s %9s  %s" % (
        "stage", "under", "at_s", "seconds", "self_s", "attrs")]
    for row in stages:
        lines.append("%-28s %-16s %9.3f %9.3f %9.3f  %s" % (
            row["name"], (row.get("parent") or "").removeprefix("startup."),
            row["at_s"], row["seconds"], row["self_s"],
            json.dumps(row.get("attrs") or {}) if row.get("attrs") else ""))
    lines.append("%-28s %-16s %9s %9.3f %9.3f" % (
        ROOT, "", "", ready, unattributed)
        + "  (self_s here: unattributed, what no direct stage covers)")
    lines.append("%-16s %8s %9s %9s %9s %12s %9s %5s %6s" % (
        "first calls", "programs", *PARTS, "hits", "misses"))
    for name, fam in sorted(families.items()):
        lines.append("%-16s %8d %9.3f %9.3f %9.3f %12.3f %9.3f %5d %6d" % (
            name, fam["programs"], *(fam[p] for p in PARTS), fam["hits"],
            fam["misses"]))
    if sums:
        lines.append("setup_s %.3f = before the launcher %.3f + "
                     "startup_ready_s %.3f + ready to the window %.3f" % (
                         sums["setup_s"], sums["before_launcher_s"],
                         sums["startup_ready_s"],
                         sums["ready_to_window_s"]))
    return "\n".join(lines)


_REPORTED = []


def report(reading=None, out=sys.stderr) -> None:
    """In a run's process, once: the stage table, the first calls by
    family and ``setup_s`` in its terms, on stderr (the readers' numbers
    are the result line's)."""
    if _REPORTED or start_spans() is None:
        return
    _REPORTED.append(True)
    rows = first_calls("startup") or []
    t_start = getattr(sys.modules.get("__main__"), "_T_START", None)
    sums = arithmetic(t_start, getattr(reading, "t0", None))
    stages = stage_rows()
    print("benchmark: the start, by the program's own record\n"
          + table(stages, ready_s(), unattributed_s(), by_family(rows), sums),
          file=out)
    late = first_calls("serving") or []
    print("benchmark: startup " + json.dumps({
        "stages": [{k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in row.items()} for row in stages],
        "families": {k: {p: round(v, 4) if isinstance(v, float) else v
                         for p, v in fam.items()}
                     for k, fam in by_family(rows).items()},
        "setup_s": sums,
        "first_calls_serving": [
            {"program": r["program"], "key": repr(r["key"]),
             "cache": r["cache"], "wall_s": round(r["wall_s"], 4)}
            for r in late]}, default=str), file=out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[0], encoding="utf-8") as fh:
        body = json.load(fh)
    start = body.get("startup") or next(
        (e["startup"] for e in (body.get("engines") or {}).values()
         if "startup" in e), None)
    if not start or "ready_s" not in start:
        print("no start-up record in this body", file=sys.stderr)
        return 1
    calls = start.get("first_calls") or {}
    print(table(start["stages"], start["ready_s"], start["unattributed_s"],
                calls.get("families") or {}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
