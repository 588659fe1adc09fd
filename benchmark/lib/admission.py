"""What the program's own record says of its admission (PR 36), for the eight
readers that share it.

Three sources, all written by the program, all on ``time.monotonic``, the
clock of the benchmark's window:

* the limiter's **events**: a zero-length span ``overload.limit`` whenever
  ``int(limit)`` changes and on every decrease (dynamo_tpu/runtime/overload.py),
  with ``before``, ``after``, ``direction`` and ``judged_ms``;
* the **permit** as a span: ``http.request`` is held exactly as long as the
  permit (dynamo_tpu/llm/http_service.py), so the spans that cover an instant
  are the permits held at it; once the first token came it carries
  ``permit_to_first_ms``, what the limiter judges;
* the flight ring's columns ``prefilling`` and ``admit_stop``
  (dynamo_tpu/runtime/flight.py), taken at the instant ``rows`` is.

``Reading.spans`` holds names and times only, so the attributes come from
the program's span ring, as ``trace_spans_dropped`` reads it. A program that
lacks the event, the attribute or the column (the parent of PR 36) gives
every function here nothing to read: None, never an error.
"""

from __future__ import annotations

LIMIT_EVENT = "overload.limit"
JUDGED = "permit_to_first_ms"


def ring_spans() -> list:
    """The finished spans of the program's span ring, oldest first; empty
    where the program has no such ring."""
    try:
        from dynamo_tpu.runtime import tracing
        snapshot = getattr(tracing.get_recorder(), "snapshot", None)
    except ImportError:
        return []
    if snapshot is None:
        return []
    return [s for s in snapshot()[0] if s.end_mono is not None]


def limit_events() -> list:
    """The limiter's events in order of time: (t, before, after, direction)."""
    found = []
    for s in ring_spans():
        attrs = s.attrs or {}
        if s.name == LIMIT_EVENT and "before" in attrs and "after" in attrs:
            found.append((s.start_mono, attrs["before"], attrs["after"],
                          attrs.get("direction")))
    return sorted(found, key=lambda e: e[0])


def step_mean(events: list, lo: float, hi: float) -> float | None:
    """Time-weighted mean over [lo, hi] of the step function ``int(limit)``
    that the events describe: ``int(before)`` of the first event until it,
    ``int(after)`` of each event from it on."""
    if not events or hi <= lo:
        return None
    level = int(events[0][1])
    cursor, area = lo, 0.0
    for t, _before, after, _direction in events:
        if t >= hi:
            break
        if t > cursor:
            area += level * (t - cursor)
            cursor = t
        level = int(after)
    area += level * (hi - cursor)
    return area / (hi - lo)


def clipped_seconds(intervals, lo: float, hi: float) -> float:
    """Seconds of the (start, end) intervals that lie inside [lo, hi]."""
    return sum(max(0.0, min(end, hi) - max(start, lo))
               for start, end in intervals)


def permit_spans() -> list | None:
    """The ``http.request`` spans of the ring, or None where none of them
    carries what the limiter judged: that program does not say that the
    span IS the permit (the span is older than the attribute)."""
    spans = [s for s in ring_spans() if s.name == "http.request"]
    if not any(JUDGED in (s.attrs or {}) for s in spans):
        return None
    return spans


def permits_held_mean(r) -> float | None:
    spans = permit_spans()
    if spans is None or r.t1 <= r.t0:
        return None
    return clipped_seconds(((s.start_mono, s.end_mono) for s in spans),
                           r.t0, r.t1) / (r.t1 - r.t0)


def stages(r) -> dict | None:
    """Mean number of requests, over the window, that held a permit and
    stood in each stage: before the engine's queue, in it, in prefill,
    decoding (the one that holds a row), and after the last token."""
    spans = permit_spans()
    if spans is None or r.t1 <= r.t0:
        return None
    by_trace: dict = {}
    for s in ring_spans():
        if s.name in ("engine.queue_wait", "engine.prefill", "engine.decode"):
            by_trace.setdefault(s.trace_id, {}).setdefault(
                s.name, []).append((s.start_mono, s.end_mono))
    parts = dict.fromkeys(("before_engine", "engine.queue_wait",
                           "engine.prefill", "engine.decode",
                           "after_last_token"), 0.0)
    for req in spans:
        own = by_trace.get(req.trace_id, {})
        for name in ("engine.queue_wait", "engine.prefill", "engine.decode"):
            parts[name] += clipped_seconds(own.get(name, ()), r.t0, r.t1)
        inside = [iv for ivs in own.values() for iv in ivs]
        if not inside:
            continue
        first = min(start for start, _ in inside)
        last = max(end for _, end in inside)
        parts["before_engine"] += clipped_seconds(
            [(req.start_mono, min(first, req.end_mono))], r.t0, r.t1)
        parts["after_last_token"] += clipped_seconds(
            [(max(last, req.start_mono), req.end_mono)], r.t0, r.t1)
    return {k: v / (r.t1 - r.t0) for k, v in parts.items()}


def populations(r) -> dict | None:
    """The two populations that ``ttft_p50_ms.batch`` and
    ``http_admit_wait_p50_ms`` read, from the program's own spans: the
    requests whose permit was RELEASED in the window (they ended in it)
    against those whose permit was GRANTED in it. For each: how many, the
    median wait for the permit, the median permit-to-first-token, the
    median seconds the permit was held; and how many belong to both."""
    from benchmark.lib import stats
    spans = permit_spans()
    if spans is None:
        return None
    waited = {s.trace_id: s.end_mono - s.start_mono for s in ring_spans()
              if s.name == "http.admit_wait"}

    def describe(reqs):
        return {"n": len(reqs),
                "admit_wait_p50_ms": stats.percentile(
                    [waited[s.trace_id] * 1e3 for s in reqs
                     if s.trace_id in waited], 50),
                "permit_to_first_p50_ms": stats.percentile(
                    [s.attrs[JUDGED] for s in reqs
                     if JUDGED in (s.attrs or {})], 50),
                "held_p50_s": stats.percentile(
                    [s.end_mono - s.start_mono for s in reqs], 50)}

    ended = [s for s in spans if r.t0 <= s.end_mono <= r.t1]
    granted = [s for s in spans if r.t0 <= s.start_mono <= r.t1]
    return {"ended_in_window": describe(ended),
            "granted_in_window": describe(granted),
            "both": sum(1 for s in ended if r.t0 <= s.start_mono <= r.t1)}


def slot_share_pct(r, column: str) -> float | None:
    """Share of the engine's slots in the state ``column`` counts (``rows``:
    live; ``prefilling``: held without a row), over the window: each flight
    row weighted by the seconds since the row before it, over
    ``max_num_seqs``. None where the ring lacks ``prefilling``: without it
    the program does not say that the two were taken at one instant."""
    from benchmark.lib import host_phases
    slots = (r.engine or {}).get("max_num_seqs")
    cols = host_phases.window_rows(r)
    if cols is None or not slots or "prefilling" not in cols \
            or column not in cols or len(cols["t_mono"]) < 2:
        return None
    t = cols["t_mono"]
    weights = t[1:] - t[:-1]
    if weights.sum() <= 0:
        return None
    mean = float((cols[column][1:] * weights).sum() / weights.sum())
    return 100.0 * mean / slots
