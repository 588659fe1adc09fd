"""Percentiles and the arithmetic of the client-side metrics.

Times are seconds on ``time.monotonic()`` (CLOCK_MONOTONIC, shared by the
client process and this one); results are milliseconds where the name says.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0..100) with linear interpolation between the
    order statistics at rank q/100 * (n-1); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def ttfts_ms(records: list[dict], open_loop: bool, t_end: float
             ) -> list[float]:
    """Time to the first content chunk of each measured request, from the
    moment it was DUE (open loop) or sent (closed loop). A request that
    never produced a first chunk counts from its start to the end of the
    run: it missed every limit."""
    out = []
    for r in records:
        start = r["due"] if open_loop else r["sent"]
        first = r["chunk_t"][0] if r["chunk_t"] else t_end
        out.append((first - start) * 1e3)
    return out


def tpots_ms(records: list[dict]) -> list[float]:
    """(last chunk - first chunk) / (completion_tokens - 1) of each completed
    request with at least two tokens."""
    out = []
    for r in records:
        n = (r.get("usage") or {}).get("completion_tokens", 0)
        if r["ok"] and n > 1 and len(r["chunk_t"]) > 1:
            out.append((r["chunk_t"][-1] - r["chunk_t"][0]) / (n - 1) * 1e3)
    return out


#: A stretch of a stream over which one time per token is taken: some ten
#: decode windows, so that it neither hangs on how the server cuts its
#: tokens into chunks (eight a chunk today; one at a time in bursts would
#: make most gaps read zero) nor on the host clock's half millisecond.
STRETCH_S = 3.0


def stretch_tpots_ms(records: list[dict], t0: float, t1: float,
                     stretch_s: float = STRETCH_S) -> list[float]:
    """Time per output token over every stretch of every stream inside
    [t0, t1], whichever request it belongs to: a stream's chunks there are
    cut, from its first one on, into stretches that each end at the first
    chunk ``stretch_s`` or more after their start; a stretch's value is its
    length over the tokens that arrived in it after its start. What is left
    at a stream's end joins its last stretch, or is one if it is the only
    one. A stream's first chunk is where the clock starts (the wait before
    it is the time to first token)."""
    out = []
    for r in records:
        inside = [(t, n) for t, n in zip(r["chunk_t"], r["chunk_n"])
                  if t0 <= t <= t1]
        spans = []                      # [seconds, tokens]
        start, tokens = (inside[0][0] if inside else None), 0
        for t, n in inside[1:]:
            tokens += n
            if t - start >= stretch_s:
                spans.append([t - start, tokens])
                start, tokens = t, 0
        if tokens:
            left = inside[-1][0] - start
            if spans:
                spans[-1][0] += left
                spans[-1][1] += tokens
            else:
                spans.append([left, tokens])
        out += [sec / n * 1e3 for sec, n in spans if n > 0]
    return out


def chunk_gaps_ms(records: list[dict], t0: float, t1: float) -> list[float]:
    """Time between successive content chunks of one stream, pooled over
    all streams, for pairs that both arrived inside [t0, t1]."""
    out = []
    for r in records:
        ts = r["chunk_t"]
        for a, b in zip(ts, ts[1:]):
            if a >= t0 and b <= t1:
                out.append((b - a) * 1e3)
    return out


def tokens_in_window(records: list[dict], t0: float, t1: float) -> int:
    """Output tokens that reached the client inside [t0, t1], whichever
    request they belong to: all the work of the window, no more."""
    return sum(n for r in records
               for t, n in zip(r["chunk_t"], r["chunk_n"]) if t0 <= t <= t1)
