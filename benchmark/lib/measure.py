"""What a run collected, and the end-to-end metrics taken from it.

``Reading`` is the one object a per-layer metric's reader gets. It holds
only plain data: the client's records, the window, counter snapshots before
and after the window, periodic samples of the engine's occupancy, the
program's spans, the engine's emissions and, in a traced run, the reduced
profiler trace.
"""

from __future__ import annotations

import dataclasses

from benchmark.lib import stats, trace_reduce


@dataclasses.dataclass
class Reading:
    records: list            # client records (lib/client_proc.py)
    open_loop: bool
    t0: float                # window, on time.monotonic()
    t1: float
    t_end: float             # when the client stopped
    before: dict             # counter snapshots at t0 and t1
    after: dict
    samples: list            # [{"t", "pages_active", "rows", "context"}]
    spans: list              # program spans: {"name", "start", "end"}
    emissions: dict          # prompt key -> [first engine emission times]
    prompt_keys: dict        # request id -> prompt key
    engine: dict             # decode_window, num_pages, tp, quant, ...
    model: dict              # the configuration file
    peaks: dict | None       # row of peaks.json; None in a CPU rehearsal
    metrics_text: str        # /metrics at the end of the window
    trace: dict | None = None          # trace_reduce.load() of the capture
    trace_span_ns: tuple | None = None  # traced window on the trace clock
    trace_mono: tuple | None = None     # the same on time.monotonic()

    # -- selections ---------------------------------------------------------
    def measured(self) -> list:
        """The requests the window answers for: those due in it (open
        loop), or those that came to an end in it, completed or failed
        (closed loop: a caller's request may wait and run for longer than
        the window, so the ones sent in it are mostly still running when
        it closes, and are cut by the client, not failed)."""
        if self.open_loop:
            return [r for r in self.records if r["measured"]]
        return [r for r in self.records
                if not r["aborted"] and self.t0 <= r["done"] <= self.t1]

    def completed_in_window(self) -> list:
        return [r for r in self.measured() if r["ok"]]

    def samples_in(self, lo: float, hi: float) -> list:
        return [s for s in self.samples if lo <= s["t"] <= hi]


# -- end-to-end metrics, by name ---------------------------------------------
# run.py reports the ones BENCHMARK.json lists for the cell. All are taken at
# the client, over HTTP.

def ttft_ms(r: Reading, q: float):
    """The q-th percentile of time to first token over the measured
    requests (a request that never answered counts to the end of the run)."""
    return stats.percentile(
        stats.ttfts_ms(r.measured(), r.open_loop, r.t_end), q)


def gap_ms(r: Reading, q: float):
    """The q-th percentile of the gaps between successive content chunks
    of one stream, pooled over the streams, inside the window."""
    return stats.percentile(stats.chunk_gaps_ms(r.records, r.t0, r.t1), q)


def ttft_p50_ms(r: Reading):
    return ttft_ms(r, 50)


def tpot_p50_ms(r: Reading):
    """Median time per output token over all the streams' stretches of
    about 3 s inside the window (stats.stretch_tpots_ms): the window's work
    and time and no more. Not the median over completed requests of first
    chunk to last: where a request lasts as long as the window that reaches
    back into the ramp, which is set-up, and one stall of a few seconds
    anywhere in a request's 40 s moves it by a tenth (PERF.md, section 2;
    the whole-request figure is the per-layer ``tpot_request_p50_ms``)."""
    return stats.percentile(stats.stretch_tpots_ms(r.records, r.t0, r.t1),
                            50)


def tpot_request_p50_ms(r: Reading):
    """ISSUE 24's time per output token: median over the requests completed
    in the window of (last chunk - first chunk) / (completion_tokens - 1)."""
    return stats.percentile(stats.tpots_ms(r.completed_in_window()), 50)


def gap_p95_ms(r: Reading):
    return gap_ms(r, 95)


def out_tok_s(r: Reading):
    """Output tokens a second, for the whole cell: the tokens that reached
    the clients inside the window, whichever request they belong to, over
    the window's seconds. All the work of the window and no more: a stall
    inside it lowers the number even if every request completes later, in
    the drain, and a request that outlasts the window counts for the part
    streamed in it (counting whole requests would swing by whole requests
    where they last as long as the window)."""
    return stats.tokens_in_window(r.records, r.t0, r.t1) / (r.t1 - r.t0)


END_TO_END = {"ttft_p50_ms": ttft_p50_ms, "tpot_p50_ms": tpot_p50_ms,
              "gap_p95_ms": gap_p95_ms, "out_tok_s": out_tok_s}


def summary(r: Reading) -> dict:
    """The earlier output line: counts and medians that are not metrics of
    the manifest."""
    late = [(x["sent"] - x["due"]) * 1e3 for x in r.records
            if x["due"] is not None and x["sent"] is not None]
    measured = r.measured()
    return {
        "requests_recorded": len(r.records),
        "measured": len(measured),
        "completed": sum(1 for x in measured if x["ok"]),
        "failed": sum(1 for x in measured if not x["ok"]),
        "cut_at_window_end": sum(1 for x in r.records if x["aborted"]),
        "statuses": sorted({str(x["status"]) for x in r.records}),
        "ttft_p50_ms": ttft_p50_ms(r),
        "other_percentiles": {
            "ttft_ms": {q: ttft_ms(r, q) for q in (75, 90, 95)},
            "gap_ms": {q: gap_ms(r, q) for q in (50, 90, 95, 99)},
            "tpot_ms": {q: stats.percentile(stats.stretch_tpots_ms(
                r.records, r.t0, r.t1), q) for q in (10, 50, 90)},
            "tpot_request_ms": {q: stats.percentile(stats.tpots_ms(
                r.completed_in_window()), q) for q in (10, 50, 90)}},
        "ttft_samples": len(measured),
        "tpot_samples": len(stats.stretch_tpots_ms(r.records, r.t0, r.t1)),
        "tpot_request_samples": len(stats.tpots_ms(
            r.completed_in_window())),
        "gap_samples": len(stats.chunk_gaps_ms(r.records, r.t0, r.t1)),
        "tokens_in_window": stats.tokens_in_window(r.records, r.t0, r.t1),
        "loadgen_late_p50_ms": stats.percentile(late, 50),
        "loadgen_late_max_ms": max(late, default=None),
    }


# -- the traced run's breakdown ------------------------------------------------

def breakdown(r: Reading) -> dict | None:
    """Device operations that took most time, and the longest idle gaps by
    what the benchmark's own spans know of the host: whether any request
    was in flight, whether one still waited for its first chunk, and which
    program ran next. What the host did inside the program needs
    TraceAnnotations there (PERF.md, section 7)."""
    if r.trace is None:
        return None
    ops = trace_reduce.top(
        trace_reduce.op_times(r.trace, leaves_only=True), 10)
    offset = trace_reduce.clock_offset_ns(r.trace)
    labels: dict = {}
    lo, hi = r.trace_span_ns or (None, None)
    for start, dur, nxt in trace_reduce.idle_gaps(r.trace):
        if lo is not None and not (lo <= start <= hi):
            continue
        label = f"before {nxt}"
        if offset is not None:
            t = (start - offset) / 1e9
            flying = [x for x in r.records if x["sent"] is not None
                      and x["sent"] <= t <= (x["done"] or r.t_end)]
            if not flying:
                label = "no request in flight"
            elif any(not x["chunk_t"] or x["chunk_t"][0] > t
                     for x in flying):
                label += "; a request awaits its first chunk"
        labels[label] = labels.get(label, 0.0) + dur / 1e9
    return {"device_ops": ops, "idle_gaps": trace_reduce.top(labels, 10)}
