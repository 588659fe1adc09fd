"""What the limiter judged: the median ``permit_to_first_ms`` (permit granted to
first chunk, the latency ``AdaptiveLimiter._observe`` compares with
``target_latency_ms``) of the ``http.request`` spans whose first token fell in
the window. The largest goes to stderr beside the target: the distance to a
decrease; and beside it the two populations that ``ttft_p50_ms.batch``
(requests that ENDED in the window) and ``http_admit_wait_p50_ms`` (permits
GRANTED in it) read, each with its wait and its first token."""

NAME = "admit_judged_p50_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "http admission"
MOVES = "out_tok_s"
SOURCE = "program_span"


def read(r):
    import json
    import sys
    from benchmark.lib import admission, stats
    judged = []
    for s in admission.ring_spans():
        ms = (s.attrs or {}).get(admission.JUDGED)
        if s.name == "http.request" and ms is not None \
                and r.t0 <= s.start_mono + ms / 1e3 <= r.t1:
            judged.append(ms)
    if judged:
        print(f"benchmark: the limiter judged {len(judged)} first tokens in "
              f"the window, the slowest {max(judged):.0f} ms (a decrease "
              f"takes more than target_latency_ms, 5000 by default)",
              file=sys.stderr)
        print("benchmark: populations " + json.dumps(
            admission.populations(r)), file=sys.stderr)
    return stats.percentile(judged, 50)
