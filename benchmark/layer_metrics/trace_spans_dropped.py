"""Spans the program's span ring had evicted when the run ended
(``SpanRecorder.dropped``): above zero, the readers of program spans
(``http_admit_wait_p50_ms``, ``queue_wait_p90_ms``) read a part of what was
recorded."""

NAME = "trace_spans_dropped"
UNIT = "count"
BETTER = "lower"
LAYER = "scheduler"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    import sys
    try:
        from dynamo_tpu.runtime import tracing
    except ImportError:
        return None
    rec = tracing.get_recorder()
    print(f"benchmark: span ring holds {len(r.spans)} finished spans of "
          f"{getattr(rec, 'capacity', None)}", file=sys.stderr)
    return getattr(rec, "dropped", None)
