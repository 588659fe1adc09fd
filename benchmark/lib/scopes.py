"""Device seconds by scope inside the decode-window program.

The program names the regions of its compiled programs with
``jax.named_scope`` (dynamo_tpu/engine/perf.py ``SCOPES``) and its compile
registry maps every instruction of an executable to its scope
(``ops_by_scope``: ``%fusion.296`` -> ``attn.kv_gather``), from the
executable's own HLO text. A trace event of the ``XLA Ops`` line carries the
instruction's name, so: the events inside the executions of the window
program, summed by the scope of their instruction.

An instruction that several scopes were fused into (``attn.kv_gather+
attn.core``) counts under the first of them in PRECEDENCE: the one that
moves the most bytes, in a program that bandwidth bounds.

Where the trace's executions are of several window programs (the page-table
width changed inside the traced seconds), only the one that ran most is
read, with the map of the program the flight ring names for those seconds.
Without scopes (the parent of PR 25, or an executable that the compile
cache of such a tree handed over) every reader returns None, never 0.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

#: The window program in the trace's XLA Modules line, and in the registry.
MODULE = "run_window"
PROGRAM = "decode_window"
MAP_FILE = "scopes.json"
PRECEDENCE = ("attn.kv_gather", "kv.commit", "mlp", "lm_head", "attn.qkv",
              "attn.out", "embed", "attn.core", "sample")


def primary(scope: str | None) -> str | None:
    if not scope:
        return None
    parts = scope.split("+")
    for name in PRECEDENCE:
        if name in parts:
            return name
    return parts[0]


def window_key(decode_window: int, page_bucket: int) -> tuple:
    """The key ModelRunner._get_window memoizes the plain window program
    under: (steps, page-table width, penalized, seeded)."""
    return (decode_window, page_bucket, False, False)


def seconds_by_scope(trace: dict, ops_by_scope: dict | None) -> dict | None:
    """{"module", "executions", "median_ms", "leaf_seconds", "scopes":
    {scope: seconds}, "unscoped": seconds, "top_unscoped": [[op, s], ...]}
    for the window program that ran most in the trace (first device);
    None when the trace has no such program or no event in a scope."""
    from benchmark.lib import trace_reduce
    planes = trace_reduce.device_planes(trace)
    if not planes or not ops_by_scope:
        return None
    lines = planes[min(planes)]
    runs: dict = {}
    for name, _start, dur in lines.get(trace_reduce.MODULES_LINE, []):
        if MODULE in name:
            runs.setdefault(name, []).append(dur)
    if not runs:
        return None
    module = max(runs, key=lambda k: len(runs[k]))
    per_op = trace_reduce.op_times({"/device:TPU:0": lines}, inside=module,
                                   leaves_only=True)
    table: dict = {}
    loose: dict = {}
    for op, seconds in per_op.items():
        scope = primary(ops_by_scope.get(op.split(" ", 1)[0]))
        if scope is None:
            loose[op] = seconds
        else:
            table[scope] = table.get(scope, 0.0) + seconds
    if not table:
        return None
    return {"module": module, "executions": len(runs[module]),
            "median_ms": statistics.median(runs[module]) / 1e6,
            "module_seconds": sum(runs[module]) / 1e9,
            "leaf_seconds": sum(per_op.values()), "scopes": table,
            "unscoped": sum(loose.values()),
            "top_unscoped": trace_reduce.top(loose, 5)}


def _ops_by_scope(r) -> dict | None:
    """The registry's map for the window program of the traced seconds."""
    try:
        from dynamo_tpu.engine import perf
        from dynamo_tpu.runtime import flight
    except ImportError:
        return None
    read = getattr(perf.get_registry(), "ops_by_scope", None)
    between = getattr(flight.get_recorder(), "between", None)
    if read is None:
        return None
    key = None
    if between is not None and r.trace_mono is not None:
        widths = between(*r.trace_mono)["columns"].get("page_bucket", [])
        if len(widths):
            key = window_key(r.engine["decode_window"],
                             int(statistics.mode(int(w) for w in widths)))
    try:
        return (read(PROGRAM, key) if key is not None else None) \
            or read(PROGRAM)
    except Exception as exc:  # noqa: BLE001 — a reader never fails the run
        print(f"benchmark: ops_by_scope failed: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return None


def reduced(r) -> dict | None:
    """seconds_by_scope() of the run's trace, once a Reading; leaves the map
    beside the trace file for ``python3 -m benchmark.lib.host_phases``."""
    if r.trace is None:
        return None
    if not hasattr(r, "_by_scope"):
        ops = _ops_by_scope(r)
        r._by_scope = seconds_by_scope(r.trace, ops)
        if ops:
            _save_map(ops)
        print("benchmark: scopes " + json.dumps(r._by_scope),
              file=sys.stderr, flush=True)
    return r._by_scope


def _save_map(ops: dict) -> None:
    from benchmark.lib import manifest, trace_reduce
    try:
        path = trace_reduce.find_xplane(os.path.join(manifest.RUN_DIR,
                                                     "trace"))
        with open(os.path.join(os.path.dirname(path), MAP_FILE), "w",
                  encoding="utf-8") as fh:
            json.dump({"program": PROGRAM, "ops_by_scope": ops}, fh)
    except OSError:
        pass


def ms_per_step(r, scopes: tuple[str, ...]):
    """Device milliseconds a decode step spends in ``scopes``: their seconds
    inside the window program's executions over the steps those executions
    hold (their device time over the median execution's, times the steps of
    a window: the executions at the trace's edges are recorded in part)."""
    got = reduced(r)
    if got is None or not got["median_ms"]:
        return None
    steps = (got["module_seconds"] * 1e3 / got["median_ms"]
             * r.engine["decode_window"])
    return sum(got["scopes"].get(s, 0.0) for s in scopes) * 1e3 / steps
