"""End-to-end request tracing: spans, ring-buffer recorder, exporters.

Capability parity with the reference's W3C trace-context threading
(lib/runtime/src/logging.rs:111-175) plus what the Rust side delegates to
the OTEL SDK: actually *recording* spans so "why was this request slow?"
is answerable without a debugger. Pieces:

- ``span(name, ctx=..., **attrs)`` — a context manager (sync AND async)
  that records start/end monotonic+wall timestamps, parent/child links
  (via a contextvar, or an explicit request ``Context``), status
  (ok/error/cancelled), and attributes.
- ``SpanRecorder`` — a bounded in-process ring buffer with per-trace
  assembly and two exporters: Chrome trace-event JSON (loadable in
  Perfetto / ``chrome://tracing``) and OTLP-JSON-shaped dicts.
- a module-global recorder (``DTPU_TRACING=0`` disables, default
  capacity ``DTPU_TRACE_CAPACITY=8192``) with a no-op fast path: when
  disabled, ``span()`` returns a shared singleton and ``add()`` returns
  immediately — zero allocations on the per-token path.
- ``phase_metrics(registry)`` — the per-phase latency histograms
  (queue wait / prefill / decode / KV transfer) every span-producing
  site also feeds, so SLO dashboards get phase breakdowns, not just
  edge TTFT/ITL.
- ``PhaseClock`` — the ENGINE THREAD's phases (``ENGINE_PHASES``): each
  ``with clock.phase(name)`` is a ``jax.profiler.TraceAnnotation`` (on the
  profiler's clock, beside the device planes, when a profiler session
  runs; nothing otherwise) and self time in a preallocated accumulator.
  No ``Span``, no lock, not the span ring.
- ``Startup`` — one start of a worker as ONE trace: a root span
  ``startup`` from the launcher's entry to ready, a child where each
  stage's work happens (``startup_stage(name)``: on whichever thread, the
  stage open on that thread is the parent, else the root), kept by the
  object as well as recorded in the ring, so ``/debug/perf`` still has a
  start's stages after the ring turned over.
- ``capture_profile(...)`` — the on-demand ``jax.profiler`` hook behind
  ``POST /debug/profile``, degrading to a span-recorder dump when JAX
  profiling is unavailable; its reply carries the engine phases' seconds
  and the flight rows of the capture.

Threading: spans are recorded from the event loop AND the engine thread;
the recorder takes a lock per record (one append per span, not per
token). Contextvar parenting is per-thread/per-task by construction;
engine-thread spans link explicitly via (trace_id, parent_id) instead.
"""

from __future__ import annotations

import asyncio
import collections
import contextvars
import json
import os
import threading
import time
import weakref

from dynamo_tpu.runtime.logging import (current_trace, generate_span_id,
                                        generate_trace_id, get_logger)

log = get_logger("tracing")

# The active span for the current task/thread (parenting).
current_span: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "dtpu_span", default=None)


class Span:
    """One recorded operation. ``end_mono`` is None while open."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name",
                 "start_wall", "start_mono", "end_mono", "status", "attrs",
                 "thread_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_span_id: str | None, name: str,
                 start_wall: float, start_mono: float,
                 attrs: dict | None = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.name = name
        self.start_wall = start_wall
        self.start_mono = start_mono
        self.end_mono: float | None = None
        self.status = "ok"
        self.attrs = attrs
        self.thread_id = threading.get_ident()

    @property
    def duration_s(self) -> float:
        end = self.end_mono if self.end_mono is not None else self.start_mono
        return end - self.start_mono

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "name": self.name,
            "start_wall": self.start_wall,
            "start_mono": self.start_mono,
            "duration_s": self.duration_s,
            "status": self.status,
            "attrs": self.attrs or {},
        }


class SpanRecorder:
    """Bounded ring buffer of finished spans with per-trace assembly."""

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0  # spans evicted by the ring (observability)

    # -- recording ------------------------------------------------------------
    def record(self, span: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append(span)

    def add(self, name: str, trace_id: str, parent_id: str | None,
            start_mono: float, end_mono: float, status: str = "ok",
            attrs: dict | None = None) -> str | None:
        """Record an already-timed span (engine-thread hot paths measure
        their own intervals; no contextvar juggling). Returns the span id,
        or None when disabled (fast path: one attribute read, no
        allocation)."""
        if not self.enabled:
            return None
        now_mono = time.monotonic()
        span = Span(trace_id=trace_id, span_id=generate_span_id(),
                    parent_span_id=parent_id, name=name,
                    start_wall=time.time() - (now_mono - start_mono),
                    start_mono=start_mono, attrs=attrs)
        span.end_mono = end_mono
        span.status = status
        self.record(span)
        return span.span_id

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # -- per-trace assembly ---------------------------------------------------
    def trace(self, trace_id: str) -> list[Span]:
        with self._lock:
            spans = [s for s in self._spans if s.trace_id == trace_id]
        spans.sort(key=lambda s: s.start_mono)
        return spans

    def recent(self, limit: int = 50) -> list[dict]:
        """Newest-first index of recorded traces (for /debug/traces/recent)."""
        with self._lock:
            snapshot = list(self._spans)
        by_trace: dict[str, list[Span]] = {}
        for s in snapshot:
            by_trace.setdefault(s.trace_id, []).append(s)
        out = []
        for trace_id, spans in by_trace.items():
            ids = {s.span_id for s in spans}
            roots = [s for s in spans
                     if s.parent_span_id is None
                     or s.parent_span_id not in ids]
            root = min(roots or spans, key=lambda s: s.start_mono)
            t0 = min(s.start_mono for s in spans)
            t1 = max(s.end_mono or s.start_mono for s in spans)
            out.append({
                "trace_id": trace_id,
                "root": root.name,
                "start_wall": root.start_wall,
                "spans": len(spans),
                "duration_s": t1 - t0,
                "status": ("error" if any(s.status == "error" for s in spans)
                           else "ok"),
            })
        out.sort(key=lambda e: e["start_wall"], reverse=True)
        return out[:limit]

    # -- exporters ------------------------------------------------------------
    def export_chrome(self, trace_id: str | None = None) -> dict:
        """Chrome trace-event JSON ("X" complete events, microsecond
        timestamps relative to the earliest span) — drop the payload in
        Perfetto or chrome://tracing."""
        spans = (self.trace(trace_id) if trace_id is not None
                 else sorted(self.snapshot()[0], key=lambda s: s.start_mono))
        events = []
        if spans:
            base = min(s.start_mono for s in spans)
            pid = os.getpid()
            for s in spans:
                args = dict(s.attrs or {})
                args["trace_id"] = s.trace_id
                args["span_id"] = s.span_id
                if s.parent_span_id:
                    args["parent_span_id"] = s.parent_span_id
                if s.status != "ok":
                    args["status"] = s.status
                events.append({
                    "name": s.name,
                    "ph": "X",
                    "ts": (s.start_mono - base) * 1e6,
                    "dur": s.duration_s * 1e6,
                    "pid": pid,
                    "tid": s.thread_id,
                    "cat": "dtpu",
                    "args": args,
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_otlp(self, trace_id: str | None = None) -> dict:
        """OTLP/JSON-shaped dict (ExportTraceServiceRequest): importable
        by any OTLP-JSON consumer without an OTEL SDK dependency."""
        spans = (self.trace(trace_id) if trace_id is not None
                 else sorted(self.snapshot()[0], key=lambda s: s.start_mono))
        status_code = {"ok": 1, "error": 2, "cancelled": 2}
        otlp_spans = []
        for s in spans:
            start_ns = int(s.start_wall * 1e9)
            otlp_spans.append({
                "traceId": s.trace_id,
                "spanId": s.span_id,
                "parentSpanId": s.parent_span_id or "",
                "name": s.name,
                "kind": 1,  # SPAN_KIND_INTERNAL
                "startTimeUnixNano": str(start_ns),
                "endTimeUnixNano": str(start_ns + int(s.duration_s * 1e9)),
                "status": {"code": status_code.get(s.status, 0)},
                "attributes": [
                    {"key": k, "value": _otlp_value(v)}
                    for k, v in (s.attrs or {}).items()
                ],
            })
        return {"resourceSpans": [{
            "resource": {"attributes": [{
                "key": "service.name",
                "value": {"stringValue": "dynamo-tpu"}}]},
            "scopeSpans": [{
                "scope": {"name": "dynamo_tpu.runtime.tracing"},
                "spans": otlp_spans,
            }],
        }]}

    def snapshot(self) -> tuple[list[Span], int]:
        """The ring's spans, oldest first, and how many the ring has
        evicted so far: a reader that finds ``dropped`` above zero is
        looking at a part of what was recorded."""
        with self._lock:
            return list(self._spans), self.dropped


def _otlp_value(v) -> dict:
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


# -- module-global recorder ----------------------------------------------------

def _env_enabled() -> bool:
    return os.environ.get("DTPU_TRACING", "1").strip().lower() not in (
        "0", "false", "no", "off")


_RECORDER = SpanRecorder(
    capacity=int(os.environ.get("DTPU_TRACE_CAPACITY", "8192") or 8192),
    enabled=_env_enabled())


def get_recorder() -> SpanRecorder:
    return _RECORDER


def set_enabled(flag: bool) -> None:
    _RECORDER.enabled = flag


class _NullSpan:
    """Shared no-op span: the disabled-recorder fast path allocates
    nothing (``span(...)`` returns this singleton)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


class span:
    """Record one span around a block. Usable as both ``with span(...)``
    and ``async with span(...)``.

    Parenting: an explicit request ``Context`` pins the span to that
    request's identity (span_id = ctx.span_id, parent = ctx.parent_span_id
    — the ids already propagated on wire frames), otherwise the ambient
    ``current_span`` contextvar parents it; with neither, a new root
    trace starts. While open, the span also publishes itself to
    ``current_trace`` so log lines carry trace_id/span_id.
    """

    __slots__ = ("_name", "_ctx", "_attrs", "_recorder", "_span",
                 "_tok_span", "_tok_trace")

    def __new__(cls, name: str, ctx=None, recorder: SpanRecorder | None = None,
                **attrs):
        rec = recorder if recorder is not None else _RECORDER
        if not rec.enabled:
            return NULL_SPAN
        self = object.__new__(cls)
        self._name = name
        self._ctx = ctx
        self._attrs = attrs or None
        self._recorder = rec
        self._span = None
        self._tok_span = None
        self._tok_trace = None
        return self

    def set(self, **attrs) -> None:
        """Attach attributes to the open span."""
        if self._span is not None:
            if self._span.attrs is None:
                self._span.attrs = {}
            self._span.attrs.update(attrs)

    # -- sync protocol --------------------------------------------------------
    def __enter__(self) -> "span":
        parent = current_span.get()
        if self._ctx is not None:
            trace_id = self._ctx.trace_id
            span_id = self._ctx.span_id
            parent_id = self._ctx.parent_span_id
            if parent is not None and parent.trace_id == trace_id:
                # Nested under an already-open local span of the same
                # trace (e.g. the worker.request span already carries
                # ctx.span_id): parent locally and mint a fresh id so
                # the child never collides with its parent.
                parent_id = parent.span_id
                span_id = generate_span_id()
        elif parent is not None:
            trace_id = parent.trace_id
            span_id = generate_span_id()
            parent_id = parent.span_id
        else:
            trace_id = generate_trace_id()
            span_id = generate_span_id()
            parent_id = None
        s = Span(trace_id=trace_id, span_id=span_id, parent_span_id=parent_id,
                 name=self._name, start_wall=time.time(),
                 start_mono=time.monotonic(), attrs=self._attrs)
        self._span = s
        self._tok_span = current_span.set(s)
        self._tok_trace = current_trace.set(
            {"trace_id": trace_id, "span_id": span_id})
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._span
        s.end_mono = time.monotonic()
        if exc_type is not None:
            s.status = ("cancelled"
                        if issubclass(exc_type, asyncio.CancelledError)
                        else "error")
            if s.status == "error":
                if s.attrs is None:
                    s.attrs = {}
                s.attrs.setdefault("error", f"{exc_type.__name__}: {exc}")
        for var, tok in ((current_span, self._tok_span),
                         (current_trace, self._tok_trace)):
            try:
                var.reset(tok)
            except ValueError:
                # Token from another context (generator finalized
                # elsewhere): drop the reset rather than crash cleanup.
                pass
        self._recorder.record(s)
        return False

    # -- async protocol -------------------------------------------------------
    async def __aenter__(self) -> "span":
        return self.__enter__()

    async def __aexit__(self, exc_type, exc, tb) -> bool:
        return self.__exit__(exc_type, exc, tb)


# -- a worker's start as one trace ------------------------------------------------

class _Stage:
    """One stage of a start: a span under the stage open on this thread
    (else under the root), recorded as it ends."""

    __slots__ = ("_start", "_span")

    def __init__(self, start: "Startup", name: str, attrs: dict):
        self._start = start
        self._span = Span(start.trace_id, generate_span_id(), None, name,
                          0.0, 0.0, attrs or None)

    def set(self, **attrs) -> None:
        if self._span.attrs is None:
            self._span.attrs = {}
        self._span.attrs.update(attrs)

    def __enter__(self) -> "_Stage":
        s, start = self._span, self._start
        open_here = start._open_on_this_thread()
        s.parent_span_id = (open_here[-1].span_id if open_here
                            else start.span_id)
        s.thread_id = threading.get_ident()
        s.start_wall, s.start_mono = time.time(), time.monotonic()
        open_here.append(s)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        s, start = self._span, self._start
        s.end_mono = time.monotonic()
        start._open_on_this_thread().pop()
        if exc_type is not None:
            s.status = "error"
            self.set(error=f"{exc_type.__name__}: {exc}")
            if start.failed_stage is None:   # the innermost raises first
                start.failed_stage = s.name
        start._keep(s)
        return False


class Startup:
    """One start of a worker, from the launcher's entry to the instant the
    engine is ready and the service listens: the root span ``startup`` and
    the stages under it, on ``time.monotonic()``. A stage's self time is
    its span less what its children cover; what the root's direct children
    leave uncovered is ``unattributed_s``. The spans go to the recorder
    (``/debug/traces?trace_id=``) and stay here (``/debug/perf``)."""

    def __init__(self, recorder: SpanRecorder | None = None):
        self.recorder = recorder if recorder is not None else _RECORDER
        self.trace_id = generate_trace_id()
        self.span_id = generate_span_id()
        self.root = Span(self.trace_id, self.span_id, None, "startup",
                         time.time(), time.monotonic())
        self.spans: list[Span] = []     # ended stages, then the root
        self.failed_stage: str | None = None
        self._lock = threading.Lock()
        self._local = threading.local()

    @property
    def open(self) -> bool:
        return self.root.end_mono is None

    def _open_on_this_thread(self) -> list:
        return self._local.__dict__.setdefault("stages", [])

    def _keep(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)
        self.recorder.record(span)

    def stage(self, name: str, **attrs):
        """``with start.stage("startup.weights", source="given") as st:``
        (``st.set(bytes=n)``); a no-op once the start has ended."""
        return _Stage(self, name, attrs) if self.open else NULL_SPAN

    def finish(self, error: BaseException | None = None) -> None:
        """Close the root: ready, or failed with the stage that raised."""
        if not self.open:
            return
        root = self.root
        root.end_mono = time.monotonic()
        if error is not None:
            root.status = "error"
            root.attrs = {"error": f"{type(error).__name__}: {error}",
                          "failed_stage": self.failed_stage}
        self._keep(root)

    def summary(self) -> dict:
        """The stages in the order they began, each with its seconds and
        its self seconds, ``ready_s`` and ``unattributed_s``."""
        with self._lock:
            spans = list(self.spans)
        root = self.root
        end = root.end_mono if root.end_mono is not None else time.monotonic()
        stages = sorted((s for s in spans if s is not root),
                        key=lambda s: s.start_mono)
        names = {s.span_id: s.name for s in stages}
        by_parent: dict = {}
        for s in stages:
            by_parent.setdefault(s.parent_span_id, []).append(
                (s.start_mono, s.end_mono))
        rows = [{"name": s.name,
                 "parent": names.get(s.parent_span_id, "startup"),
                 "at_s": round(s.start_mono - root.start_mono, 4),
                 "seconds": round(s.duration_s, 4),
                 "self_s": round(s.duration_s - covered_seconds(
                     by_parent.get(s.span_id, ())), 4),
                 **({"status": s.status} if s.status != "ok" else {}),
                 **({"attrs": s.attrs} if s.attrs else {})}
                for s in stages]
        ready = end - root.start_mono
        return {"trace_id": self.trace_id,
                "status": "starting" if self.open else root.status,
                "failed_stage": self.failed_stage,
                "t_start_mono": root.start_mono,
                "ready_s": round(ready, 4),
                "unattributed_s": round(ready - covered_seconds(
                    by_parent.get(root.span_id, ())), 4),
                "stages": rows}


    def ready_line(self, programs: str = "") -> str:
        """The ONE log line of a start: the root's stages in order, the
        stages under each in brackets (``programs``, what the compile
        registry says of the first calls, after the warm-up's), and what
        no stage covers."""
        told = self.summary()
        under: dict[str, list] = {}
        for stage in told["stages"]:
            under.setdefault(stage["parent"], []).append(stage)

        def said(stage: dict) -> str:
            name = stage["name"].removeprefix("startup.")
            source = (stage.get("attrs") or {}).get("source")
            inner = [said(child) for child in under.get(stage["name"], ())]
            if name == "warmup" and programs:
                inner.append(programs)
            notes = [n for n in (source, ", ".join(inner)) if n]
            return "%s %.1f%s" % (name, stage["seconds"],
                                  " (%s)" % "; ".join(notes) if notes else "")

        return "%s in %.1f s: %s, unattributed %.1f" % (
            "start-up FAILED at %s" % self.failed_stage
            if told["status"] == "error" else "ready", told["ready_s"],
            ", ".join(said(stage) for stage in under.get("startup", ())),
            told["unattributed_s"])


def covered_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            total += hi - lo
            reach = hi
        elif hi > reach:
            total += hi - reach
            reach = hi
    return total


#: The process's newest start (launch.run makes one at its entry).
_STARTUP: Startup | None = None


def begin_startup() -> Startup:
    global _STARTUP
    _STARTUP = Startup()
    return _STARTUP


def last_startup() -> Startup | None:
    return _STARTUP


def startup_stage(name: str, **attrs):
    """A stage of the start that is under way, wherever its work happens
    (runner, engine thread); a no-op where no launcher opened one."""
    start = _STARTUP
    return start.stage(name, **attrs) if start is not None else NULL_SPAN


# -- the engine thread's phases -------------------------------------------------

#: What the engine loop does, in loop order. One vocabulary for the trace's
#: host line, ``engine_phase_seconds_total{phase}``, ``/debug/perf`` and the
#: flight ring's ``host_s`` / ``wait_s`` (docs/OBSERVABILITY.md "Engine
#: phases"). ``engine.other`` is loop time in no named phase.
ENGINE_PHASES = (
    "engine.jobs", "engine.resolve_first", "engine.kvbm",
    "engine.retire_chunks", "engine.admit", "engine.dispatch_chunks",
    "engine.dispatch_window", "engine.readback_wait",
    "engine.process_window", "engine.publish", "engine.release_pages",
    "engine.idle", "engine.other")
_OTHER = ENGINE_PHASES.index("engine.other")
#: Phases in which the engine thread waits (for the device, or for work):
#: everything else is host time a window costs.
WAIT_PHASES = ("engine.readback_wait", "engine.idle")
_MAX_DEPTH = 8

_CLOCKS: "weakref.WeakSet[PhaseClock]" = weakref.WeakSet()


class _Phase:
    """One phase of one clock as a reusable context manager (made once per
    clock and name: entering allocates the annotation only)."""

    __slots__ = ("_clock", "_index", "_name")

    def __init__(self, clock: "PhaseClock", index: int):
        self._clock = clock
        self._index = index
        self._name = ENGINE_PHASES[index]

    def __enter__(self) -> None:
        c = self._clock
        ann = None
        trace_me = c._trace_me
        if trace_me is not None and trace_me.is_enabled():
            ann = trace_me(self._name)
            ann.__enter__()
        d = c._depth
        c._ann[d] = ann
        c._outer[d] = c._current
        c._depth = d + 1
        now = time.monotonic()
        c.seconds[c._current] += now - c._t_last
        c._t_last = now
        c._current = self._index

    def __exit__(self, *exc) -> bool:
        c = self._clock
        now = time.monotonic()
        c.seconds[c._current] += now - c._t_last
        c._t_last = now
        d = c._depth - 1
        c._depth = d
        c._current = c._outer[d]
        ann = c._ann[d]
        if ann is not None:
            c._ann[d] = None
            ann.__exit__(None, None, None)
        return False


class PhaseClock:
    """Self time of the engine thread by phase. ONE writer (the engine
    thread): plain stores into preallocated lists, no lock, nothing kept
    per call; readers tolerate a torn read of a counter. A phase entered
    inside another suspends the outer one, so the seconds add up to the
    thread's wall time since ``restart()``."""

    def __init__(self):
        n = len(ENGINE_PHASES)
        self.seconds = [0.0] * n
        self._phases = {name: _Phase(self, i)
                        for i, name in enumerate(ENGINE_PHASES)}
        self._outer = [_OTHER] * _MAX_DEPTH
        self._ann: list = [None] * _MAX_DEPTH
        self._depth = 0
        self._current = _OTHER
        self._t_last = time.monotonic()
        try:  # a no-op unless a profiler session runs
            from jax.profiler import TraceAnnotation
            self._trace_me = TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax here: counters only
            self._trace_me = None
        _CLOCKS.add(self)

    def restart(self) -> None:
        """Count from now (the engine loop calls this as it starts: what
        the thread did before is warm-up, not a phase)."""
        self._t_last = time.monotonic()

    def phase(self, name: str) -> _Phase:
        return self._phases[name]

    def sync(self, now: float) -> None:
        """Credit the running phase up to ``now`` (ENGINE THREAD)."""
        self.seconds[self._current] += now - self._t_last
        self._t_last = now

    def waited(self) -> tuple[float, float]:
        """(seconds in engine.readback_wait, seconds in engine.idle)."""
        return (self.seconds[_WAIT_INDEX[0]], self.seconds[_WAIT_INDEX[1]])

    def total(self) -> float:
        return sum(self.seconds)

    def totals(self) -> dict[str, float]:
        return dict(zip(ENGINE_PHASES, self.seconds))


_WAIT_INDEX = tuple(ENGINE_PHASES.index(n) for n in WAIT_PHASES)

def engine_phase_totals(clocks=None) -> dict[str, float]:
    """Seconds by engine phase, summed over this process's engines (or
    over ``clocks``: a caller that differences two readings holds the
    clocks in between, so that an engine collected meanwhile does not
    read as negative seconds)."""
    out = dict.fromkeys(ENGINE_PHASES, 0.0)
    for clock in (list(_CLOCKS) if clocks is None else clocks):
        for name, seconds in clock.totals().items():
            out[name] += seconds
    return out


# -- per-phase latency histograms ----------------------------------------------

_LATENCY_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
                    1.0, 2.5, 5.0, 10.0, 30.0)
_BYTES_BUCKETS = (1 << 12, 1 << 16, 1 << 20, 4 << 20, 16 << 20, 64 << 20,
                  256 << 20, 1 << 30)


class PhaseMetrics:
    """The four phase histograms (+ transfer bytes) on a MetricsRegistry
    node. Every constructor touches its hierarchy-labeled child so the
    series appear in /metrics exposition before first traffic."""

    def __init__(self, registry):
        self.queue_wait = registry.histogram(
            "request_queue_wait_seconds",
            "Time a request waited for engine admission",
            buckets=_LATENCY_BUCKETS)
        self.prefill = registry.histogram(
            "prefill_step_seconds",
            "Prefill dispatch to first-token readback",
            buckets=_LATENCY_BUCKETS)
        self.decode = registry.histogram(
            "decode_step_seconds",
            "One decode window of the device: readback complete to "
            "readback complete while the pipe is full, dispatch to "
            "readback otherwise",
            buckets=_LATENCY_BUCKETS)
        self.kv_transfer = registry.histogram(
            "kv_transfer_seconds",
            "KV parcel transfer (send or recv) duration",
            ["direction"], buckets=_LATENCY_BUCKETS)
        self.kv_transfer_bytes = registry.histogram(
            "kv_transfer_bytes",
            "KV parcel transfer size in bytes",
            ["direction"], buckets=_BYTES_BUCKETS)
        for bound in (self.queue_wait, self.prefill, self.decode):
            bound.ensure()
        for direction in ("send", "recv"):
            self.kv_transfer.ensure(direction=direction)
            self.kv_transfer_bytes.ensure(direction=direction)


def phase_metrics(registry) -> PhaseMetrics:
    """Get-or-create the phase histograms for a registry node (cached on
    the ROOT registry per hierarchy position: node objects are ephemeral
    — ``namespace()``/``component()`` mint a new one per call — so
    repeated wiring of the same position stays idempotent)."""
    root = getattr(registry, "_root", registry)
    cache = getattr(root, "_dtpu_phase_metrics", None)
    if cache is None:
        cache = root._dtpu_phase_metrics = {}
    key = getattr(registry, "_hierarchy", None)
    cached = cache.get(key)
    if cached is None:
        cached = cache[key] = PhaseMetrics(registry)
    return cached


# -- debug endpoint payloads (shared by health.py and http_service.py) --------

def traces_index(recorder: SpanRecorder | None = None,
                 limit: int = 50) -> dict:
    rec = recorder or _RECORDER
    return {"enabled": rec.enabled, "capacity": rec.capacity,
            "dropped": rec.dropped, "traces": rec.recent(limit)}


def trace_payload(trace_id: str, fmt: str = "chrome",
                  recorder: SpanRecorder | None = None) -> dict | None:
    """Export one trace; None when the trace id is unknown."""
    rec = recorder or _RECORDER
    if not rec.trace(trace_id):
        return None
    if fmt == "chrome":
        return rec.export_chrome(trace_id)
    if fmt == "otlp":
        return rec.export_otlp(trace_id)
    if fmt == "spans":
        return {"trace_id": trace_id,
                "spans": [s.to_dict() for s in rec.trace(trace_id)]}
    raise ValueError(f"unknown trace format {fmt!r} "
                     "(expected chrome|otlp|spans)")


# -- on-demand profiler capture ------------------------------------------------

_profile_lock = threading.Lock()  # one capture at a time per process


async def capture_profile(duration_ms: int, out_dir: str,
                          recorder: SpanRecorder | None = None) -> dict:
    """Capture ``duration_ms`` of runtime activity into ``out_dir``.

    Preferred mode: a ``jax.profiler`` trace (TensorBoard/Perfetto
    loadable) covering device programs — one curl away from a TPU
    hot-path investigation. The engine thread's phases are annotations
    on its line of that trace, on the device planes' clock
    (``python3 -m benchmark.lib.host_phases <xplane.pb>`` reduces both).
    When JAX profiling is unavailable (CPU-only builds, profiler already
    claimed), degrades to dumping the span recorder's current contents
    as Chrome trace JSON so the capture is never empty-handed. Either
    way the reply carries the engine phases' seconds over the capture
    and the flight rows (one per decode window) recorded during it.
    """
    duration_ms = max(1, min(int(duration_ms), 60_000))
    os.makedirs(out_dir, exist_ok=True)
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already running")
    try:
        from dynamo_tpu.runtime import flight
        started = time.monotonic()
        clocks = list(_CLOCKS)  # strong references for the capture
        phases0 = engine_phase_totals(clocks)
        mode = "jax"
        try:
            import jax

            jax.profiler.start_trace(out_dir)
            try:
                await asyncio.sleep(duration_ms / 1e3)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 — degrade, never fail
            log.warning("jax profiler capture unavailable (%s); "
                        "dumping span recorder instead", exc)
            mode = "spans"
            await asyncio.sleep(duration_ms / 1e3)
        rec = recorder or _RECORDER
        span_path = os.path.join(out_dir, "spans.chrome.json")

        # The ring buffer can hold tens of thousands of spans; serialize
        # and write off the loop — this endpoint runs DURING live serving.
        def _dump() -> None:
            with open(span_path, "w") as fh:
                json.dump(rec.export_chrome(), fh)

        await asyncio.to_thread(_dump)
        ended = time.monotonic()
        phases1 = engine_phase_totals(clocks)
        windows = flight.get_recorder().between(started, ended)
        return {"mode": mode, "out_dir": out_dir,
                "span_dump": span_path,
                "duration_ms": duration_ms,
                "wall_s": round(ended - started, 3),
                "engine_phase_seconds": {
                    name: round(phases1[name] - phases0[name], 6)
                    for name in ENGINE_PHASES},
                "flight": {"rows": windows["rows"],
                           "missed": windows["missed"],
                           "columns": {k: v.tolist() for k, v
                                       in windows["columns"].items()}}}
    finally:
        _profile_lock.release()
