"""Engine perf plane: compile observatory, roofline-attributed window
timing, and HBM telemetry (docs/OBSERVABILITY.md "Engine perf plane").

The device/compiler layer was the last dark subsystem: tracing covers
requests, the flight recorder covers engine-loop state, the KV pane
covers the cache — but nothing measured *compiles*, per-window device
time, or HBM occupancy, so a roofline share or a compile stall was
found by hand. This module makes them live series (what they read on
this chip: PERF.md section 5 and PERF_LEDGER.jsonl):

- ``CompileRegistry``: every ``jax.jit`` program in the serving path is
  built through :func:`instrumented_jit` (enforced by the
  ``unregistered-jit`` lint rule), which wraps the jitted callable and
  detects BUILDS via ``jax.monitoring``'s backend-compile events: the
  executable was compiled, or loaded from the persistent cache (the
  event is opened around ``compile_or_get_cached``, so it fires on a
  cache hit too) — dispatch-cache churn (e.g. committed-ness changes)
  does not count (falling back to first-call counting when the
  monitoring API is unavailable). Per program family it records build
  counts and backend seconds, beside them the cache loads among those
  builds (``cache_loads`` / ``cache_load_seconds``), the set of
  shape-signature keys seen, and a one-time FLOPs/bytes cost estimate
  from ``lower().cost_analysis()`` (with a typed error fallback on
  backends without the API).
- **First calls** (``CompileRegistry.first_calls``): one record a
  wrapper's first call, and one a later call in which something was
  built: wall seconds split into Python trace, lowering, cache load and
  compile from the same events, ``when`` it happened (``startup`` before
  ``mark_ready()``, ``serving`` after) and whether the persistent cache
  answered (``hit`` | ``miss`` | ``off``). A warm start is some 150 of
  them; :func:`startup_status` is the ``/debug/perf`` ``startup`` body
  (docs/OBSERVABILITY.md "Start-up").
- **Unexpected-recompile detector** — the runtime twin of the
  ``jit-recompile-hazard`` lint rule: the SAME wrapper (one program
  instance, one shape signature) compiling again after ``mark_ready()``
  (the engine's warmup boundary) means the jit cache was invalidated on
  the serving path (dtype/weak-type drift, shape leak, donation
  mismatch). It bumps ``perf_unexpected_recompiles_total{program}``,
  logs a WARNING, and emits a ``perf.recompile`` span with
  ``status="warn"``. Judged per-wrapper so two runners in one process
  don't cross-flag each other's first compiles; pre-ready compiles are
  never flagged (warmup intentionally double-compiles signatures whose
  input shardings converge after the first run).
- ``note_window``: the engine feeds one (window-seconds, tokens,
  active-slots, steps) sample per processed decode window — plain
  float stores on the engine thread, no locks, no allocation — from
  which the registry derives EWMA step seconds, achieved tok/s, and
  the fraction of the weight-read roofline those tokens achieved. The
  seconds are the window's PERIOD (readback complete to readback
  complete) while the pipe is full, so a step is a step of the device
  and not the latency through ``pipeline_depth`` queued windows.
- **Scopes** (``SCOPES``, :func:`scope`): the one vocabulary of
  ``jax.named_scope`` regions inside the decode-window and prefill
  programs, and ``CompileRegistry.ops_by_scope(program)``: each
  instruction of the compiled executable mapped to its scope from the
  executable's own HLO text, so a device trace's ``%fusion.296`` has a
  name that survives a rewrite of the program.
- ``PerfMetricsUpdater``: throttled exporter (same discipline as
  engine/kv_metrics.py KvMetricsUpdater) turning the registry's plain
  ints into ``dynamo_tpu_perf_*`` counters/gauges, plus periodic
  ``device.memory_stats()`` HBM gauges from the runner.

This module also owns the persistent XLA compile cache
(:func:`configure_compile_cache`: ``JAX_COMPILATION_CACHE_DIR`` when set,
else a fixed ``.jax_cache`` in the checkout; inside it the sub-directory
of the scope vocabulary's version), because every program it would hold
is built through :func:`instrumented_jit`.

Env knobs: ``DTPU_PERF_COST`` = ``lower`` (default: cheap unoptimized-
HLO estimate) | ``compile`` (accurate, pays a second XLA compile per
program family) | ``off``.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time
import weakref

import jax

from dynamo_tpu.engine import program_store
from dynamo_tpu.runtime import flight, tracing
from dynamo_tpu.runtime.logging import (generate_span_id, generate_trace_id,
                                        get_logger)

log = get_logger("perf")

#: EWMA smoothing for the per-window series (0.2 = ~5-window memory).
_EWMA = 0.2
#: First-call records kept (a start makes some 150; only a program that
#: keeps rebuilding could reach this, and the family sums go on counting).
_FIRST_CALLS_KEPT = 4096


# -- the programs' regions --------------------------------------------------------
#: Regions of the decode-window and prefill programs (engine/model.py,
#: engine/runner.py wrap them in ``scope(name)``): the names a device trace
#: is reduced by (docs/OBSERVABILITY.md "Program scopes"). Metadata only:
#: a scope changes no HLO instruction and so no device time.
SCOPES = ("embed", "attn.qkv", "attn.kv_gather", "attn.core", "attn.out",
          "mlp", "lm_head", "sample", "kv.commit")
#: Scopes BESIDE those, drawn only in programs of one block kind: the latent
#: block's indexer (its projections, its read and scores of the index keys
#: in context, the choice); a drafting window's prediction module (``mtp``:
#: its projection of [embedding ; hidden], its block with that block's
#: attention, its norm and its read of the head; the expert layer inside
#: keeps its sub-scopes: ``mtp+moe.experts``); a recurrent layer (``ssm``:
#: the Nemotron-H block's Mamba-2 mixer, engine/hybrid.py: its norm, its
#: in-projection, the convolution, the recurrence over the state, the gated
#: norm and the out-projection, and in prefill the rows' state gathered from
#: and written back to their slots; the MiniCPM-SALA block's
#: lightning mixer likewise: norm, projections, rotation, the recurrence,
#: output norm, gate, out-projection); the writes of a compressed-key array
#: (``attn.compress``: a prefill chunk's stripes into their pages, a
#: window's completed stripes at its commit; the choice of blocks over them
#: is ``attn.index``); the norm between two passes of a looped stack
#: (``loop.norm``, model.scan_passes). In a block whose recurrent mixer and
#: attention layer run SIDE BY SIDE (``ModelSpec.parallel_mixers``, the
#: Falcon-H1 block) the mixer's branch is ``ssm`` and the attention's
#: ``attn.qkv`` / ``attn.kv_gather`` / ``attn.core`` / ``attn.out``; the ONE
#: norm that feeds both and the ONE residual sum behind both are drawn under
#: ``ssm``. The other blocks' programs
#: have none, so their names, and SCOPES_VERSION, stand.
BLOCK_SCOPES = ("attn.index", "mtp", "ssm", "attn.compress", "loop.norm")
#: Regions INSIDE a scope, drawn only in programs of a routed block (the
#: expert layer's router and experts and, where the block has them, its
#: shared experts, inside ``mlp``). An instruction in one
#: keeps its scope and names the sub-scope after it (``mlp+moe.experts``), so
#: a reader that sums ``mlp`` still counts it. Dense programs have none:
#: their names, and so SCOPES_VERSION, stand.
SUBSCOPES = ("moe.router", "moe.experts", "moe.shared",
             # Inside ``ssm``, in programs of a block with delta-rule mixers
             # alone (engine/hybrid.py): the convolution over q | k | v and
             # their norms; the decay, beta and the output gate; the
             # state's update and read (the kernel of engine/recurrence.py
             # on the chip, ``delta_update`` under XLA) and NOTHING else;
             # the chunked solve of a prefill. ``ssm.conv`` is also drawn
             # in a Mamba-2 block's WINDOW program, around the step of its
             # convolution's carried inputs alone (``hybrid.conv_token``):
             # those programs changed with it (PR 53), so no executable of
             # an older tree is theirs and SCOPES_VERSION stands.
             # ``ssm.state`` is drawn in EVERY recurrent block's window
             # program since PR 54: around the first form's update and read
             # too (``hybrid.window_step`` ``update``: the kernel
             # ``state_step`` on the chip, ``state_update`` and the layer's
             # slice in and out under XLA), metadata alone in the Mamba-2
             # and lightning blocks' programs, hence SCOPES_VERSION 3.
             "ssm.conv", "ssm.gates", "ssm.state", "ssm.chunk")
#: Bump when SCOPES or where a scope is drawn changes. jax's persistent
#: cache key leaves debug info out (jax/_src/cache_key.py strips it), so an
#: executable cached by a tree with other scopes would be loaded with ITS
#: names; the version is a sub-directory of the cache directory, and a
#: change of vocabulary costs one cold start instead. 2: ``ssm`` (PR 41).
#: 3: ``ssm.state`` around the first form's update (PR 54).
SCOPES_VERSION = 3


def scope(name: str):
    """``jax.named_scope`` for one name of SCOPES, BLOCK_SCOPES or
    SUBSCOPES."""
    assert name in SCOPES + BLOCK_SCOPES + SUBSCOPES, name
    return jax.named_scope(name)


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s*\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.-]+)\s*=\s.*?\s([a-z][a-z0-9-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.-]+)")
_HLO_NAME = re.compile(r"%([\w.-]+)")
#: Layout changes and transfers the compiler puts in by itself: they carry
#: no op_name, or the name of a parameter.
_HLO_MOVES = ("copy", "copy-start", "copy-done")


def _scope_of(op_name: str) -> str | None:
    """The innermost component of an ``op_name`` path that is a scope, and
    after it (joined with ``+``) the innermost that is a sub-scope."""
    parts = op_name.split("/")
    found = [next((p for p in reversed(parts) if p in names), None)
             for names in (SCOPES + BLOCK_SCOPES, SUBSCOPES)]
    return "+".join(p for p in found if p) or None


def _operands(line: str, opcode_end: int) -> list[str]:
    """Names inside the parentheses that open at ``opcode_end - 1``."""
    depth, i = 1, opcode_end
    while i < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return ["%" + n for n in _HLO_NAME.findall(line[opcode_end:i])]


def scopes_of_hlo(text: str) -> dict[str, str | None]:
    """``{"%fusion.296": "attn.kv_gather", ...}`` from optimized HLO text:
    an instruction takes the scope its ``metadata={op_name=...}`` names; a
    fusion takes the scopes of the instructions of its fused computation,
    in SCOPES order and joined with ``+`` when more than one; a copy the
    compiler put in (no scope of its own) takes the scope of what reads
    it, else of what it reads: the pool's layout changes around the commit
    scatter are the commit's. None for an instruction in no scope."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    members: dict[str, list[str]] = {}
    reads: dict[str, list[str]] = {}
    moves: list[str] = []
    computation = None
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m and computation is not None:
            name, opcode = "%" + m.group(1), m.group(2)
            found = _HLO_OP_NAME.search(line)
            own[name] = _scope_of(found.group(1)) if found else None
            members[computation].append(name)
            reads[name] = _operands(line, m.end())
            if opcode == "fusion":
                called = _HLO_CALLS.search(line)
                if called:
                    calls[name] = called.group(1)
            elif opcode in _HLO_MOVES and own[name] is None:
                moves.append(name)
            continue
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            members[computation] = []
        elif line.startswith("}"):
            computation = None
    out = dict(own)
    for name, fused in calls.items():
        inside = {part for i in members.get(fused, ()) if own.get(i)
                  for part in own[i].split("+")}
        if inside:
            out[name] = "+".join(s for s in SCOPES + BLOCK_SCOPES + SUBSCOPES
                                 if s in inside)
    read_by: dict[str, list[str]] = {}
    for name, operands in reads.items():
        for operand in operands:
            read_by.setdefault(operand, []).append(name)
    for _ in range(2):  # copy -> copy-start -> copy-done chains
        for name in moves:
            if out.get(name):
                continue
            near = ({out.get(u) for u in read_by.get(name, ())} - {None}
                    or {out.get(o) for o in reads[name]} - {None})
            if len(near) == 1:
                out[name] = near.pop()
    return out


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def _cost_mode() -> str:
    return os.environ.get("DTPU_PERF_COST", "lower").strip().lower()


#: A first-call record's seconds, in the order the family sums keep them.
FIRST_CALL_PARTS = ("wall_s", "trace_s", "lower_s", "cache_load_s",
                    "compile_s")
#: What the program store (engine/program_store.py) can answer a wrapper: the
#: executable was loaded (``hit``); there was none, it was built and written
#: (``miss``); there was one and it could not be used: it went, and the
#: program was built and written as on a miss (``reject``); a LATER call
#: brought arguments the loaded executable does not take and the wrapper went
#: back to its jit for good (``fallback``).
STORE_COUNTERS = {"hit": "store_hits", "miss": "store_misses",
                  "reject": "store_rejects", "fallback": "store_fallbacks"}
STORE_RESULTS = tuple(STORE_COUNTERS)
#: Arguments of ``jax.jit`` under which a wrapper has no store: a static
#: argument is part of the program and no part of a ``Compiled``'s call. The
#: others (the donation, shardings) are in the key by their ``repr``.
_UNSTORABLE_JIT_KWARGS = frozenset({"static_argnums", "static_argnames"})


class _Program:
    """Plain-int per-program-family telemetry (engine-thread writers;
    snapshot readers tolerate torn reads — these are gauges/counters,
    not invariants)."""

    __slots__ = ("name", "compiles", "compile_seconds", "unexpected",
                 "sigs", "cost", "last_compile_ts", "cache_loads",
                 "cache_load_seconds", "cache_misses", "first_call_seconds",
                 "store", "store_reject_reason")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0          # builds: compiled OR loaded from the cache
        self.compile_seconds = 0.0
        self.unexpected = 0
        self.sigs: dict = {}      # signature key -> compile count
        self.cost: dict | None = None  # one-time FLOPs/bytes estimate
        self.last_compile_ts = 0.0
        self.cache_loads = 0       # of the builds, persistent-cache hits
        self.cache_load_seconds = 0.0
        self.cache_misses = 0      # asked of the cache and compiled
        self.first_call_seconds = dict.fromkeys(FIRST_CALL_PARTS, 0.0)
        # What the program store answered (STORE_RESULTS), and why it last
        # threw an entry away.
        self.store = dict.fromkeys(STORE_RESULTS, 0)
        self.store_reject_reason: str | None = None


# -- build detection probe -----------------------------------------------------
# jax.monitoring fires ``/jax/core/compile/backend_compile_duration``
# synchronously in the calling thread for every BUILD of an executable:
# pxla opens the event around ``compile_or_get_cached``, so it fires when
# XLA compiled and when the persistent cache handed the executable over —
# the only signal that separates builds from dispatch-cache churn (the
# private ``_cache_size`` probe also grows on fast-path entries for
# committed-ness changes, which produced false recompile alarms). The
# same thread receives the trace and lowering events and the cache's own
# (requests, hits, retrieval seconds). The listeners keep ONE immutable
# tuple a thread, ``_tls.totals``, replaced on every event: a wrapper reads
# it before and after a call and knows by identity that nothing was built.

_tls = threading.local()

#: Indices into ``_tls.totals``. ``_N`` / ``_S`` count the backend event as
#: before (``compiles`` / ``compile_seconds``); ``_TRACE``, ``_LOWER`` and
#: ``_BACKEND`` are SELF seconds (an eager program built while another is
#: traced is the inner one's; a nested trace is counted once), so they add
#: up to no more than the wall time around them.
_N, _S, _TRACE, _LOWER, _BACKEND, _LOAD, _ASKED, _HITS = range(8)
_ZERO = (0, 0.0, 0.0, 0.0, 0.0, 0.0, 0, 0)
#: Event names of the installed jax (jax/_src/dispatch.py, compiler.py). A
#: jax without one of them leaves that part of a record 0.
_SPAN_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": _TRACE,
                "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
                "/jax/core/compile/backend_compile_duration": _BACKEND}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: Ended spans a thread remembers, for the one that may still end around
#: them: a program's trace holds a span a jitted helper it calls (hundreds),
#: and each ended top-level span stays as one entry.
_SPANS_KEPT = 8192


def _bump(index: int, amount) -> None:
    totals = list(getattr(_tls, "totals", _ZERO))
    totals[index] += amount
    _tls.totals = tuple(totals)


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    if "backend_compile" in event:
        _bump(_N, 1)
        _bump(_S, duration)
    elif event == _CACHE_LOAD_EVENT:
        _bump(_LOAD, duration)


def _on_compile_span(event: str, start: float, end: float, **_kw) -> None:
    """Self seconds of a trace, a lowering or a backend build. The events
    arrive as each ends, an inner one before the one around it: what the
    spans inside this one already took stays theirs."""
    kind = _SPAN_EVENTS.get(event)
    if kind is None:
        return
    done = _tls.__dict__.setdefault("done", [])  # (start, seconds taken)
    inside = 0.0
    while done and done[-1][0] >= start:
        inside += done.pop()[1]
    own = max(0.0, (end - start) - inside)
    if len(done) >= _SPANS_KEPT:    # the oldest enclose nothing to come
        del done[:_SPANS_KEPT // 2]
    done.append((start, inside + own))
    _bump(kind, own)


_PROBE_OK = False
try:  # pragma: no branch — registration is once at import
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    _PROBE_OK = True
except Exception:  # noqa: BLE001 — older jax: degrade to first-call counting
    log.info("jax.monitoring unavailable; compile observatory degrades "
             "to first-call counting")
try:
    jax.monitoring.register_event_time_span_listener(_on_compile_span)
except Exception:  # noqa: BLE001 — a first-call record keeps wall_s alone
    log.info("jax.monitoring has no time spans; first-call records carry "
             "wall seconds alone")


# -- persistent compile cache ---------------------------------------------------
#: Where compiled programs persist when JAX_COMPILATION_CACHE_DIR is not
#: set: a fixed path inside the checkout (the path is part of what makes a
#: cache findable again — never a temp name, pid or timestamp).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# Process-wide persistent-cache traffic (jax.monitoring events; plain ints).
_cache_events = {"hits": 0, "misses": 0}


def _on_cache_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _cache_events["hits"] += 1
        _bump(_HITS, 1)
    elif event == _CACHE_ASKED_EVENT:
        _bump(_ASKED, 1)
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


jax.monitoring.register_event_listener(_on_cache_event)


def compile_cache_dir() -> str:
    """The persistent compile cache directory this process uses: inside
    what JAX_COMPILATION_CACHE_DIR says when it is set, else inside the
    fixed in-checkout default, the sub-directory of the scope vocabulary's
    version (SCOPES_VERSION says why)."""
    return os.path.join(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                        or DEFAULT_COMPILE_CACHE_DIR,
                        f"scopes-v{SCOPES_VERSION}")


def configure_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache before the first
    compile (ModelRunner construction calls this, so every entry point
    passes through it). Thresholds drop to zero so the many small bucket
    programs are kept, not only those that take a second to build.
    Returns the directory, or None where the cache is switched off
    (``JAX_ENABLE_COMPILATION_CACHE=false``, as tests/conftest.py does)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax opens its cache once, at the first compile: a process that
        # compiled before this call (a test, a generator of weights) holds
        # the directory above this one open.
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def compile_cache_status() -> dict:
    """Directory, entry count and this process's hit/miss counts."""
    path = compile_cache_dir()
    enabled = bool(jax.config.jax_enable_compilation_cache)
    try:
        entries = sum(1 for name in os.listdir(path)
                      if name.endswith("-cache"))
    except FileNotFoundError:
        entries = 0
    return {"dir": path if enabled else None, "enabled": enabled,
            "entries": entries, **_cache_events}


class _InstrumentedJit:
    """Transparent wrapper around one jitted callable: forwards calls,
    counts compiles, triggers one-time cost analysis. One wrapper per
    (program, signature key) — the runner's shape-bucket caches store
    these in place of the raw jitted function. A wrapper whose builder
    gave it a store context (:func:`program_context`) takes its
    executable from the program store at its first call, or builds it and
    writes it there (engine/program_store.py)."""

    __slots__ = ("_fn", "_run", "_registry", "_program", "_key", "_calls",
                 "_compiles", "_signature", "_scopes", "_labels",
                 "_context", "_jit_kwargs", "__weakref__")

    def __init__(self, registry: "CompileRegistry", program: str,
                 fn, key, labels: dict | None = None, context=None,
                 jit_kwargs: dict | None = None):
        self._fn = fn
        # What a call runs: the jit, or the executable the store loaded or
        # the store's miss path built (a ``jax.stages.Compiled``).
        self._run = fn
        # What the builder chose statically for this program (the window
        # program's kv_commit_backend: Backends.labels): a name, not a count.
        self._labels = dict(labels or {})
        self._registry = registry
        self._program = program
        self._key = key
        self._calls = 0
        self._compiles = 0
        self._signature = None  # the first call's arguments, as shapes
        self._scopes = None     # ops_by_scope(), built on demand
        self._jit_kwargs = dict(jit_kwargs or {})
        self._context = (None if _UNSTORABLE_JIT_KWARGS & set(self._jit_kwargs)
                         else context)

    def __call__(self, *args, **kwargs):
        if self._signature is None:
            return self._first_call(args, kwargs)
        before = getattr(_tls, "totals", _ZERO)
        t0 = time.monotonic()
        store = None
        try:
            out = self._run(*args, **kwargs)
        except (TypeError, ValueError) as exc:
            # A stored executable checks its arguments before it runs or
            # donates anything (other shardings than the first call's: the
            # penalised window's counts in the warm-up); the jit takes them.
            if self._run is self._fn:
                raise
            out, store = self._fall_back(exc, args, kwargs), "fallback"
        self._calls += 1
        if getattr(_tls, "totals", _ZERO) is not before:
            # This thread traced, lowered or built something meanwhile.
            self._note_built(before, t0, args, kwargs, store)
        return out

    def _first_call(self, args, kwargs):
        """The call that loads the program from the store, or traces,
        lowers and loads or compiles it: the unit a start is made of. After
        ``mark_ready`` (a bucket drawn lazily) it is also an annotation on
        the profiler's host line, so that an idle gap of the device names
        the program that caused it."""
        before = getattr(_tls, "totals", _ZERO)
        t0 = time.monotonic()
        # Shapes, dtypes and shardings only: the arrays themselves are
        # donated. Once per wrapper; ops_by_scope() lowers from it.
        self._signature = jax.tree.map(_abstract, (args, kwargs))
        entry = self._store_entry(args, kwargs)
        with (jax.profiler.TraceAnnotation(
                "program.first_call", program=self._program,
                key=repr(self._key))
              if self._registry.warmup_complete
              else contextlib.nullcontext()):
            if entry is None:
                out, store, load_s = self._fn(*args, **kwargs), None, 0.0
            else:
                out, store, load_s = self._open(entry, args, kwargs)
        self._calls += 1
        self._note_built(before, t0, args, kwargs, store, load_s)
        return out

    # -- the program store -----------------------------------------------------
    def _store_entry(self, args, kwargs):
        """This wrapper's place in the store; None for a wrapper without a
        context, or first called while a module of the package holds code
        no key can see (program_store.foreign_code)."""
        context = self._context
        if context is None:
            return None
        try:
            foreign = program_store.foreign_code()
            if foreign:
                log.info("program store: closed for %s %r: %s is bound to "
                         "code from outside the package", self._program,
                         self._key, ", ".join(foreign[:4]))
                return None
            return program_store.Entry(context, self._program, (
                program_store.key_text(
                    context, self._program, self._key, self._labels,
                    self._jit_kwargs, args, kwargs, SCOPES_VERSION)))
        except Exception:  # noqa: BLE001 — a start never fails for the store
            log.exception("program store: no key for %s %r", self._program,
                          self._key)
            return None

    def _open(self, entry, args, kwargs):
        """(the call's result, what the store answered, seconds of the read
        and the load). A hit runs the loaded executable. Anything else
        traces and lowers the program as jax would have, COMPILES it (jax's
        persistent cache stepped around), runs what was built and writes
        it."""
        registry, program = self._registry, self._program
        t0 = time.monotonic()
        store, found = "miss", None
        try:
            found = entry.load()
        except program_store.Reject as exc:
            store = "reject"
            registry.note_store(program, "reject", str(exc))
            log.warning("program store: %s %r: entry thrown away (%s)",
                        program, self._key, exc)
        load_s = time.monotonic() - t0
        if found is not None:
            compiled, header = found
            try:
                out = compiled(*args, **kwargs)
            except (TypeError, ValueError) as exc:
                # The key holds the signature, so this is a key's fault:
                # the entry goes and the program is built.
                entry.delete()
                store = "reject"
                registry.note_store(program, "reject",
                                    f"{type(exc).__name__}: {exc}"[:200])
                log.warning("program store: %s %r: the loaded executable "
                            "refused its first call (%s)", program,
                            self._key, exc)
            else:
                self._run = compiled
                registry.note_store(program, "hit")
                registry.adopt_cost(program, header.get("cost"))
                return out, "hit", load_s
        lowered = self._fn.trace(*args, **kwargs).lower()
        with _persistent_cache_stepped_around():
            compiled = lowered.compile()
        try:
            out = compiled(*args, **kwargs)
        except (TypeError, ValueError) as exc:
            return self._fall_back(exc, args, kwargs), "fallback", 0.0
        self._run = compiled
        if store == "miss":
            registry.note_store(program, "miss")
        cost = registry.maybe_cost(program, lambda: lowered)
        try:    # after the call: the device runs while the host serializes
            entry.save(compiled, cost)
        except Exception as exc:  # noqa: BLE001 — a start never fails for the store
            log.warning("program store: %s %r not written (%s: %s)", program,
                        self._key, type(exc).__name__, str(exc)[:200])
        return out, store, 0.0

    def _fall_back(self, exc, args, kwargs):
        """The stored executable refused a call's arguments: the jit takes
        this call and every later one."""
        self._run = self._fn
        self._registry.note_store(self._program, "fallback")
        log.info("program store: %s %r goes back to its jit (%s)",
                 self._program, self._key, str(exc).splitlines()[0][:200])
        return self._fn(*args, **kwargs)

    def _note_built(self, before: tuple, t0: float, args, kwargs,
                    store: str | None = None, load_s: float = 0.0) -> None:
        """One first-call record, and the build counted as before.
        ``store`` is what the program store answered this call (one of
        STORE_RESULTS; None for a wrapper without one), ``load_s`` the
        seconds of its read and load."""
        wall = time.monotonic() - t0
        after = getattr(_tls, "totals", _ZERO)
        asked = after[_ASKED] - before[_ASKED]
        hits = after[_HITS] - before[_HITS]
        load = after[_LOAD] - before[_LOAD]
        builds = after[_N] - before[_N]
        loaded = store == "hit"
        missed = store in ("miss", "reject")
        registry = self._registry
        registry.note_first_call({
            "program": self._program, "key": self._key,
            "labels": self._labels,
            "when": "serving" if registry.warmup_complete else "startup",
            "t_mono": t0, "wall_s": wall,
            "trace_s": after[_TRACE] - before[_TRACE],
            "lower_s": after[_LOWER] - before[_LOWER],
            # Did a cache hold what was built here? The program store's
            # answer reads as the persistent cache's (which is not asked on
            # the store's miss path). "off": no cache was asked (switched
            # off, or nothing was built).
            "cache": ("hit" if loaded else
                      "miss" if missed or asked > hits else
                      "hit" if asked else "off"),
            # The store's read and load are timed here (jax's retrieval
            # event fires for its own cache alone).
            "cache_load_s": load + (load_s if loaded else 0.0),
            "compile_s": max(0.0, after[_BACKEND] - before[_BACKEND] - load),
            "builds": builds,
            "store": store,
            # Where the executable of this call came from.
            "source": ("store" if loaded else
                       "fallback" if store == "fallback" else
                       "compiled" if missed else
                       "jax_cache" if asked and hits == asked else
                       "compiled" if builds else "retrace")},
            hits + loaded, asked - hits + missed)
        compiled = loaded or (builds > 0 if _PROBE_OK else self._calls == 1)
        if compiled:
            # Unexpected = THIS wrapper (one program instance, one
            # shape signature) compiling again AFTER warmup declared
            # steady state. Judged per-wrapper, not per registry key:
            # two runners in one process (tests, in-process
            # multi-worker launchers) each legitimately compile the
            # same (program, key) once. The warmup gate exists because
            # warmup itself intentionally double-compiles signatures
            # whose input shardings converge only after the first run
            # (e.g. the penalized window's counts under tp > 1).
            unexpected = (self._key is not None and self._compiles >= 1
                          and registry.warmup_complete)
            self._compiles += 1
            # The backend event's seconds (a cache load among them) or the
            # store's load, not wall time.
            registry.note_compile(
                self._program, self._key,
                load_s if loaded else
                after[_S] - before[_S] if _PROBE_OK else wall,
                unexpected=unexpected)
            if self._run is self._fn:   # the store's paths have their own
                registry.maybe_cost(
                    self._program,
                    lambda: self._fn.lower(*args, **kwargs))

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def ops_by_scope(self) -> dict | None:
        """scopes_of_hlo() of this wrapper's executable; None before the
        first call. A stored executable answers for itself; a jit's comes
        from jax's caches (this process compiled or loaded it already), so
        the names are those of the program that RUNS, also where the
        persistent cache handed over what another tree had compiled."""
        if self._scopes is None and self._signature is not None:
            args, kwargs = self._signature
            try:
                text = (self._run.as_text() if self._run is not self._fn
                        else self._fn.lower(*args, **kwargs).compile()
                        .as_text())
            except Exception:  # noqa: BLE001 — the perf plane never raises
                # (a program that needs the runner's mesh context to lower)
                log.exception("ops_by_scope: %s %r does not lower here",
                              self._program, self._key)
                return None
            self._scopes = scopes_of_hlo(text)
        return self._scopes


class CompileRegistry:
    """Process-wide compile observatory + per-window perf accumulator."""

    def __init__(self):
        self._lock = threading.Lock()  # compile bookkeeping only (rare)
        self._programs: dict[str, _Program] = {}
        self._wrappers: dict[str, list] = {}  # program -> weakrefs
        # One record a wrapper's first call and a later call that built
        # (_InstrumentedJit._note_built): a plain list, as long as the
        # process has programs, never a ring.
        self.first_calls: list[dict] = []
        self.warmup_complete = False
        self.warmup_complete_ts = 0.0
        # A launcher's start is under way (mark_starting .. mark_ready).
        self.starting = False
        # Per-window series (single engine-thread writer, lock-free).
        self.windows_total = 0
        self.window_seconds_total = 0.0
        self.window_tokens_total = 0
        self.step_seconds = 0.0        # EWMA seconds per decode step
        self.achieved_tok_s = 0.0      # EWMA tokens/s over device windows
        self.roofline_frac = 0.0       # EWMA achieved / weight-read roofline

    # -- compile observatory ---------------------------------------------------
    def wrap(self, program: str, fn, key=None, labels: dict | None = None,
             context=None, jit_kwargs: dict | None = None
             ) -> _InstrumentedJit:
        wrapper = _InstrumentedJit(self, program, fn, key, labels, context,
                                   jit_kwargs)
        with self._lock:
            self._programs.setdefault(program, _Program(program))
            refs = self._wrappers.setdefault(program, [])
            refs[:] = [r for r in refs if r() is not None]
            refs.append(weakref.ref(wrapper))
        return wrapper

    def ops_by_scope(self, program: str, key=None) -> dict | None:
        """Each instruction name of a compiled executable of ``program``
        (``%fusion.296``) mapped to its scope (scopes_of_hlo). ``key`` is
        the shape-signature key the caller memoizes under; without it, the
        wrapper of that program called most often. Built on demand (one
        lowering, and a compile that jax's caches answer), never in
        set-up. None when no such wrapper has run."""
        with self._lock:
            live = [w for w in (r() for r in self._wrappers.get(program, ()))
                    if w is not None and w._calls
                    and (key is None or w._key == key)]
        if not live:
            return None
        return max(live, key=lambda w: w._calls).ops_by_scope()

    def note_first_call(self, record: dict, cache_hits: int = 0,
                        cache_misses: int = 0) -> None:
        """Keep ``record`` and add it to its family's sums. One taken after
        ``mark_ready`` is also a span ``program.first_call``: a
        ``compiles_in_window`` other than 0 then names its program."""
        with self._lock:
            prog = self._programs.setdefault(record["program"],
                                             _Program(record["program"]))
            for part in FIRST_CALL_PARTS:
                prog.first_call_seconds[part] += record[part]
            prog.cache_loads += cache_hits
            prog.cache_load_seconds += record["cache_load_s"]
            prog.cache_misses += cache_misses
            if len(self.first_calls) < _FIRST_CALLS_KEPT:
                self.first_calls.append(record)
        if record["when"] == "serving":
            tracing.get_recorder().add(
                "program.first_call", generate_trace_id(), None,
                record["t_mono"], record["t_mono"] + record["wall_s"],
                attrs={"program": record["program"],
                       "key": repr(record["key"]), "cache": record["cache"],
                       "wall_s": round(record["wall_s"], 4)})

    def note_compile(self, program: str, key, seconds: float,
                     unexpected: bool | None = None) -> None:
        """``key`` is the caller's shape-signature cache key. The
        instrumented wrapper passes ``unexpected`` explicitly (a second
        compile of the SAME wrapper — per program instance, so two
        runners in one process don't cross-flag); direct callers leave
        it None and the registry falls back to key-seen detection.
        ``key=None`` marks a self-bucketing program (one jit wrapper
        legitimately compiling per input shape — the multimodal
        encoders): compiles are counted but never flagged."""
        with self._lock:
            prog = self._programs.setdefault(program, _Program(program))
            seen = prog.sigs.get(key, 0)
            prog.sigs[key] = seen + 1
            prog.compiles += 1
            prog.compile_seconds += seconds
            prog.last_compile_ts = time.time()
            if unexpected is None:
                unexpected = key is not None and seen >= 1
            if unexpected:
                prog.unexpected += 1
        if unexpected:
            self._warn_recompile(program, key, seconds)

    def _warn_recompile(self, program: str, key, seconds: float) -> None:
        log.warning(
            "unexpected steady-state recompile: program %s key %r compiled "
            "again (%.3fs) — the jit cache for an already-served shape was "
            "invalidated (dtype/weak-type drift, donation mismatch, or a "
            "shape leak); decode pays XLA time on the hot path", program,
            key, seconds)
        rec = tracing.get_recorder()
        if rec.enabled:
            now = time.monotonic()
            rec.add("perf.recompile", generate_trace_id(),
                    generate_span_id(), now - seconds, now, status="warn",
                    attrs={"program": program, "key": repr(key),
                           "compile_s": round(seconds, 4)})

    def maybe_cost(self, program: str, lower) -> dict | None:
        """One-time FLOPs/bytes estimate per program family; returns what
        the family has (None while another thread works on it, or with the
        mode ``off``). ``lower()`` gives the ``Lowered`` program: the jit's
        path lowers once more for it, the store's miss path has it at hand.
        Cheap path (``cost_analysis()`` of the lowered module) never
        XLA-compiles; the ``compile`` mode pays a real second compile for
        optimized numbers. Every failure is recorded, never raised — the
        perf plane must not be able to take down serving."""
        mode = _cost_mode()
        if mode == "off":
            return None
        with self._lock:
            prog = self._programs.setdefault(program, _Program(program))
            if prog.cost is not None:
                return None if prog.cost.get("pending") else prog.cost
            prog.cost = {"pending": True}  # claim before the slow work
        cost: dict
        try:
            lowered = lower()
            raw = (lowered.compile().cost_analysis() if mode == "compile"
                   else lowered.cost_analysis())
            if isinstance(raw, (list, tuple)):  # compiled returns per-device
                raw = raw[0] if raw else {}
            cost = {"flops": float(raw.get("flops", 0.0)),
                    "bytes_accessed": float(raw.get("bytes accessed", 0.0)),
                    "source": mode}
        except Exception as exc:  # noqa: BLE001 — backend-dependent API
            cost = {"error": f"{type(exc).__name__}: {exc}"[:200],
                    "source": mode}
        with self._lock:
            prog.cost = cost
        return cost

    def adopt_cost(self, program: str, cost: dict | None) -> None:
        """The estimate a stored executable's header carries (its family's,
        as of the start that wrote it), where the family has none yet: a
        start that loads every program lowers none for an estimate."""
        if cost:
            with self._lock:
                prog = self._programs.setdefault(program, _Program(program))
                if prog.cost is None:
                    prog.cost = cost

    def note_store(self, program: str, result: str,
                   reason: str | None = None) -> None:
        """The program store answered a wrapper of ``program`` (``result``
        one of STORE_RESULTS; ``reason`` why an entry was thrown away)."""
        with self._lock:
            prog = self._programs.setdefault(program, _Program(program))
            prog.store[result] += 1
            if reason is not None:
                prog.store_reject_reason = reason

    def mark_ready(self) -> None:
        """Warmup boundary: compiles recorded after this are post-warmup
        (the pane surfaces the flag; the recompile detector itself is
        per-signature and needs no boundary), and first calls read
        ``when: "serving"``."""
        self.warmup_complete = True
        self.warmup_complete_ts = time.time()
        self.starting = False

    def mark_starting(self) -> None:
        """The launcher is about to build an engine: what is built from
        here to the next ``mark_ready`` is a start's, also in a process
        that served before; a runner built in between may keep its
        programs in the program store (:func:`program_context`)."""
        self.warmup_complete = False
        self.starting = True

    # -- roofline-attributed window timing ------------------------------------
    def note_window(self, window_s: float, tokens: int, active: int,
                    steps: int, step_floor_ms: float,
                    latency_s: float) -> None:
        """One processed decode window (ENGINE THREAD: plain stores
        only). ``window_s`` is the window's period (its readback
        complete minus the previous window's) when it was queued behind
        that window, else dispatch -> readback complete; ``tokens`` the
        tokens it emitted, ``active`` the dispatched slot rows,
        ``step_floor_ms`` the time to read the bytes a step reads of the
        shard's weights (ModelSpec.weight_read_step_ms: a looped stack's
        layers once a pass). ``window_seconds_total`` keeps
        summing ``latency_s`` (dispatch -> readback complete)."""
        if window_s <= 0 or steps <= 0:
            return
        self.windows_total += 1
        self.window_seconds_total += latency_s
        self.window_tokens_total += tokens
        step_s = window_s / steps
        tok_s = tokens / window_s
        if self.windows_total == 1:
            self.step_seconds = step_s
            self.achieved_tok_s = tok_s
        else:
            self.step_seconds += _EWMA * (step_s - self.step_seconds)
            self.achieved_tok_s += _EWMA * (tok_s - self.achieved_tok_s)
        if active > 0 and step_floor_ms > 0:
            roofline_tok_s = active / (step_floor_ms / 1e3)
            frac = min(tok_s / roofline_tok_s, 1.0)
            if self.windows_total == 1:
                self.roofline_frac = frac
            else:
                self.roofline_frac += _EWMA * (frac - self.roofline_frac)

    # -- panes -----------------------------------------------------------------
    @property
    def compiles_total(self) -> int:
        return sum(p.compiles for p in self._programs.values())

    @property
    def unexpected_total(self) -> int:
        return sum(p.unexpected for p in self._programs.values())

    def snapshot(self) -> dict:
        """The /debug/perf "compiles" body."""
        with self._lock:
            programs = {
                name: {
                    "compiles": p.compiles,
                    "compile_seconds": round(p.compile_seconds, 4),
                    "cache_loads": p.cache_loads,
                    "cache_load_seconds": round(p.cache_load_seconds, 4),
                    "signatures": len(p.sigs),
                    "unexpected_recompiles": p.unexpected,
                    "cost": p.cost,
                    "last_compile_ts": p.last_compile_ts,
                    "labels": self._labels_of(name),
                    **{STORE_COUNTERS[result]: n
                       for result, n in p.store.items()},
                    "store_reject_reason": p.store_reject_reason,
                }
                for name, p in sorted(self._programs.items())
            }
        return {
            "programs": programs,
            "compiles_total": sum(v["compiles"] for v in programs.values()),
            "compile_seconds_total": round(
                sum(v["compile_seconds"] for v in programs.values()), 4),
            "cache_loads_total": sum(v["cache_loads"]
                                     for v in programs.values()),
            "unexpected_recompiles_total": sum(
                v["unexpected_recompiles"] for v in programs.values()),
            "warmup_complete": self.warmup_complete,
        }

    def label_values(self, label: str) -> dict:
        """{program family: the distinct values its live programs carry
        under ``label``}; a family without the label is left out."""
        with self._lock:
            families = sorted(self._wrappers)
        return {name: values for name in families
                if (values := self._labels_of(name).get(label))}

    def _labels_of(self, program: str) -> dict:
        """Each label of the family's live programs with the distinct
        values they carry (one, unless two runners in a process differ)."""
        out: dict[str, list] = {}
        for ref in self._wrappers.get(program, ()):
            for k, v in getattr(ref(), "_labels", {}).items():
                if v not in out.setdefault(k, []):
                    out[k].append(v)
        return out

    def window_snapshot(self) -> dict:
        """The /debug/perf "window" body (EWMA-smoothed live series)."""
        return {
            "windows_total": self.windows_total,
            "window_seconds_total": round(self.window_seconds_total, 4),
            "window_tokens_total": self.window_tokens_total,
            "step_seconds": self.step_seconds,
            "achieved_tok_per_s": round(self.achieved_tok_s, 2),
            "roofline_frac": round(self.roofline_frac, 4),
        }

    def reset(self) -> None:
        """Tests only: drop every program and window sample."""
        with self._lock:
            self._programs.clear()
            self._wrappers.clear()
            self.first_calls.clear()
        self.warmup_complete = False
        self.warmup_complete_ts = 0.0
        self.starting = False
        self.windows_total = 0
        self.window_seconds_total = 0.0
        self.window_tokens_total = 0
        self.step_seconds = 0.0
        self.achieved_tok_s = 0.0
        self.roofline_frac = 0.0


_REGISTRY = CompileRegistry()


def get_registry() -> CompileRegistry:
    return _REGISTRY


def instrumented_jit(program: str, fun, *, key=None, registry=None,
                     labels: dict | None = None, context=None,
                     **jit_kwargs):
    """The ONE sanctioned way to build a serving-path jit program:
    ``jax.jit`` + compile observatory in a drop-in wrapper. ``program``
    is the family label (``prefill``, ``decode_window``, ...); ``key``
    the shape-signature cache key the caller memoizes under (the
    recompile detector treats a second compile of the same key as
    unexpected); ``labels`` what the caller chose statically for the
    family (the snapshot's ``labels``); ``context`` the caller's
    :func:`program_context` (everything ``fun`` closes over, for the
    program store's key; None, or no argument: the program is traced and
    built at every start). Extra kwargs go straight to ``jax.jit``."""
    reg = registry if registry is not None else _REGISTRY
    # dtpu: ignore[jit-recompile-hazard] until=2027-08-01 -- this IS the caching chokepoint: every caller memoizes the returned wrapper by its shape key
    return reg.wrap(program, jax.jit(fun, **jit_kwargs), key=key,
                    labels=labels, context=context, jit_kwargs=jit_kwargs)


# -- the program store's rule of engagement ------------------------------------
_store_closed = 0   # depth of program_store_closed()
_config_lock = threading.Lock()


def program_context(*closed_over, mesh, registry=None):
    """What a runner hands :func:`instrumented_jit` as ``context``: the
    ``repr`` of everything its programs close over (``ModelSpec``,
    ``EngineConfig``, ``Backends``: plain dataclasses) and its mesh. None,
    and so no store, unless this process can see that a stale executable
    has no way in: a launcher marked a start that is still under way (a
    runner a test or a script builds directly may run patched model code
    under an unchanged configuration), the persistent compile cache is on
    (its directory holds the store) and the process is the only one
    (multi-controller serving compiles in lockstep)."""
    reg = registry if registry is not None else _REGISTRY
    if (not reg.starting or _store_closed
            or not jax.config.jax_enable_compilation_cache
            or jax.process_count() != 1):
        return None
    text = "\n".join([*(repr(part) for part in closed_over),
                      f"mesh={dict(mesh.shape)!r}"])
    return program_store.Context(
        text, mesh.devices.flat,
        os.path.join(compile_cache_dir(), program_store.SUBDIR))


@contextlib.contextmanager
def program_store_closed():
    """No runner built inside gets a store context: for a test that goes
    through the launcher AND patches what its programs trace in a way
    program_store.foreign_code cannot see."""
    global _store_closed
    _store_closed += 1
    try:
        yield
    finally:
        _store_closed -= 1


@contextlib.contextmanager
def _persistent_cache_stepped_around():
    """A compile inside is a real compile: jax's persistent cache is neither
    asked nor written. What the program store is about to write must come
    from the compiler: an executable the cache handed over does not
    serialize back (on the CPU its payload loads and then fails its first
    run: "Function ... not found"), and one it kept would lie in the
    directory twice. The switch is the process's, so a compile another
    thread makes meanwhile is stepped around too (it compiles; nothing
    breaks)."""
    from jax.experimental.compilation_cache import compilation_cache
    with _config_lock:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


def first_calls_by_family(records: list[dict]) -> dict:
    """``records`` summed: in all and a family (``programs``, the five
    seconds of FIRST_CALL_PARTS, ``hits``, ``misses``, and what the program
    store answered: STORE_COUNTERS), with the ten longest by ``wall_s``."""
    def summed(rows: list[dict]) -> dict:
        return {"programs": len(rows),
                **{part: round(sum(r[part] for r in rows), 4)
                   for part in FIRST_CALL_PARTS},
                "hits": sum(r["cache"] == "hit" for r in rows),
                "misses": sum(r["cache"] == "miss" for r in rows),
                **{name: sum(r.get("store") == result for r in rows)
                   for result, name in STORE_COUNTERS.items()}}

    families: dict[str, list] = {}
    for r in records:
        families.setdefault(r["program"], []).append(r)
    longest = sorted(records, key=lambda r: r["wall_s"], reverse=True)[:10]
    return {**summed(records),
            "families": {name: summed(rows)
                         for name, rows in sorted(families.items())},
            "longest": [{**r, "key": repr(r["key"]),
                         **{part: round(r[part], 4)
                            for part in FIRST_CALL_PARTS}}
                        for r in longest]}


def startup_status() -> dict:
    """The /debug/perf ``startup`` body: the newest start's stages
    (runtime/tracing.py ``Startup.summary``; none in a process no launcher
    started) and the first calls made since it began and before
    ``mark_ready``, a family; beside them the programs first called while
    serving."""
    start = tracing.last_startup()
    reg = get_registry()
    with reg._lock:
        records = list(reg.first_calls)
    body = {"status": None, "stages": []}
    if start is not None:   # this start's calls, not an earlier engine's
        body = start.summary()
        records = [r for r in records
                   if r["t_mono"] >= start.root.start_mono]
    body["first_calls"] = first_calls_by_family(
        [r for r in records if r["when"] == "startup"])
    body["first_calls_serving"] = first_calls_by_family(
        [r for r in records if r["when"] == "serving"])
    return body


def describe_first_calls() -> str:
    """What was first called before ``mark_ready``, in a log line's words:
    "131 programs: trace 9.8, lower 6.1, cache 11.2 (131 hits, 21 from the
    program store), compile 0.0"."""
    calls = startup_status()["first_calls"]
    return ("%d programs: trace %.1f, lower %.1f, cache %.1f (%d hits, %d "
            "from the program store), compile %.1f%s" % (
                calls["programs"], calls["trace_s"], calls["lower_s"],
                calls["cache_load_s"], calls["hits"], calls["store_hits"],
                calls["compile_s"],
                " (%d MISSED the cache)" % calls["misses"]
                if calls["misses"] else ""))


def process_perf_status() -> dict:
    """Fallback /debug/perf body for a process without an engine (a
    frontend in proxy mode, a bare status server): the compile
    observatory is process-global, so it still answers."""
    reg = get_registry()
    return {"role": "process", "compiles": reg.snapshot(),
            "window": reg.window_snapshot(), "startup": startup_status(),
            "hbm": {}, "memory": {}}


class PerfMetricsUpdater:
    """dynamo_tpu_perf_* exporter: registry plain-ints -> Prometheus,
    throttled so the engine thread never takes a Prometheus lock per
    window (same pattern as KvMetricsUpdater). Counters export DELTAS
    so a registry reset can't make them go backwards. Every series is
    documented in docs/OBSERVABILITY.md "Engine perf plane" (tier-1
    docs-drift guard)."""

    def __init__(self, registry, min_interval_s: float = 0.5):
        self.min_interval_s = min_interval_s
        self._next = 0.0
        self._last: dict[tuple, float] = {}
        self.c_compiles = registry.counter(
            "perf_compiles_total", "Builds per jit program family: XLA "
            "compiles AND loads from the persistent compile cache (the "
            "backend event fires for both; perf_cache_loads_total tells "
            "them apart)", ["program"])
        self.c_compile_seconds = registry.counter(
            "perf_compile_seconds_total", "Seconds of the backend event "
            "per jit program family: compiling, or on a cache hit "
            "retrieving the executable", ["program"])
        self.c_unexpected = registry.counter(
            "perf_unexpected_recompiles_total", "Compiles of an "
            "already-seen (program, signature) after first use — the "
            "runtime twin of the jit-recompile-hazard lint rule; any "
            "nonzero rate in steady state is a serving-path bug",
            ["program"])
        self.c_cache_loads = registry.counter(
            "perf_cache_loads_total", "Builds that asked the persistent "
            "compile cache, per jit program family: result hit (the "
            "executable was loaded; perf_compiles_total counts it too) or "
            "miss (XLA compiled and the entry was written); a warm start "
            "has no miss", ["program", "result"])
        self.c_program_store = registry.counter(
            "perf_program_store_total", "What the program store "
            "(engine/program_store.py: executables kept under a key that "
            "costs no trace) answered a wrapper's first call, per jit "
            "program family: result hit (loaded: no trace, no lowering), "
            "miss (built and written), reject (an entry that could not be "
            "used: deleted, built and written) or fallback (a LATER call's "
            "arguments the loaded executable does not take: the wrapper "
            "went back to its jit); a warm start through the launcher is "
            "all hits", ["program", "result"])
        self.c_first_call = registry.counter(
            "perf_first_call_seconds_total", "Seconds of the calls that "
            "built a program (a wrapper's first call, or a later one that "
            "rebuilt), per jit program family and part: wall, and inside "
            "it trace (Python), lower (to MLIR), cache_load and compile",
            ["program", "part"])
        self.g_startup = registry.gauge(
            "startup_seconds", "Seconds of the newest start by stage "
            "(runtime/tracing.py Startup: the span of that name under the "
            "root startup), set once at ready; stage startup is launcher "
            "entry to ready, unattributed what its stages leave uncovered",
            ["stage"])
        self._startup_told = None   # the Startup whose stages are exported
        self.g_step_seconds = registry.gauge(
            "perf_step_seconds", "EWMA seconds per decode step (a "
            "window's period while the pipe is full, else dispatch to "
            "readback, over its steps)")
        self.g_achieved = registry.gauge(
            "perf_achieved_tok_per_s", "EWMA decode tokens/s over "
            "dispatched windows (tokens over the window's period)")
        self.c_phase_seconds = registry.counter(
            "engine_phase_seconds_total", "Engine-thread self time by "
            "loop phase (runtime/tracing.py ENGINE_PHASES); the phases "
            "add up to the thread's wall time", ["phase"])
        self.g_roofline = registry.gauge(
            "perf_roofline_frac", "EWMA fraction of the shard's "
            "weight-read roofline achieved by decode windows")
        self.g_hbm_in_use = registry.gauge(
            "perf_hbm_bytes_in_use", "device.memory_stats bytes_in_use "
            "on this worker's first addressable device")
        self.g_hbm_peak = registry.gauge(
            "perf_hbm_peak_bytes", "device.memory_stats "
            "peak_bytes_in_use on this worker's first addressable device")
        self.g_hbm_limit = registry.gauge(
            "perf_hbm_limit_bytes", "device.memory_stats bytes_limit on "
            "this worker's first addressable device")
        self.g_kv_commit = registry.gauge(
            "perf_kv_commit_info", "1 under the label of how this worker's "
            "decode window program commits its tokens to the KV pool "
            "(engine/backends.py Backends.kv_commit): in_place (the touched "
            "pages are rewritten where they lie, beside the Pallas reader) or "
            "scatter (XLA's scatter; pool-sized layout copies on a TPU)",
            ["backend"])
        self.g_attention = registry.gauge(
            "perf_attention_info", "1 under the label of who reads this "
            "worker's KV pool in decode (engine/backends.py "
            "Backends.attention): pallas (a kernel walks a row's live "
            "pages: K and V heads, or latent entries) or xla (the gather "
            "of every slot's page-table bucket)", ["backend"])
        self.g_index = registry.gauge(
            "perf_index_info", "1 under the label of who runs the decode "
            "indexer of this worker's latent pool (engine/backends.py "
            "Backends.index): pallas (a kernel walks a row's live pages "
            "of index keys, or of a compressed-key array's stripes, and "
            "scores them) or xla (the gather of every slot's page-table "
            "bucket, scored); the choice over the scores is XLA's under "
            "either; no sample for a block whose queries choose nothing",
            ["backend"])
        self.g_kv_page = registry.gauge(
            "perf_kv_page_info", "1 under the label of how many tokens a "
            "KV page of this worker holds (runner.page_size): 16, or the "
            "page derived for the Pallas reader on one TPU device",
            ["tokens"])
        self.c_spec_draft_tokens = registry.counter(
            "perf_spec_draft_tokens_total", "Speculative draft tokens "
            "proposed on the device: by the n-gram drafter (spec_window) "
            "or by the model's own prediction module inside the window "
            "program (spec_decode mtp: perf_draft_info)")
        self.c_spec_accepted_tokens = registry.counter(
            "perf_spec_accepted_tokens_total", "Speculative draft tokens "
            "accepted by the fused verify of either drafter "
            "(rejection-sampled for temperature > 0; exact-match under "
            "greedy)")
        self.c_spec_verify_steps = registry.counter(
            "perf_spec_verify_steps_total", "Speculative verify steps by "
            "tokens emitted — the per-window emitted-token histogram "
            "(emitted=1 means no draft accepted; emitted=spec_k+1 means "
            "the whole draft block landed; emitted=0 a frozen slot); under "
            "spec_decode mtp a verify step is one scan step of the window "
            "program, and logprobs are served from its logits",
            ["emitted"])
        self.g_spec_acceptance = registry.gauge(
            "perf_spec_acceptance_rate", "Lifetime accepted/proposed "
            "draft-token ratio of the speculative verify (either drafter)")
        self.c_spec_brownout = registry.counter(
            "perf_spec_brownout_windows_total", "Decode windows where "
            "brownout pressure suspended n-gram drafting (a prediction "
            "module drafts inside the one window program and is not "
            "suspended)")
        self.g_draft = registry.gauge(
            "perf_draft_info", "1 under the label of who drafts inside the "
            "decode window program's steps: mtp (the model's own "
            "prediction module, verified in the same step) or none",
            ["kind"])
        # What decode windows counted, by flight-ring column: the table
        # flight.COUNTS names each column's counter, and update() walks it.
        self.c_counts: dict = {}
        self.c_counts["moe_layer_steps"] = registry.counter(
            "moe_layer_steps_total", "Routed block: (decode step, expert "
            "layer) pairs with a live row, the denominator of the two "
            "series below")
        self.c_counts["moe_touched"] = registry.counter(
            "moe_experts_touched_total", "Routed block: distinct experts "
            "the live rows chose, summed over decode steps and expert "
            "layers (over moe_layer_steps_total: experts a layer-step "
            "touches)")
        self.c_counts["moe_load"] = registry.counter(
            "moe_expert_load_max_over_mean_total", "Routed block: the "
            "fullest expert's tokens over the mean per expert, summed over "
            "decode steps and expert layers (over moe_layer_steps_total: "
            "1.0 is an even load)")
        self.c_counts["moe_local_picks"] = registry.counter(
            "moe_local_picks_total", "Expert layer told its share: (row, "
            "choice) pairs of live rows that fell on experts held here "
            "(over moe_picks_total: an even router gives held / routed)")
        self.c_counts["moe_picks"] = registry.counter(
            "moe_picks_total", "Expert layer told its share: all (row, "
            "choice) pairs of live rows, wherever the expert is held")
        self.c_moe_grouped_pairs = registry.counter(
            "moe_grouped_pairs_total", "Routed block: (row, choice) pairs a "
            "layer of the prefill calls whose expert layers took the "
            "grouped product (the kernel of engine/experts.py: each pair "
            "by its own expert), counted on the host from the rows sent")
        self.g_expert_product = registry.gauge(
            "perf_expert_product_info", "1 under the labels of a program "
            "family of a routed block (prefill, decode_window) and the "
            "product its expert layers take, static by the program's rows "
            "(model.expert_product): touched (a step's rows by the experts "
            "its live rows chose), grouped (sorted pairs, each by its own "
            "expert) or masked (every row by every resident expert)",
            ["program", "kind"])
        self.g_moe_experts = registry.gauge(
            "moe_experts_info", "Expert layer told its share: experts the "
            "router chooses among, held here and shared", ["kind"])
        self.c_counts["attn_selected"] = registry.counter(
            "attn_selected_total", "Latent block: keys the live rows "
            "attended (the indexer's choice, at most index_topk a row), "
            "summed over rows, layers and decode steps")
        self.c_counts["attn_context"] = registry.counter(
            "attn_context_total", "Latent block: keys the live rows had in "
            "context, summed over rows, layers and decode steps (every one "
            "is scored by the indexer)")
        self.c_counts["attn_index_read"] = registry.counter(
            "attn_index_read_total", "Block that attends chosen blocks of "
            "keys: keys whose stripes the choice of blocks READ, summed "
            "over layers and decode steps: attn_context_total where a "
            "kernel walks the live rows' pages of the compressed-key "
            "array (index_backend pallas), slots x page-table bucket a "
            "layer and step under XLA's gather")
        self.c_counts["ssm_row_steps"] = registry.counter(
            "ssm_row_steps_total", "Block with recurrent layers: (decode "
            "step, live row) pairs, summed on the device: the rows whose "
            "recurrent state a step had to read and write (over steps x "
            "max_num_seqs: the share of the state arrays in use)")
        self.c_counts["loop_passes"] = registry.counter(
            "loop_passes_total", "Looped stack: passes over the layers "
            "that live rows took in decode steps, counted in the window "
            "program where the passes run (over loop_row_steps_total: the "
            "passes a token took)")
        self.c_counts["loop_row_steps"] = registry.counter(
            "loop_row_steps_total", "Looped stack: (decode step, live "
            "row) pairs, summed on the device")
        self.g_loop = registry.gauge(
            "perf_loop_info", "1 under the labels of a looped stack: "
            "passes a token takes over the layers, pool_layers (the (pass, "
            "layer) pairs a token leaves K and V in) and kv_token_bytes; "
            "no sample for a block whose layers run once",
            ["passes", "pool_layers", "kv_token_bytes"])
        self.g_ssm_state = registry.gauge(
            "perf_ssm_state_info", "1 under the labels of what a row (a "
            "slot) of this worker keeps beside its pages over all recurrent "
            "layers: bytes_per_row (the state and the convolution's last "
            "inputs), dtype (the state's) and parallel (1 where a layer's "
            "recurrent mixer and its attention layer read ONE normed input "
            "side by side); no sample for a block whose whole per-request "
            "state is pages", ["bytes_per_row", "dtype", "parallel"])
        self.g_kv_entry = registry.gauge(
            "perf_kv_entry_info", "1 under the labels of what a token "
            "holds in this worker's KV pool over all layers: kind "
            "(kv: K and V heads; latent: a latent entry and an index key) "
            "and bytes (config.kv_token_bytes, lane padding included)",
            ["kind", "bytes"])
        for bound in (self.g_step_seconds, self.g_achieved, self.g_roofline,
                      self.g_hbm_in_use, self.g_hbm_peak, self.g_hbm_limit):
            bound.ensure()

    def _delta(self, bound, key: tuple, current: float, **labels) -> None:
        prev = self._last.get(key, 0.0)
        if current > prev:
            bound.inc(current - prev, **labels)
        self._last[key] = current

    def update(self, engine, force: bool = False) -> None:
        """``engine`` duck-types TPUEngine: needs ``.runner.hbm_stats``
        (optional). Throttled; safe from the engine thread."""
        now = time.monotonic()
        if not force and now < self._next:
            return
        self._next = now + self.min_interval_s
        reg = get_registry()
        with reg._lock:
            per_prog = [(p.name, p.compiles, p.compile_seconds, p.unexpected,
                         p.cache_loads, p.cache_misses,
                         dict(p.first_call_seconds), dict(p.store))
                        for p in reg._programs.values()]
        for (name, compiles, seconds, unexpected, loads, misses,
             first, store) in per_prog:
            self._delta(self.c_compiles, ("c", name), compiles, program=name)
            self._delta(self.c_compile_seconds, ("s", name), seconds,
                        program=name)
            self._delta(self.c_unexpected, ("u", name), unexpected,
                        program=name)
            self._delta(self.c_cache_loads, ("ch", name), loads,
                        program=name, result="hit")
            self._delta(self.c_cache_loads, ("cm", name), misses,
                        program=name, result="miss")
            for part, value in first.items():
                self._delta(self.c_first_call, ("fc", name, part), value,
                            program=name, part=part.removesuffix("_s"))
            for result, n in store.items():
                self._delta(self.c_program_store, ("ps", name, result), n,
                            program=name, result=result)
        start = tracing.last_startup()
        if (start is not None and not start.open
                and start is not self._startup_told):
            self._startup_told = start
            told = start.summary()
            for stage in told["stages"]:
                self.g_startup.set(stage["seconds"], stage=stage["name"])
            self.g_startup.set(told["ready_s"], stage="startup")
            self.g_startup.set(told["unattributed_s"], stage="unattributed")
        clock = getattr(engine, "phase_clock", None)
        if clock is not None:
            for name, seconds in clock.totals().items():
                self._delta(self.c_phase_seconds, ("ph", name), seconds,
                            phase=name)
        self.g_step_seconds.set(reg.step_seconds)
        self.g_achieved.set(reg.achieved_tok_s)
        self.g_roofline.set(reg.roofline_frac)
        runner = getattr(engine, "runner", None)
        hbm = runner.hbm_stats() if runner is not None and hasattr(
            runner, "hbm_stats") else {}
        # The window program's labels (engine/backends.py), one info
        # series each.
        backends = getattr(runner, "backends", None)
        window = backends.labels("decode_window") if backends else {}
        for gauge, key, label in (
                (self.g_kv_commit, "kv_commit_backend", "backend"),
                (self.g_attention, "attention_backend", "backend"),
                (self.g_index, "index_backend", "backend"),
                (self.g_kv_page, "page_size", "tokens"),
                (self.g_draft, "draft", "kind")):
            if window.get(key):
                gauge.set(1, **{label: str(window[key])})
        config = getattr(engine, "config", None)
        if config is not None and hasattr(config, "kv_token_bytes"):
            latent = config.model.latent
            self.g_kv_entry.set(1, kind="latent" if latent else "kv",
                                bytes=str(config.kv_token_bytes()))
        if hbm:
            self.g_hbm_in_use.set(hbm.get("bytes_in_use", 0))
            self.g_hbm_peak.set(hbm.get("peak_bytes_in_use", 0))
            self.g_hbm_limit.set(hbm.get("bytes_limit", 0))
        recurrent = getattr(getattr(runner, "spec", None), "recurrent", False)
        if recurrent:
            self.g_ssm_state.set(
                1, bytes_per_row=str(runner.spec.ssm_state_bytes_per_row),
                dtype=str(runner.ssm_state.dtype),
                parallel=str(int(bool(runner.spec.parallel_mixers))))
        spec = getattr(runner, "spec", None)
        if getattr(spec, "loop_passes", 1) > 1 and config is not None:
            self.g_loop.set(1, passes=str(spec.loop_passes),
                            pool_layers=str(spec.pool_layers),
                            kv_token_bytes=str(config.kv_token_bytes()))
        totals = getattr(engine, "counts_total", None) or {}
        for columns in flight.COUNTS.values():
            for column, metric in columns:
                if metric:
                    self._delta(self.c_counts[column], (column,),
                                totals.get(column, 0.0))
        for program, kinds in reg.label_values("expert_product").items():
            for kind in kinds:
                self.g_expert_product.set(1, program=program, kind=kind)
        self._delta(self.c_moe_grouped_pairs, ("moe_g",),
                    float(getattr(runner, "moe_grouped_pairs", 0)))
        if totals.get("moe_picks"):     # an expert layer told its share
            spec = engine.runner.spec
            for kind, n in (("routed", spec.router_width),
                            ("held", spec.num_experts),
                            ("shared", spec.num_shared_experts)):
                self.g_moe_experts.set(n, kind=kind)
        if getattr(engine, "spec_emit_hist", None):
            self._delta(self.c_spec_draft_tokens, ("spec_dt",),
                        engine.spec_tokens)
            self._delta(self.c_spec_accepted_tokens, ("spec_at",),
                        engine.spec_accepted)
            self._delta(self.c_spec_brownout, ("spec_bw",),
                        engine.spec_brownout_windows)
            for e, n in enumerate(engine.spec_emit_hist):
                self._delta(self.c_spec_verify_steps, ("spec_eh", e), n,
                            emitted=str(e))
            if engine.spec_tokens:
                self.g_spec_acceptance.set(
                    engine.spec_accepted / engine.spec_tokens)
