"""One clock for host and device (PR 25): the engine thread's phases
(runtime/tracing.py PhaseClock), host time per window in the flight ring,
one ``engine.decode`` span a request, the ``http.admit_wait`` span, and the
compile registry's ``ops_by_scope`` (docs/OBSERVABILITY.md "Engine phases",
"Program scopes")."""

import asyncio
import gc
import time
import tracemalloc
from types import SimpleNamespace

import aiohttp
import numpy as np
import pytest
from conftest import async_test

from dynamo_tpu.engine import perf
from dynamo_tpu.runtime import flight, tracing
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.tracing import ENGINE_PHASES, PhaseClock, get_recorder


# -- the clock -----------------------------------------------------------------

def test_phase_clock_self_time_nests_and_adds_up_to_wall_time():
    clock = PhaseClock()
    clock.restart()
    t0 = time.monotonic()
    with clock.phase("engine.process_window"):
        time.sleep(0.02)
        with clock.phase("engine.readback_wait"):
            time.sleep(0.03)
        time.sleep(0.01)
    time.sleep(0.01)  # in no phase: engine.other
    with clock.phase("engine.idle"):
        time.sleep(0.01)
    now = time.monotonic()
    clock.sync(now)
    got = clock.totals()
    assert set(got) == set(ENGINE_PHASES)
    # The inner phase suspended the outer one's clock.
    assert 0.03 <= got["engine.readback_wait"] < 0.05
    assert 0.03 <= got["engine.process_window"] < 0.05
    assert got["engine.other"] >= 0.01
    assert clock.waited() == (got["engine.readback_wait"],
                              got["engine.idle"])
    assert clock.total() == pytest.approx(now - t0, abs=2e-3)
    assert tracing.engine_phase_totals()["engine.idle"] >= got["engine.idle"]


def test_phase_clock_restores_the_outer_phase_when_the_body_raises():
    clock = PhaseClock()
    with pytest.raises(KeyError):
        with clock.phase("engine.admit"):
            with clock.phase("engine.kvbm"):
                raise KeyError("x")
    assert clock._depth == 0 and clock._current == len(ENGINE_PHASES) - 1


def _retained_in(filename: str, loop, rounds: int = 3) -> int:
    """Bytes ``loop`` leaves allocated in ``filename`` (tests/test_slo.py's
    discipline: one clean steady-state round within three). The warm-up
    runs under tracemalloc too: a float or int the loop REPLACES in a
    preallocated list is then seen both made and freed."""
    grown = None
    for _ in range(rounds):
        tracemalloc.start()
        try:
            loop(300)   # warm-up: method caches, frames, ints past 256
            before = tracemalloc.take_snapshot()
            loop(5000)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                    if filename in (s.traceback[0].filename or ""))
        if grown <= 0:
            break
    return grown


def test_phase_retains_no_allocation_and_costs_microseconds():
    """No profiler session: a phase is two clock reads and list stores. The
    engine loop enters about thirteen an iteration (3.4 iterations a second
    under load, 500 when idle)."""
    clock = PhaseClock()

    def iteration(n):
        for _ in range(n):
            for name in ENGINE_PHASES[:-1]:
                with clock.phase(name):
                    pass

    assert _retained_in("tracing.py", iteration) <= 0
    t0 = time.perf_counter()
    iteration(2000)
    per_iteration_us = (time.perf_counter() - t0) / 2000 * 1e6
    # Measured 9 us here; the bound leaves room for a loaded machine.
    assert per_iteration_us < 100, per_iteration_us


# -- the flight ring's new columns -------------------------------------------------

def _row(rec, t, **kw):
    return rec.record(t, 0.3, 4, 1, 100, 0, 0, 0, 0, 0.0, int(t), 32, **kw)


def test_flight_between_returns_columns_and_counts_what_it_lacks():
    rec = flight.FlightRecorder(capacity=8)
    for i in range(5):
        _row(rec, 10.0 + i, period_s=0.29 if i else 0.0, host_s=0.02,
             wait_s=0.25, idle_s=0.0, rows=17, page_bucket=128)
    got = rec.between(11.0, 13.0)
    assert got["rows"] == 3 and got["missed"] == 0
    cols = got["columns"]
    assert set(cols) == set(flight.FIELDS)
    assert cols["t_mono"].tolist() == [11.0, 12.0, 13.0]
    assert cols["period_s"].tolist() == [0.29] * 3
    assert cols["page_bucket"].tolist() == [128.0] * 3
    assert rec.dump()[-1]["rows"] == 17  # ints stay ints in the dump
    # A frozen ring refuses rows; the reader learns how many.
    rec.freeze("bundle")
    assert _row(rec, 15.0) is False and _row(rec, 16.0) is False
    assert rec.between(10.0, 20.0)["missed"] == 2
    rec.thaw()
    _row(rec, 17.0)
    late = rec.between(16.5, 20.0)
    assert late["rows"] == 1 and late["missed"] == 2
    assert rec.between(10.0, 14.5)["missed"] == 0
    # The ring turned over inside the span: at least one row is gone.
    for i in range(8):
        _row(rec, 20.0 + i)
    assert rec.between(10.0, 30.0)["missed"] >= 1
    assert rec.between(21.0, 30.0)["missed"] == 0


def test_default_ring_outlasts_a_benchmark_read_at_the_bandwidth_floor():
    """The benchmark's readers run after the window AND the drain (51 s +
    30 s + the client's wait, about 100 s after the ramp's first row); the
    ring must still hold the window's first row then, also once a window
    is as short as the chip's bandwidth allows (8 steps of 9.7 ms for the
    7B cell, PERF.md 5.1) AND at the next halving of the shortest window a
    cell has today (SmallThinker's 4 steps, 62.5 ms, so 40 ms and under):
    300 s of them, in 35 columns of 8 bytes (2.3 MB: three columns came with
    the drafting window's counts, one with a recurrent block's live rows,
    one with the keys a choice of blocks read, two with a looped stack's
    passes and live rows)."""
    ring = flight.FlightRecorder()
    assert ring.capacity * 8 * 0.0097 >= 120.0
    assert ring.capacity * 0.040 >= 300.0
    assert len(flight.FIELDS) == 35
    assert sum(c.nbytes for c in ring._cols.values()) <= 2_300_000


def test_flight_record_with_the_new_columns_retains_nothing():
    rec = flight.FlightRecorder(capacity=64)

    def hot(n):
        for _ in range(n):
            rec.record(1.5, 0.01, 4, 1, 100, 32, 1, 0, 0, 0.0, 7, 64,
                       0.29, 0.02, 0.25, 0.0, 17, 128,
                       prefilling=2, admit_stop=5)

    assert _retained_in("flight.py", hot) <= 0
    last = rec.dump()[-1]
    assert last["prefilling"] == 2 and last["admit_stop"] == 5
    rec.freeze("x")
    assert _retained_in("flight.py", hot) <= 0  # the refusing path too


# -- what a window counted: one table, stored by name -----------------------------

@pytest.mark.parametrize("key, values, want", [
    # A told share's five land in the table's order, a plain router's three
    # in the first three; every other count column stays 0.
    ("moe", [11.0, 2.5, 6.0, 40.0, 72.0], {
        "moe_touched": 11.0, "moe_load": 2.5, "moe_layer_steps": 6.0,
        "moe_local_picks": 40.0, "moe_picks": 72.0}),
    ("moe", [11.0, 2.5, 6.0], {
        "moe_touched": 11.0, "moe_load": 2.5, "moe_layer_steps": 6.0}),
    ("attn", [80.0, 104.0], {"attn_selected": 80.0, "attn_context": 104.0}),
    ("ssm", [[3.0]], {"ssm_row_steps": 3.0}),
    ("spec", (5, 2, 7), {"spec_drafted": 5.0, "spec_accepted": 2.0,
                         "spec_row_steps": 7.0}),
    ("moe", [1.0] * 6, ValueError),     # more values than columns
    ("emit", [1.0], KeyError),          # no such key in the table
])
def test_a_window_s_counts_land_in_the_table_s_columns(key, values, want):
    rec = flight.FlightRecorder(capacity=4)
    if isinstance(want, type):
        with pytest.raises(want):
            flight.columns_of(key, values)
        return
    counts = flight.columns_of(key, values)
    assert counts == want and list(counts) == [
        column for column, _ in flight.COUNTS[key]][:len(counts)]
    assert _row(rec, 1.0, counts=counts) and _row(rec, 2.0)
    first, second = rec.dump()
    for column in flight.COUNT_COLUMNS:
        assert first[column] == want.get(column, 0.0), column
        assert second[column] == 0.0        # a row that names none
    # Columns outside the table are not counts: record takes them itself.
    assert not {"rows", "prefilling", "admit_stop"} & set(
        flight.COUNT_COLUMNS)
    with pytest.raises(KeyError, match="no count column named"):
        _row(rec, 3.0, counts={**counts, "moe_touchd": 1.0})
    with pytest.raises(TypeError):
        _row(rec, 3.0, moe_touched=1.0)     # the twelve keywords are gone
    assert len(rec.dump()) == 2             # a refused row stores nothing


def test_the_table_s_counters_are_the_exporter_s():
    """Every column the table gives a /metrics counter is exported under
    that name with that column's total; the engine's own ``spec`` sums have
    none (perf_spec_* follow the engine's accounting)."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    registry = MetricsRegistry()
    updater = perf.PerfMetricsUpdater(registry, min_interval_s=0.0)
    totals = {column: float(i + 1)
              for i, column in enumerate(flight.COUNT_COLUMNS)}
    spec = SimpleNamespace(router_width=16, num_experts=4,
                           num_shared_experts=1)
    updater.update(SimpleNamespace(counts_total=totals,
                                   runner=SimpleNamespace(spec=spec)),
                   force=True)
    assert updater.g_moe_experts.get(kind="held") == 4  # picks were counted
    text = registry.expose().decode()
    named = [(column, metric) for columns in flight.COUNTS.values()
             for column, metric in columns]
    assert [c for c, _ in named] == list(flight.COUNT_COLUMNS)
    assert set(updater.c_counts) == {c for c, m in named if m}
    assert {c for c, m in named if not m} == {
        "spec_drafted", "spec_accepted", "spec_row_steps"}
    for column, metric in named:
        if metric:
            line, = [ln for ln in text.splitlines()
                     if ln.startswith(f"dynamo_tpu_{metric}" + "{")
                     or ln.startswith(f"dynamo_tpu_{metric} ")]
            assert float(line.rsplit(" ", 1)[1]) == totals[column], line


# -- the engine: rows, spans, phases on the profiler's clock ---------------------------

def _tiny_engine(**kw):
    from test_engine import tiny_config
    from dynamo_tpu.engine.engine import TPUEngine
    return TPUEngine(tiny_config(**kw))


async def _generate(engine, n_tokens: int, prompt: int = 24) -> Context:
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    req = PreprocessedRequest(model="m", token_ids=list(range(prompt)))
    req.stop_conditions.max_tokens = n_tokens
    req.stop_conditions.ignore_eos = True
    ctx = Context()
    got = []
    async for out in engine.generate(req, ctx):
        got.extend(out.get("token_ids", []))
    assert len(got) == n_tokens
    return ctx


@async_test(timeout=240)
async def test_rows_add_up_to_wall_time_and_one_decode_span_a_request():
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    engine = _tiny_engine()
    try:
        window = engine.decode_window
        n = 5 * window + 1          # first token + five full windows
        t_lo = time.monotonic()
        ctx, _ = await asyncio.gather(_generate(engine, n),
                                      _generate(engine, 2 * window + 1))
        await asyncio.sleep(0.05)   # the loop's idle rows after the last
        got = ring.between(t_lo, time.monotonic())
        cols = got["columns"]
        assert got["rows"] >= 5 and got["missed"] <= 1, got
        # host + wait + idle between two rows IS the time between them.
        parts = cols["host_s"] + cols["wait_s"] + cols["idle_s"]
        wall = np.diff(cols["t_mono"])
        assert parts[1:].sum() == pytest.approx(wall.sum(), rel=0.03)
        assert (cols["host_s"] >= 0).all() and (cols["wait_s"] >= 0).all()
        assert set(cols["page_bucket"].astype(int)) <= {8, 16}
        assert cols["rows"].max() == 2
        # A window queued behind another is timed by its period, which is
        # no longer than its latency through the pipeline.
        full = cols["period_s"] > 0
        assert full.any()
        assert (cols["period_s"][full] <= cols["dur_s"][full] + 1e-3).all()
        # The registry's step clock follows the period, the counters the
        # benchmark reads keep counting windows and tokens.
        reg = perf.get_registry()
        assert reg.windows_total >= 5 and reg.window_tokens_total >= n - 1
        assert reg.window_seconds_total > 0 and reg.step_seconds > 0
        # One decode span for the request, whatever the number of windows.
        spans = [s for s in get_recorder().trace(ctx.trace_id)
                 if s.name == "engine.decode"]
        assert len(spans) == 1, [s.attrs for s in spans]
        attrs = spans[0].attrs
        assert attrs["tokens"] == n and attrs["windows"] == 5
        assert attrs["preemptions"] == 0
        assert spans[0].parent_span_id == ctx.span_id
        # The split an operator sees: /debug/perf and /metrics.
        phases = engine.perf_status()["phases"]
        assert set(phases) == set(ENGINE_PHASES)
        assert phases["engine.readback_wait"] > 0
        assert phases["engine.dispatch_window"] > 0
        assert sum(phases.values()) == pytest.approx(
            engine.phase_clock.total(), rel=0.05)
    finally:
        engine.stop()


@pytest.mark.parametrize("n_windows", [0, 2])
@async_test(timeout=240)
async def test_decode_span_is_recorded_before_the_finish_frame(n_windows):
    """A caller that reads the trace the moment its stream ends finds
    engine.decode there: the span goes in ahead of the finishing push, not
    in the slot's clean-up after it (slowed here so the race cannot hide)."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    engine = _tiny_engine()
    release = engine._release_adapter

    def slow_release(r):
        time.sleep(0.05)
        release(r)

    engine._release_adapter = slow_release
    try:
        req = PreprocessedRequest(model="m", token_ids=list(range(24)))
        req.stop_conditions.max_tokens = n_windows * engine.decode_window + 1
        req.stop_conditions.ignore_eos = True
        ctx = Context()
        at_finish = None
        async for out in engine.generate(req, ctx):
            if out.get("finish_reason") is not None:
                at_finish = [s.name for s in get_recorder().trace(ctx.trace_id)]
        assert at_finish is not None
        assert at_finish.count("engine.decode") == 1, at_finish
        await asyncio.sleep(0.2)    # the clean-up adds no second one
        names = [s.name for s in get_recorder().trace(ctx.trace_id)]
        assert names.count("engine.decode") == 1, names
    finally:
        engine.stop()


@async_test(timeout=240)
async def test_phases_are_on_the_profiler_trace_and_in_the_capture_reply(
        tmp_path, monkeypatch):
    """Under a profiler session every phase is a TraceAnnotation on the
    engine thread's line, which benchmark.lib.host_phases reduces; the
    /debug/profile capture's reply carries the same split and the rows.

    The capture ends on its rows, not on the clock: its one sleep returns
    when the ring holds three rows younger than the capture (a fixed 1.5 s
    was too short for three windows on a loaded machine)."""
    import types

    from benchmark.lib import host_phases, trace_reduce
    ring = flight.get_recorder()
    ring.thaw()
    begun, rows_in = asyncio.Event(), asyncio.Event()

    async def until_rows(_seconds):
        begun.set()
        await rows_in.wait()

    monkeypatch.setattr(tracing, "asyncio", types.SimpleNamespace(
        **{**vars(asyncio), "sleep": until_rows}))
    engine = _tiny_engine()
    try:
        await _generate(engine, 4)  # compile outside the capture
        t0 = time.monotonic()
        task = asyncio.ensure_future(
            tracing.capture_profile(60_000, str(tmp_path)))
        await begun.wait()
        await _generate(engine, 3 * engine.decode_window + 1)
        while ring.between(t0, time.monotonic())["rows"] < 3:
            await asyncio.sleep(0.01)
        rows_in.set()
        reply = await task
    finally:
        engine.stop()
    assert set(reply["engine_phase_seconds"]) == set(ENGINE_PHASES)
    assert reply["engine_phase_seconds"]["engine.readback_wait"] > 0
    assert reply["flight"]["rows"] >= 3
    assert len(reply["flight"]["columns"]["host_s"]) == reply["flight"]["rows"]
    if reply["mode"] != "jax":
        pytest.skip("no jax profiler in this build")
    trace = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    seen = {name for _, _, name in host_phases.phase_events(trace)}
    assert {"engine.admit", "engine.dispatch_window", "engine.readback_wait",
            "engine.process_window", "engine.publish"} <= seen, seen
    assert seen <= set(ENGINE_PHASES)
    by_phase = host_phases.seconds_by_phase(host_phases.phase_intervals(trace))
    assert by_phase["engine.readback_wait"] > 0
    # Self time: the intervals do not overlap.
    ivs = host_phases.phase_intervals(trace)
    assert all(a[1] <= b[0] + 1 for a, b in zip(ivs, ivs[1:]))


@async_test(timeout=60)
async def test_capture_holds_the_clocks_it_differences(tmp_path, monkeypatch):
    """The capture's split is a difference of the process's phase totals
    at its two ends: it holds the clocks meanwhile, so an engine that is
    collected during the capture cannot read as negative seconds."""
    import types
    begun, go_on = asyncio.Event(), asyncio.Event()

    async def until_told(_seconds):
        begun.set()
        await go_on.wait()

    monkeypatch.setattr(tracing, "asyncio", types.SimpleNamespace(
        **{**vars(asyncio), "sleep": until_told}))
    doomed = PhaseClock()
    with doomed.phase("engine.admit"):
        time.sleep(0.02)
    task = asyncio.ensure_future(
        tracing.capture_profile(60_000, str(tmp_path)))
    await begun.wait()
    with doomed.phase("engine.publish"):
        time.sleep(0.01)
    del doomed          # the engine stops and is collected mid-capture
    gc.collect()
    go_on.set()
    reply = await task
    seconds = reply["engine_phase_seconds"]
    assert min(seconds.values()) >= 0.0, seconds
    assert seconds["engine.publish"] >= 0.009


# -- every slot has a state in every window; the engine says why it stops ----------

@async_test(timeout=240)
async def test_rows_and_prefilling_are_one_instant_and_never_pass_the_slots():
    """``rows`` and ``prefilling`` are both taken as the window is
    dispatched: with the empty slots they add up to max_num_seqs in every
    row, and a long prompt in chunked prefill beside two live decoders
    reads prefilling 1, rows 2."""
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    engine = _tiny_engine(prefill_chunk_tokens=32)
    slots = engine.config.max_num_seqs
    try:
        window = engine.decode_window
        t_lo = time.monotonic()
        # 28 windows each (the toy's context holds no more): the long
        # prompt arrives with up to pipeline_depth of them dispatched.
        decoders = [asyncio.ensure_future(_generate(engine, 28 * window + 1))
                    for _ in range(2)]
        while sum(r is not None and not r.prefilling
                  for r in engine.slot_req) < 2:
            await asyncio.sleep(0.005)
        await _generate(engine, window + 1, prompt=150)   # five chunks
        await asyncio.gather(*decoders)
        cols = ring.between(t_lo, time.monotonic())["columns"]
        rows = cols["rows"].astype(int)
        prefilling = cols["prefilling"].astype(int)
        assert len(rows) >= 12
        assert ((rows + prefilling) <= slots).all()
        assert (prefilling >= 0).all()
        # From the first window that holds both decoders and nothing else
        # (before it, one of them may hold its slot and await its first
        # token): only the long prompt is ever without a row.
        steady = np.flatnonzero((rows == 2) & (prefilling == 0))[0]
        rows, prefilling = rows[steady:], prefilling[steady:]
        assert prefilling.max() == 1
        assert set(rows[prefilling == 1]) == {2}
        assert (prefilling == 1).sum() >= 2      # a chunk a window
        assert rows.max() == 3                   # then it decodes beside them
        assert (cols["admit_stop"] == 0).all()   # nobody was turned away
        assert engine.admit_stops == {"no_slot": 0, "no_pages": 0,
                                      "ttft_budget": 0}
    finally:
        engine.stop()


@pytest.mark.parametrize("cause,config", [
    ("no_slot", dict(max_num_seqs=2)),
    ("no_pages", dict(num_pages=9, max_num_seqs=4)),
    ("ttft_budget", dict(ttft_budget_ms=1.0, max_num_seqs=4)),
])
@async_test(timeout=240)
async def test_each_admit_stop_sets_its_bit_and_its_counter(cause, config):
    """A full batch, a pool too small for the queue's head, and the TTFT
    budget each leave requests queued: the pass that ends so counts it
    under its cause, and the next flight row carries the cause's bit."""
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from test_engine import tiny_config
    ring = flight.get_recorder()
    ring.thaw()
    ring.clear()
    reg = MetricsRegistry()
    engine = TPUEngine(tiny_config(**config), metrics_registry=reg)
    if cause == "ttft_budget":
        engine.prefill_rate_tok_s = 1.0   # the gate needs a measured rate
    try:
        window = engine.decode_window
        t_lo = time.monotonic()
        # Four callers of 40-token prompts (3 pages each, 4 with their
        # output): two slots, or eight usable pages, hold two of them.
        await asyncio.gather(*(
            _generate(engine, 3 * window + 1, prompt=40) for _ in range(4)))
        others = set(flight.ADMIT_STOPS) - {cause}
        assert engine.admit_stops[cause] >= 1, engine.admit_stops
        assert all(engine.admit_stops[c] == 0 for c in others), \
            engine.admit_stops
        cols = ring.between(t_lo, time.monotonic())["columns"]
        bits = set(cols["admit_stop"].astype(int)) - {0}
        assert bits == {flight.ADMIT_STOPS[cause]}, bits
        expo = reg.expose().decode()
        for name in flight.ADMIT_STOPS:
            (line,) = [ln for ln in expo.splitlines() if ln.startswith(
                "dynamo_tpu_engine_admit_stops_total{")
                and f'cause="{name}"' in ln]
            assert float(line.rsplit(" ", 1)[1]) \
                == engine.admit_stops[name], line
    finally:
        engine.stop()


# -- scopes ------------------------------------------------------------------------

HLO = """HloModule jit_run_window

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %g = f32[8]{0} gather(%p0), metadata={op_name="jit(run_window)/while/body/attn.core/attn.kv_gather/gather"}
  ROOT %d = f32[8]{0} dot(%g, %g), metadata={op_name="jit(run_window)/while/body/attn.core/dot_general"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %s = f32[8]{0} scatter(%p0.1), metadata={op_name="jit(run_window)/kv.commit/scatter"}
}

ENTRY %main (pool: f32[8]) -> f32[8] {
  %pool = f32[8]{0} parameter(0), metadata={op_name="k_cache"}
  %copy.1 = f32[8]{0} copy(%pool), metadata={op_name="k_cache"}
  %fusion.7 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(run_window)/kv.commit/scatter"}
  %copy.2 = f32[8]{0} copy(%fusion.7)
  %fusion.9 = f32[8]{0} fusion(%copy.2), kind=kOutput, calls=%fused_computation.1
  %add.3 = f32[8]{0} add(%fusion.9, %fusion.9), metadata={op_name="jit(run_window)/mlp/add"}
  %copy.4 = f32[8]{0} copy(%add.3)
  ROOT %neg = f32[8]{0} negate(%copy.4), metadata={op_name="jit(run_window)/neg"}
}
"""


def test_scopes_of_hlo_fusions_join_and_compiler_copies_inherit():
    got = perf.scopes_of_hlo(HLO)
    assert got["%fusion.7"] == "kv.commit"
    # A fusion takes the scopes of what was fused into it, in SCOPES order.
    assert got["%fusion.9"] == "attn.kv_gather+attn.core"
    assert got["%g"] == "attn.kv_gather"        # the innermost scope
    # The compiler's copies: the scope of their reader, else of their source.
    assert got["%copy.1"] == "kv.commit"
    assert got["%copy.2"] == "attn.kv_gather+attn.core"
    assert got["%copy.4"] == "mlp"              # read by an unscoped op
    assert got["%add.3"] == "mlp" and got["%neg"] is None
    assert got["%pool"] is None


@async_test(timeout=240)
async def test_ops_by_scope_names_every_scope_of_the_window_program():
    """The CPU-compiled window and prefill programs: every scope of the
    vocabulary labels some instruction of the executable that ran."""
    # The registry answers with the live wrapper called most often: an
    # engine of an earlier test of this process (a routed block's, with its
    # sub-scopes) lives on until its cycles are collected.
    import gc
    gc.collect()
    engine = _tiny_engine()
    try:
        await _generate(engine, engine.decode_window + 1)
        reg = perf.get_registry()
        window = reg.ops_by_scope("decode_window")
        prefill = reg.ops_by_scope("prefill")
        key = (engine.decode_window, 8, False, False)
        assert reg.ops_by_scope("decode_window", key) is not None
        assert reg.ops_by_scope("decode_window", ("no", "such")) is None
        assert reg.ops_by_scope("no_such_program") is None
    finally:
        engine.stop()
    for name, ops in (("decode_window", window), ("prefill", prefill)):
        assert ops and all(k.startswith("%") for k in ops), name
        seen = {part for v in ops.values() if v for part in v.split("+")}
        want = set(perf.SCOPES)
        if name == "prefill":
            want -= {"attn.kv_gather"}  # a first chunk reads no history
        assert want <= seen, (name, want - seen)
        assert seen <= set(perf.SCOPES)


def test_note_window_steps_by_period_and_sums_latency():
    reg = perf.CompileRegistry()
    reg.note_window(0.3, 64, 8, 8, 10.0, latency_s=1.2)
    assert reg.step_seconds == pytest.approx(0.3 / 8)
    assert reg.achieved_tok_s == pytest.approx(64 / 0.3)
    assert reg.window_seconds_total == pytest.approx(1.2)
    assert (reg.windows_total, reg.window_tokens_total) == (1, 64)
    reg.note_window(0.3, 64, 8, 8, 10.0, latency_s=0.3)  # no pipeline
    assert reg.window_seconds_total == pytest.approx(1.5)


# -- http.admit_wait -----------------------------------------------------------------

@async_test(timeout=120)
async def test_admit_wait_span_shares_the_trace_and_covers_a_forced_wait():
    from test_overload import (start_frontend, start_mocker, wait_model)
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.logging import make_traceparent
    from dynamo_tpu.runtime.overload import OverloadConfig

    coord = Coordinator()
    await coord.start()
    overload = OverloadConfig(
        seed=3, initial_concurrency=1, max_concurrency=1, min_concurrency=1,
        queue_depth=4, default_deadline_ms=20_000, target_latency_ms=60_000)
    mocker = await start_mocker(coord, max_num_seqs=4)
    rt, manager, watcher, service = await start_frontend(coord,
                                                         overload=overload)
    rec = get_recorder()
    traces = [f"{i:032x}" for i in (0xabc1, 0xabc2)]
    try:
        await wait_model(manager)

        async def post(session, trace_id):
            async with session.post(
                    f"http://127.0.0.1:{service.port}/v1/chat/completions",
                    headers={"traceparent": make_traceparent(
                        trace_id, "feedfacecafebeef")},
                    json={"model": "mock-model", "max_tokens": 48,
                          "messages": [{"role": "user",
                                        "content": "wait for me"}]}) as resp:
                assert resp.status == 200
                await resp.json()

        async with aiohttp.ClientSession() as session:
            await asyncio.gather(*(post(session, t) for t in traces))
        by_trace = {}
        for trace_id in traces:
            spans = {s.name: s for s in rec.trace(trace_id)}
            assert {"http.admit_wait", "http.request"} <= set(spans), spans
            wait, req = spans["http.admit_wait"], spans["http.request"]
            # Siblings under the caller's span; the wait ends as the
            # request's own span opens.
            assert wait.parent_span_id == req.parent_span_id \
                == "feedfacecafebeef"
            assert wait.end_mono <= req.start_mono + 0.05
            assert wait.attrs["outcome"] == "granted"
            assert wait.attrs["limit"] == 1
            assert wait.attrs["priority"] == "interactive"
            by_trace[trace_id] = (wait, req)
        # One permit: one of the two waited for the other's whole request.
        (w1, r1), (w2, r2) = by_trace.values()
        waited, held = (w1, r2) if w1.duration_s > w2.duration_s else (w2, r1)
        assert waited.duration_s >= 0.8 * held.duration_s > 0
        assert waited.attrs["waiting"] == 0 and min(
            w1.duration_s, w2.duration_s) < 0.05
        # A caller that leaves while it queues is "cancelled", not "shed":
        # the shed waits are the limiter's decisions alone.
        from aiohttp.test_utils import make_mocked_request
        held = await service.overload.admit()
        ctx = Context(trace_id=f"{0xabc3:032x}")
        queued = asyncio.ensure_future(service._admit(
            make_mocked_request("POST", "/v1/chat/completions"), "chat",
            ctx=ctx))
        await asyncio.sleep(0.05)
        queued.cancel()
        with pytest.raises(asyncio.CancelledError):
            await queued
        with held:
            pass
        (left,) = [s for s in rec.trace(ctx.trace_id)
                   if s.name == "http.admit_wait"]
        assert left.attrs["outcome"] == "cancelled" and left.status == "error"
        assert left.duration_s >= 0.04
    finally:
        await service.stop()
        await watcher.stop()
        mrt, engine, server = mocker
        await engine.stop()
        await server.shutdown()
        await mrt.close()
        await rt.close()
        await coord.stop()


def test_span_recorder_snapshot_is_public_and_says_what_was_dropped():
    rec = tracing.SpanRecorder(capacity=2)
    for i in range(3):
        rec.add("engine.decode", "t" * 32, None, float(i), float(i) + 1.0)
    spans, dropped = rec.snapshot()
    assert [s.start_mono for s in spans] == [1.0, 2.0] and dropped == 1
