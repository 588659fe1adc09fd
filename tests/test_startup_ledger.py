"""A worker's start, accounted from inside (docs/OBSERVABILITY.md "Start-up"):
one trace a start with a span a stage (runtime/tracing.py ``Startup``), one
record a compiled program's first call in the compile registry
(engine/perf.py), ``/debug/perf`` ``startup`` and the three ``/metrics``
families, the one log line at ready, and a start that fails loudly.

One tiny engine is started through ``launch.run`` ONCE (module fixture) and
what it left is looked at from many sides; the unit tests beside it build
nothing larger than ``x * 2``. Times here are CPU times and go nowhere.
"""

import asyncio
import logging
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from conftest import TINY_LAUNCH as LAUNCH
from conftest import async_test
from conftest import launched as _launched

from dynamo_tpu.engine import perf
from dynamo_tpu.engine.perf import (FIRST_CALL_PARTS, CompileRegistry,
                                    instrumented_jit)
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.tracing import SpanRecorder, Startup, covered_seconds

async def _get(session, service, path, json_body=True):
    async with session.get(
            f"http://127.0.0.1:{service.port}{path}") as resp:
        assert resp.status == 200, (path, resp.status)
        return await (resp.json() if json_body else resp.text())


@pytest.fixture(scope="module")
def started(caplog_lines, tmp_path_factory):
    """One start with the prefill ladder, then ONE long request (its page
    bucket was not warmed: a program drawn lazily), and what both left. The
    start is COLD: its compile cache, and so its program store, is an empty
    directory of this module's own (tests/test_program_store.py has the warm
    one)."""
    import aiohttp

    from dynamo_tpu.llm.protocols import PreprocessedRequest
    registry = perf.get_registry()
    before = len(registry.first_calls)
    compiles_before = registry.compiles_total

    async def inside(runtime, service, engine):
        out = {"t_ready": time.monotonic(),
               "start": tracing.last_startup(),
               "at_ready": list(registry.first_calls[before:])}
        async with aiohttp.ClientSession() as session:
            out["perf"] = await _get(session, service, "/debug/perf")
            out["metrics"] = await _get(session, service, "/metrics", False)
            trace_id = out["perf"]["startup"]["trace_id"]
            out["chrome"] = await _get(
                session, service, f"/debug/traces?trace_id={trace_id}")
            out["spans"] = (await _get(
                session, service,
                f"/debug/traces?trace_id={trace_id}&format=spans"))["spans"]
            # 140 tokens are nine pages: past the smallest page bucket.
            req = PreprocessedRequest(model="m", token_ids=list(range(140)))
            req.stop_conditions.max_tokens = 3
            req.stop_conditions.ignore_eos = True
            async for _ in engine.generate(req, Context()):
                pass
            out["perf_after"] = await _get(session, service, "/debug/perf")
        return out

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("JAX_COMPILATION_CACHE_DIR",
                     str(tmp_path_factory.mktemp("compile_cache")))
        out = asyncio.run(asyncio.wait_for(_launched(
            [*LAUNCH, "--warmup-prefill-ladder"], inside), 600))
    out["records"] = list(registry.first_calls[before:])
    out["compiles"] = registry.compiles_total - compiles_before
    out["log"] = list(caplog_lines)
    return out


@pytest.fixture(scope="module")
def caplog_lines():
    """The launcher's log lines (caplog is function-scoped)."""
    lines: list[str] = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Keep(level=logging.INFO)
    logger = logging.getLogger("dynamo_tpu.launch")
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    yield lines
    logger.removeHandler(handler)
    logger.setLevel(level)


# -- one trace a start ---------------------------------------------------------

def test_a_start_is_one_trace_whose_stages_hang_on_the_root(started):
    spans = started["spans"]
    roots = [s for s in spans if s["parent_span_id"] is None]
    assert [s["name"] for s in roots] == ["startup"]
    root = roots[0]
    assert root["status"] == "ok"
    by_id = {s["span_id"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert {"startup.runtime", "startup.config", "startup.tokenizer",
            "startup.engine", "startup.mesh", "startup.pool_sizing",
            "startup.weights", "startup.pool_alloc", "startup.warmup",
            "startup.prefill_ladder", "startup.wait_ready",
            "startup.model_card", "startup.observability",
            "startup.http"} <= names
    parent = {s["name"]: by_id[s["parent_span_id"]]["name"]
              for s in spans if s["parent_span_id"]}
    assert parent["startup.engine"] == "startup"
    assert parent["startup.warmup"] == "startup"       # the engine thread's
    assert parent["startup.wait_ready"] == "startup"
    assert parent["startup.http"] == "startup"
    for name in ("mesh", "pool_sizing", "weights", "pool_alloc"):
        assert parent[f"startup.{name}"] == "startup.engine"
    assert parent["startup.prefill_ladder"] == "startup.warmup"
    # Every stage lies inside the root.
    lo, hi = root["start_mono"], root["start_mono"] + root["duration_s"]
    for s in spans:
        assert lo - 1e-6 <= s["start_mono"]
        assert s["start_mono"] + s["duration_s"] <= hi + 1e-6, s["name"]


def test_the_roots_children_cover_it_and_do_not_overlap_on_one_thread(
        started):
    start = started["start"]
    root = start.root
    direct = [s for s in start.spans if s.parent_span_id == root.span_id]
    covered = covered_seconds((s.start_mono, s.end_mono) for s in direct)
    assert covered >= 0.95 * root.duration_s
    by_thread: dict = {}
    for s in direct:
        by_thread.setdefault(s.thread_id, []).append(s)
    assert len(by_thread) == 2      # the launcher's and the engine's
    for spans in by_thread.values():
        spans.sort(key=lambda s: s.start_mono)
        for a, b in zip(spans, spans[1:]):
            assert a.end_mono <= b.start_mono + 1e-6, (a.name, b.name)
    # The wait lies beside the warm-up it waits for.
    told = {s.name: s for s in direct}
    assert told["startup.wait_ready"].thread_id \
        != told["startup.warmup"].thread_id


def test_stage_attributes_say_where_the_weights_came_from_and_how_large(
        started):
    stages = {s["name"]: s for s in started["perf"]["startup"]["stages"]}
    weights = stages["startup.weights"]["attrs"]
    assert weights["source"] == "random" and weights["bytes"] > 0
    assert stages["startup.pool_alloc"]["attrs"]["bytes"] > 0
    assert stages["startup.pool_sizing"]["attrs"]["num_pages"] == 64


def test_debug_perf_startup_has_stages_self_times_and_the_unattributed_rest(
        started):
    body = started["perf"]["startup"]
    assert body["status"] == "ok" and body["failed_stage"] is None
    stages = body["stages"]
    assert [s["at_s"] for s in stages] == sorted(s["at_s"] for s in stages)
    by_name = {s["name"]: s for s in stages}
    engine = by_name["startup.engine"]
    inner = sum(by_name[f"startup.{n}"]["seconds"]
                for n in ("mesh", "pool_sizing", "weights", "pool_alloc"))
    assert engine["self_s"] == pytest.approx(engine["seconds"] - inner,
                                             abs=2e-3)
    assert by_name["startup.warmup"]["self_s"] == pytest.approx(
        by_name["startup.warmup"]["seconds"]
        - by_name["startup.prefill_ladder"]["seconds"], abs=2e-3)
    assert 0.0 <= body["unattributed_s"] < 0.05 * body["ready_s"]
    # An engine's own body carries the same.
    worker = next(iter(started["perf"]["engines"].values()))
    assert worker["startup"]["trace_id"] == body["trace_id"]


def test_debug_traces_shows_the_start_as_a_flame_chart(started):
    events = started["chrome"]["traceEvents"]
    names = [e["name"] for e in events]
    assert names[0] == "startup" and "startup.warmup" in names
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    # Two threads: the launcher's and the engine's.
    assert len({e["tid"] for e in events}) == 2


# -- one record a program's first call ----------------------------------------------

def test_one_first_call_record_a_program_that_was_called(started):
    at_ready = started["at_ready"]
    assert at_ready and all(r["when"] == "startup" for r in at_ready)
    families = {r["program"] for r in at_ready}
    assert {"decode_window", "prefill"} <= families
    keys = [(r["program"], r["key"]) for r in at_ready if r["builds"]]
    # A wrapper is one (program, key): its FIRST call is one record (a key
    # may show twice only where warm-up rebuilt it, as the penalized
    # window under a mesh does).
    assert len(set(keys)) >= len(keys) - 2
    body = started["perf"]["startup"]["first_calls"]
    assert body["programs"] == len(at_ready)
    assert set(body["families"]) == families
    assert sum(f["programs"] for f in body["families"].values()) \
        == body["programs"]
    assert len(body["longest"]) == min(10, len(at_ready))
    walls = [r["wall_s"] for r in body["longest"]]
    assert walls == sorted(walls, reverse=True)


def test_a_records_parts_fit_in_its_wall_time(started):
    for r in started["records"]:
        parts = (r["trace_s"] + r["lower_s"] + r["cache_load_s"]
                 + r["compile_s"])
        assert min(r[p] for p in FIRST_CALL_PARTS) >= 0.0
        assert r["wall_s"] + 1e-3 >= parts, r
        assert r["cache"] in ("hit", "miss", "off")


def test_startup_records_lie_inside_the_root_and_sum_below_ready(started):
    root = started["start"].root
    at_ready = started["at_ready"]
    for r in at_ready:
        assert root.start_mono <= r["t_mono"]
        assert r["t_mono"] + r["wall_s"] <= root.end_mono + 1e-6
    assert sum(r["wall_s"] for r in at_ready) <= root.duration_s


def test_a_bucket_drawn_after_ready_reads_serving_and_is_a_span(started):
    late = [r for r in started["records"] if r["when"] == "serving"]
    assert late, "the long request drew no new program"
    assert all(r["t_mono"] >= started["t_ready"] - 1.0 for r in late)
    assert "decode_window" in {r["program"] for r in late}
    after = started["perf_after"]["startup"]
    assert after["first_calls_serving"]["programs"] == len(late)
    # The start's own table does not move once the engine is ready.
    assert after["first_calls"]["programs"] \
        == started["perf"]["startup"]["first_calls"]["programs"]
    spans = [s for s in tracing.get_recorder().snapshot()[0]
             if s.name == "program.first_call"]
    told = {(s.attrs["program"], s.attrs["key"]) for s in spans}
    assert {(r["program"], repr(r["key"])) for r in late} <= told


def test_compiles_count_every_build_and_cache_loads_stand_beside(started):
    records = started["records"]
    assert started["compiles"] == sum(r["builds"] > 0 for r in records)
    programs = started["perf_after"]["compiles"]["programs"]
    for name in ("decode_window", "prefill"):
        assert programs[name]["compiles"] >= 1
        assert 0 <= programs[name]["cache_loads"] <= sum(
            r["builds"] for r in perf.get_registry().first_calls
            if r["program"] == name)
        assert programs[name]["cache_load_seconds"] >= 0.0
    assert "cache_loads_total" in started["perf_after"]["compiles"]


# -- what an operator sees ------------------------------------------------------------

def test_the_three_metric_families_are_served(started):
    text = started["metrics"]
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]

    def series(name):
        return [ln for ln in lines if ln.startswith(name + "{")]

    stages = series("dynamo_tpu_startup_seconds")
    for stage in ("startup", "unattributed", "startup.warmup",
                  "startup.engine", "startup.http"):
        assert any(f'stage="{stage}"' in ln for ln in stages), stage
    first = series("dynamo_tpu_perf_first_call_seconds_total")
    for part in ("wall", "trace", "lower"):
        assert any(f'part="{part}"' in ln and 'program="prefill"' in ln
                   for ln in first), part
    loads = series("dynamo_tpu_perf_cache_loads_total")
    cached = [r for r in started["at_ready"] if r["cache"] != "off"]
    assert bool(loads) == bool(cached)
    ready = next(ln for ln in stages if 'stage="startup"' in ln)
    assert float(ready.rsplit(" ", 1)[1]) == pytest.approx(
        started["perf"]["startup"]["ready_s"], abs=1e-3)


def test_one_log_line_at_ready_and_none_a_bucket(started):
    lines = [ln for ln in started["log"] if ln.startswith("ready in ")]
    assert len(lines) == 1, started["log"]
    line = lines[0]
    for piece in ("weights", "(random)", "pool_alloc", "warmup",
                  "prefill_ladder", "programs: trace", "http",
                  "unattributed"):
        assert piece in line, (piece, line)
    assert not [ln for ln in started["log"] if ln.startswith("warmed ")]


# -- a start that fails; a second start; the cache -------------------------------------

@async_test(timeout=240)
async def test_a_start_that_raises_closes_the_root_with_the_stage(
        monkeypatch):
    from dynamo_tpu.engine.engine import TPUEngine

    def refuse(self):
        raise RuntimeError("this program does not compile")

    monkeypatch.setattr(TPUEngine, "_warmup_prefill_ladder", refuse)

    async def never(*_a):
        raise AssertionError("the start did not fail")

    with pytest.raises(RuntimeError, match="engine start-up failed"):
        await _launched([*LAUNCH, "--warmup-prefill-ladder"], never)
    start = tracing.last_startup()
    assert not start.open and start.root.status == "error"
    assert start.failed_stage == "startup.prefill_ladder"
    assert start.root.attrs["failed_stage"] == "startup.prefill_ladder"
    assert "does not compile" in next(
        s for s in start.spans
        if s.name == "startup.prefill_ladder").attrs["error"]
    # The stages around it closed with the error too, the root is in the
    # ring, and /debug/perf says so.
    status = {s.name: s.status for s in start.spans}
    assert status["startup.warmup"] == "error"
    assert status["startup.wait_ready"] == "error"
    assert status["startup.engine"] == "ok"
    assert tracing.get_recorder().trace(start.trace_id)[0].status == "error"
    body = perf.startup_status()
    assert body["status"] == "error"
    assert body["failed_stage"] == "startup.prefill_ladder"
    assert start.ready_line().startswith(
        "start-up FAILED at startup.prefill_ladder in ")


@async_test(timeout=480)
async def test_a_second_start_of_the_same_shapes_loads_from_the_cache(
        tmp_path, monkeypatch):
    """A persistent cache in a directory of the test's own: the first start
    compiles and writes, the second (same shapes, fresh closures) loads:
    through the launcher, from the program store inside that directory, and
    the records read as a load from jax's cache did."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    registry = perf.get_registry()

    async def records(*_a):
        return None

    walls = []
    for _ in range(2):
        jax.clear_caches()
        before = len(registry.first_calls)
        loads = registry.snapshot()["cache_loads_total"]
        builds = registry.compiles_total
        await _launched(LAUNCH, records)
        walls.append({
            "records": [r for r in registry.first_calls[before:]
                        if r["when"] == "startup"
                        and (r["builds"] or r["source"] == "store")],
            "loads": registry.snapshot()["cache_loads_total"] - loads,
            "builds": registry.compiles_total - builds})
    cold, warm = walls
    assert cold["records"] and len(warm["records"]) == len(cold["records"])
    assert warm["builds"] == cold["builds"]     # compiles counts as before
    if not jax.config.jax_enable_compilation_cache:
        assert {r["cache"] for r in warm["records"]} == {"off"}
        return
    assert {r["cache"] for r in cold["records"]} == {"miss"}
    assert {r["cache"] for r in warm["records"]} == {"hit"}
    assert cold["loads"] == 0 and warm["loads"] >= warm["builds"]
    assert all(r["cache_load_s"] > 0.0 for r in warm["records"])
    assert sum(r["compile_s"] for r in warm["records"]) \
        < sum(r["compile_s"] for r in cold["records"])
    # A start in a process that served before reads "startup" again.
    assert registry.warmup_complete


# -- the registry alone ----------------------------------------------------------------

def test_a_hundred_calls_after_the_first_add_no_record():
    reg = CompileRegistry()
    fn = instrumented_jit("unit", lambda x: x * 2, key="k", registry=reg)
    x = jnp.ones(4)
    fn(x)
    assert len(reg.first_calls) == 1
    record = dict(reg.first_calls[0])
    compiles = reg.compiles_total
    for _ in range(100):
        fn(x)
    assert len(reg.first_calls) == 1 and reg.first_calls[0] == record
    assert reg.compiles_total == compiles
    assert fn._calls == 101


def test_when_follows_the_warmup_boundary_and_mark_starting_reopens_it():
    reg = CompileRegistry()
    a = instrumented_jit("unit", lambda x: x + 1, key="a", registry=reg)
    b = instrumented_jit("unit", lambda x: x + 2, key="b", registry=reg)
    c = instrumented_jit("unit", lambda x: x + 3, key="c", registry=reg)
    a(jnp.ones(3))
    reg.mark_ready()
    b(jnp.ones(3))
    reg.mark_starting()
    c(jnp.ones(3))
    assert [(r["key"], r["when"]) for r in reg.first_calls] == [
        ("a", "startup"), ("b", "serving"), ("c", "startup")]
    assert all(r["t_mono"] <= time.monotonic() for r in reg.first_calls)


def test_a_later_call_that_builds_is_a_record_of_its_own():
    reg = CompileRegistry()
    fn = instrumented_jit("unit", lambda x: x * 3, key="k", registry=reg,
                          labels={"flavour": "plain"})
    fn(jnp.ones(4))
    fn(jnp.ones(4))
    fn(jnp.ones(8))     # the same wrapper, another shape: a build
    assert [r["builds"] for r in reg.first_calls] == [1, 1]
    assert reg.first_calls[1]["labels"] == {"flavour": "plain"}
    assert reg.snapshot()["programs"]["unit"]["compiles"] == 2
    sums = reg._programs["unit"].first_call_seconds
    assert sums["wall_s"] == pytest.approx(
        sum(r["wall_s"] for r in reg.first_calls))


def test_nested_traces_and_an_eager_build_inside_are_counted_once():
    """jnp's own jitted helpers fire a trace event each inside the outer
    trace, and an eager op on a constant is built while the outer program
    is traced: self times, so the parts stay inside the wall time."""
    reg = CompileRegistry()

    def body(x):
        table = jnp.arange(16) * 3          # eager, at trace time
        return jnp.where(x > 0, x, 0).sum() + jnp.clip(x, 0, 1).mean() \
            + table.sum()

    fn = instrumented_jit("unit", body, key="k", registry=reg)
    jax.clear_caches()
    fn(jnp.ones(16))
    (r,) = reg.first_calls
    assert r["trace_s"] > 0 and r["lower_s"] > 0
    assert r["wall_s"] >= (r["trace_s"] + r["lower_s"] + r["cache_load_s"]
                           + r["compile_s"])


def test_the_listeners_keep_their_totals_a_thread():
    """A build on another thread does not show in this thread's wrapper."""
    reg = CompileRegistry()
    fn = instrumented_jit("unit", lambda x: x - 1, key="k", registry=reg)
    fn(jnp.ones(5))

    def elsewhere():
        jax.jit(lambda x: x * 7 + 1)(jnp.ones(11))

    worker = threading.Thread(target=elsewhere)
    before = perf._tls.totals
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive()
    assert perf._tls.totals is before
    fn(jnp.ones(5))
    assert len(reg.first_calls) == 1


# -- the start's object alone -------------------------------------------------------------

def test_startup_parents_by_thread_and_keeps_self_time():
    rec = SpanRecorder(capacity=64)
    start = Startup(recorder=rec)
    with start.stage("startup.engine"):
        with start.stage("startup.weights", source="given") as st:
            st.set(bytes=12)
            time.sleep(0.02)
        time.sleep(0.01)

    def engine_thread():
        with start.stage("startup.warmup"):
            with start.stage("startup.prefill_ladder"):
                time.sleep(0.02)

    worker = threading.Thread(target=engine_thread)
    worker.start()
    with start.stage("startup.wait_ready"):
        worker.join(timeout=30)
    assert not worker.is_alive()
    start.finish()
    start.finish()      # once
    told = start.summary()
    rows = {r["name"]: r for r in told["stages"]}
    assert rows["startup.weights"]["parent"] == "startup.engine"
    assert rows["startup.weights"]["attrs"] == {"source": "given",
                                                "bytes": 12}
    assert rows["startup.prefill_ladder"]["parent"] == "startup.warmup"
    assert rows["startup.warmup"]["parent"] == "startup"
    assert rows["startup.engine"]["self_s"] == pytest.approx(
        rows["startup.engine"]["seconds"]
        - rows["startup.weights"]["seconds"], abs=1e-3)
    assert told["status"] == "ok"
    assert 0.0 <= told["unattributed_s"] <= 0.01
    assert told["ready_s"] >= 0.05
    # The ring holds the same trace, the root last; a stage after the end
    # is nothing.
    ring = rec.trace(start.trace_id)
    assert len(ring) == 6 and ring[0].name == "startup"
    assert start.stage("startup.late") is tracing.NULL_SPAN
    assert "weights" in start.ready_line("7 programs: ...")
    assert "7 programs" in start.ready_line("7 programs: ...")


def test_startup_error_names_the_innermost_stage_and_stays_in_the_ring():
    rec = SpanRecorder(capacity=16)
    start = Startup(recorder=rec)
    with pytest.raises(ValueError):
        with start.stage("startup.engine"):
            with start.stage("startup.pool_sizing"):
                raise ValueError("no memory left")
    start.finish(error=ValueError("no memory left"))
    assert start.failed_stage == "startup.pool_sizing"
    root = rec.trace(start.trace_id)[0]
    assert root.status == "error"
    assert root.attrs["failed_stage"] == "startup.pool_sizing"
    assert start.summary()["status"] == "error"


def test_a_disabled_recorder_still_leaves_the_stages_with_the_start():
    rec = SpanRecorder(capacity=16, enabled=False)
    start = Startup(recorder=rec)
    with start.stage("startup.runtime"):
        pass
    start.finish()
    assert rec.snapshot()[0] == []
    assert [r["name"] for r in start.summary()["stages"]] == [
        "startup.runtime"]


@pytest.mark.parametrize("intervals, seconds", [
    ([], 0.0),
    ([(0.0, 1.0), (2.0, 3.5)], 2.5),
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),            # overlapping
    ([(0.0, 5.0), (1.0, 2.0), (4.0, 6.0)], 6.0),    # one inside another
    ([(3.0, 4.0), (0.0, 1.0)], 2.0),            # any order
])
def test_covered_seconds_is_the_union(intervals, seconds):
    assert covered_seconds(intervals) == pytest.approx(seconds)


def test_no_launcher_no_stage():
    """A runner or an engine built by a test (no launch.run) opens no
    start: the stage is the shared no-op."""
    start = tracing.last_startup()
    if start is not None and start.open:
        pytest.skip("a start is under way in this process")
    assert tracing.startup_stage("startup.mesh") is tracing.NULL_SPAN
