"""Tracing subsystem tests: span recording, exporters, traceparent
hardening, metrics-registry fixes, phase histograms, the /debug API, and
the end-to-end distributed trace (HTTP frontend -> KV router -> mocker
worker over the real request plane, one process)."""

import asyncio
import json
import time
import tracemalloc

import aiohttp
import pytest
from conftest import async_test

from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.logging import make_traceparent, parse_traceparent
from dynamo_tpu.runtime.metrics import HistogramValue, MetricsRegistry
from dynamo_tpu.runtime.tracing import (NULL_SPAN, SpanRecorder, get_recorder,
                                        phase_metrics, span)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    rec = get_recorder()
    rec.clear()
    was = rec.enabled
    rec.enabled = True
    yield
    rec.enabled = was
    rec.clear()


# -- span recording ------------------------------------------------------------

def test_span_nesting_and_attrs():
    rec = get_recorder()
    with span("root", a=1) as sp:
        with span("child"):
            time.sleep(0.002)
        sp.set(b=2)
    spans = rec.trace(rec.snapshot()[0][0].trace_id)
    assert [s.name for s in spans] == ["root", "child"]
    root, child = spans
    assert child.parent_span_id == root.span_id
    assert child.trace_id == root.trace_id
    assert root.attrs == {"a": 1, "b": 2}
    assert root.duration_s >= child.duration_s >= 0.002
    assert root.status == child.status == "ok"


def test_span_error_status():
    rec = get_recorder()
    with pytest.raises(RuntimeError):
        with span("boom"):
            raise RuntimeError("nope")
    s = rec.snapshot()[0][-1]
    assert s.status == "error"
    assert "RuntimeError" in s.attrs["error"]


def test_span_adopts_request_context():
    """A span given a request Context pins to its wire-propagated ids."""
    rec = get_recorder()
    ctx = Context()
    with span("http.request", ctx=ctx):
        pass
    s = rec.snapshot()[0][-1]
    assert s.span_id == ctx.span_id
    assert s.trace_id == ctx.trace_id
    # Nested ctx adoption (worker.request already holds ctx.span_id):
    # child must mint a fresh id, not collide with its parent.
    with span("worker.request", ctx=ctx):
        with span("inner", ctx=ctx):
            pass
    inner = rec.snapshot()[0][-2]
    assert inner.name == "inner"
    assert inner.span_id != ctx.span_id
    assert inner.parent_span_id == ctx.span_id


@async_test
async def test_span_parenting_across_asyncio_tasks():
    rec = get_recorder()
    async with span("outer"):
        async def worker(i):
            with span("inner", i=i):
                await asyncio.sleep(0.001)

        await asyncio.gather(worker(0), worker(1), worker(2))
    spans = rec.snapshot()[0]
    outer = [s for s in spans if s.name == "outer"][0]
    inners = [s for s in spans if s.name == "inner"]
    assert len(inners) == 3
    # Each task inherited the outer span through its contextvar copy.
    assert all(s.parent_span_id == outer.span_id for s in inners)
    assert all(s.trace_id == outer.trace_id for s in inners)
    assert {s.attrs["i"] for s in inners} == {0, 1, 2}


def test_ring_buffer_eviction():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.add(f"s{i}", "ab" * 16, None, float(i), float(i) + 0.5)
    spans = rec.snapshot()[0]
    assert len(spans) == 8
    assert rec.dropped == 12
    # Oldest evicted first.
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_recent_index_groups_by_trace():
    rec = get_recorder()
    ctx1, ctx2 = Context(), Context()
    with span("req1", ctx=ctx1):
        with span("part"):
            pass
    with span("req2", ctx=ctx2):
        pass
    idx = tracing.traces_index()
    assert idx["enabled"] is True
    by_id = {t["trace_id"]: t for t in idx["traces"]}
    assert by_id[ctx1.trace_id]["spans"] == 2
    assert by_id[ctx1.trace_id]["root"] == "req1"
    assert by_id[ctx2.trace_id]["spans"] == 1


# -- exporters -----------------------------------------------------------------

def _containment_ok(events):
    """Chrome export invariant: every child slice sits inside its parent."""
    by_id = {e["args"]["span_id"]: e for e in events}
    eps = 1.0  # µs slack for float rounding
    for e in events:
        parent_id = e["args"].get("parent_span_id")
        parent = by_id.get(parent_id)
        if parent is None:
            continue
        assert e["ts"] >= parent["ts"] - eps, (e, parent)
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + eps, \
            (e, parent)


def test_chrome_export_schema():
    rec = get_recorder()
    ctx = Context()
    with span("root", ctx=ctx):
        with span("mid"):
            with span("leaf"):
                time.sleep(0.001)
    chrome = rec.export_chrome(ctx.trace_id)
    # Round-trips through JSON (what /debug/traces serves).
    parsed = json.loads(json.dumps(chrome))
    events = parsed["traceEvents"]
    assert len(events) == 3
    for e in events:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == ctx.trace_id
    # Monotonic: sorted by start time.
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    _containment_ok(events)


def test_otlp_export_shape():
    rec = get_recorder()
    ctx = Context()
    with span("root", ctx=ctx, model="m"):
        pass
    otlp = rec.export_otlp(ctx.trace_id)
    spans = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(spans) == 1
    s = spans[0]
    assert s["traceId"] == ctx.trace_id
    assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
    assert {"key": "model", "value": {"stringValue": "m"}} in s["attributes"]


# -- traceparent hardening (satellite) ----------------------------------------

def test_traceparent_roundtrip():
    trace_id, span_id = "0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331"
    header = make_traceparent(trace_id, span_id)
    parsed = parse_traceparent(header)
    assert parsed == {"trace_id": trace_id, "parent_id": span_id,
                      "flags": "01", "version": "00"}
    assert make_traceparent(parsed["trace_id"], parsed["parent_id"]) == header


def test_traceparent_rejects_invalid():
    good_t, good_p = "ab" * 16, "cd" * 8
    bad = [
        "",
        "00-abc-def-01",                          # wrong lengths
        f"00-{good_t}-{good_p}",                  # missing flags
        f"00-{'0' * 32}-{good_p}-01",             # all-zero trace id
        f"00-{good_t}-{'0' * 16}-01",             # all-zero parent id
        f"00-{'zz' * 16}-{good_p}-01",            # non-hex trace id
        f"00-{good_t}-{'xy' * 4 + 'cd' * 4}-01",  # non-hex parent id
        f"00-{good_t.upper()}-{good_p}-01",       # uppercase (spec: lower)
        f"ff-{good_t}-{good_p}-01",               # forbidden version
        f"0g-{good_t}-{good_p}-01",               # non-hex version
    ]
    for header in bad:
        assert parse_traceparent(header) is None, header


def test_context_wire_carries_traceparent():
    ctx = Context()
    wire = ctx.to_wire()
    assert wire["traceparent"] == make_traceparent(ctx.trace_id, ctx.span_id)
    # Worker side: same trace, new span, parented to the caller's span.
    child = Context.from_wire(wire)
    assert child.trace_id == ctx.trace_id
    assert child.parent_span_id == ctx.span_id
    assert child.span_id != ctx.span_id
    # A peer that only sends the W3C header still joins the trace.
    w3c_only = Context.from_wire({"id": "r1", "traceparent":
                                  wire["traceparent"]})
    assert w3c_only.trace_id == ctx.trace_id
    assert w3c_only.parent_span_id == ctx.span_id


# -- metrics registry fixes (satellite) ---------------------------------------

def test_metrics_registry_label_mismatch_raises():
    m = MetricsRegistry()
    node = m.namespace("ns")
    node.counter("thing_total", "things", ["route"])
    with pytest.raises(ValueError, match="labels"):
        node.counter("thing_total", "things", ["route", "status"])
    with pytest.raises(ValueError, match="Counter"):
        node.histogram("thing_total", "things", ["route"])
    # Identical re-registration is fine (idempotent wiring).
    node.counter("thing_total", "things", ["route"])


def test_bound_get_works_for_histograms():
    m = MetricsRegistry()
    node = m.namespace("ns")
    h = node.histogram("lat_seconds", "latency")
    assert h.get() == HistogramValue(0, 0.0)
    h.observe(0.25)
    h.observe(0.75)
    v = h.get()
    assert v.count == 2
    assert abs(v.total - 1.0) < 1e-9
    c = node.counter("n_total", "count")
    c.inc(3)
    assert c.get() == 3.0


def test_phase_metrics_preregistered_in_exposition():
    m = MetricsRegistry()
    pm = phase_metrics(m.namespace("ns").component("tpu"))
    assert phase_metrics(m.namespace("ns").component("tpu")) is pm
    expo = m.expose().decode()
    for name in ("request_queue_wait_seconds", "prefill_step_seconds",
                 "decode_step_seconds", "kv_transfer_seconds",
                 "kv_transfer_bytes"):
        assert f"dynamo_tpu_{name}" in expo, name
    # Hierarchy labels are on the series even before traffic.
    assert 'dynamo_namespace="ns"' in expo
    assert 'dynamo_component="tpu"' in expo
    assert 'direction="recv"' in expo


# -- disabled-recorder fast path (acceptance: bounded overhead) ---------------

def test_disabled_recorder_is_noop_singleton():
    rec = get_recorder()
    rec.enabled = False
    s1 = span("decode")
    s2 = span("prefill", tokens=8)
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with span("x") as sp:
        sp.set(a=1)  # no-op, no error
    assert rec.add("x", "ab" * 16, None, 0.0, 1.0) is None
    assert rec.snapshot()[0] == []


def test_disabled_recorder_zero_allocations():
    """The per-token fast path (`if recorder.enabled: recorder.add(...)`)
    must allocate nothing when tracing is off."""
    rec = get_recorder()
    rec.enabled = False
    trace_id = "ab" * 16

    def hot_loop(n):
        for _ in range(n):
            if rec.enabled:
                rec.add("engine.decode", trace_id, None, 0.0, 1.0)

    hot_loop(10)  # warm up (method caches, etc.)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot_loop(5000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = [s for s in after.compare_to(before, "filename")
             if "tracing.py" in (s.traceback[0].filename or "")]
    grown = sum(s.size_diff for s in stats)
    assert grown <= 0, stats


# -- TPU engine phase histograms + spans --------------------------------------

@async_test(timeout=240)
async def test_tpu_engine_phase_histograms_and_spans():
    from test_engine import tiny_config
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest

    rec = get_recorder()
    registry = MetricsRegistry()
    engine = TPUEngine(tiny_config(),
                       metrics_registry=registry.namespace("ns")
                       .component("tpu"))
    try:
        req = PreprocessedRequest(model="m", token_ids=list(range(24)))
        req.stop_conditions.max_tokens = 8
        req.stop_conditions.ignore_eos = True
        ctx = Context()
        tokens = []
        async for out in engine.generate(req, ctx):
            tokens.extend(out.get("token_ids", []))
        assert len(tokens) == 8
        # Phase histograms observed real values.
        assert engine.phase.queue_wait.get().count >= 1
        assert engine.phase.prefill.get().count >= 1
        assert engine.phase.decode.get().count >= 1
        expo = registry.expose().decode()
        assert "dynamo_tpu_request_queue_wait_seconds" in expo
        assert 'dynamo_component="tpu"' in expo
        # Spans: queue wait + prefill + decode, all in the request's trace.
        names = {s.name for s in rec.trace(ctx.trace_id)}
        assert {"engine.queue_wait", "engine.prefill",
                "engine.decode"} <= names, names
        for s in rec.trace(ctx.trace_id):
            assert s.parent_span_id == ctx.span_id
    finally:
        engine.stop()


# -- e2e: distributed trace through the real stack ----------------------------

async def _start_traced_stack():
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.kv_router import make_kv_router_factory
    from dynamo_tpu.llm.kv_router.publisher import (KvEventPublisher,
                                                    WorkerMetricsPublisher)
    from dynamo_tpu.llm.mocker import MockerConfig, MockerEngine
    from dynamo_tpu.llm.model_card import register_llm
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    ns = "test"
    coord = Coordinator()
    await coord.start()
    cfg = lambda: RuntimeConfig(coordinator_url=coord.url,  # noqa: E731
                                lease_ttl_s=3.0, namespace=ns)
    worker_rt = await DistributedRuntime.from_settings(cfg())
    frontend_rt = await DistributedRuntime.from_settings(cfg())
    config = MockerConfig(prefill_tokens_per_s=1e6, decode_step_s=0.001)
    kv_pub = KvEventPublisher(worker_rt, ns, "mocker", worker_rt.instance_id)
    m_pub = WorkerMetricsPublisher(worker_rt, ns, "mocker",
                                   worker_rt.instance_id,
                                   min_interval_s=0.01)
    engine = MockerEngine(config, kv_pub, m_pub)
    endpoint = worker_rt.namespace(ns).component("mocker").endpoint("generate")
    server = await endpoint.serve_endpoint(engine.handler(),
                                           graceful_shutdown=False)
    await register_llm(worker_rt, endpoint, "mock-model",
                       make_test_tokenizer(),
                       kv_cache_block_size=config.block_size)
    engine.start()
    manager = ModelManager()
    watcher = ModelWatcher(frontend_rt, manager, router_mode="kv",
                           kv_router_factory=make_kv_router_factory())
    await watcher.start()
    service = HttpService(frontend_rt, manager, host="127.0.0.1", port=0)
    await service.start()
    for _ in range(200):
        if manager.get("mock-model"):
            break
        await asyncio.sleep(0.02)
    assert manager.get("mock-model") is not None

    async def stop():
        await service.stop()
        await watcher.stop()
        await engine.stop()
        await server.shutdown()
        await frontend_rt.close()
        await worker_rt.close()
        await coord.stop()

    return service, stop


@async_test(timeout=240)
async def test_e2e_distributed_trace_and_debug_api():
    """Acceptance: a request through the in-proc e2e path yields a
    retrievable /debug/traces trace with http.request -> router.decide ->
    engine.prefill -> engine.decode sharing one trace id, and the Chrome
    export is valid JSON with monotonic, parent-contained timestamps."""
    rec = get_recorder()
    service, stop = await _start_traced_stack()
    try:
        trace_id = "1234567890abcdef1234567890abcdef"
        header = make_traceparent(trace_id, "feedfacecafebeef")
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"{base}/v1/chat/completions",
                    headers={"traceparent": header},
                    json={"model": "mock-model", "max_tokens": 4,
                          "messages": [{"role": "user",
                                        "content": "trace me"}]}) as resp:
                assert resp.status == 200
                await resp.json()
            want = {"http.request", "router.decide", "worker.request",
                    "engine.queue_wait", "engine.prefill", "engine.decode"}
            # Engine-side spans land asynchronously; poll briefly.
            for _ in range(100):
                names = {s.name for s in rec.trace(trace_id)}
                if want <= names:
                    break
                await asyncio.sleep(0.02)
            assert want <= names, names

            # Every span shares the externally-supplied trace id, and the
            # http.request span is parented to the external caller.
            spans = rec.trace(trace_id)
            assert all(s.trace_id == trace_id for s in spans)
            http_span = [s for s in spans if s.name == "http.request"][0]
            assert http_span.parent_span_id == "feedfacecafebeef"
            # Distributed: the worker-side span crossed the request plane
            # and parents back to the frontend's span.
            worker_span = [s for s in spans
                           if s.name == "worker.request"][0]
            assert worker_span.parent_span_id == http_span.span_id

            # /debug/traces/recent lists the trace.
            async with session.get(
                    f"{base}/debug/traces/recent") as resp:
                assert resp.status == 200
                idx = await resp.json()
            assert any(t["trace_id"] == trace_id for t in idx["traces"])

            # Chrome export over HTTP: valid JSON, monotonic,
            # parent-contained.
            async with session.get(
                    f"{base}/debug/traces",
                    params={"trace_id": trace_id,
                            "format": "chrome"}) as resp:
                assert resp.status == 200
                chrome = json.loads(await resp.text())
            events = chrome["traceEvents"]
            assert {e["name"] for e in events} >= want
            assert [e["ts"] for e in events] == \
                sorted(e["ts"] for e in events)
            _containment_ok(events)

            # OTLP export works; unknown trace 404s; bad format 400s.
            async with session.get(
                    f"{base}/debug/traces",
                    params={"trace_id": trace_id,
                            "format": "otlp"}) as resp:
                assert resp.status == 200
                otlp = await resp.json()
                assert otlp["resourceSpans"]
            async with session.get(
                    f"{base}/debug/traces",
                    params={"trace_id": "ff" * 16}) as resp:
                assert resp.status == 404
            async with session.get(
                    f"{base}/debug/traces",
                    params={"trace_id": trace_id,
                            "format": "nope"}) as resp:
                assert resp.status == 400
    finally:
        await stop()


@async_test(timeout=120)
async def test_profile_endpoint(tmp_path):
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.health import SystemStatusServer

    runtime = await DistributedRuntime.detached(RuntimeConfig())
    server = SystemStatusServer(runtime, host="127.0.0.1", port=0)
    await server.start()
    try:
        with span("profiled.work"):
            await asyncio.sleep(0.005)
        base = f"http://127.0.0.1:{server.port}"
        out_dir = str(tmp_path / "prof")
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"{base}/debug/profile",
                    json={"duration_ms": 50, "out_dir": out_dir}) as resp:
                assert resp.status == 200
                result = await resp.json()
        assert result["mode"] in ("jax", "spans")
        assert result["out_dir"] == out_dir
        # The span dump is always written and is valid Chrome JSON
        # containing the recorded span.
        with open(result["span_dump"]) as fh:
            dump = json.load(fh)
        assert any(e["name"] == "profiled.work"
                   for e in dump["traceEvents"])
    finally:
        await server.stop()
        await runtime.close()


# -- the permit on the request's own span (PR 36) -------------------------------

@async_test(timeout=120)
async def test_http_request_span_is_the_permit_and_carries_what_was_judged():
    """``http.request`` is held exactly as long as the permit, so the spans
    that cover an instant are the permits held at it, and each carries the
    permit-to-first-token time the limiter judged as ``permit_to_first_ms``."""
    from test_overload import start_frontend, start_mocker, wait_model
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.overload import OverloadConfig

    coord = Coordinator()
    await coord.start()
    mocker = await start_mocker(coord, max_num_seqs=8, decode_step_s=0.004)
    rt, manager, watcher, service = await start_frontend(
        coord, overload=OverloadConfig(
            seed=3, initial_concurrency=3, max_concurrency=3,
            min_concurrency=3, queue_depth=16, default_deadline_ms=60_000,
            target_latency_ms=60_000))
    rec = get_recorder()
    limiter = service.overload
    try:
        await wait_model(manager)

        async def post(session, i):
            async with session.post(
                    f"http://127.0.0.1:{service.port}/v1/chat/completions",
                    json={"model": "mock-model", "max_tokens": 100 + 30 * i,
                          "messages": [{"role": "user",
                                        "content": f"hold {i}"}]}) as resp:
                assert resp.status == 200
                await resp.json()

        seen = []   # (instant, permits held at it, callers queued at it)

        async def watch(done):
            while not done.is_set():
                seen.append((time.monotonic(), limiter.inflight,
                             limiter.waiting()))
                await asyncio.sleep(0.01)

        done = asyncio.Event()
        watcher_task = asyncio.ensure_future(watch(done))
        async with aiohttp.ClientSession() as session:
            await asyncio.gather(*(post(session, i) for i in range(7)))
        done.set()
        await watcher_task
        spans = rec.snapshot()[0]
        reqs = [s for s in spans if s.name == "http.request"]
        waits = [s for s in spans if s.name == "http.admit_wait"]
        assert len(reqs) == 7 == len(waits)
        for s in reqs:
            first = s.attrs["permit_to_first_ms"]
            assert 0 < first <= s.duration_s * 1e3
        assert limiter.limit_changes["increase"] == 7  # each one judged
        # Away from a span's own edges (a sample may fall between the
        # grant and the span's first instruction), the cover IS the count.
        edges = [t for s in reqs + waits for t in (s.start_mono, s.end_mono)]
        compared = 0
        for t, inflight, waiting in seen:
            if any(abs(t - e) < 0.015 for e in edges):
                continue
            compared += 1
            assert sum(s.start_mono <= t <= s.end_mono for s in reqs) \
                == inflight, t
            assert sum(s.start_mono <= t <= s.end_mono for s in waits) \
                == waiting, t
        assert compared >= 10 and max(n for _, n, _ in seen) == 3
        assert max(w for _, _, w in seen) >= 3
    finally:
        await service.stop()
        await watcher.stop()
        mrt, engine, server = mocker
        await engine.stop()
        await server.shutdown()
        await mrt.close()
        await rt.close()
        await coord.stop()
