"""Engine perf plane (docs/OBSERVABILITY.md "Engine perf plane"):
compile observatory units, the unexpected-recompile detector, the
cost-analysis fallback on CPU, the flight ring's tokens column staying
allocation-free, the fleet-pane perf merge, and a tiny-CPU-engine smoke
asserting zero unexpected recompiles across consecutive decode windows
with /debug/perf served on both the worker status server and the
frontend.

All near-free: fake data or one tiny engine. Speed is measured by
benchmark/ on the chip and recorded in PERF_LEDGER.jsonl, not here.
"""

import dataclasses
import tracemalloc

import aiohttp
import numpy as np
import pytest
from conftest import async_test

from dynamo_tpu.engine.backends import Backends
from dynamo_tpu.engine.perf import (CompileRegistry, PerfMetricsUpdater,
                                    instrumented_jit)
from dynamo_tpu.runtime import flight
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.metrics import MetricsRegistry


# -- CompileRegistry units ----------------------------------------------------


def test_registry_counts_and_detects_recompiles():
    reg = CompileRegistry()
    reg.note_compile("prefill", (128, 1), 1.5)
    reg.note_compile("prefill", (256, 1), 2.0)  # new key: expected
    snap = reg.snapshot()
    assert snap["programs"]["prefill"]["compiles"] == 2
    assert snap["programs"]["prefill"]["signatures"] == 2
    assert snap["unexpected_recompiles_total"] == 0
    # Second compile of a SEEN key = unexpected steady-state recompile.
    reg.note_compile("prefill", (128, 1), 0.5)
    snap = reg.snapshot()
    assert snap["programs"]["prefill"]["unexpected_recompiles"] == 1
    assert snap["unexpected_recompiles_total"] == 1
    assert snap["programs"]["prefill"]["compile_seconds"] == pytest.approx(
        4.0)
    # key=None marks a self-bucketing program (multimodal encoders):
    # compiles counted, never flagged.
    reg.note_compile("audio_encoder", None, 0.1)
    reg.note_compile("audio_encoder", None, 0.1)
    snap = reg.snapshot()
    assert snap["programs"]["audio_encoder"]["compiles"] == 2
    assert snap["programs"]["audio_encoder"]["unexpected_recompiles"] == 0
    assert snap["unexpected_recompiles_total"] == 1


def test_registry_warmup_marker_and_reset():
    reg = CompileRegistry()
    assert reg.snapshot()["warmup_complete"] is False
    reg.mark_ready()
    assert reg.snapshot()["warmup_complete"] is True
    reg.note_compile("x", 1, 1.0)
    reg.reset()
    assert reg.snapshot() == {
        "programs": {}, "compiles_total": 0, "compile_seconds_total": 0,
        "cache_loads_total": 0, "unexpected_recompiles_total": 0,
        "warmup_complete": False}


def test_instrumented_jit_real_compile_detection():
    """Real jax on CPU: one compile for repeat same-shape calls; a new
    shape on the SAME key (a genuine jit-cache invalidation from the
    wrapper's point of view) is flagged; dispatch-cache churn is not."""
    import jax.numpy as jnp
    reg = CompileRegistry()
    fn = instrumented_jit("unit", lambda x: x * 2, key="k", registry=reg)
    np.testing.assert_allclose(fn(jnp.ones(4)), 2 * np.ones(4))
    fn(jnp.ones(4))
    fn(jnp.ones(4))
    snap = reg.snapshot()
    assert snap["programs"]["unit"]["compiles"] == 1
    assert snap["unexpected_recompiles_total"] == 0
    reg.mark_ready()  # steady state declared: recompiles now flag
    fn(jnp.ones(8))  # same key, new shape -> post-warmup recompile
    snap = reg.snapshot()
    assert snap["programs"]["unit"]["compiles"] == 2
    assert snap["unexpected_recompiles_total"] == 1


def test_two_program_instances_do_not_cross_flag():
    """Two runners in one process (tests, in-process multi-worker
    launchers) each compile the same (program, key) once — judged
    per-wrapper, that is two expected compiles, not a recompile."""
    import jax.numpy as jnp
    reg = CompileRegistry()
    a = instrumented_jit("prefill", lambda x: x + 1, key=(64, 1),
                         registry=reg)
    b = instrumented_jit("prefill", lambda x: x + 2, key=(64, 1),
                         registry=reg)
    a(jnp.ones(4))
    b(jnp.ones(4))
    snap = reg.snapshot()
    assert snap["programs"]["prefill"]["compiles"] == 2
    assert snap["unexpected_recompiles_total"] == 0


def test_warmup_compiles_are_never_flagged():
    """Before mark_ready, a wrapper may compile several times (warmup
    intentionally double-compiles signatures whose input shardings
    converge after the first run) without flagging."""
    import jax.numpy as jnp
    reg = CompileRegistry()
    fn = instrumented_jit("decode_window", lambda x: x * 3, key=(8, 8),
                          registry=reg)
    fn(jnp.ones(4))
    fn(jnp.ones(8))  # pre-warmup recompile: expected, not flagged
    assert reg.snapshot()["unexpected_recompiles_total"] == 0
    assert reg.snapshot()["programs"]["decode_window"]["compiles"] == 2


def test_cost_analysis_present_or_typed_fallback():
    """The one-time FLOPs/bytes estimate either resolves (CPU lowering
    supports cost_analysis) or degrades to a typed error dict — never
    raises into the serving path."""
    import jax.numpy as jnp
    reg = CompileRegistry()
    fn = instrumented_jit("costed", lambda x: (x @ x.T).sum(), key="k",
                          registry=reg)
    fn(jnp.ones((8, 8)))
    cost = reg.snapshot()["programs"]["costed"]["cost"]
    assert isinstance(cost, dict)
    assert ("flops" in cost) or ("error" in cost)
    if "flops" in cost:
        assert cost["flops"] > 0
        assert cost["source"] in ("lower", "compile")


def test_cost_mode_off(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setenv("DTPU_PERF_COST", "off")
    reg = CompileRegistry()
    fn = instrumented_jit("uncosted", lambda x: x + 1, key="k",
                          registry=reg)
    fn(jnp.ones(4))
    assert reg.snapshot()["programs"]["uncosted"]["cost"] is None


# -- roofline-attributed window series ----------------------------------------


def test_note_window_derives_roofline_gauges():
    reg = CompileRegistry()
    # 8 steps x 8 active rows in 8 ms against a 1 ms step floor:
    # achieved = 8000 tok/s, roofline = 8 / 1ms = 8000 -> frac 1.0.
    reg.note_window(window_s=0.008, tokens=64, active=8, steps=8,
                    step_floor_ms=1.0, latency_s=0.008)
    assert reg.step_seconds == pytest.approx(0.001)
    assert reg.achieved_tok_s == pytest.approx(8000.0)
    assert reg.roofline_frac == pytest.approx(1.0)
    # Half the tokens at the same device time: frac EWMAs down.
    reg.note_window(window_s=0.008, tokens=32, active=8, steps=8,
                    step_floor_ms=1.0, latency_s=0.008)
    assert 0.5 < reg.roofline_frac < 1.0
    w = reg.window_snapshot()
    assert w["windows_total"] == 2
    assert w["window_tokens_total"] == 96
    # Degenerate inputs never divide by zero.
    reg.note_window(0.0, 0, 0, 0, 1.0, 0.0)
    assert reg.window_snapshot()["windows_total"] == 2


class _FakeRunner:
    backends = Backends(attention="pallas", kv_commit="in_place",
                        page_size=64)

    def __init__(self, hbm):
        self._hbm = hbm

    def hbm_stats(self):
        return self._hbm


class _FakeEngine:
    def __init__(self, hbm):
        self.runner = _FakeRunner(hbm)


def test_perf_metrics_updater_exports_deltas_and_gauges(monkeypatch):
    from dynamo_tpu.engine import perf as perf_mod
    reg = CompileRegistry()
    monkeypatch.setattr(perf_mod, "_REGISTRY", reg)
    metrics = MetricsRegistry()
    up = PerfMetricsUpdater(metrics, min_interval_s=0.0)
    reg.note_compile("decode_window", (8,), 2.0)
    reg.note_compile("decode_window", (8,), 1.0)  # unexpected
    reg.note_window(0.01, 32, 4, 8, 1.0, 0.01)
    eng = _FakeEngine({"bytes_in_use": 100, "peak_bytes_in_use": 150,
                       "bytes_limit": 200})
    up.update(eng, force=True)
    assert up.c_compiles.get(program="decode_window") == 2.0
    assert up.c_compile_seconds.get(program="decode_window") == \
        pytest.approx(3.0)
    assert up.c_unexpected.get(program="decode_window") == 1.0
    assert up.g_roofline.get() == pytest.approx(reg.roofline_frac)
    assert up.g_hbm_in_use.get() == 100
    assert up.g_hbm_limit.get() == 200
    # How the window program commits: an info series, 1 under its label.
    assert up.g_kv_commit.get(backend="in_place") == 1
    assert up.g_attention.get(backend="pallas") == 1
    assert 'backend="in_place"' in metrics.expose().decode()
    # Deltas: a second update with no new compiles adds nothing.
    up.update(eng, force=True)
    assert up.c_compiles.get(program="decode_window") == 2.0
    # CPU backend (no memory_stats): gauges untouched, no raise.
    up.update(_FakeEngine({}), force=True)
    assert up.g_hbm_limit.get() == 200


@pytest.mark.parametrize("backend", ["pallas", "xla", None])
def test_the_indexer_s_backend_is_an_info_series(monkeypatch, backend):
    """dynamo_tpu_perf_index_info{backend}: 1 under the label of who runs
    the decode indexer of a latent pool (Backends.index); no sample at all
    from a worker whose blocks have no indexer (None)."""
    from dynamo_tpu.engine import perf as perf_mod
    monkeypatch.setattr(perf_mod, "_REGISTRY", CompileRegistry())
    metrics = MetricsRegistry()
    up = PerfMetricsUpdater(metrics, min_interval_s=0.0)
    eng = _FakeEngine({})
    eng.runner.backends = dataclasses.replace(eng.runner.backends,
                                              index=backend)
    up.update(eng, force=True)
    samples = [line for line in metrics.expose().decode().splitlines()
               if line.startswith("dynamo_tpu_perf_index_info{")]
    if backend is None:
        assert samples == []
    else:
        assert len(samples) == 1 and f'backend="{backend}"' in samples[0]
        assert samples[0].endswith(" 1.0")
        assert up.g_index.get(backend=backend) == 1


def test_the_expert_product_is_an_info_series_and_a_counter(monkeypatch):
    """dynamo_tpu_perf_expert_product_info{program,kind}: 1 under each
    (program family, product) the registry's live programs carry as the
    label ``expert_product``; dynamo_tpu_moe_grouped_pairs_total follows
    the runner's host-side count; a dense worker exposes neither sample."""
    from dynamo_tpu.engine import perf as perf_mod
    registry = CompileRegistry()
    monkeypatch.setattr(perf_mod, "_REGISTRY", registry)
    metrics = MetricsRegistry()
    up = PerfMetricsUpdater(metrics, min_interval_s=0.0)
    eng = _FakeEngine({})
    up.update(eng, force=True)

    def samples(prefix):
        return [line for line in metrics.expose().decode().splitlines()
                if line.startswith(prefix)]

    assert samples("dynamo_tpu_perf_expert_product_info{") == []
    kept = [registry.wrap("prefill", lambda x: x, key=(rows,),
                          labels={"expert_product": kind})
            for rows, kind in ((512, "grouped"), (128, "masked"))]
    kept.append(registry.wrap("decode_window", lambda x: x, key=(8,),
                              labels={"expert_product": "touched"}))
    eng.runner.moe_grouped_pairs = 3072
    up.update(eng, force=True)
    info = samples("dynamo_tpu_perf_expert_product_info{")
    assert len(info) == 3 and all(line.endswith(" 1.0") for line in info)
    assert up.g_expert_product.get(program="prefill", kind="grouped") == 1
    assert up.g_expert_product.get(program="decode_window",
                                   kind="touched") == 1
    assert up.c_moe_grouped_pairs.get() == 3072
    eng.runner.moe_grouped_pairs += 1536
    up.update(eng, force=True)
    assert up.c_moe_grouped_pairs.get() == 4608


# -- flight ring: tokens column stays allocation-free -------------------------


def test_flight_tokens_column_recorded_and_zero_alloc():
    rec = flight.FlightRecorder(capacity=64)
    assert rec.record(1.0, 0.01, 2, 0, 10, 0, 0, 0, 0, 0.0, 1, 48)
    row = rec.dump()[-1]
    assert row["tokens"] == 48 and isinstance(row["tokens"], int)

    def hot_loop(n):
        for _ in range(n):
            rec.record(1.5, 0.01, 4, 1, 100, 32, 1, 0, 0, 0.0, 7, 16)

    hot_loop(200)  # warm-up: method caches, numpy casts, frame reuse
    ok = False
    for _ in range(3):
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            hot_loop(5000)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        stats = [s for s in after.compare_to(before, "filename")
                 if "flight.py" in (s.traceback[0].filename or "")]
        if sum(s.size_diff for s in stats) <= 0:
            ok = True
            break
    assert ok, "flight.record with the tokens column allocates per call"


# -- fleet pane merge ---------------------------------------------------------


def test_fleet_aggregate_sums_perf_views():
    from dynamo_tpu.llm.fleet import _aggregate
    workers = {
        "a": {"ok": True,
              "kv": {"allocator": {"pages_total": 10, "pages_free": 5,
                                   "pages_active": 5}},
              "perf": {"compiles": {"compiles_total": 7,
                                    "unexpected_recompiles_total": 0}}},
        "b": {"ok": True,
              "kv": {"allocator": {"pages_total": 10, "pages_free": 10,
                                   "pages_active": 0}},
              "perf": {"compiles": {"compiles_total": 3,
                                    "unexpected_recompiles_total": 2}}},
        "c": {"ok": False, "error": "down"},
        "d": {"ok": True, "kv": {}},  # pre-perf-plane worker: no perf key
    }
    agg = _aggregate(workers)
    assert agg["workers_ok"] == 3 and agg["workers_down"] == 1
    assert agg["compiles_total"] == 10
    assert agg["unexpected_recompiles"] == 2


# -- tiny-engine smoke: zero unexpected recompiles + the pane -----------------


@async_test(timeout=300)
async def test_perf_smoke_engine_zero_recompiles_and_pane(tmp_path):
    """Acceptance: steady-state decode on the tiny CPU engine shows ZERO
    unexpected recompiles after warmup across consecutive decode
    windows, /debug/perf reports per-program compile stats + live
    roofline/HBM fields on both the worker status server and the
    frontend, and doctor's perf probe reads them."""
    from dynamo_tpu.doctor import FAIL, OK, WARN, Report, check_perf
    from dynamo_tpu.engine.config import EngineConfig, PRESETS
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.health import SystemStatusServer

    spec = PRESETS["tiny-test"]
    cfg = EngineConfig(model=spec, page_size=16, num_pages=128,
                       max_pages_per_seq=16, max_num_seqs=4,
                       prefill_buckets=(32, 64, 128),
                       max_prefill_tokens=64, attention_backend="xla",
                       decode_window=4)
    metrics = MetricsRegistry()
    engine = TPUEngine(cfg, metrics_registry=metrics)
    runtime = await DistributedRuntime.detached(RuntimeConfig())

    async def generate(seed, n=12):
        rng = np.random.default_rng(seed)
        req = PreprocessedRequest(
            model="m",
            token_ids=rng.integers(0, spec.vocab_size, size=24).tolist())
        req.stop_conditions.max_tokens = n
        got = 0
        async for out in engine.generate(req, Context()):
            got += len(out.get("token_ids", []))
            if out.get("finish_reason"):
                break
        assert got == n

    server = None
    frontend = None
    try:
        # First request compiles prefill + decode_window; max_tokens=12
        # at window 4 = 3+ decode windows in one request. The registry
        # is process-global (other engines in this pytest process may
        # have contributed), so every steady-state assertion is a DELTA
        # across THIS engine's requests.
        await generate(1)
        snap0 = engine._perf.snapshot()
        assert "prefill" in snap0["programs"]
        assert "decode_window" in snap0["programs"]
        assert snap0["programs"]["decode_window"]["compiles"] >= 1
        # Steady state: two more same-shape requests (many more decode
        # windows) must add ZERO compiles and ZERO unexpected recompiles.
        await generate(2)
        await generate(3)
        snap1 = engine._perf.snapshot()
        assert snap1["unexpected_recompiles_total"] == \
            snap0["unexpected_recompiles_total"], (
            "steady-state decode flagged a recompile: "
            f"{snap1['programs']}")
        assert snap1["programs"]["decode_window"]["compiles"] == \
            snap0["programs"]["decode_window"]["compiles"]
        assert snap1["programs"]["prefill"]["compiles"] == \
            snap0["programs"]["prefill"]["compiles"]

        # Window series is live and the exporter published it.
        status = engine.perf_status()
        assert status["window"]["windows_total"] >= 2
        assert status["window"]["achieved_tok_per_s"] > 0
        assert 0 <= status["roofline"]["frac"] <= 1
        assert status["memory"]["params_bytes"] > 0
        assert status["memory"]["kv_pool_bytes"] > 0
        # How the window program writes the pool, as the runner decided
        # (the CPU engine: XLA's gather, so its scatter): on the pane, in
        # the registry's record of the window programs, and as an info
        # series.
        assert status["kv_commit_backend"] == \
            engine.runner.backends.kv_commit == "scatter"
        assert "scatter" in snap1["programs"]["decode_window"]["labels"][
            "kv_commit_backend"]
        # ... and who reads it (this engine asked for the gather).
        assert status["attention_backend"] == \
            engine.runner.attention_backend == "xla"
        assert "xla" in snap1["programs"]["decode_window"]["labels"][
            "attention_backend"]
        # ... and beside it how many tokens a page holds (a CPU engine:
        # 16, nothing derived).
        assert status["page_size"] == engine.runner.page_size == \
            engine.config.page_size == 16
        # (the registry is the process's: this engine's own programs)
        assert sorted({fn._labels["page_size"] for fn in
                       engine.runner._window_cache.values()}) == [16]
        assert 16 in snap1["programs"]["decode_window"]["labels"][
            "page_size"]
        # A dense block's prefill programs carry no label (the registry is
        # the process's: a routed runner of an earlier test may still have
        # ``expert_product`` on its own).
        assert engine.runner._prefill_cache and all(
            fn._labels == {} for fn in engine.runner._prefill_cache.values())
        engine.perf_metrics.update(engine, force=True)
        text = metrics.expose().decode()
        assert text.count("dynamo_tpu_perf_") > 0
        assert [line for line in text.splitlines()
                if line.startswith("dynamo_tpu_perf_kv_commit_info{")
                and 'backend="scatter"' in line and line.endswith(" 1.0")]
        assert [line for line in text.splitlines()
                if line.startswith("dynamo_tpu_perf_attention_info{")
                and 'backend="xla"' in line and line.endswith(" 1.0")]
        assert [line for line in text.splitlines()
                if line.startswith("dynamo_tpu_perf_kv_page_info{")
                and 'tokens="16"' in line and line.endswith(" 1.0")]

        # The pane: worker status server (explicit provider) + frontend
        # (process-global fallback + in-process engine discovery off).
        server = SystemStatusServer(runtime, host="127.0.0.1", port=0,
                                    perf_provider=engine.perf_status)
        await server.start()
        frontend = HttpService(runtime, ModelManager(), host="127.0.0.1",
                               port=0)
        await frontend.start()
        async with aiohttp.ClientSession() as session:
            async with session.get(
                    f"http://127.0.0.1:{server.port}/debug/perf") as resp:
                assert resp.status == 200
                body = await resp.json()
                assert body["role"] == "engine"
                # Matches the live registry (delta-safe: no new ones
                # appeared since snap1 was taken).
                assert body["compiles"]["unexpected_recompiles_total"] \
                    == snap1["unexpected_recompiles_total"]
                assert "decode_window" in body["compiles"]["programs"]
                assert "roofline_frac" in body["window"]
                assert body["kv_commit_backend"] == "scatter"
                assert body["attention_backend"] == "xla"
                assert body["page_size"] == 16
            async with session.get(
                    f"http://127.0.0.1:{frontend.port}/debug/perf") as resp:
                assert resp.status == 200
                body = await resp.json()
                assert body["role"] == "frontend"
                assert "programs" in body["compiles"]

        # Doctor reads the same pane; no FAIL ever. The compile row is
        # OK when the process-global registry is clean, WARN when an
        # earlier test in this pytest process flagged a recompile.
        rep = Report()
        await check_perf(rep, f"http://127.0.0.1:{server.port}")
        by_check = {c: s for s, c, _ in rep.rows}
        expected_row = (OK if snap1["unexpected_recompiles_total"] == 0
                        else WARN)
        assert by_check.get("perf engine") == expected_row
        assert not any(s == FAIL for s, _, _ in rep.rows)

        # Doctor WARNs on a sick pane (recompiles + thin HBM headroom +
        # regressed roofline) — served through the same status route.
        sick = {
            "role": "engine",
            "compiles": {"programs": {"decode_window": {"compiles": 9}},
                         "compiles_total": 9,
                         "unexpected_recompiles_total": 4},
            "window": {"roofline_frac": 0.1},
            "roofline": {"frac": 0.1, "expected_frac": 0.34},
            "hbm": {"bytes_in_use": 99, "bytes_limit": 100},
            "memory": {},
        }
        server.perf_provider = None  # rebuild app with the sick provider
        sick_server = SystemStatusServer(runtime, host="127.0.0.1", port=0,
                                         perf_provider=lambda: sick)
        await sick_server.start()
        try:
            rep2 = Report()
            await check_perf(rep2, f"http://127.0.0.1:{sick_server.port}")
            statuses = {c: s for s, c, _ in rep2.rows}
            assert statuses.get("perf engine") == WARN
            assert statuses.get("perf engine HBM") == WARN
            assert statuses.get("perf engine roofline") == WARN
        finally:
            await sick_server.stop()
    finally:
        if frontend is not None:
            await frontend.stop()
        if server is not None:
            await server.stop()
        engine.stop()
        await runtime.close()
