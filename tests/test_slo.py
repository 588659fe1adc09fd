"""SLO plane, per-request accounting, and the engine flight recorder
(docs/OBSERVABILITY.md "SLO plane" / "Per-request accounting" /
"Engine flight recorder").

Everything latency-sensitive is fake-clock driven: burn-rate alerts
fire and clear purely from observe() calls against an injected clock.
The chaos scenario runs the REAL tiny TPUEngine under an
``engine.stall_ms`` fault plan and asserts the decode-stall anomaly
trigger produces a diagnostic bundle with the flight ring, recent
spans, and a metrics snapshot. The docs-drift guard pins every
``dynamo_tpu_*`` name in docs/OBSERVABILITY.md to a real registration
site in the source.
"""

import asyncio
import json
import pathlib
import re
import time
import tracemalloc

import aiohttp
import pytest
from conftest import async_test

from dynamo_tpu.llm.recorder import (RequestLedger, finish_account,
                                     make_account)
from dynamo_tpu.runtime import flight, slo
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.slo import (WINDOWS, SloConfig, SloPlane,
                                    SloPressure)

REPO = pathlib.Path(__file__).resolve().parent.parent


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_plane(clk, metrics=None, **cfg) -> SloPlane:
    defaults = dict(ttft_p99_ms=100.0, min_events=5)
    defaults.update(cfg)
    return SloPlane(SloConfig(**defaults), metrics=metrics, clock=clk)


# -- burn-rate alerting (fake clock) ------------------------------------------


def test_slo_unit_fast_burn_fires_at_documented_threshold_and_clears():
    """100% bad traffic burns at 1/budget = 100x: both fast windows
    cross the documented 14.4 threshold -> page; good traffic drains
    the 5m window -> clears. No wall time involved."""
    clk = FakeClock()
    pages = []
    plane = make_plane(clk)
    plane.on_page(lambda target, sev: pages.append((target, sev)))
    # 30 minutes of healthy traffic: no alert, SLI 1.0.
    for _ in range(180):
        clk.advance(10.0)
        plane.observe_ttft(0.01)
    assert plane.alerts["ttft"] == {"fast": False, "slow": False}
    # 10 minutes of 100% SLO-violating traffic.
    for _ in range(60):
        clk.advance(10.0)
        plane.observe_ttft(5.0)
    plane.evaluate()
    assert plane.alerts["ttft"]["fast"] is True
    assert ("ttft", "fast") in pages
    assert plane.pages_total == 1
    burn_5m, _ = plane.burn_rate("ttft", WINDOWS["5m"])
    assert burn_5m > plane.cfg.fast_burn
    # Recovery: healthy traffic clears the short window.
    for _ in range(60):
        clk.advance(10.0)
        plane.observe_ttft(0.01)
    plane.evaluate()
    assert plane.alerts["ttft"]["fast"] is False
    # The re-fire on renewed burn is a NEW page (rising edge counted).
    for _ in range(60):
        clk.advance(10.0)
        plane.observe_ttft(5.0)
    plane.evaluate()
    assert plane.pages_total == 2


def test_slo_unit_fast_page_needs_both_windows():
    """A 5m blip with a healthy 1h window must NOT page (the long
    window is the not-a-blip guard)."""
    clk = FakeClock()
    plane = make_plane(clk)
    # 55 minutes healthy, then 4 minutes of pure badness.
    for _ in range(330):
        clk.advance(10.0)
        plane.observe_ttft(0.01)
    for _ in range(24):
        clk.advance(10.0)
        plane.observe_ttft(5.0)
    plane.evaluate()
    b5, _ = plane.burn_rate("ttft", WINDOWS["5m"])
    b1h, _ = plane.burn_rate("ttft", WINDOWS["1h"])
    assert b5 > plane.cfg.fast_burn > b1h
    assert plane.alerts["ttft"]["fast"] is False


def test_slo_unit_min_events_suppresses_idle_page():
    clk = FakeClock()
    plane = make_plane(clk, min_events=10)
    for _ in range(3):  # 3 bad events on an idle fleet: not a page
        clk.advance(10.0)
        plane.observe_ttft(9.0)
    plane.evaluate()
    assert plane.alerts["ttft"]["fast"] is False


def test_slo_unit_slow_burn_ticket_and_availability_semantics():
    clk = FakeClock()
    plane = make_plane(clk, ttft_p99_ms=0.0, error_rate=0.01,
                       goodput=0.9, min_events=5)
    assert set(plane.targets) == {"availability", "goodput"}
    # 2% errors sustained: burn 2.0 > slow threshold 1.0 but far from
    # the 14.4 page. Sheds count against goodput only.
    for i in range(3000):
        clk.advance(60.0)
        ok = i % 50 != 0
        plane.observe_request(ok=ok, shed=False)
    plane.evaluate()
    assert plane.alerts["availability"]["slow"] is True
    assert plane.alerts["availability"]["fast"] is False
    # Sheds: availability unaffected, goodput burns.
    clk2 = FakeClock()
    plane2 = make_plane(clk2, ttft_p99_ms=0.0, error_rate=0.01,
                        goodput=0.99, min_events=5)
    for _ in range(600):
        clk2.advance(10.0)
        plane2.observe_request(ok=False, shed=True)
    plane2.evaluate()
    assert plane2.alerts["goodput"]["fast"] is True
    a_burn, _ = plane2.burn_rate("availability", WINDOWS["5m"])
    assert a_burn == 0.0


def test_slo_unit_pressure_levels_and_snapshot():
    clk = FakeClock()
    m = MetricsRegistry()
    plane = make_plane(clk, metrics=m.namespace("ns"), error_rate=0.001)
    p = plane.pressure()
    assert isinstance(p, SloPressure)
    assert p.level == 0 and p.failing == ()
    for _ in range(120):
        clk.advance(10.0)
        plane.observe_ttft(9.0)  # ttft pages
    p = plane.pressure()
    assert p.level == 2 and "ttft" in p.failing
    assert p.worst_burn > plane.cfg.fast_burn
    # availability paging escalates to level 3 (ttft still burning).
    for _ in range(120):
        clk.advance(10.0)
        plane.observe_ttft(9.0)
        plane.observe_request(ok=False)
    p = plane.pressure()
    assert p.level == 3
    snap = plane.snapshot()
    assert snap["enabled"] is True
    assert snap["targets"]["ttft"]["alerts"]["fast"] is True
    assert snap["targets"]["ttft"]["windows"]["5m"]["burn"] > 14.4
    assert snap["pressure"]["level"] == 3
    # Gauges landed in exposition with objective/window labels.
    expo = m.expose().decode()
    assert "dynamo_tpu_slo_sli" in expo
    assert "dynamo_tpu_slo_burn_rate" in expo
    assert 'objective="ttft"' in expo
    assert 'severity="fast"' in expo


def test_slo_unit_disabled_plane_is_noop():
    plane = SloPlane(SloConfig(enabled=False, ttft_p99_ms=50.0))
    assert not plane.enabled
    plane.observe_ttft(9.0)
    plane.observe_request(ok=False)
    assert plane.pressure().level == 0
    assert plane.snapshot()["targets"] == {}


def test_config_unit_slo_env_and_toml_layering(tmp_path, monkeypatch):
    cfg = RuntimeConfig.from_settings()
    assert cfg.slo.enabled and cfg.slo.ttft_p99_ms == 0.0
    toml = tmp_path / "cfg.toml"
    toml.write_text("[slo]\nttft_p99_ms = 250.0\nerror_rate = 0.01\n")
    monkeypatch.setenv("DTPU_SLO_TTFT_P99_MS", "500")
    monkeypatch.setenv("DTPU_SLO_REQUEST_LOG_PATH", "/tmp/reqs.jsonl")
    cfg = RuntimeConfig.from_settings(str(toml))
    assert cfg.slo.ttft_p99_ms == 500.0          # env beats TOML
    assert cfg.slo.error_rate == 0.01            # TOML beats default
    assert cfg.slo.request_log_path == "/tmp/reqs.jsonl"  # str field
    targets = cfg.slo.targets()
    assert targets["ttft"] == (0.5, 0.99)
    assert targets["availability"] == (0.0, 0.99)


# -- per-request accounting ----------------------------------------------------


def test_ledger_unit_ring_counts_and_percentiles():
    ledger = RequestLedger(capacity=4)
    clk_seen = []
    for i in range(6):
        acct = make_account("chat_completions", "m")
        acct["_itls"] = [0.01] * 99 + [0.5]
        acct.update(prompt_tokens=10, output_tokens=5)
        finish_account(acct, "ok" if i % 2 == 0 else "shed",
                       reason=None if i % 2 == 0 else "queue_full",
                       http_status=200 if i % 2 == 0 else 503,
                       ledger=ledger)
        clk_seen.append(acct)
    assert ledger.total == 6
    assert ledger.counts["ok"] == 3 and ledger.counts["shed"] == 3
    recent = ledger.recent(10)
    assert len(recent) == 4  # bounded ring
    rec = recent[0]
    assert rec["itl_p50_s"] == pytest.approx(0.01)
    assert rec["itl_p99_s"] == pytest.approx(0.5)
    assert "_t0" not in rec and "_itls" not in rec
    snap = ledger.snapshot(limit=2)
    assert snap["total"] == 6 and len(snap["records"]) == 2


def test_ledger_unit_ctx_attribution_and_slo_feed():
    class Ctx:
        id = "r1"
        trace_id = "t" * 32
        values = {"worker_id": "3f2a", "migrations": 2,
                  "reuse_tokens": 128, "kv_hit_ratio": 0.5}

    clk = FakeClock()
    plane = make_plane(clk, ttft_p99_ms=0.0, goodput=0.9, min_events=1)
    ledger = RequestLedger(capacity=8)
    acct = make_account("chat_completions", "m", Ctx())
    finish_account(acct, "shed", "deadline", 429, ctx=Ctx(),
                   ledger=ledger, slo_plane=plane)
    rec = ledger.recent(1)[0]
    assert rec["worker_id"] == "3f2a" and rec["migrations"] == 2
    assert rec["reuse_tokens"] == 128 and rec["queue_wait_s"] is None
    assert rec["reason"] == "deadline" and rec["status"] == "shed"
    good, total = plane._series["goodput"].window(300)
    assert (good, total) == (0, 1)  # shed = bad for goodput


@async_test
async def test_ledger_unit_jsonl_sink_reuses_recorder(tmp_path):
    path = str(tmp_path / "requests.jsonl")
    ledger = RequestLedger(capacity=8, path=path)
    for i in range(3):
        acct = make_account("completions", "m")
        finish_account(acct, "ok", http_status=200, ledger=ledger)
    await asyncio.sleep(0.05)  # let the appender drain
    await ledger.close()
    lines = [json.loads(line) for line in open(path)]
    assert len(lines) == 3
    assert all(rec["status"] == "ok" for rec in lines)


def test_slo_report_rollup(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "slo_report", REPO / "scripts" / "slo_report.py")
    slo_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(slo_report)

    path = tmp_path / "requests.jsonl"
    rows = []
    for tenant, status, reason, ttft in (
            ("acme", "ok", None, 0.1), ("acme", "ok", None, 0.2),
            ("acme", "shed", "deadline", None),
            ("bigco", "error", "TypeError", 0.9),
            ("bigco", "ok", None, 0.3)):
        rows.append({"tenant": tenant, "priority": "interactive",
                     "status": status, "reason": reason, "ttft_s": ttft,
                     "prompt_tokens": 10, "output_tokens": 4,
                     "itl_p99_s": 0.02})
    path.write_text("\n".join(json.dumps(r) for r in rows)
                    + "\nnot json\n")
    records = slo_report.load_records(str(path))
    assert len(records) == 5  # torn line skipped
    table = slo_report.rollup(records, ["tenant"])
    acme = table[("acme",)]
    assert acme["requests"] == 3 and acme["shed"] == 1
    assert acme["shed_rate"] == pytest.approx(1 / 3, abs=1e-3)
    assert acme["reasons"] == {"deadline": 1}
    bigco = table[("bigco",)]
    assert bigco["error_rate"] == 0.5
    out = slo_report.render(table, ["tenant"])
    assert "acme" in out and "deadline=1" in out
    rc = slo_report.main([str(path), "--by", "tenant", "--json"])
    assert rc == 0


# -- flight recorder -----------------------------------------------------------


def test_flight_unit_ring_wrap_idle_skip_freeze():
    rec = flight.FlightRecorder(capacity=4)
    for i in range(6):
        rec.record(float(i), 0.01, 2, 0, 100, 0, 1, 0, 0, 0.0, i)
    rows = rec.dump()
    assert [r["step"] for r in rows] == [2, 3, 4, 5]  # oldest evicted
    assert rows[0]["active"] == 2 and rows[0]["free_pages"] == 100
    # Idle-stable windows are skipped; the transition row is kept.
    rec.record(7.0, 0.0, 0, 0, 100, 0, 0, 0, 0, 0.0, 7)   # first idle: kept
    rec.record(8.0, 0.0, 0, 0, 100, 0, 0, 0, 0, 0.0, 8)   # stable: skipped
    rec.record(9.0, 0.0, 0, 0, 100, 0, 0, 0, 0, 0.0, 9)   # stable: skipped
    assert rec.skipped_idle == 2
    assert rec.dump()[-1]["step"] == 7
    # Freeze: first wins, writes stop, thaw resumes.
    assert rec.freeze("anomaly") is True
    assert rec.freeze("second") is False
    rec.record(10.0, 0.01, 3, 0, 50, 0, 0, 0, 0, 0.0, 10)
    assert rec.dump()[-1]["step"] == 7
    assert rec.meta()["frozen_reason"] == "anomaly"
    rec.thaw()
    rec.record(11.0, 0.01, 3, 0, 50, 0, 0, 0, 0, 0.0, 11)
    assert rec.dump()[-1]["step"] == 11


def test_flight_steady_state_zero_allocations():
    """Acceptance: the flight recorder's per-window cost is
    allocation-free in steady state — both the recording path and the
    idle-stable skip path retain nothing (same discipline as
    test_disabled_recorder_zero_allocations)."""
    rec = flight.FlightRecorder(capacity=64)

    def hot_loop(n):
        for _ in range(n):
            rec.record(1.5, 0.01, 4, 1, 100, 32, 1, 0, 0, 0.0, 7)

    def idle_loop(n):
        for _ in range(n):
            rec.record(1.5, 0.0, 0, 0, 100, 0, 0, 0, 0, 0.0, 7)

    def measure(loop):
        loop(200)   # warm-up: method caches, numpy casts, frame reuse
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            loop(5000)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        stats = [s for s in after.compare_to(before, "filename")
                 if "flight.py" in (s.traceback[0].filename or "")]
        return sum(s.size_diff for s in stats), stats

    for name, loop in (("record", hot_loop), ("idle-skip", idle_loop)):
        # The interpreter may allocate one frame/cache object at the
        # first traced call (a one-time CPython artifact, not recorder
        # state) — so require a CLEAN steady-state round within three
        # measurements. A genuine per-call allocation (5000 calls per
        # round) can never produce one.
        results = []
        for _ in range(3):
            grown, stats = measure(loop)
            results.append((grown, stats))
            if grown <= 0:
                break
        assert results[-1][0] <= 0, (name, results)


def test_flight_trigger_freezes_for_the_copy_and_loses_no_row(
        tmp_path, monkeypatch):
    """The ring is frozen while ``trigger`` copies it, not while the
    bundle is written: the row the engine hands in next is kept (a
    reader of that window finds ``missed`` 0), and the bundle holds the
    rows before the anomaly under the reason that froze them."""
    monkeypatch.setattr(flight, "_bundle_dir", str(tmp_path))
    monkeypatch.setattr(flight, "_last_trigger_t", -1e18)
    rec = flight.get_recorder()
    rec.thaw()
    rec.clear()
    for i in range(3):
        assert rec.record(10.0 + i, 0.01, 2, 0, 10, 0, 0, 0, 1, 0.0, i)
    assert flight.trigger("unit_copy") is True
    assert rec.frozen is False
    assert rec.record(13.0, 0.01, 2, 0, 10, 0, 0, 0, 1, 0.0, 3) is True
    got = rec.between(9.0, 14.0)
    assert (got["rows"], got["missed"]) == (4, 0)
    for _ in range(100):   # the writer renames a finished file into place
        bundles = list(tmp_path.glob("flight-*unit_copy*.json"))
        if bundles:
            break
        time.sleep(0.02)
    bundle = json.loads(bundles[0].read_text())
    assert [w["step"] for w in bundle["flight"]["windows"]] == [0, 1, 2]
    assert bundle["flight"]["meta"]["frozen_reason"] == "unit_copy"


def test_flight_trigger_throttles_and_writes_bundle(tmp_path):
    clk = FakeClock(1000.0)
    flight.configure(bundle_dir=str(tmp_path), cooldown_s=60.0,
                     config_fingerprint={"decode_window": 8})
    flight._last_trigger_t = -1e18
    rec = flight.get_recorder()
    rec.thaw()
    rec.record(1.0, 0.01, 2, 0, 10, 0, 0, 0, 1, 0.0, 1)
    assert flight.trigger("unit_anomaly", clock=clk) is True
    assert flight.trigger("unit_anomaly", clock=clk) is False  # cooldown
    clk.advance(61.0)
    # Background writer: wait for the first bundle to land + thaw.
    for _ in range(100):
        if list(tmp_path.glob("flight-*unit_anomaly*.json")) \
                and not rec.frozen:
            break
        time.sleep(0.02)
    bundles = list(tmp_path.glob("flight-*unit_anomaly*.json"))
    assert bundles, "bundle never written"
    bundle = json.loads(bundles[0].read_text())
    assert bundle["reason"] == "unit_anomaly"
    assert bundle["flight"]["windows"]
    assert "traceEvents" in bundle["spans"]
    assert bundle["config_fingerprint"]["config"]["decode_window"] == 8
    assert bundle["config_fingerprint"]["sha256"]
    assert rec.frozen is False  # thawed after capture
    assert flight.trigger("unit_anomaly_2", clock=clk) is True


def test_flight_slo_page_hook(tmp_path):
    """A fast-burn SLO page freezes the ring and captures a bundle; a
    slow ticket does not."""
    flight.configure(bundle_dir=str(tmp_path), cooldown_s=0.0)
    flight._last_trigger_t = -1e18
    flight.on_slo_page("ttft", "slow")
    assert not list(tmp_path.glob("flight-*.json"))
    flight.on_slo_page("ttft", "fast")
    for _ in range(100):
        if list(tmp_path.glob("flight-*slo_burn_ttft*.json")):
            break
        time.sleep(0.02)
    assert list(tmp_path.glob("flight-*slo_burn_ttft*.json"))


# -- chaos: induced decode stall -> diagnostic bundle --------------------------


@async_test(timeout=240)
async def test_chaos_decode_stall_produces_diagnostic_bundle(tmp_path):
    """Acceptance: under the seeded chaos plane an induced decode stall
    trips the flight-recorder anomaly trigger; the resulting bundle
    holds the flight ring (with live windows), recent spans, and a
    metrics snapshot."""
    from test_engine import tiny_config

    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime import chaos
    from dynamo_tpu.runtime.context import Context

    registry = MetricsRegistry()
    # Cooldown shorter than the run but long enough that the ring is
    # thawed (capture finished) while stalled windows record — the
    # SECOND trigger's bundle must contain them.
    flight.configure(metrics=registry, bundle_dir=str(tmp_path),
                     stall_s=0.05, cooldown_s=0.25,
                     config_fingerprint={"engine": "tiny"})
    flight._last_trigger_t = -1e18
    flight.get_recorder().thaw()
    flight.get_recorder().clear()  # windows from earlier tests
    # Small decode windows force MANY window dispatches, so the chaos
    # stall produces a train of over-threshold gaps (and the ring holds
    # live windows by the time later captures fire).
    engine = TPUEngine(tiny_config(decode_window=2, pipeline_depth=1),
                       metrics_registry=registry.namespace("ns")
                       .component("tpu"))
    try:
        # Every engine-loop iteration freezes 120ms: every decode
        # dispatch gap crosses the 50ms threshold deterministically.
        with chaos.active("seed=3;engine.stall_ms@engine=120..120:1"):
            req = PreprocessedRequest(model="m", token_ids=list(range(24)))
            req.stop_conditions.max_tokens = 20
            req.stop_conditions.ignore_eos = True
            tokens = []
            async for out in engine.generate(req, Context()):
                tokens.extend(out.get("token_ids", []))
            assert len(tokens) == 20  # the stall must not break serving
        # The cooldown-free trigger fires on every stalled gap; the
        # earliest capture can precede the first recorded window, and a
        # bundle may still be mid-write when globbed — poll until one
        # parseable bundle with live windows appears.
        bundle = None
        for _ in range(300):
            for path in sorted(tmp_path.glob(
                    "flight-*decode_stall*.json")):
                try:
                    candidate = json.loads(path.read_text())
                except json.JSONDecodeError:
                    continue  # writer still flushing
                if any(w["stall_s"] >= 0.05
                       for w in candidate["flight"]["windows"]):
                    bundle = candidate
                    break
            if bundle is not None:
                break
            await asyncio.sleep(0.02)
        assert bundle is not None, \
            "decode stall never produced a bundle with flight windows"
        assert bundle["reason"].startswith("decode_stall")
        windows = bundle["flight"]["windows"]
        assert any(w["active"] > 0 for w in windows)
        assert any(w["stall_s"] >= 0.05 for w in windows)
        assert "traceEvents" in bundle["spans"]
        assert "dynamo_tpu_decode_stall_seconds" in bundle["metrics"]
        assert engine.decode_stall_max_s >= 0.05
    finally:
        engine.stop()


# -- /debug endpoints on the status server + frontend --------------------------


@async_test(timeout=120)
async def test_debug_endpoints_on_status_server_and_frontend(tmp_path):
    """/debug/slo, /debug/requests, /debug/flight are served by BOTH
    the worker SystemStatusServer and the OpenAI frontend (shared
    add_debug_routes), and the doctor's observability probe reads them."""
    from dynamo_tpu.doctor import FAIL, OK, WARN, Report, \
        check_observability
    from dynamo_tpu.llm.discovery import ModelManager
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.recorder import get_ledger
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.health import SystemStatusServer

    runtime = await DistributedRuntime.detached(RuntimeConfig())
    plane = slo.configure(SloConfig(ttft_p99_ms=500.0),
                          metrics=runtime.metrics)
    flight.configure(metrics=runtime.metrics, bundle_dir=str(tmp_path))
    plane.observe_ttft(0.1)
    get_ledger().record({"ts": 1.0, "status": "ok", "route": "chat"})
    server = SystemStatusServer(runtime, host="127.0.0.1", port=0)
    await server.start()
    frontend = HttpService(runtime, ModelManager(), host="127.0.0.1",
                           port=0)
    await frontend.start()
    try:
        async with aiohttp.ClientSession() as session:
            for port in (server.port, frontend.port):
                base = f"http://127.0.0.1:{port}"
                async with session.get(f"{base}/debug/slo") as resp:
                    assert resp.status == 200
                    body = await resp.json()
                    assert body["enabled"] is True
                    assert "ttft" in body["targets"]
                async with session.get(
                        f"{base}/debug/requests?limit=5") as resp:
                    assert resp.status == 200
                    body = await resp.json()
                    assert body["total"] >= 1
                async with session.get(f"{base}/debug/flight") as resp:
                    assert resp.status == 200
                    body = await resp.json()
                    assert body["meta"]["capacity"] > 0
            # Manual capture via POST writes a bundle.
            async with session.post(
                    f"http://127.0.0.1:{server.port}/debug/flight",
                    json={"reason": "operator",
                          "out_dir": str(tmp_path)}) as resp:
                assert resp.status == 200
                body = await resp.json()
            assert pathlib.Path(body["bundle"]).exists()
        # Doctor: OK rows for the whole observability surface.
        rep = Report()
        await check_observability(
            rep, f"http://127.0.0.1:{server.port}")
        by_check = {check: status for status, check, _ in rep.rows}
        assert by_check["metrics exposition"] == OK
        assert by_check["/debug/slo"] == OK
        assert by_check["/debug/flight"] == OK
        assert not any(s == FAIL for s, _, _ in rep.rows)
        # No targets configured -> WARN, not FAIL.
        slo.configure(SloConfig())
        rep2 = Report()
        await check_observability(
            rep2, f"http://127.0.0.1:{server.port}")
        assert {c: s for s, c, _ in rep2.rows}["/debug/slo"] == WARN
    finally:
        await frontend.stop()
        await server.stop()
        await runtime.close()
        slo.configure(SloConfig())


# -- docs-drift guard ----------------------------------------------------------

_REGISTER_RE = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"']([a-z0-9_]+)[\"']")
_DOC_NAME_RE = re.compile(r"dynamo_tpu_([a-z0-9_]+)")
_EXPO_SUFFIXES = ("_bucket", "_sum", "_count", "_total")


def _registered_metric_names() -> set:
    names = set()
    for path in (REPO / "dynamo_tpu").rglob("*.py"):
        names.update(_REGISTER_RE.findall(path.read_text()))
    return names


def test_docs_drift_every_documented_metric_is_registered():
    """docs/OBSERVABILITY.md can't name series that don't exist: every
    dynamo_tpu_* token in the doc must match a registration site in
    the source (modulo prometheus exposition suffixes)."""
    registered = _registered_metric_names()
    assert registered, "metric registration scan found nothing"
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    assert documented, "no dynamo_tpu_* names found in the doc"
    unknown = []
    for name in sorted(documented):
        if name.endswith("_"):  # wildcard family, e.g. dynamo_tpu_slo_*
            if not any(r.startswith(name) for r in registered):
                unknown.append(name + "*")
            continue
        candidates = {name}
        for suffix in _EXPO_SUFFIXES:
            if name.endswith(suffix):
                candidates.add(name[: -len(suffix)])
        if not candidates & registered:
            unknown.append(name)
    assert not unknown, (
        f"documented in docs/OBSERVABILITY.md but registered nowhere in "
        f"dynamo_tpu/: {unknown}")


def test_docs_drift_new_series_are_documented():
    """...and the SLO/flight/overload series this round wired into the
    dashboard must be documented (satellite acceptance)."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    required = {
        "slo_sli", "slo_burn_rate", "slo_alert_active",
        "shed_total", "admitted_total", "concurrency_limit",
        "breaker_open", "breaker_opens_total",
        "prefill_chunk_tokens_total", "prefill_chunks_inflight",
        "decode_stall_seconds",
        "role_flips_total", "worker_role",
    }
    missing = required - documented
    assert not missing, f"undocumented series: {sorted(missing)}"


def test_docs_drift_perf_series_are_documented():
    """PR 9 acceptance: every dynamo_tpu_perf_* series registered in the
    source is documented in docs/OBSERVABILITY.md "Engine perf plane" —
    the whole family, scanned from registration sites so a new perf_
    metric can't ship undocumented."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    perf_registered = {n for n in _registered_metric_names()
                       if n.startswith("perf_")}
    assert len(perf_registered) >= 9, \
        f"expected the full perf_ family, scan found {sorted(perf_registered)}"
    missing = perf_registered - documented
    assert not missing, f"undocumented perf series: {sorted(missing)}"


def test_docs_drift_journal_series_are_documented():
    """PR 10 acceptance: every dynamo_tpu_journal_* series registered in
    the source is documented in docs/OBSERVABILITY.md "Decision plane" —
    whole-family scan like the kv_/perf_ guards."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    registered = {n for n in _registered_metric_names()
                  if n.startswith("journal_")}
    assert len(registered) >= 2, \
        f"expected the journal_ family, scan found {sorted(registered)}"
    missing = registered - documented
    assert not missing, f"undocumented journal series: {sorted(missing)}"


def test_docs_drift_canary_series_are_documented():
    """...and the canary prober's whole family likewise."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    registered = {n for n in _registered_metric_names()
                  if n.startswith("canary_")}
    assert len(registered) >= 2, \
        f"expected the canary_ family, scan found {sorted(registered)}"
    missing = registered - documented
    assert not missing, f"undocumented canary series: {sorted(missing)}"


def test_docs_drift_autoscale_series_are_documented():
    """Autoscaling acceptance: the planner-side autoscale_ family and
    the worker-side standby_ family are whole-family documented in
    docs/OBSERVABILITY.md "Autoscaling"."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    for family, minimum in (("autoscale_", 5), ("standby_", 3)):
        registered = {n for n in _registered_metric_names()
                      if n.startswith(family)}
        assert len(registered) >= minimum, \
            f"expected the {family} family, scan found {sorted(registered)}"
        missing = registered - documented
        assert not missing, \
            f"undocumented {family} series: {sorted(missing)}"


def test_docs_drift_adapter_series_are_documented():
    """Batched-LoRA acceptance: the dynamo_tpu_adapter_* family
    (engine/lora.py AdapterStore -> AdapterMetricsUpdater) is
    whole-family documented in docs/OBSERVABILITY.md "Adapters"."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    registered = {n for n in _registered_metric_names()
                  if n.startswith("adapter_")}
    assert len(registered) >= 5, \
        f"expected the adapter_ family, scan found {sorted(registered)}"
    missing = registered - documented
    assert not missing, f"undocumented adapter series: {sorted(missing)}"


def test_docs_drift_kv_series_are_documented():
    """PR 8 acceptance: every dynamo_tpu_kv_* series registered in the
    source is documented in docs/OBSERVABILITY.md "KV & capacity" — the
    whole family, scanned from registration sites so a new kv_ metric
    can't ship undocumented."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(_DOC_NAME_RE.findall(doc))
    kv_registered = {n for n in _registered_metric_names()
                     if n.startswith("kv_")
                     and not n.startswith("kv_transfer")}
    assert len(kv_registered) >= 20, \
        f"expected the full kv_ family, scan found {sorted(kv_registered)}"
    missing = kv_registered - documented
    assert not missing, f"undocumented kv series: {sorted(missing)}"
