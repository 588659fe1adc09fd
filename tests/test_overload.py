"""Overload defense: deterministic limiter/breaker unit matrix + the
chaos-driven overload scenario matrix (docs/RESILIENCE.md "Overload
model").

Unit tests (``-k unit``, the scripts/check.sh overload smoke stage) are
fully deterministic: a fake clock drives the AIMD limiter, the deadline
projections, and the breaker state machine — no sleeps, no wall time.

The e2e scenarios run a mocker fleet behind the real HTTP frontend at
5x offered load and assert the core overload invariant:

    every request either completes, or is shed with a typed 429/503 +
    Retry-After, before its deadline — zero silent drops; a chaos-
    stalled worker's breaker opens within the configured failure window
    and traffic converges on healthy workers, then recovers on a
    half-open probe.
"""

import asyncio
import os
import time

import aiohttp
import pytest
from conftest import async_test

from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.kv_router import make_kv_router_factory
from dynamo_tpu.llm.kv_router.publisher import (KvEventPublisher,
                                                WorkerMetricsPublisher)
from dynamo_tpu.llm.mocker import MockerConfig, MockerEngine
from dynamo_tpu.llm.model_card import register_llm
from dynamo_tpu.llm.tokenizer import make_test_tokenizer
from dynamo_tpu.runtime import chaos
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.coordinator import Coordinator
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.errors import OverloadedError, RateLimitedError
from dynamo_tpu.runtime.overload import (CLOSED, OPEN, AdaptiveLimiter,
                                         BreakerBoard, CircuitBreaker,
                                         OverloadConfig)

NS = "ovl"
FAST = dict(prefill_tokens_per_s=1e7, decode_step_s=0.0005)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# -- AIMD limiter unit matrix (deterministic, no sleeps) -----------------------


@async_test
async def test_limiter_unit_aimd_increase_and_decrease():
    clk = FakeClock()
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=4, min_concurrency=1, max_concurrency=8,
        target_latency_ms=100, decrease_cooldown_s=1.0), clock=clk)
    # Under-target completions grow the limit additively (~ +1 per
    # limit-many completions).
    for _ in range(8):
        p = await lim.admit()
        p.note_latency(0.01)
        p.release()
    assert 5.0 <= lim.limit <= 7.0, lim.limit
    # One over-target completion shrinks multiplicatively.
    before = lim.limit
    clk.advance(5.0)
    p = await lim.admit()
    p.note_latency(1.0)
    p.release()
    assert lim.limit == pytest.approx(before * 0.7)
    # A burst of stale over-target completions inside the cooldown only
    # decreases once.
    after_first = lim.limit
    for _ in range(3):
        p = await lim.admit()
        p.note_latency(1.0)
        p.release()
    assert lim.limit == after_first
    # ...and never below the floor.
    for _ in range(50):
        clk.advance(2.0)
        p = await lim.admit()
        p.note_latency(9.9)
        p.release()
    assert lim.limit == 1.0


@async_test
async def test_limiter_unit_queue_bound_sheds_typed_503():
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=1, queue_depth=2), clock=FakeClock())
    held = await lim.admit()
    waiters = [asyncio.ensure_future(lim.admit()) for _ in range(2)]
    await asyncio.sleep(0)  # let them enqueue
    with pytest.raises(OverloadedError) as exc_info:
        await lim.admit()
    assert exc_info.value.retryable
    assert exc_info.value.retry_after_s is not None
    assert lim.shed_counts[("queue_full", "interactive")] == 1
    held.release()
    for w in waiters:
        (await w).release()


@async_test
async def test_limiter_unit_deadline_infeasible_sheds_immediately():
    """A deadline the admission-queue projection cannot meet is rejected
    NOW (429, non-retryable) instead of timing out in the queue."""
    clk = FakeClock()
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=1, queue_depth=8), clock=clk)
    lim.avg_service_s = 2.0  # calibrated: each slot takes ~2s
    held = await lim.admit()
    queued = [asyncio.ensure_future(lim.admit(deadline_ms=60_000))
              for _ in range(3)]
    await asyncio.sleep(0)
    t0 = time.monotonic()
    with pytest.raises(RateLimitedError) as exc_info:
        # 3 ahead at limit 1 and 2s each -> ~8s projected; 500ms deadline
        # is infeasible.
        await lim.admit(deadline_ms=500)
    assert time.monotonic() - t0 < 1.0, "shed must not wait for the deadline"
    assert not exc_info.value.retryable
    assert exc_info.value.retry_after_s is not None
    assert lim.shed_counts[("deadline", "interactive")] == 1
    # An uncalibrated limiter never deadline-sheds (projection is 0).
    lim2 = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=1, queue_depth=8), clock=clk)
    h2 = await lim2.admit()
    q2 = asyncio.ensure_future(lim2.admit(deadline_ms=1))
    await asyncio.sleep(0)
    assert lim2.waiting() == 1  # queued, not shed
    h2.release()
    (await q2).release()
    held.release()
    for w in queued:
        w.cancel()


@async_test
async def test_limiter_unit_batch_sheds_first_and_cannot_starve_interactive():
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=1, queue_depth=10, batch_shed_level=2,
        level1_pressure=0.95, level2_pressure=1.25), clock=FakeClock())
    held = await lim.admit()
    # Saturated but queue nearly empty: batch still queues (level 1).
    batch_wait = asyncio.ensure_future(lim.admit(priority="batch"))
    await asyncio.sleep(0)
    assert lim.waiting() == 1
    # Interactive waiters push pressure past level 2: new batch sheds.
    inter_waits = [asyncio.ensure_future(lim.admit()) for _ in range(4)]
    await asyncio.sleep(0)
    assert lim.pressure_level() >= 2
    with pytest.raises(RateLimitedError):
        await lim.admit(priority="batch")
    assert lim.shed_counts[("priority", "batch")] == 1
    # Freed slots go to interactive waiters STRICTLY before the batch
    # waiter that queued first.
    held.release()
    for fut in inter_waits:
        permit = await fut
        assert not batch_wait.done(), "batch must not pass queued interactive"
        permit.release()
    (await batch_wait).release()


@async_test
async def test_limiter_unit_deadline_expires_while_queued():
    """A queued request whose (real-time) deadline lapses before a slot
    frees is shed typed, not left hanging."""
    lim = AdaptiveLimiter(OverloadConfig(initial_concurrency=1,
                                         queue_depth=4))
    held = await lim.admit()
    with pytest.raises(RateLimitedError):
        await lim.admit(deadline_ms=50)
    assert lim.shed_counts[("deadline_wait", "interactive")] == 1
    held.release()
    assert lim.inflight == 0


@async_test
async def test_limiter_unit_cancelled_waiter_leaks_no_capacity():
    """A queued caller cancelled around the tick its slot is granted
    (client disconnect) must not leak the slot. Python version
    semantics differ — 3.10 wait_for returns the already-granted permit
    (released by the caller's context manager as it unwinds), 3.11+
    raises CancelledError into the wait (the limiter hands the slot
    back itself) — either way capacity fully recovers."""
    lim = AdaptiveLimiter(OverloadConfig(initial_concurrency=1,
                                         queue_depth=4), clock=FakeClock())
    held = await lim.admit()
    waiter = asyncio.ensure_future(lim.admit())
    await asyncio.sleep(0)
    held.release()            # grants the slot to the waiter...
    waiter.cancel()           # ...which is cancelled before resuming
    try:
        permit = await waiter
        permit.release()      # what `with permit:` does while unwinding
    except asyncio.CancelledError:
        pass
    assert lim.inflight == 0
    # ...and cancellation BEFORE the grant simply drops the waiter.
    held = await lim.admit()
    waiter = asyncio.ensure_future(lim.admit())
    await asyncio.sleep(0)
    waiter.cancel()
    with pytest.raises(asyncio.CancelledError):
        await waiter
    held.release()
    assert lim.inflight == 0
    (await lim.admit()).release()   # capacity fully recovered


@async_test
async def test_limiter_unit_seeded_retry_after_deterministic():
    def script(seed):
        lim = AdaptiveLimiter(OverloadConfig(seed=seed,
                                             initial_concurrency=1),
                              clock=FakeClock())
        lim.avg_service_s = 1.0
        return [lim.retry_after_s() for _ in range(10)]

    assert script(7) == script(7)
    assert script(7) != script(8)


@async_test
async def test_limiter_unit_brownout_levels_and_clamp():
    cfg = OverloadConfig(initial_concurrency=2, queue_depth=10,
                         level1_pressure=0.95, level2_pressure=1.25,
                         level3_pressure=1.75, brownout_clamp_level=2,
                         brownout_max_tokens=64)
    lim = AdaptiveLimiter(cfg, clock=FakeClock())
    assert lim.pressure_level() == 0
    assert lim.clamp_max_tokens(1000) is None
    p1, p2 = await lim.admit(), await lim.admit()
    assert lim.pressure_level() == 1          # saturated, queue empty
    waiters = [asyncio.ensure_future(lim.admit()) for _ in range(4)]
    await asyncio.sleep(0)
    assert lim.pressure_level() == 2          # queue 40% full
    assert lim.clamp_max_tokens(1000) == 64   # brownout clamps
    assert lim.clamp_max_tokens(16) is None   # never raises a request
    more = [asyncio.ensure_future(lim.admit()) for _ in range(5)]
    await asyncio.sleep(0)
    assert lim.pressure_level() == 3
    for p in (p1, p2):
        p.release()
    for w in waiters + more:
        (await w).release()


@async_test
async def test_limiter_unit_zero_silent_drops_accounting():
    """Every admit() call lands in exactly one bucket: admitted or
    shed_counts."""
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=2, queue_depth=1, batch_shed_level=2),
        clock=FakeClock())
    lim.avg_service_s = 0.01
    outcomes = {"admitted": 0, "shed": 0}
    permits = []
    for i in range(12):
        try:
            # Deadlines are tiny so queued admits shed in ~100ms of real
            # time instead of completing: the point is the accounting,
            # not the outcome mix.
            permits.append(await lim.admit(
                priority="batch" if i % 3 == 0 else "interactive",
                deadline_ms=1 if i % 4 == 0 else 100))
            outcomes["admitted"] += 1
        except (OverloadedError, RateLimitedError):
            outcomes["shed"] += 1
    assert outcomes["admitted"] + outcomes["shed"] == 12
    assert sum(lim.admitted_total.values()) == outcomes["admitted"]
    assert sum(lim.shed_counts.values()) == outcomes["shed"]
    for p in permits:
        p.release()


# -- the limiter's ledger of its own decisions (PR 36) --------------------------

async def _complete(lim, latency_s: float) -> None:
    p = await lim.admit()
    p.note_latency(latency_s)
    p.release()


def _limit_events(rec):
    return [s for s in rec.snapshot()[0] if s.name == "overload.limit"]


@pytest.fixture
def span_ring(monkeypatch):
    """A span ring of the test's own in place of the process's."""
    from dynamo_tpu.runtime import tracing
    rec = tracing.SpanRecorder(capacity=4096)
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    return rec


def test_limiter_unit_counts_each_decision_once(span_ring):
    """increase / decrease / held are counted at the completion that
    made them, on the object and on /metrics, with the latency judged;
    an ``overload.limit`` event exactly when ``int(limit)`` changes and
    on every decrease, and no other."""
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    async def run():
        clk = FakeClock(100.0)
        reg = MetricsRegistry()
        lim = AdaptiveLimiter(OverloadConfig(
            initial_concurrency=4, min_concurrency=1, max_concurrency=8,
            target_latency_ms=100, decrease_cooldown_s=1.0), metrics=reg,
            clock=clk)
        # Three completions under the target: 4 -> 4.25 -> 4.485 -> 4.708,
        # int(limit) stays 4: counted, no event.
        for _ in range(3):
            await _complete(lim, 0.01)
        assert lim.limit_changes == {"increase": 3}
        assert _limit_events(span_ring) == []
        # The fifth crosses 5: one event, with what it judged.
        await _complete(lim, 0.01)
        await _complete(lim, 0.02)
        assert int(lim.limit) == 5 and lim.limit_changes["increase"] == 5
        (up,) = _limit_events(span_ring)
        assert up.attrs["direction"] == "increase"
        assert int(up.attrs["before"]) == 4 and int(up.attrs["after"]) == 5
        assert up.attrs["judged_ms"] == pytest.approx(20.0)
        assert up.start_mono == up.end_mono == 100.0  # the limiter's clock
        # Over the target: a decrease, then two held inside the cooldown,
        # then a second decrease that does NOT change int(limit) at the
        # floor's neighbourhood still is an event.
        clk.advance(5.0)
        await _complete(lim, 1.0)
        await _complete(lim, 1.0)
        await _complete(lim, 1.0)
        assert lim.limit_changes == {"increase": 5, "decrease": 1, "held": 2}
        events = _limit_events(span_ring)
        assert [e.attrs["direction"] for e in events] == ["increase",
                                                          "decrease"]
        down = events[-1]
        assert down.attrs["after"] == pytest.approx(
            down.attrs["before"] * 0.7)
        assert down.attrs["judged_ms"] == pytest.approx(1000.0)
        assert down.attrs["inflight"] == 0 and down.attrs["waiting"] == 0
        assert down.start_mono == 105.0
        lim.limit = 1.2
        clk.advance(2.0)
        await _complete(lim, 1.0)     # 1.2 -> 1.0 (the floor): int stays 1
        assert lim.limit == 1.0 and len(_limit_events(span_ring)) == 3
        clk.advance(2.0)
        await _complete(lim, 1.0)     # at the floor: decided, and an event
        assert lim.limit_changes["decrease"] == 3
        assert len(_limit_events(span_ring)) == 4
        # One trace for the limiter's life.
        assert len({e.trace_id for e in _limit_events(span_ring)}) == 1
        expo = reg.expose().decode()
        for direction, n in (("increase", 5), ("decrease", 3), ("held", 2)):
            (line,) = [ln for ln in expo.splitlines()
                       if ln.startswith(
                           "dynamo_tpu_overload_limit_changes_total{")
                       and f'direction="{direction}"' in ln]
            assert float(line.rsplit(" ", 1)[1]) == n, line
        (count,) = [ln for ln in expo.splitlines() if ln.startswith(
            "dynamo_tpu_overload_judged_latency_seconds_count")]
        assert float(count.rsplit(" ", 1)[1]) == 10
        # The 5 s target of the default configuration is a bucket edge.
        assert 'overload_judged_latency_seconds_bucket{' in expo \
            and 'le="5.0"' in expo

    asyncio.run(run())


def test_limiter_unit_limit_events_cannot_flood_the_ring(span_ring):
    """The integer of the limit rises once in about ``limit`` completions:
    1,000 completions under the target at limit 64 leave at most 16
    events, whatever the request rate."""
    async def run():
        lim = AdaptiveLimiter(OverloadConfig(
            initial_concurrency=64, max_concurrency=512), clock=FakeClock())
        for _ in range(1000):
            await _complete(lim, 0.5)
        assert lim.limit_changes == {"increase": 1000}
        events = _limit_events(span_ring)
        assert 1 <= len(events) <= 16, len(events)
        assert len(events) == int(lim.limit) - 64
        # The step function a reader rebuilds is unbroken.
        for a, b in zip(events, events[1:]):
            assert int(a.attrs["after"]) == int(b.attrs["before"])

    asyncio.run(run())


def test_limiter_unit_disabled_recorder_records_and_allocates_no_event(
        monkeypatch):
    import tracemalloc

    from dynamo_tpu.runtime import overload as overload_mod
    from dynamo_tpu.runtime import tracing
    rec = tracing.SpanRecorder(capacity=64, enabled=False)
    monkeypatch.setattr(tracing, "_RECORDER", rec)
    clk = FakeClock(10.0)
    lim = AdaptiveLimiter(OverloadConfig(
        initial_concurrency=2, target_latency_ms=100,
        decrease_cooldown_s=0.0), clock=clk)
    journal_off = overload_mod.journal.get_journal()
    monkeypatch.setattr(journal_off, "enabled", False)

    def churn(n):
        for _ in range(n):
            lim._observe(0.01)   # crosses an integer every few calls
            lim._observe(1.0)    # a decrease every time (no cooldown)

    churn(50)  # warm the counters' keys
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        churn(500)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(st.size_diff for st in after.compare_to(before, "filename")
                if st.traceback[0].filename.endswith(
                    ("overload.py", "tracing.py")) and st.size_diff > 0)
    assert grown < 2048, grown
    assert rec.snapshot() == ([], 0)
    assert lim.limit_changes["decrease"] == 550


def test_limiter_unit_decrease_is_a_journal_event_with_its_cause(span_ring):
    from dynamo_tpu.runtime import journal
    from dynamo_tpu.runtime.journal import EventKind

    async def run():
        clk = FakeClock(50.0)
        lim = AdaptiveLimiter(OverloadConfig(
            initial_concurrency=10, target_latency_ms=5000), clock=clk)
        j = journal.get_journal()
        seq0 = j.snapshot(limit=1)["seq"]
        await _complete(lim, 1.2)   # an increase is no decision to journal
        await _complete(lim, 7.5)
        mine = [e for e in j.since(seq0)[0]
                if e["kind"] == EventKind.LIMIT_DECREASE]
        assert len(mine) == 1
        ev = mine[0]
        assert ev["cause"] is None
        attrs = ev["attrs"]
        assert attrs["judged_ms"] == 7500.0 and attrs["target_ms"] == 5000
        assert attrs["after"] == pytest.approx(attrs["before"] * 0.7,
                                               abs=2e-3)
        # With a chaos injection active, the decrease names it.
        with chaos.active("seed=1;engine.stall_ms=x1"):
            assert chaos.fire("engine.stall_ms", "engine")
            injected = journal.recent_ref(EventKind.CHAOS_INJECT)
            assert injected is not None
            clk.advance(2.0)
            await _complete(lim, 7.5)
        caused = [e for e in j.since(seq0)[0]
                  if e["kind"] == EventKind.LIMIT_DECREASE][-1]
        assert caused["cause"] == injected

    asyncio.run(run())


REPLAY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "limiter_replay.json")


async def replay_limiter(make_limiter, config: dict, ops: list) -> list:
    """Drive one limiter through ``ops`` on a fake clock and return, per
    op, what an observer of admission can see: the grants and sheds the
    op caused (in order), then ``limit`` (as float.hex: bit for bit),
    ``inflight`` and the waiting count."""
    clk = FakeClock(1000.0)
    lim = make_limiter(OverloadConfig(**config), clk)
    events: list = []
    held: list = []        # (id, permit), in grant order
    pending: dict = {}     # id -> task still inside admit()

    async def arrive(rid, priority, deadline_ms):
        try:
            permit = await lim.admit(priority, deadline_ms)
        except (OverloadedError, RateLimitedError) as exc:
            events.append(["shed", rid, type(exc).__name__, exc.shed_reason,
                           float(exc.retry_after_s).hex()])
        except asyncio.CancelledError:
            events.append(["left", rid])
        else:
            held.append((rid, permit))
            events.append(["grant", rid])
        finally:
            pending.pop(rid, None)

    trace = []
    for op in ops:
        kind = op["op"]
        if kind == "arrive":
            pending[op["id"]] = asyncio.ensure_future(
                arrive(op["id"], op["priority"], op["deadline_ms"]))
        elif kind == "complete" and held:
            rid, permit = held.pop(op["k"] % len(held))
            if op["latency_s"] is not None:
                permit.note_latency(op["latency_s"])
            permit.release()
        elif kind == "leave" and pending:
            pending[sorted(pending)[op["k"] % len(pending)]].cancel()
        elif kind == "advance":
            clk.advance(op["dt"])
        for _ in range(4):
            await asyncio.sleep(0)
        trace.append({"events": events[:], "limit": lim.limit.hex(),
                      "inflight": lim.inflight, "waiting": lim.waiting()})
        events.clear()
    for task in pending.values():
        task.cancel()
    return trace


def test_limiter_unit_admits_exactly_as_the_parent_of_pr_36_did():
    """Counting its decisions changed nothing the limiter decides: one
    recorded sequence of arrivals, completions, latencies and departures
    replayed against today's limiter gives the trace the limiter of the
    parent commit gave (tests/data/limiter_replay.json, written by
    running this same ``replay_limiter`` over the parent's
    ``runtime/overload.py``): every grant and shed in order, ``limit``
    bit for bit, ``inflight`` and the queue after each step."""
    import json
    with open(REPLAY, encoding="utf-8") as fh:
        pinned = json.load(fh)
    got = asyncio.run(replay_limiter(
        lambda cfg, clk: AdaptiveLimiter(cfg, clock=clk),
        pinned["config"], pinned["ops"]))
    assert len(got) == len(pinned["trace"]) >= 600
    for i, (mine, theirs) in enumerate(zip(got, pinned["trace"])):
        assert mine == theirs, (i, pinned["ops"][i], mine, theirs)
    # The sequence is worth replaying: it reaches every kind of decision.
    kinds = {(e[0], *e[2:4]) for step in got for e in step["events"]}
    assert {("grant",), ("left",),
            ("shed", "OverloadedError", "queue_full"),
            ("shed", "RateLimitedError", "deadline"),
            ("shed", "RateLimitedError", "priority")} <= kinds, kinds
    limits = [float.fromhex(step["limit"]) for step in got]
    falls = sum(b < a for a, b in zip(limits, limits[1:]))
    assert falls >= 4 and max(limits) == 12.0 and min(limits) == 4.0


def test_config_unit_overload_env_and_toml_layering(tmp_path, monkeypatch):
    """OverloadConfig rides RuntimeConfig: defaults <- [overload] TOML
    table <- DTPU_OVERLOAD_* env, with per-field type mapping."""
    cfg = RuntimeConfig.from_settings()
    assert cfg.overload.enabled and cfg.overload.queue_depth == 64
    toml = tmp_path / "cfg.toml"
    toml.write_text("[overload]\nqueue_depth = 16\n"
                    "target_latency_ms = 1234.5\n")
    monkeypatch.setenv("DTPU_OVERLOAD_QUEUE_DEPTH", "8")
    monkeypatch.setenv("DTPU_OVERLOAD_ENABLED", "false")
    monkeypatch.setenv("DTPU_OVERLOAD_BREAKER_COOLDOWN_S", "2.5")
    cfg = RuntimeConfig.from_settings(str(toml))
    assert cfg.overload.queue_depth == 8          # env beats TOML
    assert cfg.overload.target_latency_ms == 1234.5   # TOML beats default
    assert cfg.overload.enabled is False
    assert cfg.overload.breaker_cooldown_s == 2.5


def test_engine_unit_brownout_level_from_ttft_projection():
    """Engine-local brownout (engine/engine.py _update_brownout): the
    projected-TTFT/budget ratio maps to pressure levels 0..3, and level
    0 whenever the budget or the projection is absent."""
    import types

    from dynamo_tpu.engine.engine import TPUEngine

    def fake(budget_ms, projected_ms):
        return types.SimpleNamespace(
            config=types.SimpleNamespace(ttft_budget_ms=budget_ms),
            estimated_ttft_ms=lambda: projected_ms,
            brownout_level=None)

    cases = [(None, 500.0, 0), (1000.0, None, 0), (1000.0, 500.0, 0),
             (1000.0, 1200.0, 1), (1000.0, 2000.0, 2), (1000.0, 9000.0, 3)]
    for budget, projected, expected in cases:
        eng = fake(budget, projected)
        TPUEngine._update_brownout(eng)
        assert eng.brownout_level == expected, (budget, projected)


# -- circuit breaker unit matrix ----------------------------------------------


def test_breaker_unit_opens_after_consecutive_failures():
    clk = FakeClock()
    cfg = OverloadConfig(breaker_failures=3, breaker_cooldown_s=2.0)
    b = CircuitBreaker(cfg, clock=clk)
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED and b.allows()
    b.record_success(0.1)      # success resets the streak
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED
    b.record_failure()
    assert b.state == OPEN and not b.allows()


def test_breaker_unit_half_open_probe_then_close_or_reopen():
    clk = FakeClock()
    cfg = OverloadConfig(breaker_failures=1, breaker_cooldown_s=2.0)
    b = CircuitBreaker(cfg, clock=clk)
    b.record_failure()
    assert b.state == OPEN and not b.allows()
    clk.advance(1.0)
    assert not b.allows()                     # still cooling down
    clk.advance(1.5)
    assert b.allows()                         # half-open: one probe
    b.on_dispatch()
    assert not b.allows()                     # probe in flight: no more
    b.record_failure()                        # probe failed -> reopen
    assert b.state == OPEN and not b.allows()
    clk.advance(2.5)
    assert b.allows()
    b.on_dispatch()
    b.record_success(0.1)                     # probe succeeded -> close
    assert b.state == CLOSED and b.allows()


def test_breaker_unit_latency_outlier_opens():
    clk = FakeClock()
    cfg = OverloadConfig(breaker_failures=2, breaker_latency_factor=5.0,
                         breaker_min_samples=5)
    b = CircuitBreaker(cfg, clock=clk)
    for _ in range(10):
        b.record_success(0.1)                 # calibrate EWMA ~0.1s
    b.record_success(3.0)                     # 30x the EWMA: outlier
    assert b.state == CLOSED and b.streak == 1
    b.record_success(3.0)
    assert b.state == OPEN
    # Under-calibrated breakers never count outliers.
    b2 = CircuitBreaker(cfg, clock=clk)
    b2.record_success(0.1)
    b2.record_success(3.0)
    b2.record_success(3.0)
    assert b2.state == CLOSED and b2.streak == 0


def test_breaker_unit_board_admits_and_excludes():
    clk = FakeClock()
    board = BreakerBoard(OverloadConfig(breaker_failures=2,
                                        breaker_cooldown_s=1.0), clock=clk)
    workers = [1, 2, 3]
    assert board.admitted(workers) == [1, 2, 3]
    board.record_failure(2)
    board.record_failure(2)
    assert board.state(2) == OPEN
    assert board.admitted(workers) == [1, 3]
    clk.advance(1.5)
    assert board.admitted(workers) == [1, 2, 3]   # half-open probe
    board.on_dispatch(2)
    assert board.admitted(workers) == [1, 3]      # probe in flight
    board.record_success(2, 0.1)
    assert board.state(2) == CLOSED
    assert board.admitted(workers) == [1, 2, 3]
    # Disabled boards never exclude.
    off = BreakerBoard(OverloadConfig(breaker_enabled=False), clock=clk)
    for _ in range(10):
        off.record_failure(1)
    assert off.admitted([1]) == [1]


# -- e2e: mocker fleet behind the real HTTP frontend --------------------------


async def start_mocker(coord, name="mock-model", migration_limit=0,
                       **cfg_kwargs):
    rt = await DistributedRuntime.from_settings(
        RuntimeConfig(coordinator_url=coord.url, lease_ttl_s=2.0,
                      namespace=NS))
    config = MockerConfig(**{**FAST, **cfg_kwargs})
    kv_pub = KvEventPublisher(rt, NS, "mocker", rt.instance_id)
    m_pub = WorkerMetricsPublisher(rt, NS, "mocker", rt.instance_id,
                                   min_interval_s=0.01)
    engine = MockerEngine(config, kv_pub, m_pub)
    endpoint = rt.namespace(NS).component("mocker").endpoint("generate")
    server = await endpoint.serve_endpoint(engine.handler(),
                                           graceful_shutdown=False)
    await register_llm(rt, endpoint, name, make_test_tokenizer(),
                       kv_cache_block_size=config.block_size,
                       migration_limit=migration_limit)
    engine.start()
    return rt, engine, server


async def start_frontend(coord, overload: OverloadConfig | None = None,
                         router_mode="round_robin",
                         stream_idle_timeout_s=300.0):
    cfg = RuntimeConfig(coordinator_url=coord.url, lease_ttl_s=2.0,
                        namespace=NS,
                        stream_idle_timeout_s=stream_idle_timeout_s)
    if overload is not None:
        cfg.overload = overload
    rt = await DistributedRuntime.from_settings(cfg)
    manager = ModelManager()
    factory = (make_kv_router_factory() if router_mode == "kv" else None)
    watcher = ModelWatcher(rt, manager, router_mode=router_mode,
                           kv_router_factory=factory)
    await watcher.start()
    limiter = (AdaptiveLimiter(cfg.overload, metrics=rt.metrics)
               if overload is not None else None)
    service = HttpService(rt, manager, host="127.0.0.1", port=0,
                          overload=limiter)
    await service.start()
    return rt, manager, watcher, service


async def wait_model(manager, name="mock-model", n_instances=1, timeout=5.0):
    for _ in range(int(timeout / 0.02)):
        served = manager.get(name)
        if served and len(served.client.instance_ids()) >= n_instances:
            return served
        await asyncio.sleep(0.02)
    raise AssertionError(f"model {name} never discovered")


async def post_chat(session, port, content, max_tokens=8, headers=None):
    t0 = time.monotonic()
    async with session.post(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        headers=headers or {},
        json={"model": "mock-model", "max_tokens": max_tokens,
              "messages": [{"role": "user", "content": content}]}) as resp:
        body = await resp.json()
        return (resp.status, body, dict(resp.headers),
                time.monotonic() - t0)


@async_test(timeout=180)
async def test_overload_matrix_5x_capacity():
    """Offered load 5x the admission capacity, under a seeded chaos
    plan: every request completes or is shed typed with Retry-After;
    goodput stays within a bound of capacity; admitted p99 is bounded;
    zero silent drops."""
    from dynamo_tpu.llm.recorder import get_ledger

    coord = Coordinator()
    await coord.start()
    overload = OverloadConfig(
        seed=11, initial_concurrency=2, max_concurrency=2,
        min_concurrency=1, queue_depth=2, default_deadline_ms=5_000,
        target_latency_ms=10_000)  # no AIMD collapse mid-test
    m1 = await start_mocker(coord, max_num_seqs=4)
    f = await start_frontend(coord, overload=overload)
    rt, manager, watcher, service = f
    deadline_s = overload.default_deadline_ms / 1000.0
    ledger_before = get_ledger().total
    try:
        await wait_model(manager)
        # Mild seeded response-plane latency chaos: shedding decisions
        # and typing must hold under jitter too.
        with chaos.active("seed=11;frame.delay_ms@service=1..5:0.3"):
            async with aiohttp.ClientSession() as session:
                # 5x: capacity in the system is concurrency 2 + queue 2.
                results = await asyncio.gather(
                    *(post_chat(session, service.port, f"req {i} words",
                                max_tokens=4)
                      for i in range(20)))
        assert len(results) == 20, "zero silent drops: every request answers"
        good = [r for r in results if r[0] == 200]
        shed = [r for r in results if r[0] in (429, 503)]
        assert len(good) + len(shed) == 20, [r[0] for r in results]
        # Goodput within a bound of capacity: everything the limiter
        # admitted completed.
        limiter = service.overload
        assert len(good) == sum(limiter.admitted_total.values())
        assert len(good) >= 2
        assert sum(limiter.shed_counts.values()) == len(shed)
        for status, body, headers, elapsed in shed:
            assert "Retry-After" in headers, (status, headers)
            assert int(headers["Retry-After"]) >= 1
            assert body["error"]["type"] == (
                "rate_limited" if status == 429 else "overloaded")
            assert elapsed < deadline_s, "sheds must not burn the deadline"
        # Admitted p99 bounded: nothing admitted may blow its deadline.
        assert max(r[3] for r in good) < deadline_s
        # shed_total{reason,priority} landed in the metrics registry.
        total = sum(limiter._m_shed.collect().values())
        assert total == len(shed)
        # Accounting stream (llm/recorder.py): EVERY request — completed
        # or shed — produced exactly one record, and every shed record
        # carries the limiter's typed reason. Zero silent drops extends
        # to the audit trail.
        ledger = get_ledger()
        assert ledger.total - ledger_before == 20
        records = ledger.recent(limit=20)
        assert all(r["status"] in ("ok", "shed") for r in records)
        shed_records = [r for r in records if r["status"] == "shed"]
        assert len(shed_records) == len(shed)
        typed_reasons = {"queue_full", "deadline", "deadline_wait",
                         "priority", "no_instances"}
        assert all(r["reason"] in typed_reasons for r in shed_records), \
            [r["reason"] for r in shed_records]
        # ...and the reason mix matches the limiter's own shed counts.
        import collections as _c
        by_reason = _c.Counter(r["reason"] for r in shed_records)
        for (reason, _prio), n in limiter.shed_counts.items():
            assert by_reason[reason] >= min(n, 1), (reason, by_reason)
        ok_records = [r for r in records if r["status"] == "ok"]
        assert all(r["http_status"] == 200 and r["ttft_s"] is not None
                   for r in ok_records)
    finally:
        await service.stop()
        await watcher.stop()
        mrt, engine, server = m1
        await engine.stop()
        await server.shutdown()
        await mrt.close()
        await rt.close()
        await coord.stop()


@async_test(timeout=180)
async def test_breaker_e2e_stalled_worker_opens_then_recovers():
    """One worker chaos-stalled: its breaker opens within the configured
    failure window, traffic converges on the healthy worker, and a
    half-open probe re-admits it after it recovers."""
    coord = Coordinator()
    await coord.start()
    overload = OverloadConfig(breaker_failures=2, breaker_cooldown_s=0.5,
                              queue_depth=32, max_concurrency=64,
                              initial_concurrency=64)
    m1 = await start_mocker(coord, migration_limit=2)
    m2 = await start_mocker(coord, migration_limit=2)
    # Short idle deadline: a stalled worker turns into a typed
    # StreamIncompleteError (breaker failure) fast.
    f = await start_frontend(coord, overload=overload,
                             stream_idle_timeout_s=0.3)
    rt, manager, watcher, service = f
    m2rt, m2_engine, _ = m2
    stalled_id = m2rt.instance_id
    try:
        served = await wait_model(manager, n_instances=2)
        board = served.client.breakers
        calls = {"n": 0}
        real_generate = m2_engine.generate

        def install_stall():
            async def stalled(request, context):
                calls["n"] += 1
                await asyncio.sleep(60)
                yield  # pragma: no cover
            m2_engine.generate = stalled

        install_stall()
        async with aiohttp.ClientSession() as session:
            # Drive round-robin traffic until the stalled worker's
            # breaker opens. Migration (limit 2) keeps every request
            # completing despite the stall.
            for i in range(8):
                status, body, _, _ = await post_chat(
                    session, service.port, f"warm {i}", max_tokens=3)
                assert status == 200, body
                if board.state(stalled_id) == OPEN:
                    break
            assert board.state(stalled_id) == OPEN, \
                "breaker never opened for the stalled worker"
            stall_calls = calls["n"]
            assert stall_calls >= overload.breaker_failures
            # Open: traffic converges on the healthy worker — the
            # stalled engine sees no new dispatches, every request is
            # fast (no idle-timeout burn).
            for i in range(6):
                status, _, _, elapsed = await post_chat(
                    session, service.port, f"conv {i}", max_tokens=3)
                assert status == 200
                assert elapsed < 0.3, "no request may touch the stall"
            assert calls["n"] == stall_calls
            # Recover the worker; after the cooldown the half-open
            # probe re-admits it and the breaker closes.
            m2_engine.generate = real_generate
            await asyncio.sleep(overload.breaker_cooldown_s + 0.1)
            for i in range(8):
                status, _, _, _ = await post_chat(
                    session, service.port, f"probe {i}", max_tokens=3)
                assert status == 200
                if board.state(stalled_id) == CLOSED:
                    break
            assert board.state(stalled_id) == CLOSED, \
                "half-open probe never closed the breaker"
    finally:
        await service.stop()
        await watcher.stop()
        for mrt, engine, server in (m1, m2):
            await engine.stop()
            await server.shutdown()
            await mrt.close()
        await rt.close()
        await coord.stop()


@async_test(timeout=180)
async def test_breaker_e2e_kv_router_excludes_open_worker():
    """The KV scheduler shares the client's breaker board: force-open a
    worker's breaker and every KV-routed request lands on the other."""
    coord = Coordinator()
    await coord.start()
    overload = OverloadConfig(breaker_failures=1, breaker_cooldown_s=30.0)
    m1 = await start_mocker(coord)
    m2 = await start_mocker(coord)
    f = await start_frontend(coord, overload=overload, router_mode="kv")
    rt, manager, watcher, service = f
    m2rt = m2[0]
    try:
        served = await wait_model(manager, n_instances=2)
        router = served.router
        assert router.scheduler.health is served.client.breakers
        served.client.breakers.record_failure(m2rt.instance_id)
        assert served.client.breakers.state(m2rt.instance_id) == OPEN
        decisions = []
        orig_select = router.scheduler.select

        def spy(*args, **kwargs):
            result = orig_select(*args, **kwargs)
            decisions.append(result[0])
            return result

        router.scheduler.select = spy
        async with aiohttp.ClientSession() as session:
            for i in range(4):
                status, body, _, _ = await post_chat(
                    session, service.port, f"kv {i}", max_tokens=3)
                assert status == 200, body
        assert decisions and all(w != m2rt.instance_id for w in decisions)
    finally:
        await service.stop()
        await watcher.stop()
        for mrt, engine, server in (m1, m2):
            await engine.stop()
            await server.shutdown()
            await mrt.close()
        await rt.close()
        await coord.stop()
