"""Deployment doctor: one command that says what's broken.

Reference: ``deploy/dynamo_check.py`` — a diagnostic script that probes the
environment (imports, GPU, etcd/NATS connectivity, registered workers) and
prints OK/WARN/FAIL per check. The TPU-native equivalent probes:

- interpreter + required libraries
- accelerator devices visible to JAX (without forcing a compile)
- the native extension toolchain (C++ radix index builds/loads)
- coordinator connectivity + KV/queue/pub-sub round-trips + latency
- registered models and live endpoint instances (with TCP reachability)
- disaggregation roles: each worker's current role / drain state / last
  flip outcome from the role status plane (llm/reconfig.py), WARNing on
  workers stuck mid-transition or a fleet with zero prefill-capable
  workers
- an HTTP frontend, when given (``/health``, ``/v1/models``)
- the observability plane on that frontend: ``/metrics`` exposition
  (FAIL when unreachable), ``/debug/slo`` (WARN when no SLO targets are
  configured), ``/debug/flight``, and tracing (WARN when disabled)
- the KV & capacity pane: registered worker status servers on the
  coordinator, ``/debug/fleet`` (WARN on partial results — some workers
  unreachable — or an empty fleet), and the KV router's decision
  telemetry (cache-aware rate / regret) when KV routing is on
- the engine perf plane: ``/debug/perf`` (+ the fleet pane's per-worker
  perf views), WARNing on unexpected steady-state recompiles, HBM
  headroom under 10%, or live roofline_frac regressing > 20% below the
  recorded expectation (DTPU_EXPECTED_ROOFLINE_FRAC / model card)
- the decision plane: ``/debug/timeline`` (runtime/journal.py), WARNing
  on journal-ring overflow drops, breakers that flapped open more than
  N times in the window, and live canary failure streaks

Exit code 0 = no FAIL. Run: ``python -m dynamo_tpu.doctor
[--coordinator-url tcp://...] [--frontend-url http://...]``.
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time

OK, WARN, FAIL, SKIP = "OK  ", "WARN", "FAIL", "skip"


class Report:
    def __init__(self):
        self.rows: list[tuple[str, str, str]] = []

    def add(self, status: str, check: str, detail: str = "") -> None:
        self.rows.append((status, check, detail))
        print(f"[{status}] {check}" + (f" — {detail}" if detail else ""),
              flush=True)

    @property
    def failed(self) -> bool:
        return any(s == FAIL for s, _, _ in self.rows)


def check_imports(rep: Report) -> None:
    rep.add(OK, "python", sys.version.split()[0])
    # grpc/transformers are optional extras (gRPC frontend, HF
    # checkpoints): a core aggregated-serving node is healthy without
    # them, so missing ones WARN rather than FAIL.
    for mod, required in (("jax", True), ("numpy", True),
                          ("msgpack", True), ("aiohttp", True),
                          ("grpc", False), ("transformers", False)):
        try:
            m = __import__(mod)
            rep.add(OK, f"import {mod}", getattr(m, "__version__", ""))
        except ImportError as exc:
            rep.add(FAIL if required else WARN, f"import {mod}",
                    str(exc) if required else "optional; not installed")


def check_devices(rep: Report, require_tpu: bool = False) -> None:
    """A host without a TPU WARNs (a frontend or a CPU test box is
    healthy); with ``require_tpu`` (chip_smoke.py) it FAILs."""
    try:
        import jax
        devs = jax.devices()
        plat = devs[0].platform if devs else "none"
        status = OK if plat == "tpu" else FAIL if require_tpu else WARN
        rep.add(status, "jax devices",
                f"{len(devs)}x {plat} ({devs[0].device_kind})" if devs
                else "no devices")
    except Exception as exc:  # noqa: BLE001 — any backend-init failure
        rep.add(FAIL, "jax devices", str(exc)[:200])


def check_native(rep: Report) -> None:
    try:
        from dynamo_tpu.llm.kv_router.protocols import (KvCacheEvent,
                                                        RouterEvent)
        from dynamo_tpu.native import radix
        if radix.available:
            t = radix.NativeRadixTree()
            t.apply_event(RouterEvent(worker_id=1,
                                      event=KvCacheEvent.stored([11, 12])))
            assert t.find_matches([11, 12]).get(1) == 2
            rep.add(OK, "native radix (C++)", "built + loaded + sane")
        else:
            rep.add(WARN, "native radix (C++)",
                    "unavailable; Python fallback in use (g++ missing?)")
    except Exception as exc:  # noqa: BLE001
        rep.add(FAIL, "native radix (C++)", str(exc)[:200])


async def check_coordinator(rep: Report, url: str) -> None:
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.coordinator_client import CoordinatorClient
    try:
        host, port = RuntimeConfig(coordinator_url=url).coordinator_addr
    except ValueError:
        rep.add(FAIL, "coordinator connect",
                f"{url}: expected tcp://host:port")
        return
    t0 = time.monotonic()
    try:
        client = await asyncio.wait_for(
            CoordinatorClient.connect(host, port), timeout=5)
    except (OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "coordinator connect", f"{url}: {exc}")
        return
    rep.add(OK, "coordinator connect",
            f"{url} in {1e3 * (time.monotonic() - t0):.1f} ms")
    try:
        key = f"doctor/{id(client):x}"
        t0 = time.monotonic()
        await client.kv_put(key, {"t": time.time()})
        assert (await client.kv_get(key)) is not None
        await client.kv_delete(key)
        rep.add(OK, "coordinator KV round-trip",
                f"{1e3 * (time.monotonic() - t0):.1f} ms")

        sub = await client.subscribe("doctor.ping")
        await client.publish("doctor.ping", {"n": 1})
        try:
            await asyncio.wait_for(sub.messages.get(), timeout=2)
            rep.add(OK, "coordinator pub/sub", "")
        except asyncio.TimeoutError:
            rep.add(FAIL, "coordinator pub/sub", "published event not seen")
        await sub.cancel()

        q = f"doctor-q-{id(client):x}"
        await client.queue_push(q, {"n": 1})
        got = await client.queue_pop(q, timeout=2)
        rep.add(OK if got else FAIL, "coordinator queue",
                "" if got else "pushed item not popped")

        models = await client.kv_get_prefix("models/")
        names = sorted({m["v"].get("model_name", "?") for m in models})
        rep.add(OK if models else WARN, "registered models",
                ", ".join(names) if names else "none registered")
        check_adapter_cards(rep, [m["v"] for m in models])

        instances = await client.kv_get_prefix("instances/")
        rep.add(OK if instances else WARN, "live instances",
                f"{len(instances)} registered" if instances else "none")
        for item in instances:
            v = item["v"]
            where = f"{v.get('host')}:{v.get('port')}"
            path = item["k"].split("instances/", 1)[-1]
            try:
                _, w = await asyncio.wait_for(
                    asyncio.open_connection(v.get("host"), v.get("port")),
                    timeout=2)
                w.close()
                rep.add(OK, f"instance {path}", f"tcp {where} reachable")
            except (OSError, asyncio.TimeoutError) as exc:
                rep.add(FAIL, f"instance {path}", f"tcp {where}: {exc}")

        disagg = await client.kv_get_prefix("disagg/")
        if disagg:
            rep.add(OK, "disagg config",
                    "; ".join(f"{d['k']}={d['v']}" for d in disagg))
        check_roles(rep, await client.kv_get_prefix("rolestatus/"))
        check_autoscale(
            rep,
            [it["v"] for it in await client.kv_get_prefix("standby/")
             if isinstance(it.get("v"), dict)],
            [{"key": it["k"], **it["v"]}
             for it in await client.kv_get_prefix("scale/")
             if isinstance(it.get("v"), dict)])
        system = await client.kv_get_prefix("system/")
        if system:
            rep.add(OK, "status servers",
                    f"{len(system)} registered for the fleet pane "
                    "(/debug/fleet)")
        else:
            rep.add(WARN, "status servers",
                    "none registered: /debug/fleet will be empty (set "
                    "DTPU_SYSTEM_ENABLED=1 on workers)")
    except (ConnectionError, OSError, asyncio.TimeoutError) as exc:
        # Coordinator died mid-check: report it, keep the doctor alive so
        # later checks (frontend) still run.
        rep.add(FAIL, "coordinator", f"lost mid-check: {exc}")
    finally:
        await client.close()


#: A worker reporting draining/flipping longer than this is stuck — the
#: drain window (retire_drain_s default 30s) is far smaller.
ROLE_STUCK_S = 120.0


def check_roles(rep: Report, items: list[dict]) -> None:
    """Disaggregation role report (llm/reconfig.py fleet view): each
    worker's current role, drain state, and last flip outcome; WARN on a
    fleet stuck mid-transition or with zero prefill-capable workers."""
    statuses = [it["v"] for it in items if isinstance(it.get("v"), dict)]
    if not statuses:
        return  # fixed-role deployment: nothing to report
    now = time.time()
    stuck, failed = [], []
    for s in statuses:
        role, state = s.get("role", "?"), s.get("state", "?")
        detail = f"role={role} state={state} epoch={s.get('epoch', 0)}"
        last = s.get("last_outcome") or {}
        if last:
            detail += (f" last_flip={last.get('from')}->{last.get('to')}"
                       f":{last.get('outcome')}")
        age = now - float(s.get("ts") or now)
        if state in ("draining", "flipping") and age > ROLE_STUCK_S:
            stuck.append(s)
            rep.add(WARN, f"worker role {s.get('worker', '?')}",
                    f"{detail} — stuck {state} for {age:.0f}s")
            continue
        if last.get("outcome") not in (None, "ok", "noop", "duplicate"):
            failed.append(s)
            rep.add(WARN, f"worker role {s.get('worker', '?')}",
                    f"{detail} — last flip did not converge cleanly")
            continue
        rep.add(OK, f"worker role {s.get('worker', '?')}", detail)
    prefill_capable = sum(1 for s in statuses
                          if s.get("role") in ("prefill", "agg")
                          and s.get("state") == "serving")
    decode_capable = sum(1 for s in statuses
                         if s.get("role") in ("decode", "agg")
                         and s.get("state") == "serving")
    if prefill_capable == 0:
        rep.add(WARN, "role fleet", "zero prefill-capable workers serving: "
                "remote prefill degrades to local everywhere")
    elif decode_capable == 0:
        rep.add(WARN, "role fleet", "zero decode-capable workers serving: "
                "no registered model endpoint can answer")
    else:
        rep.add(OK, "role fleet",
                f"{prefill_capable} prefill-capable / {decode_capable} "
                f"decode-capable of {len(statuses)} workers")


async def check_frontend(rep: Report, url: str) -> None:
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/health",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                rep.add(OK if r.status == 200 else FAIL, "frontend /health",
                        f"{r.status}")
            async with session.get(f"{url}/v1/models",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                body = await r.json()
                names = [m.get("id") for m in body.get("data", [])]
                rep.add(OK if r.status == 200 else FAIL,
                        "frontend /v1/models",
                        ", ".join(names) if names else "no models")
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "frontend", f"{url}: {exc}")


async def check_observability(rep: Report, url: str) -> None:
    """Probe the decision-grade observability surface on a frontend (or
    a worker status server): metrics exposition, the SLO plane, and the
    flight recorder. docs/OBSERVABILITY.md documents every endpoint."""
    import os

    import aiohttp
    url = url.rstrip("/")
    if os.environ.get("DTPU_TRACING", "1").strip().lower() in (
            "0", "false", "no", "off"):
        rep.add(WARN, "tracing env", "DTPU_TRACING=0: spans disabled in "
                "processes launched from this environment")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/metrics",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                body = await r.text()
                series = sum(1 for line in body.splitlines()
                             if line.startswith("dynamo_tpu_"))
                rep.add(OK if r.status == 200 and series else FAIL,
                        "metrics exposition",
                        f"{series} dynamo_tpu_* sample lines"
                        if r.status == 200 else f"HTTP {r.status}")
            async with session.get(f"{url}/debug/slo",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/slo", f"HTTP {r.status}")
                else:
                    slo = await r.json()
                    targets = sorted(slo.get("targets") or {})
                    if not slo.get("enabled") or not targets:
                        rep.add(WARN, "/debug/slo",
                                "no SLO targets configured (set "
                                "DTPU_SLO_TTFT_P99_MS etc. or the [slo] "
                                "TOML table): burn-rate alerting is off")
                    else:
                        level = (slo.get("pressure") or {}).get("level", 0)
                        rep.add(OK, "/debug/slo",
                                f"targets: {', '.join(targets)}; "
                                f"pressure level {level}")
            async with session.get(f"{url}/debug/flight",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/flight", f"HTTP {r.status}")
                else:
                    fl = await r.json()
                    meta = fl.get("meta") or {}
                    rep.add(OK if meta.get("enabled") else WARN,
                            "/debug/flight",
                            f"{meta.get('records', 0)} windows recorded"
                            if meta.get("enabled")
                            else "flight recorder disabled "
                            "(DTPU_FLIGHT_CAPACITY=0)")
            async with session.get(f"{url}/control/role",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status == 200:
                    role = await r.json()
                    rep.add(OK, "/control/role",
                            f"role={role.get('role')} "
                            f"state={role.get('state')} "
                            f"epoch={role.get('epoch')}")
                # 404 = a frontend or a fixed-role worker: not an error.
            async with session.get(f"{url}/debug/traces/recent",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/traces", f"HTTP {r.status}")
                else:
                    idx = await r.json()
                    rep.add(OK if idx.get("enabled") else WARN,
                            "/debug/traces",
                            f"{len(idx.get('traces') or [])} recent traces"
                            if idx.get("enabled")
                            else "tracing disabled (DTPU_TRACING=0) on "
                            "the probed process")
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "observability", f"{url}: {exc}")


async def check_fleet_kv(rep: Report, url: str) -> None:
    """KV & capacity pane (docs/OBSERVABILITY.md "KV & capacity"): the
    frontend's /debug/fleet merged per-worker view. WARNs on partial
    results (some workers unreachable) and on a fleet with zero
    reachable status servers; FAILs only when the pane itself is
    broken."""
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/debug/fleet",
                                   timeout=aiohttp.ClientTimeout(15)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/fleet", f"HTTP {r.status}")
                    return
                fleet = await r.json()
            workers = fleet.get("workers") or {}
            agg = fleet.get("aggregate") or {}
            if not workers:
                rep.add(WARN, "/debug/fleet",
                        "no worker status servers registered "
                        "(DTPU_SYSTEM_ENABLED=1 enables the pane)")
            elif fleet.get("partial"):
                down = [w for w, res in workers.items()
                        if not res.get("ok")]
                rep.add(WARN, "/debug/fleet",
                        f"{agg.get('workers_ok', 0)}/{len(workers)} "
                        f"workers reachable; down: {', '.join(down)}")
            else:
                rep.add(OK, "/debug/fleet",
                        f"{agg.get('workers_ok', 0)} workers, occupancy "
                        f"{agg.get('occupancy', 0.0):.2f}, "
                        f"{agg.get('cached_blocks', 0)} cached blocks, "
                        f"hit rate {agg.get('hit_rate', 0.0):.2f}")
            router = ((fleet.get("router") or {}).get("routers") or {})
            for model, view in router.items():
                dec = view.get("decisions") or {}
                if dec.get("decisions"):
                    rate = dec.get("cache_aware_rate")
                    rep.add(OK, f"kv routing {model}",
                            f"{dec['decisions']} decisions, "
                            f"cache-aware {rate:.2f}, regret p99 "
                            f"{dec.get('regret_p99')}")
            async with session.get(f"{url}/debug/kv",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                # 404 = a round_robin/random frontend with no provider:
                # not an error, just no KV-aware routing to report.
                if r.status not in (200, 404):
                    rep.add(FAIL, "/debug/kv", f"HTTP {r.status}")
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "fleet kv pane", f"{url}: {exc}")


#: Adapter-miss storm threshold (check_adapters): WARN when more than
#: this fraction of adapter requests forced a hot-load — the resident
#: slot count is too small for the working set (raise --max-adapters or
#: pin the hot tenants).
ADAPTER_MISS_WARN_RATE = 0.3
ADAPTER_MISS_MIN_REQUESTS = 20


def check_adapter_cards(rep: Report, entries: list[dict]) -> None:
    """Model-card sanity for LoRA adapters: every adapter card's
    ``lora_base`` must name a model some worker still serves — a
    dangling binding means requests for the adapter name will route to
    a worker that 404s them (the base worker role-flipped or retired
    without its adapter cards)."""
    names = {e.get("model_name") for e in entries}
    adapters = []
    for e in entries:
        extra = (((e.get("card") or {}).get("runtime_config") or {})
                 .get("extra") or {})
        base = extra.get("lora_base")
        if not base:
            continue
        adapters.append((e.get("model_name"), base))
        if base not in names:
            rep.add(WARN, f"adapter card {e.get('model_name')}",
                    f"points at base model {base!r} which no registered "
                    f"worker serves (stale card after a role flip / "
                    f"scale-in?)")
    if adapters:
        bases = sorted({b for _, b in adapters})
        rep.add(OK, "adapter cards",
                f"{len(adapters)} adapter name(s) over base "
                f"{', '.join(bases)}")


def check_adapter_workers(rep: Report, workers: dict) -> None:
    """Per-worker AdapterStore health from the /debug/fleet pane:
    resident/registered counts, eviction totals, and the adapter-miss
    storm WARN (hot-load rate above threshold — every miss pays a
    device upload before the request can prefill)."""
    seen = False
    for worker, res in sorted(workers.items()):
        ad = (res.get("kv") or {}).get("adapters") if res.get("ok") else None
        if not ad:
            continue
        seen = True
        requests = sum((ad.get("requests_total") or {}).values())
        miss = ad.get("miss_total", 0)
        detail = (f"{len(ad.get('resident') or {})}/"
                  f"{ad.get('max_adapters')} resident, "
                  f"{len(ad.get('registered') or [])} registered, "
                  f"loads {ad.get('loads_total', 0)}, evictions "
                  f"{ad.get('evictions_total', 0)}, misses {miss}/"
                  f"{requests} req")
        if (requests >= ADAPTER_MISS_MIN_REQUESTS
                and miss > ADAPTER_MISS_WARN_RATE * requests):
            rep.add(WARN, f"adapters {worker}",
                    detail + " — adapter-miss storm: the resident slot "
                    "count is below the working set (raise "
                    "--max-adapters or pin hot tenants)")
        else:
            rep.add(OK, f"adapters {worker}", detail)
    if not seen:
        rep.add(SKIP, "adapters", "no worker reports an adapter store")


async def check_adapters(rep: Report, url: str) -> None:
    """LoRA adapter serving (docs/OBSERVABILITY.md "Adapters"): reads
    the frontend's /debug/fleet pane for per-worker adapter stores."""
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/debug/fleet",
                                   timeout=aiohttp.ClientTimeout(15)) as r:
                if r.status != 200:
                    rep.add(SKIP, "adapters", f"/debug/fleet HTTP {r.status}")
                    return
                fleet = await r.json()
        check_adapter_workers(rep, fleet.get("workers") or {})
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(SKIP, "adapters", f"{url}: {exc}")


async def check_kv_federation(rep: Report, url: str) -> None:
    """KV federation (docs/OBSERVABILITY.md "KV federation"): is the
    router scoring with inventory overlap, and is the tier/peer plane
    healthy? WARNs when federation is off, when peer breakers are
    open, and when the tier walk keeps falling back to recompute."""
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/debug/kv",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status == 404:
                    rep.add(WARN, "kv federation",
                            "frontend has no KV pane (round_robin/random "
                            "router): federated routing inactive")
                    return
                if r.status != 200:
                    rep.add(FAIL, "kv federation", f"HTTP {r.status}")
                    return
                body = await r.json()
            for model, view in (body.get("routers") or {}).items():
                if view.get("federation") is False:
                    rep.add(WARN, f"federation {model}",
                            "inventory-overlap scoring DISABLED "
                            "(--no-kv-federation): prefixes cached in "
                            "peer tiers recompute locally")
                else:
                    fleet_view = view.get("fleet") or {}
                    totals = fleet_view.get("totals") or {}
                    rep.add(OK, f"federation {model}",
                            f"{totals.get('workers', 0)} inventories, "
                            f"{totals.get('blocks', 0)} fleet blocks, "
                            f"{totals.get('stale', 0)} stale digests")
            async with session.get(f"{url}/debug/fleet",
                                   timeout=aiohttp.ClientTimeout(15)) as r:
                if r.status != 200:
                    return
                fleet = await r.json()
            for worker, res in (fleet.get("workers") or {}).items():
                kv = res.get("kv") if res.get("ok") else None
                if not isinstance(kv, dict):
                    continue
                kvbm = kv.get("kvbm") or {}
                remote = kv.get("remote") or {}
                open_breakers = remote.get("breakers_open", 0)
                if open_breakers:
                    rep.add(WARN, f"peer tier {worker}",
                            f"{open_breakers} peer breaker(s) open "
                            f"({remote.get('fetch_failures', 0)} pull "
                            "failures): cross-worker reuse degraded")
                fallbacks = kvbm.get("recompute_fallbacks", 0)
                promotions = kvbm.get("promotions", 0)
                if fallbacks > max(10, 3 * max(1, promotions)):
                    rep.add(WARN, f"kvbm {worker}",
                            f"{fallbacks} tier-walk recompute fallbacks "
                            f"vs {promotions} promotions: the ladder "
                            "rarely holds what requests need (budget or "
                            "watermark tuning?)")
                elif kvbm:
                    rep.add(OK, f"kvbm {worker}",
                            f"{kvbm.get('watermark_demotions', 0)} "
                            "watermark demotions, "
                            f"{promotions} promotions, "
                            f"{kvbm.get('peer_pull_blocks', 0)} peer "
                            "blocks pulled")
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "kv federation", f"{url}: {exc}")


def _perf_views(body: dict, fleet: dict | None) -> list[tuple[str, dict]]:
    """Flatten one /debug/perf body (+ optional /debug/fleet per-worker
    perf views) into named engine-grade views to judge."""
    views = [(str(body.get("role") or "process"), body)]
    for name, eng in (body.get("engines") or {}).items():
        views.append((f"engine {name}", eng))
    for worker, res in ((fleet or {}).get("workers") or {}).items():
        perf = res.get("perf")
        if isinstance(perf, dict) and "compiles" in perf:
            views.append((f"worker {worker}", perf))
    return views


#: HBM headroom below this fraction of bytes_limit is a WARN: the next
#: long context or shape bucket will OOM-preempt instead of serving.
PERF_HBM_HEADROOM = 0.10
#: Live roofline_frac more than this fraction BELOW the model-card /
#: config expectation is a WARN (ISSUE: "regressing > 20%").
PERF_ROOFLINE_REGRESSION = 0.20


async def check_perf(rep: Report, url: str) -> None:
    """Engine perf plane (docs/OBSERVABILITY.md "Engine perf plane"):
    probe /debug/perf (+ the fleet pane's per-worker perf views) and
    WARN on any unexpected steady-state recompile, HBM headroom below
    10%, or live roofline_frac regressing more than 20% below the
    recorded expectation."""
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/debug/perf",
                                   timeout=aiohttp.ClientTimeout(5)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/perf", f"HTTP {r.status}")
                    return
                body = await r.json()
            fleet = None
            try:
                async with session.get(
                        f"{url}/debug/fleet",
                        timeout=aiohttp.ClientTimeout(15)) as r:
                    if r.status == 200:
                        fleet = await r.json()
            except (aiohttp.ClientError, OSError,
                    asyncio.TimeoutError):
                fleet = None  # pane probed separately; perf view optional
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "perf plane", f"{url}: {exc}")
        return
    for name, view in _perf_views(body, fleet):
        compiles = view.get("compiles") or {}
        programs = compiles.get("programs") or {}
        unexpected = compiles.get("unexpected_recompiles_total", 0)
        if unexpected:
            rep.add(WARN, f"perf {name}",
                    f"{unexpected} unexpected steady-state recompile(s) — "
                    "a served shape is recompiling on the hot path (see "
                    "perf.recompile spans)")
        elif programs:
            total_s = compiles.get("compile_seconds_total", 0.0)
            rep.add(OK, f"perf {name}",
                    f"{compiles.get('compiles_total', 0)} compiles over "
                    f"{len(programs)} programs ({total_s:.1f}s), zero "
                    "unexpected recompiles")
        hbm = view.get("hbm") or {}
        limit = hbm.get("bytes_limit") or 0
        if limit:
            headroom = 1.0 - hbm.get("bytes_in_use", 0) / limit
            if headroom < PERF_HBM_HEADROOM:
                rep.add(WARN, f"perf {name} HBM",
                        f"headroom {headroom:.1%} < "
                        f"{PERF_HBM_HEADROOM:.0%} of "
                        f"{limit / (1 << 30):.1f} GiB: next shape bucket "
                        "or long context will thrash the KV pool")
            else:
                rep.add(OK, f"perf {name} HBM",
                        f"{hbm.get('bytes_in_use', 0) / (1 << 30):.2f} / "
                        f"{limit / (1 << 30):.1f} GiB in use "
                        f"(headroom {headroom:.0%})")
        roofline = view.get("roofline") or {}
        frac = roofline.get("frac")
        expected = roofline.get("expected_frac")
        if expected and frac is not None:
            floor = expected * (1.0 - PERF_ROOFLINE_REGRESSION)
            if frac < floor:
                rep.add(WARN, f"perf {name} roofline",
                        f"live roofline_frac {frac:.3f} regressed below "
                        f"{floor:.3f} ({PERF_ROOFLINE_REGRESSION:.0%} "
                        f"under the recorded expectation {expected})")
            else:
                rep.add(OK, f"perf {name} roofline",
                        f"{frac:.3f} vs expected {expected} (ok)")


#: Breaker open-transitions per worker in the timeline window above
#: which the doctor calls it flapping (open -> half-open -> open churn:
#: the worker is sick but keeps winning its half-open probe).
BREAKER_FLAP_N = 3
#: Consecutive canary failures on one worker worth a WARN.
CANARY_FAIL_N = 3


def check_decision_plane(rep: Report, timeline: dict) -> None:
    """Decision plane (docs/OBSERVABILITY.md "Decision plane"): judge a
    /debug/timeline body — journal-ring overflow drops, repeated canary
    failures, breaker flapping. Pure function over the payload so the
    checks are unit-testable without HTTP."""
    events = timeline.get("events") or []
    local = timeline.get("local") or {}
    dropped = int(local.get("dropped_overflow") or 0)
    gaps = int(timeline.get("gaps") or 0)
    if dropped or gaps:
        rep.add(WARN, "journal ring",
                f"{dropped} events dropped to ring overflow, {gaps} "
                "timeline gaps (raise DTPU_JOURNAL_CAPACITY or the "
                "publisher cadence): cause chains may be broken")
    else:
        rep.add(OK, "journal ring",
                f"{len(events)} events merged, zero overflow drops")
    # Breaker flaps: open transitions per worker in the window.
    opens: dict[str, int] = {}
    for e in events:
        attrs = e.get("attrs") or {}
        if (e.get("kind") == "breaker_transition"
                and attrs.get("to") == "open"):
            w = str(attrs.get("worker_id") or "?")
            opens[w] = opens.get(w, 0) + 1
    for w, n in sorted(opens.items()):
        if n > BREAKER_FLAP_N:
            rep.add(WARN, f"breaker {w}",
                    f"flapped open {n} times in the timeline window "
                    "(open -> half-open -> open churn): probes keep "
                    "re-admitting a sick worker")
    if opens and all(n <= BREAKER_FLAP_N for n in opens.values()):
        rep.add(OK, "breakers",
                f"{sum(opens.values())} open transition(s) across "
                f"{len(opens)} worker(s), none flapping")
    # Canary: trailing consecutive failures per worker (a fail streak
    # ended by canary_ok is a recovered incident, not a live one).
    streaks: dict[str, int] = {}
    for e in events:
        attrs = e.get("attrs") or {}
        w = str(attrs.get("worker_id") or "?")
        if e.get("kind") == "canary_fail":
            streaks[w] = streaks.get(w, 0) + 1
        elif e.get("kind") == "canary_ok":
            streaks[w] = 0
    live = {w: n for w, n in streaks.items() if n >= CANARY_FAIL_N}
    for w, n in sorted(live.items()):
        rep.add(WARN, f"canary {w}",
                f"{n} consecutive canary failures and no recovery: the "
                "worker is wedged (its breaker should be open — check "
                "breaker_transition events)")
    if streaks and not live:
        rep.add(OK, "canary", "probing active, no live failure streaks")


#: A standby not parked "ready" (warming/promoting) for longer than
#: this is stuck — warmup and joins are seconds, not minutes.
STANDBY_STUCK_S = 120.0
#: A pending scale directive older than this never applied.
SCALE_STUCK_S = 120.0
#: Scale direction changes in the timeline window that count as thrash.
SCALE_THRASH_N = 3
#: Canary failures after a worker_join that count as a rejected join.
CANARY_REJECT_N = 2


def check_autoscale(rep: Report, standbys: list[dict],
                    directives: list[dict],
                    events: list[dict] | None = None) -> None:
    """Autoscaling health (docs/RESILIENCE.md "Autoscaling"): standby
    pool state, stuck scale directives, and — given timeline events —
    scale thrash and canary-rejected joins. Pure function over the
    coordinator listings / timeline payload so it unit-tests without
    HTTP."""
    now = time.time()
    if not standbys and not directives and not events:
        return  # no autoscaling deployed: nothing to report
    ready = stuck = 0
    for s in standbys:
        state = s.get("state", "?")
        age = now - float(s.get("ts") or now)
        if state == "ready":
            ready += 1
        elif age > STANDBY_STUCK_S:
            stuck += 1
            rep.add(WARN, f"standby {s.get('worker', '?')}",
                    f"state={state} for {age:.0f}s — warmup or join is "
                    "wedged (a join should take seconds)")
    if standbys and not stuck:
        rep.add(OK, "standby pool",
                f"{len(standbys)} parked ({ready} ready to promote)")
    elif not standbys and directives:
        # Scale directives in flight but nothing warm to promote: the
        # next scale-out pays cold-start (minutes), not seconds.
        rep.add(WARN, "standby pool",
                "empty while scaling is active — launch workers with "
                "--standby so scale-outs promote instead of cold-start")
    for d in directives:
        age = now - float(d.get("ts") or now)
        if age > SCALE_STUCK_S:
            rep.add(WARN, f"scale directive {d.get('key', '?')}",
                    f"{d.get('action', '?')} pending {age:.0f}s without "
                    "applying — target dead or fenced out; the scaler "
                    "should have reaped it (planner down?)")
    if events:
        # Thrash: scale_out/scale_in direction flips in the window.
        actions = [e["attrs"].get("action") for e in events
                   if e.get("kind") == "planner_decision"
                   and (e.get("attrs") or {}).get("action")
                   in ("scale_out", "scale_out_cold", "scale_in")]
        flips = sum(1 for a, b in zip(actions, actions[1:])
                    if (a == "scale_in") != (b == "scale_in"))
        if flips >= SCALE_THRASH_N:
            rep.add(WARN, "autoscale thrash",
                    f"{flips} scale direction changes in the timeline "
                    "window — widen hysteresis/cooldown "
                    "(DTPU_PLANNER_CAPACITY_*)")
        elif actions:
            rep.add(OK, "autoscale",
                    f"{len(actions)} scale action(s), no thrash")
        # Canary-rejected joins: fails attributed to a recently-joined
        # worker with no admitting canary_ok after them.
        joined: set[str] = set()
        fails_after_join: dict[str, int] = {}
        for e in events:
            attrs = e.get("attrs") or {}
            kind = e.get("kind")
            if kind == "worker_join":
                joined.add(str(attrs.get("instance") or "?"))
            elif kind == "canary_fail":
                w = str(attrs.get("worker_id") or "?")
                if w in joined:
                    fails_after_join[w] = fails_after_join.get(w, 0) + 1
            elif kind == "canary_ok":
                fails_after_join.pop(str(attrs.get("worker_id") or "?"),
                                     None)
        for w, n in sorted(fails_after_join.items()):
            if n >= CANARY_REJECT_N:
                rep.add(WARN, f"canary-rejected join {w}",
                        f"worker joined but failed {n} canary probes and "
                        "was never admitted — it is held on probation; "
                        "if it was a standby promote, the scaler should "
                        "promote a replacement")


async def check_timeline(rep: Report, url: str) -> None:
    """Probe GET /debug/timeline and judge the decision plane."""
    import aiohttp
    url = url.rstrip("/")
    try:
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{url}/debug/timeline",
                                   timeout=aiohttp.ClientTimeout(10)) as r:
                if r.status != 200:
                    rep.add(FAIL, "/debug/timeline", f"HTTP {r.status}")
                    return
                timeline = await r.json()
    except (aiohttp.ClientError, OSError, asyncio.TimeoutError) as exc:
        rep.add(FAIL, "/debug/timeline", f"{url}: {exc}")
        return
    check_decision_plane(rep, timeline)
    # Timeline-window autoscale judgments (thrash, rejected joins).
    check_autoscale(rep, [], [], events=timeline.get("events") or [])


async def run(args) -> int:
    rep = Report()
    check_imports(rep)
    if not args.no_devices:
        check_devices(rep)
    check_native(rep)
    if args.coordinator_url:
        await check_coordinator(rep, args.coordinator_url)
    else:
        rep.add(SKIP, "coordinator", "no --coordinator-url / DTPU_COORDINATOR_URL")
    if args.frontend_url:
        await check_frontend(rep, args.frontend_url)
        await check_observability(rep, args.frontend_url)
        await check_fleet_kv(rep, args.frontend_url)
        await check_kv_federation(rep, args.frontend_url)
        await check_adapters(rep, args.frontend_url)
        await check_perf(rep, args.frontend_url)
        await check_timeline(rep, args.frontend_url)
    n_fail = sum(1 for s, _, _ in rep.rows if s == FAIL)
    print(f"doctor: {len(rep.rows)} checks, {n_fail} failures", flush=True)
    return 1 if rep.failed else 0


def main() -> None:
    import os
    parser = argparse.ArgumentParser(description="dynamo-tpu deployment doctor")
    parser.add_argument("--coordinator-url",
                        default=os.environ.get("DTPU_COORDINATOR_URL"),
                        help="probe this control plane (tcp://host:port)")
    parser.add_argument("--frontend-url", default=None,
                        help="probe this OpenAI frontend (http://host:port)")
    parser.add_argument("--no-devices", action="store_true",
                        help="skip jax device probe (avoids backend init)")
    sys.exit(asyncio.run(run(parser.parse_args())))


if __name__ == "__main__":
    main()
