#!/usr/bin/env bash
# Repo gate: static analysis first (fast, catches async/JAX/wire hazards
# before any test runs), then the tier-1 pytest command from ROADMAP.md.
# Exits nonzero on lint findings or test failures.
set -uo pipefail

cd "$(dirname "$0")/.."

echo "== dtpu-lint (interprocedural analysis + suppression ratchet) =="
# --stats prints the module/function/edge/rule counts so gate logs
# record call-graph size drift; --budget is the suppression ratchet
# (deploy/lint-budget.json counts may only go down; docs/ANALYSIS.md);
# --sarif-out emits the SARIF 2.1.0 artifact CI/code-review surfaces
# ingest to annotate findings inline on diffs. Warm runs hit the
# .dtpu-lint-cache content-hash cache and finish in milliseconds.
DTPU_LINT_SARIF="${DTPU_LINT_SARIF:-/tmp/dtpu-lint.sarif}"
python -m dynamo_tpu.analysis dynamo_tpu \
    --budget deploy/lint-budget.json --stats \
    --sarif-out "$DTPU_LINT_SARIF" || exit 1
echo "clean. (sarif artifact: $DTPU_LINT_SARIF)"

echo "== chaos smoke (seeded fault injection, docs/RESILIENCE.md) =="
# The fast scenario subset; the combined high-fault matrix is -m slow.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py \
    -q -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== overload smoke (deterministic limiter/breaker unit matrix) =="
# Fake-clock-driven AIMD/deadline/priority/breaker units: no sleeps, no
# network — fails in seconds when shedding or breaker semantics drift.
timeout -k 10 120 env JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py \
    -q -m 'not slow' -k 'unit' -p no:cacheprovider -p no:xdist \
    -p no:randomly || exit 1

echo "== reconfig smoke (live role flip, zero dropped requests) =="
# Mocker fleet + one scripted prefill/decode flip under load: asserts
# every accepted request completes exactly or fails typed, the ledger
# records zero silent drops, and the fleet converges. The heavier chaos
# matrix (crash mid-drain, coordinator restart mid-flip) is tier-1;
# the 5x-overload flip is -m slow.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_reconfig.py -q -m 'not slow' -k 'smoke' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== fleet-pane smoke (KV & capacity observability) =="
# 2 mocker workers + frontend: /debug/fleet aggregates both, tolerates
# one worker's status server down (typed partial result), digests reach
# the router's fleet view, doctor reads the pane. All mocker-backed.
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_fleet_pane.py -q -k 'smoke' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== perf smoke (compile observatory) =="
# Tiny CPU engine: /debug/perf shape on status server + frontend, ZERO
# unexpected recompiles across consecutive decode windows.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_perf_plane.py -q -m 'not slow' -k 'smoke' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== timeline smoke (decision plane: journal -> causal timeline) =="
# Mocker fleet + a seeded chaos key: asserts /debug/timeline contains
# the linked chain chaos_inject -> breaker_transition -> shed ->
# slo_alert_fire (every link via explicit cause refs) and that the
# canary ejects a wedged worker with zero user-visible errors.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_journal.py -q -m 'not slow' -k 'smoke or chain or canary' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== quant-kv smoke (int8 KV cache parity + capacity) =="
# Tiny CPU model, --quant-kv int8 vs bf16 KV: greedy/seeded/chunked
# golden parity gates, prefill-logit cosine, and the ~2x page-capacity
# accounting (tests/test_kv_quant.py).
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_kv_quant.py -q -m 'not slow' \
    -k 'parity or agrees or capacity or teacher' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== federation smoke (KVBM tiers + inventory routing + peer pulls) =="
# 2-mocker fleet: a prefix cached only in worker B's host tier routes
# to B under federated scoring (cache_aware_rate rises vs the same
# workload radix-only), and a peer pull moves blocks over the real KV
# plane with a kv_peer_pull journal event. Plus the KVBM watermark/pin
# policy units (docs/OBSERVABILITY.md "KV federation").
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_kv_federation.py -q -m 'not slow' \
    -k 'smoke or watermark or pinned or breaker' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== autoscale smoke (burn -> scale-out -> canary-gated join -> scale-in) =="
# Mocker fleet + scripted SLO burn: the capacity scaler promotes a
# pre-warmed standby, the canary gate holds it on probation until a
# probe chain passes, sustained headroom scales it back in with a
# zero-drop drain, and the whole causal chain (slo_alert_fire ->
# planner_decision -> standby_promote -> worker_join -> canary_ok) is
# walked via explicit cause refs. The chaos matrix (standby crash
# mid-join, fencing races, coordinator restart) is tier-1; the
# 5x-overload convergence run is -m slow.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_autoscale.py -q -m 'not slow' \
    -k 'smoke or scaler or model or gate or parks or doctor' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== lora smoke (batched multi-tenant adapters) =="
# Tiny CPU engine with 2 registered adapters: heterogeneous-window
# token parity vs sequential single-adapter runs (greedy + seeded),
# adapter_id=0 bit-identity with the LoRA-free engine, repeated
# MIXED-adapter windows with ZERO unexpected recompiles via the perf
# plane, and the http e2e resolving two adapter names on one
# mocker-backed base (typed 404s, per-adapter ledger rollup).
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_lora.py -q -m 'not slow' \
    -k 'smoke or parity or bit_identical or http' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== chunked-prefill smoke (stall-free scheduling) =="
# Tiny CPU model: one long prompt prefilling in chunks with concurrent
# short decoders — asserts completion, decode windows interleaved between
# every chunk dispatch (no engine-loop stall beyond one chunk budget),
# and chunked/whole-prompt token parity.
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_chunked_prefill.py -q -m 'not slow' \
    -k 'decode_progresses or parity' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== tier-1 tests =="
# (reconfig smoke above covers the scripted role flip; heavier role
# chaos scenarios run inside tier-1, the 5x-overload flip is -m slow)
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
