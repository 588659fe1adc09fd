"""Multi-tenant batched LoRA serving tests (engine/lora.py, ROADMAP item 4).

Core invariants:
- a LoRA-enabled engine with adapter_id=0 is BIT-identical to a
  LoRA-disabled engine (slot 0's stacks are exact zeros);
- a heterogeneous decode window (several adapters + base batched
  together) is TOKEN-identical to sequential single-adapter runs,
  greedy and seeded — adapter ids are per-row data, so rows cannot
  influence each other;
- adapter-conditioned KV never aliases base KV (salted hash chains);
- hot-load/evict/pin follow the KVBM-style LRU discipline;
- the frontend resolves adapter model names end to end and the ledger
  attributes per-adapter.

Heavy compose variants (tp2, quant-kv) are ``-m slow``.
"""

import asyncio
import importlib.util
import json
import pathlib

import numpy as np
import pytest
from conftest import async_test

import ml_dtypes

from dynamo_tpu.engine.config import EngineConfig, PRESETS
from dynamo_tpu.engine.engine import TPUEngine
from dynamo_tpu.engine.lora import AdapterStore
from dynamo_tpu.engine.runner import ModelRunner, PrefillSeq
from dynamo_tpu.engine.weights import load_lora_weights
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.llm.tokens import TokenBlockSequence, chain_salt, \
    compute_block_hashes
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.errors import AdapterNotFoundError, OverloadedError

SPEC = PRESETS["tiny-test"]
PAGE = 16
REPO = pathlib.Path(__file__).resolve().parent.parent


def cfg(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=128,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128),
                    max_prefill_tokens=64, attention_backend="xla")
    defaults.update(kw)
    return EngineConfig(**defaults)


def rnd_adapter(seed: int, shapes: dict, L: int, rank: int = 8,
                scale: float = 0.2) -> dict:
    """Host A/B stacks at the store's expected (padded) shapes."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, (din, dout) in shapes.items():
        A = (rng.standard_normal((L, din, rank)) * scale).astype(
            ml_dtypes.bfloat16)
        B = (rng.standard_normal((L, rank, dout)) * scale).astype(
            ml_dtypes.bfloat16)
        out[k] = (A, B)
    return out


def make_peft_dir(tmp_path, rank=2, alpha=4.0, layers=(0, 1),
                  targets=("q_proj", "v_proj"), seed=0):
    """A minimal HF PEFT checkpoint dir (adapter_config.json +
    adapter_model.safetensors with PEFT tensor names)."""
    from safetensors.numpy import save_file
    d = tmp_path / f"peft-{seed}"
    d.mkdir(parents=True, exist_ok=True)
    (d / "adapter_config.json").write_text(json.dumps(
        {"r": rank, "lora_alpha": alpha,
         "target_modules": list(targets)}))
    rng = np.random.default_rng(seed)
    h, nh, nkv, hd = (SPEC.hidden_size, SPEC.num_heads, SPEC.num_kv_heads,
                      SPEC.head_dim)
    dims = {"q_proj": (h, nh * hd), "k_proj": (h, nkv * hd),
            "v_proj": (h, nkv * hd), "o_proj": (nh * hd, h),
            "gate_proj": (h, SPEC.intermediate_size),
            "up_proj": (h, SPEC.intermediate_size),
            "down_proj": (SPEC.intermediate_size, h)}
    tensors = {}
    for li in layers:
        for mod in targets:
            din, dout = dims[mod]
            base = (f"base_model.model.model.layers.{li}."
                    f"{'self_attn' if mod.endswith(('q_proj', 'k_proj', 'v_proj', 'o_proj')) else 'mlp'}.{mod}")
            tensors[f"{base}.lora_A.weight"] = rng.standard_normal(
                (rank, din)).astype(np.float32)
            tensors[f"{base}.lora_B.weight"] = rng.standard_normal(
                (dout, rank)).astype(np.float32)
    save_file(tensors, str(d / "adapter_model.safetensors"))
    return d, tensors


async def collect(engine, prompt, n, adapter=None, seed=None, temp=0.0):
    req = PreprocessedRequest(model="m", token_ids=list(prompt),
                              adapter=adapter)
    req.stop_conditions.max_tokens = n
    req.stop_conditions.ignore_eos = True
    req.sampling_options.temperature = temp
    if seed is not None:
        req.sampling_options.seed = seed
    toks = []
    async for out in engine.generate(req, Context()):
        toks.extend(out.get("token_ids", []))
        if out.get("finish_reason"):
            break
    return toks


def prompt_tokens(n=24, seed=5):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC.vocab_size, size=n).tolist()


# -- PEFT loader units ---------------------------------------------------------

def test_load_peft_pad_stack(tmp_path):
    d, tensors = make_peft_dir(tmp_path, rank=2, alpha=4.0, layers=(0,),
                               targets=("q_proj", "v_proj"))
    out = load_lora_weights(SPEC, str(d), max_rank=8)
    assert sorted(out) == ["wq", "wv"]
    A, B = out["wq"]
    assert A.shape == (SPEC.num_layers, SPEC.hidden_size, 8)
    assert B.shape == (SPEC.num_layers, 8,
                       SPEC.num_heads * SPEC.head_dim)
    src_a = tensors["base_model.model.model.layers.0.self_attn."
                    "q_proj.lora_A.weight"]
    # PEFT [r, in] -> ours [in, r], padded columns zero.
    np.testing.assert_allclose(np.asarray(A[0, :, :2], np.float32),
                               src_a.T.astype(ml_dtypes.bfloat16)
                               .astype(np.float32))
    assert not np.asarray(A[0, :, 2:], np.float32).any()
    # alpha/r scale folded into B; layer 1 untargeted -> zeros.
    src_b = tensors["base_model.model.model.layers.0.self_attn."
                    "q_proj.lora_B.weight"]
    np.testing.assert_allclose(
        np.asarray(B[0, :2], np.float32),
        (src_b.astype(np.float32).T * 2.0).astype(ml_dtypes.bfloat16)
        .astype(np.float32))
    assert not np.asarray(A[1], np.float32).any()
    assert not np.asarray(B[1], np.float32).any()


def test_load_peft_rank_too_big_rejected(tmp_path):
    d, _ = make_peft_dir(tmp_path, rank=16, seed=1)
    with pytest.raises(ValueError, match="exceeds lora_max_rank"):
        load_lora_weights(SPEC, str(d), max_rank=8)


def test_register_validates_shapes():
    runner = ModelRunner(cfg(max_adapters=1, lora_max_rank=4))
    store = AdapterStore(runner, 1, 4)
    bad = {"wq": (np.zeros((SPEC.num_layers, SPEC.hidden_size, 8),
                           ml_dtypes.bfloat16),
                  np.zeros((SPEC.num_layers, 8,
                            SPEC.num_heads * SPEC.head_dim),
                           ml_dtypes.bfloat16))}
    with pytest.raises(ValueError, match="shapes"):
        store.register("bad", weights=bad)
    with pytest.raises(ValueError, match="not a LoRA target"):
        store.register("bad2", weights={"embed": bad["wq"]})


# -- store LRU / pin / refcount units -----------------------------------------

def test_store_lru_pin_refcount_units():
    runner = ModelRunner(cfg(max_adapters=1, lora_max_rank=4))
    store = AdapterStore(runner, 1, 4)
    shapes = runner.config.lora_target_shapes()
    for i, name in enumerate(("a", "b", "c")):
        store.register(name, weights=rnd_adapter(i, shapes,
                                                 SPEC.num_layers, rank=4))
    with pytest.raises(AdapterNotFoundError):
        store.acquire("nope")
    slot = store.acquire("a")
    assert slot == 1 and store.resident == 1
    # Held by a live request: hot-loading b must fail typed (503), not
    # evict under the live request.
    with pytest.raises(OverloadedError):
        store.acquire("b")
    store.release("a")
    assert store.acquire("b") == 1  # LRU-evicted a
    assert store.evictions_total == 1 and store.loads_total == 2
    store.release("b")
    store.pin("b")
    with pytest.raises(OverloadedError):
        store.acquire("c")  # pinned b is exempt from eviction
    store.unpin("b")
    assert store.acquire("c") == 1
    store.release("c")
    # Resident re-acquire is a hit, not a miss.
    miss = store.miss_total
    assert store.acquire("c") == 1
    assert store.miss_total == miss
    store.release("c")
    assert store.evict("c") is True
    assert store.resident == 0
    with pytest.raises(AdapterNotFoundError):
        store.pin("nope")
    assert store.requests_total["a"] == 1


# -- numerics: bit-identity + heterogeneous batching parity -------------------

def test_adapter_slot0_bit_identical_to_plain_runner():
    base = ModelRunner(cfg(), seed=0)
    lr = ModelRunner(cfg(max_adapters=2, lora_max_rank=4), seed=0)
    prompt = np.asarray(prompt_tokens(20), np.int32)
    seq = PrefillSeq(tokens=prompt, start_pos=0,
                     chunk_pages=np.arange(1, 3, dtype=np.int32),
                     hist_pages=None, sampling=(0.0, 0, 1.0))
    t0 = base.prefill_batch([seq])
    lg0 = np.asarray(base.last_prefill_logits, np.float32)
    t1 = lr.prefill_batch([seq])
    lg1 = np.asarray(lr.last_prefill_logits, np.float32)
    assert np.array_equal(t0, t1)
    assert np.array_equal(lg0, lg1), "slot-0 zeros must be an exact no-op"


@async_test(timeout=240)
async def test_batched_heterogeneous_parity_greedy_and_seeded():
    c = cfg(max_adapters=2, lora_max_rank=8)
    shapes = c.lora_target_shapes()

    def build():
        eng = TPUEngine(c)
        eng.register_adapter("tenant-a",
                             weights=rnd_adapter(1, shapes, SPEC.num_layers))
        eng.register_adapter("tenant-b",
                             weights=rnd_adapter(2, shapes, SPEC.num_layers))
        return eng

    seq_eng = build()
    bat_eng = build()
    plain = TPUEngine(cfg())
    prompt = prompt_tokens()
    try:
        # Sequential single-adapter references (greedy).
        sa = await collect(seq_eng, prompt, 12, adapter="tenant-a")
        sb = await collect(seq_eng, prompt, 12, adapter="tenant-b")
        s0 = await collect(plain, prompt, 12)
        assert sa != s0 and sb != s0 and sa != sb, \
            "random adapters should change greedy output"
        # One heterogeneous window: a + b + base concurrently.
        r1, r2, r3 = await asyncio.gather(
            collect(bat_eng, prompt, 12, adapter="tenant-a"),
            collect(bat_eng, prompt, 12, adapter="tenant-b"),
            collect(bat_eng, prompt, 12))
        assert r1 == sa and r2 == sb and r3 == s0, \
            "heterogeneous batch must be token-identical to sequential"
        # Seeded sampled parity (temperature > 0).
        za = await collect(seq_eng, prompt, 10, adapter="tenant-a",
                           seed=7, temp=0.8)
        q1, q2 = await asyncio.gather(
            collect(bat_eng, prompt, 10, adapter="tenant-a", seed=7,
                    temp=0.8),
            collect(bat_eng, prompt, 10, adapter="tenant-b"))
        assert q1 == za, "seeded draws must be batch-mix invariant"
    finally:
        seq_eng.stop()
        bat_eng.stop()
        plain.stop()


@async_test(timeout=240)
async def test_unknown_adapter_typed_404_and_slot0_engine_parity():
    c = cfg(max_adapters=1, lora_max_rank=4)
    eng = TPUEngine(c)
    plain = TPUEngine(cfg())
    prompt = prompt_tokens()
    try:
        with pytest.raises(AdapterNotFoundError):
            await collect(eng, prompt, 4, adapter="missing")
        got = await collect(eng, prompt, 12)
        ref = await collect(plain, prompt, 12)
        assert got == ref
    finally:
        eng.stop()
        plain.stop()


# -- hot-load / evict under serving + salted prefix cache ---------------------

@async_test(timeout=240)
async def test_hot_load_evict_storm_and_accounting():
    c = cfg(max_adapters=1, lora_max_rank=4)
    shapes = c.lora_target_shapes()
    eng = TPUEngine(c)
    eng.register_adapter("a", weights=rnd_adapter(1, shapes,
                                                  SPEC.num_layers, rank=4))
    eng.register_adapter("b", weights=rnd_adapter(2, shapes,
                                                  SPEC.num_layers, rank=4))
    prompt = prompt_tokens()
    try:
        ta1 = await collect(eng, prompt, 6, adapter="a")
        tb = await collect(eng, prompt, 6, adapter="b")   # evicts a
        ta2 = await collect(eng, prompt, 6, adapter="a")  # reloads a
        assert ta1 == ta2, "an adapter must survive eviction + reload"
        assert ta1 != tb
        st = eng.adapters.status()
        assert st["loads_total"] >= 3
        assert st["evictions_total"] >= 2
        assert st["requests_total"] == {"a": 2, "b": 1}
        # The engine thread lets go of a request's adapter after it has
        # emitted the finish the client just read: give it a moment.
        for _ in range(100):
            if not eng.adapters.status()["active_refs"]:
                break
            await asyncio.sleep(0.02)
        assert eng.adapters.status()["active_refs"] == {}
    finally:
        eng.stop()


@async_test(timeout=240)
async def test_salted_chains_never_alias_and_prefix_reuse_per_adapter():
    # Unit: salted vs unsalted chains are disjoint.
    toks = list(range(1, 1 + 3 * PAGE))
    base_h = compute_block_hashes(toks, PAGE)
    a_h = compute_block_hashes(toks, PAGE, salt=chain_salt("a"))
    b_h = compute_block_hashes(toks, PAGE, salt=chain_salt("b"))
    assert not (set(base_h) & set(a_h)) and not (set(a_h) & set(b_h))
    assert TokenBlockSequence(PAGE, toks,
                              salt=chain_salt("a")).block_hashes == a_h
    assert chain_salt(None) is None and chain_salt("") is None

    # Engine: adapter-a's pages are reused by a second adapter-a request
    # but NOT by a base request with the same tokens.
    c = cfg(max_adapters=1, lora_max_rank=4)
    eng = TPUEngine(c)
    eng.register_adapter("a", weights=rnd_adapter(
        1, c.lora_target_shapes(), SPEC.num_layers, rank=4))
    prompt = prompt_tokens(3 * PAGE + 4)
    try:
        first = await collect(eng, prompt, 4, adapter="a")
        hits0 = eng.prefix_hit_blocks
        second = await collect(eng, prompt, 4, adapter="a")
        assert second == first
        assert eng.prefix_hit_blocks > hits0, \
            "same-adapter rerun must hit the salted prefix cache"
        hits1 = eng.prefix_hit_blocks
        await collect(eng, prompt, 4)  # base: different chain
        assert eng.prefix_hit_blocks == hits1, \
            "base must NOT reuse adapter-conditioned KV"
    finally:
        eng.stop()


@async_test(timeout=300)
async def test_chunked_prefill_with_adapter_matches_whole():
    # Long prompt (> max_prefill_tokens) takes the scheduled-chunk path;
    # a one-bucket engine with the same adapter must agree token-for-
    # token (greedy), proving chunks thread the adapter id through the
    # with-history programs.
    shapes = cfg().lora_target_shapes()
    weights = rnd_adapter(3, shapes, SPEC.num_layers)
    prompt = prompt_tokens(100, seed=11)

    chunked = TPUEngine(cfg(max_adapters=1,
                            prefill_buckets=(32, 64),
                            max_prefill_tokens=48))
    chunked.register_adapter("a", weights=weights)
    whole = TPUEngine(cfg(max_adapters=1))
    whole.register_adapter("a", weights=weights)
    try:
        got = await collect(chunked, prompt, 10, adapter="a")
        ref = await collect(whole, prompt, 10, adapter="a")
        assert got == ref, "chunked-prefill adapter run diverged"
        assert chunked.chunk_dispatch_count > 0, \
            "long prompt should have taken the chunked path"
    finally:
        chunked.stop()
        whole.stop()


# -- smoke: perf plane (check.sh lora stage) ----------------------------------

@async_test(timeout=300)
async def test_smoke_mixed_windows_zero_unexpected_recompiles():
    """Repeated MIXED-adapter windows after warmup must not recompile:
    adapter ids are data, not shape (the acceptance criterion the
    check.sh lora smoke stage gates on via /debug/perf)."""
    c = cfg(max_adapters=2, lora_max_rank=4)
    shapes = c.lora_target_shapes()
    eng = TPUEngine(c)
    eng.register_adapter("a", weights=rnd_adapter(1, shapes,
                                                  SPEC.num_layers, rank=4))
    eng.register_adapter("b", weights=rnd_adapter(2, shapes,
                                                  SPEC.num_layers, rank=4))
    prompt = prompt_tokens()

    def unexpected():
        return eng.perf_status()["compiles"]["unexpected_recompiles_total"]

    try:
        # Warm every program shape once with a first mixed round.
        await asyncio.gather(
            collect(eng, prompt, 8, adapter="a"),
            collect(eng, prompt, 8, adapter="b"),
            collect(eng, prompt, 8))
        before = unexpected()
        for _ in range(3):  # repeated mixed windows, varying the mix
            await asyncio.gather(
                collect(eng, prompt, 8, adapter="b"),
                collect(eng, prompt, 8, adapter="a"),
                collect(eng, prompt, 8))
        assert unexpected() == before, \
            "mixed-adapter serving recompiled after warmup"
        adapters = eng.kv_status()["adapters"]
        assert set(adapters["resident"]) == {"a", "b"}
    finally:
        eng.stop()


# -- frontend: http e2e + ledger + slo_report + doctor ------------------------

def _load_script(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@async_test(timeout=240)
async def test_http_e2e_two_adapter_names_on_one_base():
    """Two adapter names registered over one mocker-backed base: the
    frontend resolves both to (base, adapter), both serve, an unknown
    name 404s, a worker-side AdapterNotFound surfaces as a TYPED 404,
    and the ledger attributes per-adapter."""
    import aiohttp

    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.engines import EchoEngine
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.model_card import register_adapter, register_llm
    from dynamo_tpu.llm.recorder import get_ledger
    from dynamo_tpu.llm.tokenizer import make_test_tokenizer
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    coord = Coordinator()
    await coord.start()
    mk = lambda: RuntimeConfig(coordinator_url=coord.url,  # noqa: E731
                               lease_ttl_s=3.0)
    worker_rt = await DistributedRuntime.from_settings(mk())
    frontend_rt = await DistributedRuntime.from_settings(mk())
    tokenizer = make_test_tokenizer()
    engine = EchoEngine()

    async def handler(request, context):
        # The echo engine ignores adapters; a poisoned name exercises
        # the wire-typed AdapterNotFound path end to end.
        if (request or {}).get("adapter") == "acme-broken":
            raise AdapterNotFoundError("adapter 'acme-broken' is not "
                                       "registered on this worker")
        async for out in engine.generate(request, context):
            yield out

    endpoint = worker_rt.namespace("test").component("echo") \
        .endpoint("generate")
    server = await endpoint.serve_endpoint(handler)
    await register_llm(worker_rt, endpoint, "echo-base", tokenizer)
    for name in ("acme-a", "acme-b", "acme-broken"):
        await register_adapter(worker_rt, endpoint, name, "echo-base",
                               tokenizer)
    manager = ModelManager()
    watcher = ModelWatcher(frontend_rt, manager)
    await watcher.start()
    service = HttpService(frontend_rt, manager, host="127.0.0.1", port=0)
    await service.start()
    try:
        for _ in range(100):
            if all(manager.get(n) for n in
                   ("echo-base", "acme-a", "acme-b")):
                break
            await asyncio.sleep(0.02)
        base_url = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{base_url}/v1/models") as r:
                listed = {m["id"] for m in (await r.json())["data"]}
            assert {"echo-base", "acme-a", "acme-b"} <= listed

            async def chat(model):
                async with session.post(
                        f"{base_url}/v1/chat/completions",
                        json={"model": model, "stream": False,
                              "max_tokens": 8,
                              "messages": [{"role": "user",
                                            "content": "hello there"}]}
                ) as r:
                    return r.status, await r.json()

            s1, body1 = await chat("acme-a")
            s2, body2 = await chat("acme-b")
            assert s1 == 200 and s2 == 200
            assert body1["choices"][0]["message"]["content"]
            s3, body3 = await chat("no-such-model")
            assert s3 == 404
            assert body3["error"]["type"] == "model_not_found"
            s4, body4 = await chat("acme-broken")
            assert s4 == 404, body4
            assert body4["error"]["type"] == "adapter_not_found"
        # Ledger attribution: per-adapter records (scripts/slo_report).
        recs = [r for r in get_ledger().recent(50)
                if r.get("model", "").startswith(("acme", "echo"))]
        by_adapter = {r.get("adapter") for r in recs}
        assert {"acme-a", "acme-b"} <= by_adapter
        slo_report = _load_script("slo_report")
        table = slo_report.rollup(
            [r for r in recs if r["status"] == "ok"], ["adapter"])
        assert ("acme-a",) in table and ("acme-b",) in table
        assert table[("acme-a",)]["requests"] >= 1
    finally:
        await service.stop()
        await watcher.stop()
        await server.shutdown()
        await frontend_rt.close()
        await worker_rt.close()
        await coord.stop()


def test_doctor_adapter_checks_units():
    from dynamo_tpu.doctor import (OK, SKIP, WARN, Report,
                                   check_adapter_cards,
                                   check_adapter_workers)
    entries = [
        {"model_name": "base", "card": {"runtime_config": {"extra": {}}}},
        {"model_name": "ok-ad", "card": {"runtime_config": {
            "extra": {"lora_base": "base", "adapter": "ok-ad"}}}},
        {"model_name": "dangling", "card": {"runtime_config": {
            "extra": {"lora_base": "gone-base", "adapter": "dangling"}}}},
    ]
    rep = Report()
    check_adapter_cards(rep, entries)
    rows = {c: s for s, c, _ in rep.rows}
    assert rows["adapter card dangling"] == WARN
    assert rows["adapter cards"] == OK

    rep2 = Report()
    healthy = {"kv": {"adapters": {
        "max_adapters": 4, "resident": {"a": 1}, "registered": ["a"],
        "loads_total": 1, "evictions_total": 0, "miss_total": 1,
        "requests_total": {"a": 100}}}, "ok": True}
    stormy = {"kv": {"adapters": {
        "max_adapters": 1, "resident": {"b": 1}, "registered": ["a", "b"],
        "loads_total": 60, "evictions_total": 59, "miss_total": 60,
        "requests_total": {"a": 50, "b": 50}}}, "ok": True}
    check_adapter_workers(rep2, {"w1": healthy, "w2": stormy})
    rows2 = {c: (s, d) for s, c, d in rep2.rows}
    assert rows2["adapters w1"][0] == OK
    assert rows2["adapters w2"][0] == WARN
    assert "miss storm" in rows2["adapters w2"][1]
    rep3 = Report()
    check_adapter_workers(rep3, {})
    assert rep3.rows[0][0] == SKIP


# -- heavy compose variants ----------------------------------------------------

@pytest.mark.slow
@async_test(timeout=600)
async def test_adapter_parity_composes_with_quant_kv():
    c = cfg(max_adapters=1, lora_max_rank=4, quant_kv="int8")
    shapes = c.lora_target_shapes()
    weights = rnd_adapter(4, shapes, SPEC.num_layers, rank=4)
    eng = TPUEngine(c)
    eng.register_adapter("a", weights=weights)
    ref_eng = TPUEngine(cfg(max_adapters=1, lora_max_rank=4))
    ref_eng.register_adapter("a", weights=weights)
    prompt = prompt_tokens()
    try:
        got = await collect(eng, prompt, 8, adapter="a")
        ref = await collect(ref_eng, prompt, 8, adapter="a")
        # int8 KV legitimately perturbs logits; require the FIRST token
        # (pre-quantization-error accumulation) to agree and the run to
        # complete with the adapter engaged.
        assert got[0] == ref[0]
        assert len(got) == 8
        assert eng.adapters.status()["requests_total"] == {"a": 1}
    finally:
        eng.stop()
        ref_eng.stop()


@pytest.mark.slow
def test_adapter_parity_composes_with_tp2():
    """tp=2 adapter prefill must match tp=1 within the sharding suite's
    tolerance (GSPMD changes reduction orders, so exact token equality
    only holds per-forward — test_sharding.py discipline), and the
    adapter delta must actually engage on the sharded mesh."""
    weights = rnd_adapter(5, cfg().lora_target_shapes(), SPEC.num_layers,
                          rank=4)
    prompt = np.asarray(prompt_tokens(20), np.int32)
    logits = {}
    toks = {}
    for tp in (1, 2):
        runner = ModelRunner(cfg(max_adapters=1, lora_max_rank=4, tp=tp),
                             seed=0)
        runner.set_adapter_slot(1, {k: weights[k]
                                    for k in runner.config
                                    .lora_target_shapes()})
        seq = PrefillSeq(tokens=prompt, start_pos=0,
                         chunk_pages=np.arange(1, 3, dtype=np.int32),
                         hist_pages=None, sampling=(0.0, 0, 1.0),
                         adapter_id=1)
        base_seq = PrefillSeq(tokens=prompt, start_pos=0,
                              chunk_pages=np.arange(3, 5, dtype=np.int32),
                              hist_pages=None, sampling=(0.0, 0, 1.0))
        toks[tp] = int(runner.prefill_batch([seq])[0])
        logits[tp] = np.asarray(runner.last_prefill_logits[0], np.float32)
        base_tok = int(runner.prefill_batch([base_seq])[0])
        assert toks[tp] != base_tok, \
            f"adapter delta did not engage under tp={tp}"
    assert toks[1] == toks[2], "tp=2 adapter first token diverged"
    np.testing.assert_allclose(logits[1], logits[2], atol=0.15, rtol=0.05)
