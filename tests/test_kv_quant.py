"""Quantized int8 KV cache tests (engine/kv_quant.py; ROADMAP item 2).

Quality gate styled on the int8 weight gate (tests/test_quant.py):
quantized-vs-bf16 KV logits tolerance + greedy/seeded agreement on the
tiny CPU model, across the whole-prompt, decode-window, chunked-prefill
and prefix-reuse paths. Capacity gate: ~2x PageAllocator pages at a
fixed HBM budget and the halved KV pool ledger in memory_breakdown().
Wire gate: packed int8+scales parcels round-trip extract->insert and
interoperate with bf16 pools. All near-free (tiny model, CPU).
"""

import dataclasses
import os
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import async_test

from dynamo_tpu.engine.config import EngineConfig, PRESETS
from dynamo_tpu.engine.kv_quant import (KV_SCALE_BYTES, QuantKV,
                                        dequantize_np, pack_parcel,
                                        quantize_np, unpack_parcel)
from dynamo_tpu.engine.runner import ModelRunner, PrefillSeq

SPEC = PRESETS["tiny-test"]
PAGE = 16


def tiny_config(quant_kv=None, **kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=64,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64), max_prefill_tokens=64,
                    attention_backend="xla", quant_kv=quant_kv)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _prompt(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, SPEC.vocab_size, size=n).astype(np.int32)


def _seq(prompt, pages=(1, 2), seed=None):
    return PrefillSeq(tokens=np.asarray(prompt, np.int32), start_pos=0,
                      chunk_pages=np.asarray(pages, np.int32),
                      hist_pages=None, sampling=(0.0, 0, 1.0), seed=seed)


# ---------------------------------------------------------------------------
# codec units
# ---------------------------------------------------------------------------

def test_kv_quantize_round_trip_error_bound():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 2, 5, PAGE, 32)).astype(np.float32)
    q, s = quantize_np(x)
    assert q.dtype == np.int8 and s.shape == x.shape[:-1]
    deq = np.asarray(dequantize_np(q, s), np.float32)
    # Symmetric round-to-nearest: error <= half a step per token row.
    assert float(np.max(np.abs(deq - x))) <= float(s.max()) / 2 + 1e-2
    # All-zero rows stay exactly zero (scale 1 convention).
    qz, sz = quantize_np(np.zeros((4, 8)))
    assert np.all(qz == 0) and np.all(sz == 1.0)


def test_kv_quantize_traceable_matches_numpy_twin():
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_quant import kv_quantize
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 3, PAGE, 32)).astype(np.float32)
    qj, sj = kv_quantize(jnp.asarray(x))
    qn, sn = quantize_np(x)
    np.testing.assert_array_equal(np.asarray(qj), qn)
    np.testing.assert_array_equal(np.asarray(sj), sn)


def test_pack_unpack_parcel_byte_identity():
    rng = np.random.default_rng(2)
    data = rng.integers(-127, 128, size=(2, 2, 2, 3, PAGE, 32),
                        dtype=np.int8)
    scale = rng.random((2, 2, 2, 3, PAGE)).astype(np.float32)
    packed = pack_parcel(data, scale)
    assert packed.dtype == np.uint8
    assert packed.shape[-1] == 32 + KV_SCALE_BYTES
    d2, s2 = unpack_parcel(packed)
    np.testing.assert_array_equal(d2, data)
    np.testing.assert_array_equal(s2, scale)
    # Page-axis slicing (the tier/onboard access pattern) stays exact.
    d3, s3 = unpack_parcel(packed[:, :, :, 1])
    np.testing.assert_array_equal(d3, data[:, :, :, 1])
    np.testing.assert_array_equal(s3, scale[:, :, :, 1])


# ---------------------------------------------------------------------------
# capacity: ~2x pages at a fixed HBM budget + honest ledgers
# ---------------------------------------------------------------------------

def test_capacity_pages_double_at_fixed_hbm_budget():
    """The acceptance gate: same free HBM, same model — the int8 pool
    sizes ~2x pages (exact factor 2D/(D+4); 1.94x at head_dim 128)."""
    spec = PRESETS["llama-3-8b"]

    class Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 16 << 30, "bytes_in_use": 0}

    def pages(quant_kv):
        cfg = EngineConfig(model=spec, num_pages=None, quant_kv=quant_kv)
        ns = SimpleNamespace(config=cfg, spec=spec,
                             quant_kv=cfg.resolve_quant_kv())
        ns._kv_token_head_bytes = \
            lambda: ModelRunner._kv_token_head_bytes(ns)
        ns._memory_stats = ModelRunner._memory_stats
        ModelRunner._sized_pages(ns, Dev())
        return ns.num_pages

    ratio = pages("int8") / pages(None)
    expected = 2 * spec.head_dim / (spec.head_dim + KV_SCALE_BYTES)
    assert abs(ratio - expected) < 0.01, (ratio, expected)
    assert ratio > 1.85


def test_kv_token_bytes_accounting():
    cfg_bf = tiny_config()
    cfg_q = tiny_config(quant_kv="int8")
    d = SPEC.head_dim
    assert cfg_bf.kv_token_bytes() == SPEC.kv_bytes_per_token()
    assert (cfg_q.kv_token_bytes()
            == 2 * SPEC.num_layers * SPEC.num_kv_heads
            * (d + KV_SCALE_BYTES))


def test_memory_breakdown_reports_actual_pool_dtype_bytes():
    """runner.memory_breakdown() must report int8-pool bytes (data +
    scales), not the bf16 size, so perf_hbm_* workspace attribution
    doesn't silently absorb the savings. Both modes checked against the
    real device arrays."""
    a = ModelRunner(tiny_config())
    b = ModelRunner(tiny_config(quant_kv="int8"))
    assert a.memory_breakdown()["kv_pool_bytes"] == a.kv_pool_bytes
    assert b.memory_breakdown()["kv_pool_bytes"] == b.kv_pool_bytes
    # bf16: exactly the two pool arrays' bytes.
    assert a.kv_pool_bytes == a.k_cache.nbytes + a.v_cache.nbytes
    # int8: data + scale leaves of both QuantKV pools.
    q_bytes = sum(leaf.nbytes for cache in (b.k_cache, b.v_cache)
                  for leaf in (cache.data, cache.scale))
    assert b.kv_pool_bytes == q_bytes
    d = SPEC.head_dim
    assert (b.kv_pool_bytes / a.kv_pool_bytes
            == (d + KV_SCALE_BYTES) / (2 * d))


# ---------------------------------------------------------------------------
# quality gates (styled on tests/test_quant.py)
# ---------------------------------------------------------------------------

def test_quant_kv_runner_logits_close_and_greedy_agrees():
    a = ModelRunner(tiny_config())
    b = ModelRunner(tiny_config(quant_kv="int8"))
    agree = 0
    for seed in range(4):
        prompt = _prompt(seed, 32)
        ta = int(a.prefill_batch([_seq(prompt)])[0])
        la = np.asarray(a.last_prefill_logits[0], np.float32)
        tb = int(b.prefill_batch([_seq(prompt)])[0])
        lb = np.asarray(b.last_prefill_logits[0], np.float32)
        cos = float(np.dot(la, lb)
                    / (np.linalg.norm(la) * np.linalg.norm(lb) + 1e-9))
        assert cos > 0.99, f"seed {seed}: quantized-KV logits diverged ({cos})"
        agree += int(ta == tb)
    assert agree >= 3, f"greedy top-1 agreed only {agree}/4 times"


def test_quant_kv_decode_logits_close_teacher_forced():
    """The fused quantize-commit + dequant-read loop, gated on LOGITS:
    teacher-forced decode steps (same token fed to both pools, each
    step's K/V committed through each pool's own write path) must keep
    per-step logits cosine-close. Token-chain comparisons are the wrong
    gate here — one bf16 near-tie flip legitimately diverges the whole
    autoregressive suffix."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.model import decode_forward
    a = ModelRunner(tiny_config())
    b = ModelRunner(tiny_config(quant_kv="int8"))
    prompt = _prompt(11, 32)
    tok = int(a.prefill_batch([_seq(prompt)])[0])
    int(b.prefill_batch([_seq(prompt)])[0])
    page_table = jnp.asarray(np.array([[1, 2, 3, 0]], np.int32))
    for step in range(6):
        tokens = jnp.asarray(np.array([tok], np.int32))
        pos = jnp.asarray(np.array([32 + step], np.int32))
        lens = jnp.asarray(np.array([33 + step], np.int32))
        la, a.k_cache, a.v_cache = decode_forward(
            a.params, a.spec, a.k_cache, a.v_cache, tokens, pos,
            page_table, lens)
        lb, b.k_cache, b.v_cache = decode_forward(
            b.params, b.spec, b.k_cache, b.v_cache, tokens, pos,
            page_table, lens)
        la = np.asarray(la[0], np.float32)
        lb = np.asarray(lb[0], np.float32)
        cos = float(np.dot(la, lb)
                    / (np.linalg.norm(la) * np.linalg.norm(lb) + 1e-9))
        assert cos > 0.99, f"step {step}: decode logits diverged ({cos})"
        tok = int(np.argmax(la))


@async_test(timeout=180)
async def test_quant_kv_engine_greedy_seeded_chunked_parity():
    """Engine-level golden gate: greedy, seeded-sampling, chunked-prefill
    and prefix-reuse paths on --quant-kv int8 vs bf16 KV. Reuse must be
    exactly deterministic (same engine, same pages); cross-dtype token
    agreement is a majority gate (int8 KV may flip bf16 near-ties)."""
    from dynamo_tpu.engine.engine import TPUEngine
    from dynamo_tpu.llm.protocols import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context

    async def run(engine, prompt, n, seed=None, temp=0.0):
        req = PreprocessedRequest(model="t", token_ids=list(prompt))
        req.stop_conditions.max_tokens = n
        req.stop_conditions.ignore_eos = True
        if seed is not None:
            req.sampling_options.seed = seed
            req.sampling_options.temperature = temp
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.get("token_ids", []))
            if out.get("finish_reason"):
                break
        return toks

    def agreement(x, y):
        return sum(a == b for a, b in zip(x, y))

    rng = np.random.default_rng(9)
    prompt = rng.integers(0, SPEC.vocab_size, size=24).tolist()
    long_prompt = rng.integers(0, SPEC.vocab_size, size=150).tolist()
    a = TPUEngine(tiny_config())
    b = TPUEngine(tiny_config(quant_kv="int8"))
    try:
        ga, gb = await run(a, prompt, 8), await run(b, prompt, 8)
        assert agreement(ga, gb) >= 6, (ga, gb)
        sa = await run(a, prompt, 8, seed=7, temp=0.9)
        sb = await run(b, prompt, 8, seed=7, temp=0.9)
        assert agreement(sa, sb) >= 6, (sa, sb)
        ca, cb = await run(a, long_prompt, 6), await run(b, long_prompt, 6)
        assert agreement(ca, cb) >= 4, (ca, cb)
        # Prefix reuse on the quantized engine is exactly deterministic:
        # reused int8 pages ARE the originally committed bytes.
        r1 = await run(b, prompt + [5, 9], 6)
        r2 = await run(b, prompt + [5, 9], 6)
        assert r1 == r2
        assert b.prefix_hit_blocks > 0, "prefix reuse never engaged"
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------------------------------------
# extract / insert / tiers: the compressed parcel lifecycle
# ---------------------------------------------------------------------------

def test_extract_insert_packed_roundtrip_and_mixed_pools():
    r = ModelRunner(tiny_config(quant_kv="int8"))
    r.prefill_batch([_seq(_prompt(5, 32))])
    kv = r.extract_pages([1, 2])
    d = SPEC.head_dim
    assert kv.dtype == np.uint8
    assert kv.shape == (2, SPEC.num_layers, SPEC.num_kv_heads, 2, PAGE,
                        d + KV_SCALE_BYTES)
    # ~half the bf16 parcel bytes.
    bf16_nbytes = 2 * SPEC.num_layers * SPEC.num_kv_heads * 2 * PAGE * d * 2
    assert kv.nbytes / bf16_nbytes == (d + KV_SCALE_BYTES) / (2 * d)
    # quant -> quant: byte-identical through insert + re-extract.
    r2 = ModelRunner(tiny_config(quant_kv="int8"))
    r2.insert_pages(kv, [4, 5])
    np.testing.assert_array_equal(kv, r2.extract_pages([4, 5]))
    # quant -> bf16 pool: dequantizes on upload.
    r3 = ModelRunner(tiny_config())
    r3.insert_pages(kv, [4, 5])
    back = r3.extract_pages([4, 5])
    data, scale = unpack_parcel(kv)
    np.testing.assert_array_equal(back.view(np.uint16),
                                  dequantize_np(data, scale).view(np.uint16))
    # bf16 -> quant pool: quantizes on upload. The bf16 leg rounds the
    # dequantized values, so re-quantization may shift codes by one
    # step — gate on dequantized VALUES within one quant step instead
    # of byte identity.
    r4 = ModelRunner(tiny_config(quant_kv="int8"))
    r4.insert_pages(back, [6, 7])
    d1, s1 = unpack_parcel(kv)
    d2, s2 = unpack_parcel(r4.extract_pages([6, 7]))
    va = np.asarray(dequantize_np(d1, s1), np.float32)
    vb = np.asarray(dequantize_np(d2, s2), np.float32)
    assert float(np.max(np.abs(va - vb))) <= float(s1.max()) * 1.5


def test_quant_kv_composes_with_weight_int8_and_tp():
    spec = dataclasses.replace(SPEC, quant="int8")
    r = ModelRunner(tiny_config(quant_kv="int8", model=spec, tp=2))
    r.prefill_batch([_seq(_prompt(6, 32))])
    kv = r.extract_pages([1, 2])
    assert kv.dtype == np.uint8
    # Canonical heads: replicas deduplicated, parcels portable.
    assert kv.shape[2] == SPEC.num_kv_heads
    r2 = ModelRunner(tiny_config(quant_kv="int8", model=spec, tp=2))
    r2.insert_pages(kv, [4, 5])
    np.testing.assert_array_equal(kv, r2.extract_pages([4, 5]))


def test_disk_tier_stores_packed_parcels(tmp_path):
    from dynamo_tpu.engine.kv_host_cache import DiskKVCache
    rng = np.random.default_rng(4)
    block = pack_parcel(
        rng.integers(-127, 128, size=(2, 2, 2, PAGE, 32), dtype=np.int8),
        rng.random((2, 2, 2, PAGE)).astype(np.float32))
    disk = DiskKVCache(str(tmp_path), capacity_pages=4)
    disk.put(123, block)
    got = disk.get(123)
    assert got.dtype == np.uint8
    np.testing.assert_array_equal(got, block)


# ---------------------------------------------------------------------------
# pallas kernel: fused in-register dequant (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("d", [64, 128])
def test_pallas_fused_dequant_matches_xla_quant_path(d, page):
    import jax.numpy as jnp
    import ml_dtypes

    from dynamo_tpu.engine.attention import paged_decode_attention_pallas
    from dynamo_tpu.engine.model import paged_decode_attention_xla

    rng = np.random.default_rng(0)
    # d=64 packs tpr=2 tokens per 128-lane row; d=128 is natural. A page
    # of 64 (an explicit --page-size: an int8 pool resolves 16 itself) lays
    # the scales out by the same chunk (attention._chunk_scales).
    L, nkv, P, B, qpk = 2, 2, 12, 3, 4
    k = rng.standard_normal((L, nkv, P, page, d)).astype(ml_dtypes.bfloat16)
    v = rng.standard_normal((L, nkv, P, page, d)).astype(ml_dtypes.bfloat16)
    kq, ks = quantize_np(k)
    vq, vs = quantize_np(v)
    kc = QuantKV(jnp.asarray(kq), jnp.asarray(ks))
    vc = QuantKV(jnp.asarray(vq), jnp.asarray(vs))
    q = jnp.asarray(
        rng.standard_normal((B, nkv * qpk, d)).astype(ml_dtypes.bfloat16))
    pt = jnp.asarray(rng.integers(0, P, size=(B, 8)).astype(np.int32))
    hist = jnp.asarray(np.array([5, 37, 100 * page // 16], np.int32))
    k_self = jnp.asarray(
        rng.standard_normal((B, nkv, d)).astype(ml_dtypes.bfloat16))
    v_self = jnp.asarray(
        rng.standard_normal((B, nkv, d)).astype(ml_dtypes.bfloat16))
    layer = jnp.asarray(1, jnp.int32)
    out_p = paged_decode_attention_pallas(q, kc, vc, layer, pt, hist,
                                          k_self, v_self, qpk,
                                          interpret=True)
    out_x = paged_decode_attention_xla(q, kc, vc, layer, pt, hist,
                                       k_self, v_self, qpk)
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                - out_x.astype(jnp.float32))))
    assert err < 0.05, f"pallas fused dequant diverged from xla: {err}"


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_quant_kv_cli_flag_and_env_override():
    from dynamo_tpu.backends.tpu import build_engine_config, parse_args
    args = parse_args(["--model", "tiny-test", "--quant-kv", "int8"])
    cfg = build_engine_config(args)
    assert cfg.quant_kv == "int8"
    assert cfg.resolve_quant_kv() == "int8"
    args = parse_args(["--model", "tiny-test"])
    assert build_engine_config(args).quant_kv is None
    # Env layering: DTPU_QUANT_KV wins in both directions.
    old = os.environ.get("DTPU_QUANT_KV")
    try:
        os.environ["DTPU_QUANT_KV"] = "int8"
        assert EngineConfig(model=SPEC).resolve_quant_kv() == "int8"
        os.environ["DTPU_QUANT_KV"] = "none"
        assert EngineConfig(model=SPEC,
                            quant_kv="int8").resolve_quant_kv() is None
    finally:
        if old is None:
            os.environ.pop("DTPU_QUANT_KV", None)
        else:
            os.environ["DTPU_QUANT_KV"] = old


def test_invalid_quant_kv_rejected():
    with pytest.raises(ValueError, match="quant_kv"):
        ModelRunner(tiny_config(quant_kv="fp4"))


def test_launch_parser_accepts_quant_kv():
    from dynamo_tpu.launch import parse_args as launch_parse
    args = launch_parse(["--model", "tiny-test", "--quant-kv", "int8"])
    assert args.quant_kv == "int8"
