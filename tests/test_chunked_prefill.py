"""Stall-free chunked prefill (scheduled chunk dispatches, CPU mesh).

Covers the scheduler rework that turned long-prompt prefill from a
blocking loop inside the engine thread into scheduled chunk work
interleaved with decode windows:

- exact token parity between the chunked and whole-prompt paths (greedy,
  penalties, prefix-cache reuse, multimodal spans): everything in the
  chunked token path is deterministic, so equality is asserted exactly;
  seeded sampling makes the same draws on both (held to the common prefix:
  the two programs' logits differ in the last bits);
- decode windows keep dispatching BETWEEN chunk dispatches (no
  full-prompt stall) while a long prompt prefills;
- intermediate chunks perform no blocking host readback
  (runner.sync_prefill_fetches stays 0 on the serving path);
- the SLA cold-token ledger counts the chunk backlog while prefilling;
- preemption of a still-prefilling request under KV pressure requeues
  and completes it (slow: fresh engine + pool-pressure churn).
"""

import asyncio
import time

import numpy as np
import pytest
from conftest import async_test

from dynamo_tpu.engine.config import EngineConfig, PRESETS
from dynamo_tpu.engine.engine import TPUEngine
from dynamo_tpu.engine.model import init_params
from dynamo_tpu.llm.protocols import PreprocessedRequest
from dynamo_tpu.runtime.context import Context

SPEC = PRESETS["tiny-test"]
PAGE = 16


def cfg(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=128,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128, 256),
                    max_prefill_tokens=32, attention_backend="xla")
    defaults.update(kw)
    return EngineConfig(**defaults)


@pytest.fixture(scope="module")
def params():
    import jax
    return init_params(SPEC, jax.random.key(42))


@pytest.fixture(scope="module")
def chunked_engine(params):
    # max_prefill_tokens=32: any prompt longer than 32 tokens takes the
    # scheduled chunked path, in 32-token chunks.
    eng = TPUEngine(cfg(), params=params)
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def whole_engine(params):
    # Same weights, whole-prompt path for prompts up to 256 tokens.
    eng = TPUEngine(cfg(max_prefill_tokens=256), params=params)
    yield eng
    eng.stop()


def _prompt(seed: int, n: int) -> list[int]:
    rng = np.random.default_rng(seed)
    return rng.integers(0, SPEC.vocab_size, size=n).tolist()


async def run_one(engine, prompt, max_tokens, mm=None, **sampling):
    req = PreprocessedRequest(model="m", token_ids=list(prompt),
                              mm_embeds=mm)
    req.stop_conditions.max_tokens = max_tokens
    req.stop_conditions.ignore_eos = True
    for k, v in sampling.items():
        setattr(req.sampling_options, k, v)
    toks, lps = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.get("token_ids", []))
        lps.extend(out.get("log_probs") or [])
        if out.get("finish_reason"):
            break
    return toks, lps


@async_test
async def test_chunked_whole_prompt_parity_greedy_and_seeded(
        chunked_engine, whole_engine):
    """The same prompt produces IDENTICAL greedy tokens through the chunked
    and whole-prompt paths, and seeded stochastic sampling makes the same
    draws on both: a draw folds (seed, position), so the path split cannot
    perturb it.

    What the split does perturb is the logits, by the two prefill
    programs' orders of summation (up to 0.03 nat here), and over this
    model's nearly flat distribution a draw at temperature 0.9 then lands
    on the other side of a boundary about once in thirty tokens (seeds 11,
    15 and 23 of 11 to 30 part at tokens 7, 4 and 6). So a
    seeded pair is held to its common prefix: keys that differed would
    part at the first token of every seed, rounding parts few and late."""
    p_greedy = _prompt(5, 150)
    a, _ = await run_one(chunked_engine, p_greedy, 8)
    b, _ = await run_one(whole_engine, p_greedy, 8)
    assert a == b
    p_seeded = _prompt(6, 150)
    agreed = 0
    seeds = range(11, 17)
    for seed in seeds:
        kw = dict(temperature=0.9, top_p=0.95, seed=seed, logprobs=2)
        a, lp_a = await run_one(chunked_engine, p_seeded, 8, **kw)
        b, lp_b = await run_one(whole_engine, p_seeded, 8, **kw)
        assert len(lp_a) == len(lp_b) == len(a) == len(b) == 8
        n = next((i for i in range(8) if a[i] != b[i]), 8)
        agreed += n
        # Chosen-token logprobs agree within bf16 path tolerance (the two
        # prefill programs reduce in different orders).
        np.testing.assert_allclose(lp_a[:n], lp_b[:n], atol=0.05)
    assert agreed >= 0.75 * 8 * len(seeds), agreed
    # And none of the chunked serving above performed a blocking prefill
    # readback: intermediate chunks chain KV on device; the final
    # chunk's token resolves asynchronously.
    assert chunked_engine.runner.sync_prefill_fetches == 0


@pytest.mark.slow
@async_test
async def test_chunked_whole_prompt_parity_penalties(
        chunked_engine, whole_engine):
    """Frequency/presence penalties ride only the FINAL chunk (earlier
    chunks' samples are discarded) — token parity must hold."""
    p = _prompt(7, 150)
    kw = dict(frequency_penalty=0.6, presence_penalty=0.4)
    a, _ = await run_one(chunked_engine, p, 10, **kw)
    b, _ = await run_one(whole_engine, p, 10, **kw)
    assert a == b


@async_test
async def test_chunked_prefix_cache_reuse(chunked_engine):
    """A repeated long prompt reuses cached prefix pages (fewer chunk
    tokens dispatched) and still produces identical output."""
    p = _prompt(8, 150)
    a, _ = await run_one(chunked_engine, p, 6)
    hits_before = chunked_engine.prefix_hit_blocks
    toks_before = chunked_engine.chunk_tokens_total
    b, _ = await run_one(chunked_engine, p, 6)
    assert a == b
    assert chunked_engine.prefix_hit_blocks > hits_before
    # Reuse covers all complete blocks but the last: the re-run's chunk
    # work is a fraction of the cold run's.
    assert chunked_engine.chunk_tokens_total - toks_before < 64


@pytest.mark.slow
@async_test
async def test_chunked_multimodal_span_parity(chunked_engine, whole_engine):
    """A multimodal span in the middle of a long prompt injects the same
    embeddings chunk-by-chunk as it does in one whole-prompt pass."""
    rng = np.random.default_rng(9)
    p = _prompt(9, 140)
    emb = rng.standard_normal((24, SPEC.hidden_size)).astype(np.float32)
    # Span [40, 64) crosses the 32-token chunk boundaries at 64... keep
    # it straddling chunk 2/3 of the chunked path.
    mm = [{"start": 40, "b": emb.tobytes(),
           "shape": [24, SPEC.hidden_size], "dtype": "float32"}]
    a, _ = await run_one(chunked_engine, p, 6, mm=[dict(mm[0])])
    b, _ = await run_one(whole_engine, p, 6, mm=[dict(mm[0])])
    assert a == b


@async_test
async def test_decode_progresses_during_chunked_prefill(chunked_engine):
    """While a long prompt prefills in chunks, a concurrently decoding
    request keeps emitting tokens: decode windows are dispatched BETWEEN
    chunk dispatches (bounded interference), never after the whole
    prompt. Also: the cold-token ledger carries the chunk backlog for
    the projection/brownout plane the whole time."""
    eng = chunked_engine
    events = []
    cold_during = []
    orig_win = eng.runner.decode_window
    orig_chunk = eng.runner.prefill_chunk_async
    orig_batch = eng.runner.prefill_batch

    def win(packed, window):
        events.append(("window", None))
        # A window of a real device takes time. On the CPU the tiny model's
        # eight windows take 0.3 ms each, so on a loaded machine the decoder
        # could be through all 64 tokens before this test's own coroutine
        # was scheduled to send the long prompt (seen at the parent too).
        time.sleep(0.002)
        return orig_win(packed, window)

    def chunk(seq):
        events.append(("chunk", len(seq.tokens)))
        cold_during.append(eng._cold_inflight)
        return orig_chunk(seq)

    def batch(seqs, slots=None, count_rows=None, fetch=True):
        if slots is not None and len(seqs) == 1 and seqs[0].start_pos:
            events.append(("chunk", len(seqs[0].tokens)))  # final chunk
        return orig_batch(seqs, slots=slots, count_rows=count_rows,
                          fetch=fetch)

    eng.runner.decode_window = win
    eng.runner.prefill_chunk_async = chunk
    eng.runner.prefill_batch = batch
    try:
        # Start a decoder and wait for its FIRST token before the long
        # prompt arrives, so decode is live through the whole prefill.
        req = PreprocessedRequest(model="m", token_ids=_prompt(20, 20))
        req.stop_conditions.max_tokens = 64
        req.stop_conditions.ignore_eos = True
        gen = eng.generate(req, Context())
        d_toks = []
        out = await gen.__anext__()
        d_toks.extend(out.get("token_ids", []))
        long_task = asyncio.ensure_future(run_one(eng, _prompt(21, 160), 4))
        async for out in gen:
            d_toks.extend(out.get("token_ids", []))
            if out.get("finish_reason"):
                break
        l_toks, _ = await long_task
        assert len(d_toks) == 64 and len(l_toks) == 4
        chunk_idx = [i for i, (kind, _) in enumerate(events)
                     if kind == "chunk"]
        assert len(chunk_idx) == 5, events  # 4 x 32 + final 32
        # The stall-free property: decode windows dispatch between EVERY
        # pair of consecutive chunk dispatches.
        for i, j in zip(chunk_idx, chunk_idx[1:]):
            assert any(events[k][0] == "window" for k in range(i + 1, j)), \
                f"no decode window between chunks at {i}..{j}: {events}"
        # SLA ledger: the full cold prompt is accounted while prefilling,
        # and squared away once the first token resolves.
        assert cold_during and all(c >= 160 for c in cold_during)
        assert eng._cold_inflight == 0 and not eng._prefilling
        assert eng.chunk_dispatch_count >= 4
    finally:
        (eng.runner.decode_window, eng.runner.prefill_chunk_async,
         eng.runner.prefill_batch) = (orig_win, orig_chunk, orig_batch)


@pytest.mark.slow
@async_test
async def test_prefilling_request_preempted_and_requeued(params):
    """KV pressure while a long prompt is STILL PREFILLING preempts it
    (decode victims are exhausted first), requeues it, and it completes
    correctly after re-admission — recompute semantics."""
    # 12 pages = 11 usable. Decoder: 30-token prompt (2 pages) growing to
    # ~5 pages. Long prompt: 128 tokens = 8 pages, prefilled at 16
    # tokens/iteration so the decoder's growth hits the empty pool while
    # chunks are still dispatching.
    eng = TPUEngine(cfg(num_pages=12, decode_window=8,
                        prefill_chunk_tokens=16), params=params)
    eng.start()
    try:
        decode_task = asyncio.ensure_future(
            run_one(eng, _prompt(30, 30), 40))
        while eng.step_count == 0:
            await asyncio.sleep(0.005)
        long_task = asyncio.ensure_future(run_one(eng, _prompt(31, 128), 6))
        (d_toks, _), (l_toks, _) = await asyncio.gather(
            decode_task, long_task)
        assert len(d_toks) == 40
        assert len(l_toks) == 6
        assert eng._cold_inflight == 0 and not eng._prefilling
    finally:
        eng.stop()


@pytest.mark.slow
@async_test(timeout=300)
async def test_chunked_interference_matrix(params):
    """Heavier mixed workload: several long prompts arriving mid-decode
    under a small pool and a small chunk budget — every stream completes
    with exactly its requested length, across preemption/requeue churn."""
    eng = TPUEngine(cfg(num_pages=48, max_num_seqs=6, decode_window=4,
                        prefill_chunk_tokens=16, max_prefill_tokens=32),
                    params=params)
    eng.start()
    try:
        decoders = [asyncio.ensure_future(
            run_one(eng, _prompt(50 + i, 20 + 3 * i), 48))
            for i in range(3)]
        while eng.step_count == 0:
            await asyncio.sleep(0.005)
        longs = [asyncio.ensure_future(
            run_one(eng, _prompt(60 + i, 120 + 16 * i), 8))
            for i in range(3)]
        results = await asyncio.gather(*decoders, *longs)
        for i, (toks, _) in enumerate(results[:3]):
            assert len(toks) == 48, f"decoder {i}: {len(toks)}"
        for i, (toks, _) in enumerate(results[3:]):
            assert len(toks) == 8, f"long {i}: {len(toks)}"
        assert eng._cold_inflight == 0 and not eng._prefilling
        assert not eng._chunk_inflight
    finally:
        eng.stop()


def test_resolve_prefill_chunk_tokens(monkeypatch):
    """'auto' sizes the per-iteration chunk budget from the same
    DTPU_WINDOW_TARGET_MS model as decode_window='auto', rounded down to
    a prefill bucket; env and int forms override; junk rejected."""
    monkeypatch.delenv("DTPU_PREFILL_CHUNK_TOKENS", raising=False)
    monkeypatch.delenv("DTPU_WINDOW_TARGET_MS", raising=False)
    monkeypatch.delenv("DTPU_PREFILL_KNEE_TOK", raising=False)
    from dynamo_tpu.engine.config import DEVICE_PEAKS
    v5e = DEVICE_PEAKS["TPU v5 lite"]

    def res(model="tiny-test", **kw):
        return EngineConfig(model=PRESETS[model],
                            **kw).resolve_prefill_chunk_tokens(v5e)

    # Tiny model: effectively free prefill -> budget caps at the largest
    # usable chunk (min of max_prefill_tokens and the bucket ladder).
    assert res(max_prefill_tokens=64, prefill_buckets=(32, 64, 128)) == 64
    # A big unsharded shard: one window period buys fewer tokens.
    big = res("llama-3-8b")
    small = res("qwen2.5-0.5b")
    assert big < small
    # Rounded down to a bucket so chunks don't pad past the target.
    assert big in EngineConfig().prefill_buckets
    # tp shrinks the step -> bigger chunks again.
    assert res("llama-3-8b", tp=8) >= big
    # Explicit int passes through (floored to a page).
    assert res(prefill_chunk_tokens=100) == 100
    assert res(prefill_chunk_tokens=4) == 16  # page floor
    with pytest.raises(ValueError):
        res(prefill_chunk_tokens=0)
    with pytest.raises(ValueError):
        res(prefill_chunk_tokens="big")
    # Env overrides both forms.
    monkeypatch.setenv("DTPU_PREFILL_CHUNK_TOKENS", "48")
    assert res(prefill_chunk_tokens="auto") == 48
    monkeypatch.setenv("DTPU_PREFILL_CHUNK_TOKENS", "auto")
    assert res(prefill_chunk_tokens=999,
               max_prefill_tokens=64, prefill_buckets=(32, 64)) == 64
    # The window-target knob moves the auto answer.
    monkeypatch.delenv("DTPU_PREFILL_CHUNK_TOKENS", raising=False)
    monkeypatch.setenv("DTPU_WINDOW_TARGET_MS", "10")
    assert res("llama-3-8b") <= big


@pytest.mark.slow
def test_warmup_prefill_ladder_compiles_all_buckets(params):
    """warmup_prefill_ladder=True pre-compiles every prefill bucket with
    AND without history (the chunk-path variants) before serving."""
    eng = TPUEngine(cfg(prefill_buckets=(32, 64), warmup_windows=True,
                        warmup_prefill_ladder=True), params=params)
    try:
        eng._warmup_prefill_ladder()
        keys = set(eng.runner._prefill_cache)
        for bucket in (32, 64):
            for with_h in (False, True):
                assert (bucket, 1, with_h, False, False, False) in keys, \
                    (bucket, with_h, sorted(keys))
    finally:
        eng.stop()


def test_warmup_ladder_off_is_noop(chunked_engine):
    """The flag default keeps warmup cheap: the ladder helper is a no-op
    without warmup_prefill_ladder (no new programs compile)."""
    keys_before = set(chunked_engine.runner._prefill_cache)
    chunked_engine._warmup_prefill_ladder()
    assert set(chunked_engine.runner._prefill_cache) == keys_before


@async_test
async def test_a_prompt_past_the_chunk_budget_is_chunked_not_sent_whole(params):
    """A prompt goes whole only if it fits one iteration's chunk budget,
    whatever the largest bucket would take: on the chip a 5,000-token
    prompt under the defaults (budget 1024, largest bucket 8192) was
    compiled whole, did not fit, and was answered 500 (PR 28)."""
    eng = TPUEngine(cfg(max_prefill_tokens=128, prefill_chunk_tokens=32),
                    params=params)
    try:
        short, _ = await run_one(eng, _prompt(3, 30), 4)
        assert eng.chunk_dispatch_count == 0 and len(short) == 4
        long, _ = await run_one(eng, _prompt(4, 100), 4)  # under 128, over 32
        assert len(long) == 4
        assert eng.chunk_dispatch_count == 4            # 32 + 32 + 32 + 4
        assert eng.chunk_tokens_total == 100
    finally:
        eng.stop()
