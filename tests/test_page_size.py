"""A KV page is a derived quantity (PR 31).

``EngineConfig.page_size`` and the launchers' ``--page-size`` default to
"auto": 16 tokens, and where the Pallas kernel reads the pool on one TPU
device the smallest power of two of tokens whose one strided copy moves
64 KB (engine/config.py resolve_page_size; measured in PERF.md section 6).
The number is an integer from the moment the configuration exists, the
context limit stays in tokens, and everything that counts in pages follows
the one number: the allocator and the prefix cache's hash block, the model
card's block and the KV router's indexer, and the parcels workers exchange.
Nothing here compiles a model; the kernels at these pages are compared in
tests/test_attention_pallas.py and test_kv_commit.py and compiled for a
described v5e in tests/test_tpu_compile.py.
"""

import asyncio
import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

from dynamo_tpu.engine.config import (DEFAULT_MAX_MODEL_LEN, PRESETS,
                                      EngineConfig, ModelSpec, pool_access)
from dynamo_tpu.engine.kv_cache import PageAllocator
from dynamo_tpu.llm.tokens import TokenBlockSequence


def spec_of(nkv: int, head_dim: int = 128) -> ModelSpec:
    return ModelSpec(name=f"kv{nkv}x{head_dim}", hidden_size=28 * head_dim,
                     num_heads=28 if 28 % nkv == 0 else 32, num_layers=2,
                     num_kv_heads=nkv, head_dim=head_dim)


# -- the derivation -------------------------------------------------------------

@pytest.mark.parametrize("nkv, head_dim, kw, platform, want", [
    (4, 128, {}, "tpu", 64),     # Qwen2.5-7B, SmallThinker: both cells
    (8, 128, {}, "tpu", 32),     # Llama-3-8B: a copy moves twice the bytes
    (2, 128, {}, "tpu", 128),    # the ceiling
    (1, 128, {}, "tpu", 128),
    (16, 128, {}, "tpu", 16),    # 64 KB at the floor already
    (4, 128, {}, "cpu", 16),     # the CPU (all of tier 1)
    (4, 128, {"tp": 2}, "tpu", 16),
    (4, 128, {"pp": 2}, "tpu", 16),
    (4, 128, {"dp": 2}, "tpu", 16),
    (4, 128, {"sp": 2}, "tpu", 16),
    (2, 64, {}, "tpu", 16),      # a packed head: XLA reads the pool
    (4, 128, {"quant_kv": "int8"}, "tpu", 16),
    (4, 128, {"attention_backend": "xla"}, "tpu", 16),
    (4, 128, {"attention_backend": "pallas"}, "tpu", 64),
    (4, 128, {"attention_backend": "pallas"}, "cpu", 16),  # interpreted
])
def test_auto_page_is_derived_from_what_the_runner_observes(
        nkv, head_dim, kw, platform, want):
    cfg = EngineConfig(model=spec_of(nkv, head_dim), **kw)
    assert cfg.resolve_page_size(platform) == want
    # The derivation engages exactly where the kernel reads the pool and
    # the window commits in place: one statement of that choice.
    reader, writer = pool_access(cfg.attention_backend, platform,
                                 cfg.mesh_size, head_dim,
                                 cfg.resolve_quant_kv())
    assert (want > 16) <= (platform == "tpu" and reader == "pallas"
                           and writer == "in_place")


def test_the_env_override_of_the_pool_s_type_is_honoured(monkeypatch):
    monkeypatch.setenv("DTPU_QUANT_KV", "int8")
    assert EngineConfig(model=spec_of(4)).resolve_page_size("tpu") == 16


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The process's first device says "tpu" (nothing else is asked)."""
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [SimpleNamespace(platform="tpu")])


def test_page_size_is_an_integer_as_soon_as_the_config_exists(on_a_tpu):
    """The benchmark sizes the pool from config.page_size on a bare
    ModelRunner before any engine exists, and later divides by it."""
    cfg = EngineConfig(model=spec_of(4))
    assert cfg.page_size == 64 and isinstance(cfg.page_size, int)
    assert dataclasses.replace(cfg, num_pages=100).page_size == 64
    assert EngineConfig(model=spec_of(8)).page_size == 32
    assert EngineConfig(model=spec_of(4), tp=4).page_size == 16
    assert EngineConfig(model=PRESETS["tiny-test"]).page_size == 16


def test_a_mesh_never_asks_the_platform(monkeypatch):
    """jax.distributed.initialize must precede any backend: a configuration
    that a TPU would not change resolves without touching a device."""
    import jax

    def boom(*a):
        raise AssertionError("asked for devices")

    monkeypatch.setattr(jax, "devices", boom)
    assert EngineConfig(model=spec_of(4), tp=2).page_size == 16
    assert EngineConfig(model=PRESETS["tiny-test"]).page_size == 16
    assert EngineConfig(model=spec_of(4), page_size=32).page_size == 32


@pytest.mark.parametrize("page", [16, 32, 64, 128])
def test_an_explicit_page_is_kept_and_the_limit_stays_8192_tokens(
        on_a_tpu, page):
    cfg = EngineConfig(model=spec_of(4), page_size=page)
    assert cfg.page_size == page
    assert cfg.max_pages_per_seq == DEFAULT_MAX_MODEL_LEN // page
    assert cfg.max_model_len == 8192
    assert EngineConfig(model=spec_of(4), page_size=page,
                        max_pages_per_seq=40).max_model_len == 40 * page


def test_the_default_context_limit_is_8192_before_and_after(on_a_tpu):
    assert EngineConfig(model=spec_of(4)).max_model_len == 8192       # 64
    assert EngineConfig(model=spec_of(4), tp=2).max_model_len == 8192  # 16
    assert EngineConfig(model=spec_of(4), tp=2).max_pages_per_seq == 512


@pytest.mark.parametrize("bad", ["derive", "64", 0, -16])
def test_a_page_that_is_neither_auto_nor_positive_is_refused(bad):
    with pytest.raises(ValueError, match="page_size"):
        EngineConfig(model=spec_of(4), page_size=bad)


# -- the launchers ---------------------------------------------------------------

def _parsers():
    from dynamo_tpu import launch
    from dynamo_tpu.backends import tpu
    return {"launch": lambda argv: launch.parse_args(
                ["in=http", "out=tpu", *argv]),
            "worker": tpu.parse_args}


@pytest.mark.parametrize("which", ["launch", "worker"])
def test_page_size_defaults_to_auto_and_keeps_its_meaning_when_given(
        which, on_a_tpu):
    from dynamo_tpu.backends.tpu import build_engine_config
    parse = _parsers()[which]
    args = parse(["--model", "tiny-test"])
    assert args.page_size == "auto" and args.max_pages_per_seq is None
    assert parse(["--page-size", "auto"]).page_size == "auto"
    given = parse(["--model", "tiny-test", "--page-size", "32"])
    assert given.page_size == 32
    for bad in ("0", "sixteen"):
        with pytest.raises(SystemExit):
            parse(["--page-size", bad])
    cfg = build_engine_config(args)
    assert cfg.page_size == 16 and cfg.max_model_len == 8192  # head_dim 32
    cfg = build_engine_config(given)
    assert cfg.page_size == 32 and cfg.max_model_len == 8192
    wide = parse(["--model", "tiny-test", "--max-pages-per-seq", "64"])
    assert build_engine_config(wide).max_model_len == 64 * 16


# -- pages in the cache: the prefix grain ---------------------------------------

def _serve(alloc: PageAllocator, tokens: list[int]) -> tuple[int, list[int]]:
    """What the engine does with a prompt: pin the cached prefix, allocate
    the rest, register every complete block, release at the end. Returns
    (tokens reused, the block hashes)."""
    page = alloc.page_size
    hashes = TokenBlockSequence(page, tokens).block_hashes
    # The last token is always recomputed (engine._plan_prefill).
    usable = hashes[:(len(tokens) - 1) // page]
    assert alloc.lookup(usable) == alloc.lookup(hashes)[:len(usable)]
    cached = alloc.acquire_cached(usable)
    fresh = alloc.allocate(-(-len(tokens) // page) - len(cached))
    pages = cached + fresh
    for p, h in zip(pages, hashes):
        alloc.register(p, h)
    alloc.release(pages)
    return len(cached) * page, hashes


@pytest.mark.parametrize("page, shared, reused", [
    (64, 2992, 2944),   # a 3,000-token document (R5 docqa)
    (64, 496, 448),     # a 500-token system prompt
    (16, 2992, 2992),   # the same at the page the CPU and every mesh keep
    (16, 496, 496),
    (32, 496, 480),     # 8 KV heads
    (128, 2992, 2944),
])
def test_a_shared_prefix_is_reused_in_whole_pages(page, shared, reused):
    """floor(shared / page) pages of a shared prefix come back from the
    cache: a coarser grain of reuse at a larger page, not a weaker answer.
    And the KV router's indexer, built from the block size the model card
    carries, scores the overlap the worker will find."""
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer
    from dynamo_tpu.llm.kv_router.protocols import KvCacheEvent, RouterEvent
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    rng = np.random.default_rng(page + shared)
    prefix = rng.integers(0, 50000, size=shared).tolist()
    first = prefix + rng.integers(0, 50000, size=40).tolist()
    second = prefix + rng.integers(0, 50000, size=75).tolist()
    alloc = PageAllocator(num_pages=4096 // page * 8, page_size=page)
    assert _serve(alloc, first)[0] == 0
    # What backends/tpu.py registers: kv_cache_block_size = the page.
    card = ModelDeploymentCard(name="m", kv_cache_block_size=alloc.page_size)
    indexer = KvIndexer(card.kv_cache_block_size)
    stored, _ = alloc.drain_events()
    indexer.apply(RouterEvent(worker_id=7, event=KvCacheEvent.stored(stored)))
    scored = indexer.find_matches_for_tokens(second)
    got, _ = _serve(alloc, second)
    assert got == reused == shared // page * page
    assert scored == {7: reused // page}
    assert alloc.reuse_hit_blocks == reused // page


# -- pages between workers --------------------------------------------------------

def test_foreign_pages_names_both_sizes():
    from dynamo_tpu.llm.kv_transfer import foreign_pages
    assert foreign_pages((2, 2, 2, 5, 64, 32), 64) is None
    why = foreign_pages((2, 2, 2, 19, 16, 32), 64)
    assert "16" in why and "64" in why and "--page-size" in why


def test_the_runner_refuses_pages_of_another_size_and_never_reshapes():
    """A 16-token parcel offered to a 64-token pool: insert_pages raises
    by name before it touches the pool (four 16-token pages hold the bytes
    of one 64-token page, so a reshape would have fitted)."""
    from dynamo_tpu.engine.runner import ModelRunner
    spec = PRESETS["tiny-test"]
    runner = ModelRunner(EngineConfig(model=spec, page_size=64, num_pages=8,
                                      max_num_seqs=2), seed=0)
    assert runner.page_size == 64
    before = np.asarray(runner.k_cache).copy()
    parcel = np.ones((2, spec.num_layers, spec.num_kv_heads, 4, 16,
                      spec.head_dim), np.float32)
    with pytest.raises(ValueError, match=r"pages of 16 tokens.*pages of 64"):
        runner.insert_pages(parcel, [1, 2, 3, 4])
    with pytest.raises(ValueError, match=r"pages of 16 tokens.*pages of 64"):
        runner.insert_pages(parcel[:, :, :, :1], [1])
    np.testing.assert_array_equal(np.asarray(runner.k_cache), before)
    own = runner.extract_pages([1, 2])
    assert own.shape[3:5] == (2, 64)
    runner.insert_pages(own, [3, 4])  # its own page size is taken


def test_the_decode_side_refuses_a_parcel_of_another_page_size():
    """llm/kv_transfer.collect_prefill_response, given the receiving pool's
    page: a prefill worker's 16-token pages are refused on receipt with
    both sizes named (the disaggregated handler then prefills locally)."""
    from dynamo_tpu.llm.kv_transfer import (collect_prefill_response,
                                            kv_to_chunks)
    kv = np.zeros((2, 2, 2, 3, 16, 32), np.float32)
    meta, chunks = kv_to_chunks(kv)

    async def stream():
        yield {"disagg_params": meta}
        for c in chunks:
            yield {"disagg_params": {"kv_chunk": c}}
        yield {"token_ids": [11]}

    async def go(page):
        return await collect_prefill_response(stream(), page_size=page)

    token, got = asyncio.run(go(16))
    assert token == 11 and got.shape == kv.shape
    with pytest.raises(RuntimeError, match=r"pages of 16 tokens.*pages of 64"):
        asyncio.run(go(64))
