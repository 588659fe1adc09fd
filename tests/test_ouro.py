"""A looped stack (``ouro``): the reader, the pool's 192 (pass, layer) pairs
and what follows from them, the one ``transformer_block`` under
``model.scan_passes`` through the runner and the engine, held to
benchmark/references/ouro.py on seeded weights at the rehearsal's size
(hidden 64, 3 layers run 3 times, 4 heads of 16: 9 pool layers)."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import async_test

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import manifest  # noqa: E402
from dynamo_tpu.engine import attention, model  # noqa: E402
from dynamo_tpu.engine.config import (DEVICE_PEAKS, EngineConfig,  # noqa: E402
                                      ModelSpec, OuroSpec,
                                      UnsupportedBlockError, block_refusals)
from dynamo_tpu.engine.engine import TPUEngine  # noqa: E402
from dynamo_tpu.engine.kv_quant import scatter_tokens  # noqa: E402
from dynamo_tpu.engine.runner import (PK_CAP, PK_LOGPROB, PK_POS,  # noqa: E402
                                      PK_PREFIX, PK_SEQLEN, PK_TOPP,
                                      ModelRunner, PrefillSeq)
from dynamo_tpu.llm.protocols import PreprocessedRequest  # noqa: E402
from dynamo_tpu.runtime import flight  # noqa: E402
from dynamo_tpu.runtime.context import Context  # noqa: E402

ref = manifest.load_module("references", "ouro")
CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH, "configs", "ouro-2.6b-int8.json"))
ROW = {k: v for k, v in CONFIG.items() if k not in (
    "source", "stands_for", "chips", "launch", "reference", "roofline",
    "rehearsal_model", "reduced", "assumed")}
TOY = {**ROW, **CONFIG["rehearsal_model"]}
PAGE = 16


def read_spec(cfg: dict, **fields) -> ModelSpec:
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "config.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(cfg, fh)
        return dataclasses.replace(ModelSpec.from_hf_config(path),
                                   name="ouro", **fields)


def seeded_params(spec, seed: int):
    """init_params, then every norm's weight drawn around 1 (a norm left
    out, or applied once too often, is then another number)."""
    params = model.init_params(spec, jax.random.key(seed))
    key = jax.random.key(seed + 100)
    for i, (name, leaf) in enumerate(sorted(params["layers"].items())):
        if name.endswith("_norm"):
            params["layers"][name] = (1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)).astype(jnp.bfloat16)
    params["final_norm"] = (1.0 + 0.3 * jax.random.normal(
        key, params["final_norm"].shape)).astype(jnp.bfloat16)
    return params


SPEC = read_spec(TOY)
PARAMS = seeded_params(SPEC, 11)
PUBLISHED = read_spec(ROW, quant="int8")


def config(**kw) -> EngineConfig:
    defaults = dict(model=SPEC, page_size=PAGE, num_pages=64,
                    max_pages_per_seq=16, max_num_seqs=4,
                    prefill_buckets=(32, 64, 128), max_prefill_tokens=128,
                    attention_backend="xla", decode_window=4,
                    pipeline_depth=2)
    defaults.update(kw)
    return EngineConfig(**defaults)


def prompt_of(n: int, seed: int) -> list[int]:
    return np.random.default_rng(seed).integers(
        0, SPEC.vocab_size, size=n).tolist()


async def collect(engine, prompt, max_tokens):
    req = PreprocessedRequest(model="m", token_ids=list(prompt))
    req.stop_conditions.max_tokens = max_tokens
    req.stop_conditions.ignore_eos = True
    req.sampling_options.logprobs = 1
    toks, lps = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.get("token_ids", []))
        lps.extend(out.get("log_probs") or [])
        if out.get("finish_reason"):
            break
    return toks, lps


#: Largest median and root mean square (nat) of the program's logprobs from
#: the reference's that pass here: bfloat16 activations against float32 over
#: 9 layer visits at this size read 0.006 and 0.008 over a position's whole
#: vocabulary (the reference itself computed in bfloat16 0.007 and 0.011),
#: the nearest control (a layer left out of the last pass) 0.057 and 0.10,
#: float8 activations 0.13 and 0.17.
NEAR = {"median": 0.025, "rms": 0.04}


def distance(a, b) -> dict:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"median": float(np.median(d)),
            "rms": float(np.sqrt(np.mean(d * d)))}


def near(a, b) -> bool:
    got = distance(a, b)
    return all(got[k] <= NEAR[k] for k in NEAR)


def logprobs(logits) -> np.ndarray:
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


# -- the reader and what follows from the pool's layers ---------------------------

def test_the_reader_makes_the_catalog_row_s_spec():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path, encoding="utf-8") as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Ouro-2.6B")
        assert row["config"] == ROW and CONFIG["source"] == row["source_url"]
    spec = PUBLISHED
    assert isinstance(spec, OuroSpec)
    assert (spec.loop_passes, spec.sandwich_norm,
            spec.early_exit_threshold) == (4, True, 1.0)
    assert (spec.num_layers, spec.num_heads, spec.num_kv_heads,
            spec.head_dim, spec.q_per_kv) == (48, 16, 16, 128, 1)
    assert spec.rope_theta == 1e6 and spec.rms_norm_eps == 1e-6
    assert not spec.qkv_bias and not spec.tie_word_embeddings
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert spec.num_params() == 48 * layer + 2 * 49152 * 2048 + 2048 \
        == 2_667_972_608
    shapes = model.param_shapes(spec)
    assert spec.num_params() == sum(int(np.prod(s)) for s in (
        *shapes["layers"].values(), shapes["embed"], shapes["final_norm"],
        shapes["lm_head"]))
    assert shapes["layers"]["attn_out_gain"] == (48, 2048, 1) \
        == shapes["layers"]["mlp_out_gain"]
    assert set(model.param_specs(spec)["layers"]) == set(shapes["layers"])


def test_the_pool_holds_a_layer_a_pass_and_the_step_reads_the_layers_a_pass():
    spec = PUBLISHED
    assert spec.layer_visits == spec.pool_layers == 192
    assert spec.kv_bytes_per_token() == 2 * 192 * 16 * 128 * 2 == 1_572_864
    cfg = EngineConfig(model=spec, page_size="auto", decode_window="auto",
                       max_num_seqs=4)
    assert cfg.kv_token_bytes() == 1_572_864
    assert cfg.resolve_page_size("tpu") == 16   # 16 heads x 128 x 2 B x 16
    # The layers' bytes once a pass, the embedding and the head once.
    assert spec.step_read_params() == (4 * 48 * (4 * 2048 * 2048
                                                 + 3 * 2048 * 5632 + 4 * 2048)
                                       + 2 * 49152 * 2048 + 2048)
    peaks = DEVICE_PEAKS["TPU v5 lite"]
    ms = spec.weight_read_step_ms(peaks.hbm_gbps)
    assert 12.2 < ms < 12.4
    assert cfg.resolve_decode_window(peaks) == 4        # 75 / (12.3 + 1)
    once = dataclasses.replace(spec, loop_passes=1)     # one read would say
    assert 3.2 < once.weight_read_step_ms(peaks.hbm_gbps) < 3.3
    assert dataclasses.replace(cfg, model=once).resolve_decode_window(
        peaks) == 16
    # A dense spec's estimate is what it was: one read of what is resident.
    dense = ModelSpec(num_layers=4, quant="int8")
    assert dense.step_read_params() == dense.num_params()


def test_the_pool_is_sized_by_its_192_layers():
    """The launcher's rule on a chip that reports 16.91 GB: 0.6 of what is
    free beside 2.67 GB of weights is 339 pages of 16 tokens."""

    class Chip:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 16_910_000_000, "bytes_in_use": 0}

    probe = object.__new__(ModelRunner)
    probe.spec = PUBLISHED
    probe.config = EngineConfig(model=PUBLISHED, page_size=16, max_num_seqs=4)
    probe.quant_kv = None
    probe._sized_pages(Chip())
    assert probe.num_pages == 339
    assert probe.num_pages * 16 == 5424


def test_a_prefill_group_is_bounded_by_its_fresh_k_and_v():
    """8 rows x 512 tokens would hold 6 GiB of fresh K and V ahead of the
    commit: the group runs in parts of the largest power of two of rows
    under 1 GiB; a dense model's group is what it was."""
    from dynamo_tpu.engine import runner as runner_mod
    calls = []

    class Probe(ModelRunner):
        def _prefill_parts(self, seqs, slots, count_rows, fetch, rows):
            calls.append((len(seqs), rows))
            return None

    def group(spec, bucket, n):
        probe = object.__new__(Probe)
        probe.spec = spec
        probe.config = EngineConfig(model=spec, page_size=16, num_pages=64,
                                    max_num_seqs=8)
        seq = PrefillSeq(tokens=np.zeros(bucket, np.int32), start_pos=0,
                         chunk_pages=np.zeros(1, np.int32), hist_pages=None,
                         sampling=(0.0, 0, 1.0))
        calls.clear()
        try:
            probe.prefill_batch([seq] * n, fetch=False)
        except AttributeError:      # past the bound: the probe has no arrays
            pass
        return list(calls)

    assert runner_mod.PREFILL_FRESH_KV_BYTES == 1 << 30
    assert group(PUBLISHED, 512, 8) == [(8, 1)]
    assert group(PUBLISHED, 256, 8) == [(8, 2)]
    assert group(PUBLISHED, 128, 8) == [(8, 4)]
    assert group(PUBLISHED, 128, 4) == []
    qwen = ModelSpec(hidden_size=3584, num_layers=28, num_heads=28,
                     num_kv_heads=4)
    assert group(qwen, 1024, 8) == []


@pytest.mark.parametrize("asked,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(max_adapters=2), "LoRA"),
    (dict(pp_microbatch=True, pp=1), "pipelined"),
    (dict(ring_attention=True), "ring prefill"),
    (dict(quant_kv="int8"), "int8 KV pages"),
    (dict(host_cache_pages=8), "kvbm"),
    (dict(tp=2), "mesh"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_engine_path_without_a_loop_is_refused(asked, names):
    refusals = block_refusals(SPEC, config(**asked))
    assert refusals and any(names in str(r) for r in refusals), refusals
    assert all(isinstance(r, UnsupportedBlockError) for r in refusals)
    with pytest.raises(UnsupportedBlockError):
        ModelRunner(config(**asked), params=PARAMS)


@pytest.mark.parametrize("asked,names", [
    (dict(kv_transfer=True), "KV parcel"),
    (dict(checkpoint=True), "safetensors"),
    (dict(embeddings=True), "mm_embeds"),
])
def test_a_parcel_a_checkpoint_and_embeddings_are_refused(asked, names):
    refusals = block_refusals(SPEC, **asked)
    assert len(refusals) == 1 and names in str(refusals[0])
    assert block_refusals(SPEC) == [] == block_refusals(SPEC, config())


def test_a_threshold_under_one_is_refused_by_its_mechanism():
    """Rows of one batch would leave the loop at different passes."""
    spec = read_spec({**TOY, "early_exit_threshold": 0.9})
    assert spec.early_exit_threshold == 0.9
    refusals = block_refusals(spec)
    assert len(refusals) == 1 and "different passes" in str(refusals[0])
    with pytest.raises(UnsupportedBlockError, match="different passes"):
        ModelRunner(config(model=spec), params=PARAMS)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("rope_scaling", {"type": "yarn"}), ("use_sliding_window", True),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"])])
def test_the_reader_refuses_what_is_not_written_down(key, value):
    with pytest.raises(UnsupportedBlockError, match=key):
        read_spec({**TOY, key: value})


def test_pooled_embeddings_are_refused():
    runner = ModelRunner(config(), params=PARAMS)
    with pytest.raises(UnsupportedBlockError, match="loop over passes"):
        runner.embed([[1, 2, 3]])


# -- one block ---------------------------------------------------------------------

def test_one_pass_without_sandwich_norms_is_the_dense_block():
    """The same leaves, the same programs: the two output norms and the
    loop are branches nobody else takes."""
    dense = ModelSpec(name="d", vocab_size=64, hidden_size=64,
                      intermediate_size=128, num_layers=3, num_heads=4,
                      num_kv_heads=4, head_dim=16, rope_theta=1e6,
                      rms_norm_eps=1e-6)
    plain = OuroSpec(**{f.name: getattr(dense, f.name)
                        for f in dataclasses.fields(ModelSpec)},
                     loop_passes=1, sandwich_norm=False)
    assert model.param_shapes(plain) == model.param_shapes(dense)
    assert plain.pool_layers == dense.pool_layers == 3
    assert plain.num_params() == dense.num_params()
    params = seeded_params(dense, 3)
    pool = jnp.zeros((3, 4, 8, PAGE, 16), jnp.bfloat16)
    tokens = jnp.asarray(np.arange(32, dtype=np.int32).reshape(1, 32) % 64)
    args = (pool, pool, tokens, jnp.arange(32, dtype=jnp.int32)[None],
            jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([29], jnp.int32))
    lowered = [jax.jit(lambda p, *a, s=s: model.prefill_forward(
        p, s, *a)).lower(params, *args).as_text() for s in (dense, plain)]
    assert lowered[0] == lowered[1]


# -- the runner: prefill, the single step, the window, against the reference --------

def _prefilled(n: int = 23, backend: str = "xla"):
    """A runner that prefilled ``n`` tokens into pages 1 and 2: (runner,
    tokens, the prefill's logits)."""
    runner = ModelRunner(config(attention_backend=backend), params=PARAMS)
    tokens = prompt_of(n, 5)
    pages = np.asarray([1, 2], np.int32)
    runner.prefill_batch([PrefillSeq(
        tokens=np.asarray(tokens, np.int32), start_pos=0, chunk_pages=pages,
        hist_pages=None, sampling=(0.0, 0, 1.0))])
    return runner, tokens, np.asarray(runner.last_prefill_logits[0])


def _window(runner, rows: dict, steps: int):
    """One window over ``rows`` {slot: (position, pages)}: the tokens and
    logprobs [steps, slots] and what the window counted."""
    width = max(len(p) for _, p in rows.values())
    packed = np.zeros((runner.config.max_num_seqs, PK_PREFIX + width),
                      np.int32)
    packed[:, PK_TOPP] = np.float32(1.0).view(np.int32)
    for slot, (pos, pages) in rows.items():
        packed[slot, PK_POS] = pos
        packed[slot, PK_SEQLEN] = pos + 1
        packed[slot, PK_CAP] = len(pages) * PAGE
        packed[slot, PK_LOGPROB] = 1
        packed[slot, PK_PREFIX:PK_PREFIX + len(pages)] = pages
    toks, lps, _, _, counted = runner.decode_window(packed, steps)
    return np.asarray(toks), np.asarray(lps), counted


def _step(runner, token: int, position: int, k_cache=None, v_cache=None):
    """The single decode step's logits for one row at ``position`` over
    pages 1 and 2 (model.decode_forward, the caches left as they are)."""
    table = np.zeros((1, 8), np.int32)
    table[0, :2] = [1, 2]
    logits, _, _ = model.decode_forward(
        runner.params, SPEC,
        runner.k_cache if k_cache is None else k_cache,
        runner.v_cache if v_cache is None else v_cache,
        jnp.asarray([token]), jnp.asarray([position]), jnp.asarray(table),
        jnp.asarray([position + 1]), backends=runner.backends)
    return np.asarray(logits[0])


def test_prefill_the_step_and_the_window_give_the_full_forward_s_logits():
    """23 tokens prefilled, the next token by the single step, six more by
    two windows through the paged pool: every one of them the reference's
    full forward over the same tokens; the window counts its passes."""
    runner, tokens, logits = _prefilled()
    assert runner.k_cache.shape == (9, 4, 64, PAGE, 16)
    want = np.asarray(ref.all_logprobs(PARAMS, SPEC, tokens))
    assert near(logprobs(logits), want[-1])
    first = int(np.argmax(logits))
    step = _step(runner, first, 23)
    want = np.asarray(ref.all_logprobs(PARAMS, SPEC, tokens + [first]))
    assert near(logprobs(step), want[-1]), distance(logprobs(step), want[-1])
    # The window program from the same point: tokens_dev holds the prompt's
    # sampled token (greedy), three steps, then four more.
    runner.tokens_dev = runner.tokens_dev.at[0].set(first)
    toks, lps, counted = _window(runner, {0: (23, [1, 2])}, 3)
    toks2, lps2, _ = _window(runner, {0: (26, [1, 2])}, 4)
    stream = [first, *toks[:, 0].tolist(), *toks2[:, 0].tolist()]
    got = [*lps[:, 0].tolist(), *lps2[:, 0].tolist()]
    want = ref.reference_logprobs(PARAMS, SPEC, tokens + [first], stream[1:])
    assert near(got, want), distance(got, want)
    assert toks[0, 0] == int(np.argmax(step))
    # Three live row-steps of three passes each, counted where they ran.
    assert np.asarray(counted["loop"]).tolist() == [9.0, 3.0]
    assert flight.columns_of("loop", counted["loop"]) == {
        "loop_passes": 9.0, "loop_row_steps": 3.0}


@pytest.mark.parametrize("switch", [
    dict(skip_layer=2), dict(skip_layer=0), dict(skip_pass=1),
    dict(skip_pass=2), dict(sandwich=False), dict(between=False),
    dict(precision="float8_e4m3fn")], ids=lambda s: "=".join(
        map(str, next(iter(s.items())))))
def test_each_control_fails_the_tolerance(switch):
    """What the program computes stands outside the tolerance from the
    reference with ONE thing wrong: a layer left out of the last pass, a
    whole pass left out, no norm of a sublayer's output, no norm between
    passes, float8 activations."""
    _, tokens, logits = _prefilled()
    wrong = np.asarray(ref.all_logprobs(PARAMS, SPEC, tokens, **switch))
    assert not near(logprobs(logits), wrong[-1]), (
        switch, distance(logprobs(logits), wrong[-1]))


def test_a_pass_reads_its_own_pool_layers():
    """With the first pass's K and V copied over the other passes' pool
    layers (what a pool of 3 layers shared by the passes would hold), the
    step parts from the reference: the passes' entries differ and each pass
    reads its own."""
    runner, tokens, logits = _prefilled()
    first = int(np.argmax(logits))
    want = np.asarray(ref.all_logprobs(PARAMS, SPEC, tokens + [first]))[-1]
    assert near(logprobs(_step(runner, first, 23)), want)
    k, v = np.asarray(runner.k_cache), np.asarray(runner.v_cache)
    assert not np.array_equal(k[0:3, :, 1], k[3:6, :, 1])
    shared_k = jnp.asarray(np.tile(k[:3], (3, 1, 1, 1, 1)))
    shared_v = jnp.asarray(np.tile(v[:3], (3, 1, 1, 1, 1)))
    got = logprobs(_step(runner, first, 23, shared_k, shared_v))
    assert not near(got, want), distance(got, want)


def test_a_padded_batch_and_a_dead_slot():
    """Two prompts of 9 and 30 tokens in one bucket of 32, then a window
    over slots 0 and 2 with a dead slot between them: each row's tokens are
    its own full forward's, and only the live rows' passes are counted."""
    runner = ModelRunner(config(), params=PARAMS)
    prompts = [prompt_of(9, 1), prompt_of(30, 2)]
    pages = [np.asarray([3], np.int32), np.asarray([4, 5], np.int32)]
    runner.prefill_batch([PrefillSeq(
        tokens=np.asarray(p, np.int32), start_pos=0, chunk_pages=pg,
        hist_pages=None, sampling=(0.0, 0, 1.0))
        for p, pg in zip(prompts, pages)], slots=[0, 2])
    firsts = np.asarray(runner.tokens_dev)[[0, 2]].tolist()
    toks, lps, counted = _window(
        runner, {0: (9, [3]), 2: (30, [4, 5, 6])}, 4)
    for slot, prompt, first in zip((0, 2), prompts, firsts):
        want = ref.reference_logprobs(PARAMS, SPEC, prompt + [first],
                                      toks[:, slot].tolist())
        assert near(lps[:, slot], want), (slot, distance(lps[:, slot], want))
    # Row 2 freezes at its cap (48): 4 + 4 row-steps here, 3 passes each.
    assert np.asarray(counted["loop"]).tolist() == [24.0, 8.0]


def test_the_kernels_interpreted_give_the_gather_s_window():
    """The Pallas reader (a packed head here, one query row a KV head) and
    the in-place commit by layer ranges, interpreted, against XLA's gather
    and scatter: the same tokens, the same pool."""
    a, tokens, logits = _prefilled()
    b, _, _ = _prefilled()
    b.backends = dataclasses.replace(
        b.backends, attention="pallas", kv_commit="in_place", interpret=True)
    first = int(np.argmax(logits))
    for runner in (a, b):
        runner.tokens_dev = runner.tokens_dev.at[0].set(first)
    ta, la, _ = _window(a, {0: (23, [1, 2])}, 4)
    tb, lb, _ = _window(b, {0: (23, [1, 2])}, 4)
    assert ta[:, 0].tolist() == tb[:, 0].tolist()
    np.testing.assert_allclose(la[:, 0], lb[:, 0], atol=2e-2)
    # The first layer's K and V of the first pass are the tokens' own; the
    # later ones follow two readers' roundings.
    for pa, pb in ((a.k_cache, b.k_cache), (a.v_cache, b.v_cache)):
        np.testing.assert_array_equal(np.asarray(pa[0, :, 1:3]),
                                      np.asarray(pb[0, :, 1:3]))
        np.testing.assert_allclose(
            np.asarray(pa[:, :, 1:3], np.float32),
            np.asarray(pb[:, :, 1:3], np.float32), atol=0.1)
        assert np.asarray(pb[:, :, 2, 7:11]).any()      # tokens 23 to 26


@pytest.mark.parametrize("budget,calls", [(8 << 20, 1), (100_000, 4),
                                          (1, 12)])
def test_the_commit_by_layer_ranges_is_the_commit(budget, calls, monkeypatch):
    """12 pool layers of 2 heads of 128 committed whole, in ranges of 3
    layers and a layer at a time (the ranges divide the layers): the
    scatter's pool every time."""
    monkeypatch.setattr(attention, "COMMIT_VMEM_BYTES", budget)
    made = []
    real = attention.pl.pallas_call
    monkeypatch.setattr(attention.pl, "pallas_call",
                        lambda *a, **kw: made.append(1) or real(*a, **kw))
    rng = np.random.default_rng(0)
    shape = (12, 2, 6, PAGE, 128)
    pools = [jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
             for _ in range(2)]
    wins = [jnp.asarray(rng.standard_normal((12, 2, 3, 4, 128)),
                        jnp.bfloat16) for _ in range(2)]
    positions0 = jnp.asarray([14, 3, 0], jnp.int32)
    cap = jnp.asarray([32, 5, 16], jnp.int32)
    seq_lens0 = jnp.asarray([15, 4, 0], jnp.int32)      # slot 2 is dead
    table = jnp.asarray([[1, 2], [3, 0], [4, 0]], jnp.int32)
    got = attention.commit_window_pallas(
        *pools, *wins, positions0, cap, seq_lens0, table, interpret=True)
    assert len(made) == calls
    from dynamo_tpu.engine.kv_quant import window_token_slots
    dest, off = window_token_slots(positions0, cap, seq_lens0, table, 4, PAGE)
    for pool, win, out in zip(pools, wins, got):
        want = scatter_tokens(pool, win.transpose(0, 1, 3, 2, 4), dest, off)
        np.testing.assert_array_equal(np.asarray(out[:, :, 1:]),
                                      np.asarray(want[:, :, 1:]))


def test_the_reader_at_one_query_row_a_head_of_128():
    """The published geometry's reader, interpreted: one query row a KV
    head of 128 lanes over pages of 16, against XLA's gather."""
    rng = np.random.default_rng(1)
    L, nkv, pages, d, b = 2, 4, 12, 128, 3
    k = jnp.asarray(rng.standard_normal((L, nkv, pages, PAGE, d)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((L, nkv, pages, PAGE, d)),
                    jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
    kw = jnp.asarray(rng.standard_normal((nkv, b, 4, d)), jnp.bfloat16)
    vw = jnp.asarray(rng.standard_normal((nkv, b, 4, d)), jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0]],
                        jnp.int32)
    hist = jnp.asarray([57, 20, 0], jnp.int32)
    args = (q, k, v, jnp.int32(1), table, hist, kw, vw, jnp.int32(2), ks, vs)
    want = model.paged_window_attention_xla(*args, 1)
    got = attention.paged_window_attention_pallas(*args, q_per_kv=1,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


#: (tokens a row holds, the first token a row still sees or None): rows of
#: 16 KV heads x one query row over pages of 16, a chunk of 128 tokens.
HEADS_TURN_CASES = {
    "a row of no history": ([0, 40], None),
    "a row that ends inside a page": ([57, 3], None),
    "a row of exactly one chunk": ([128], None),
    "several chunks, the last partial": ([300, 129], None),
    "a dead slot between live rows": ([200, 0, 0, 130, 0, 17], None),
    "a window that starts inside a chunk": ([300, 90], [170, 20]),
    "a window that starts past a chunk": ([400, 257, 50], [300, 256, 60]),
}


@pytest.mark.parametrize("case", list(HEADS_TURN_CASES))
def test_the_reader_s_turn_over_all_heads_at_once(case):
    """The chunk turn the published geometry takes (attention.reader_turn:
    "heads"), interpreted: every head's keys in one product, the flash
    update once over [heads, tokens], against XLA's gather."""
    hist, lo = HEADS_TURN_CASES[case]
    nkv, d, L, b = 16, 128, 2, len(hist)
    assert attention.reader_turn(1, nkv, 1, False) == "heads"
    rng = np.random.default_rng(len(case))
    maxp = -(-max(hist) // PAGE)
    pages = b * maxp + 1
    k, v = (jnp.asarray(rng.standard_normal((L, nkv, pages, PAGE, d)),
                        jnp.bfloat16) for _ in range(2))
    q, ks, vs = (jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
                 for _ in range(3))
    kw, vw = (jnp.asarray(rng.standard_normal((nkv, b, 4, d)), jnp.bfloat16)
              for _ in range(2))
    # Every row's pages apart and out of order; page 0 under the padding.
    table = np.zeros((b, maxp), np.int32)
    free = iter(rng.permutation(pages - 1) + 1)
    for r, n in enumerate(hist):
        for j in range(-(-n // PAGE)):
            table[r, j] = next(free)
    args = (q, k, v, jnp.int32(1), jnp.asarray(table),
            jnp.asarray(hist, jnp.int32), kw, vw, jnp.int32(2), ks, vs)
    kwargs = {} if lo is None else {"lo": jnp.asarray(lo, jnp.int32)}
    want = model.paged_window_attention_xla(*args, 1, **kwargs)
    got = attention.paged_window_attention_pallas(
        *args, q_per_kv=1, interpret=True, **kwargs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


#: What the benchmark's cells hand the K-and-V reader (query rows a KV
#: head, KV heads, tokens a 128-lane row, int8 pages) and the turn each
#: takes: the rule reads shapes, never a name.
READER_GEOMETRIES = {
    "qwen2.5-7b": ((7, 4, 1, False), "rows"),
    "smallthinker-21b-a3b, windowed": ((7, 4, 1, False), "rows"),
    "command-a-plus": ((16, 8, 1, False), "rows"),
    "nemotron-3-nano-30b-a3b": ((16, 2, 1, False), "rows"),
    "minicpm-sala-9b, chosen blocks": ((16, 2, 1, False), "rows"),
    "ouro-2.6b": ((1, 16, 1, False), "heads"),
    "ouro's heads over int8 pages": ((1, 16, 1, True), "rows"),
    "ouro's heads at head_dim 64": ((1, 16, 2, False), "rows"),
}


@pytest.mark.parametrize("name", list(READER_GEOMETRIES))
def test_the_turn_is_chosen_by_the_reader_s_shapes(name):
    geometry, want = READER_GEOMETRIES[name]
    assert attention.reader_turn(*geometry) == want


# -- the engine ----------------------------------------------------------------------

PROMPT = prompt_of(40, 7)


@pytest.fixture(scope="module")
def served():
    """One prompt of 40 tokens and 24 tokens after it, served cold, then
    again over its cached pages, then in chunks of 32 over their history:
    {name: (tokens, logprobs, status)}."""
    out = {}

    @async_test
    async def serve():
        engine = TPUEngine(config(), params=PARAMS)
        engine.start()
        try:
            for name in ("cold", "cached"):
                toks, lps = await collect(engine, PROMPT, 24)
                out[name] = (toks, lps, engine.perf_status(),
                             engine.prefix_hit_blocks)
        finally:
            engine.stop()
        engine = TPUEngine(config(max_prefill_tokens=32,
                                  prefill_buckets=(32,)), params=PARAMS)
        engine.start()
        try:
            toks, lps = await collect(engine, PROMPT, 24)
            out["chunks"] = (toks, lps, engine.perf_status(),
                             engine.chunk_dispatch_count)
        finally:
            engine.stop()

    serve()
    return out


def test_the_engine_serves_what_the_reference_computes(served):
    toks, lps, status, hits = served["cold"]
    assert len(toks) == 24 and hits == 0
    want = ref.reference_logprobs(PARAMS, SPEC, PROMPT, toks)
    assert near(lps, want), distance(lps, want)
    loop = status["loop"]
    assert (loop["passes"], loop["pool_layers"]) == (3, 9)
    assert loop["kv_token_bytes"] == 2 * 9 * 4 * 16 * 2
    assert loop["passes_per_token"] == 3.0


@pytest.mark.parametrize("switch", [dict(skip_layer=2), dict(skip_pass=0)],
                         ids=["a layer of the last pass", "a whole pass"])
def test_the_served_stream_fails_both_controls(served, switch):
    toks, lps, _, _ = served["cold"]
    wrong = ref.control_logprobs(PARAMS, SPEC, PROMPT, toks, **switch)
    assert not near(lps, wrong), (switch, distance(lps, wrong))


def test_a_cached_prefix_gives_the_cold_logits(served):
    """The second request takes the prompt's two full pages from the prefix
    cache (all 9 pool layers lie under one page table) and serves the cold
    request's stream."""
    cold, cached = served["cold"], served["cached"]
    assert cached[3] >= 2
    assert cached[0] == cold[0]
    np.testing.assert_allclose(cached[1], cold[1], atol=5e-2)
    want = ref.reference_logprobs(PARAMS, SPEC, PROMPT, cached[0])
    assert near(cached[1], want), distance(cached[1], want)


def test_chunks_over_their_history_serve_the_same(served):
    toks, lps, _, chunks = served["chunks"]
    assert chunks >= 1
    want = ref.reference_logprobs(PARAMS, SPEC, PROMPT, toks)
    assert near(lps, want), distance(lps, want)
    assert toks[:6] == served["cold"][0][:6]


@async_test
async def test_a_preempted_row_recomputes_to_the_same_tokens():
    """Three requests against a pool that cannot hold them: the youngest is
    preempted, requeued and prefilled again from its tokens; every stream
    is the reference's, and the oldest, never preempted, gets the tokens it
    gets alone."""
    prompts = [prompt_of(24, 40 + i) for i in range(3)]
    alone = TPUEngine(config(), params=PARAMS)
    alone.start()
    try:
        want, _ = await collect(alone, prompts[0], 40)
    finally:
        alone.stop()
    engine = TPUEngine(config(num_pages=9), params=PARAMS)
    engine.start()
    try:
        tasks = []
        for prompt in prompts:
            tasks.append(asyncio.ensure_future(collect(engine, prompt, 40)))
            await asyncio.sleep(0.05)
        results = await asyncio.gather(*tasks)
        assert engine.preempt_count > 0
        assert results[0][0] == want
        for prompt, (toks, lps) in zip(prompts, results):
            assert len(toks) == 40
            ref_lps = ref.reference_logprobs(PARAMS, SPEC, prompt, toks)
            assert near(lps, ref_lps), distance(lps, ref_lps)
    finally:
        engine.stop()
